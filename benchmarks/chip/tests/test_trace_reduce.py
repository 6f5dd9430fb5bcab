"""The reduction from a profiler trace to busy time, idle share, top
device operations and idle time per host span, on small hand-made
traces, and the readers that turn it into metrics."""

from dataclasses import dataclass, field

import pytest

import readings
import trace_reduce
from harness import Reading, Record, Window


@dataclass
class Ev:
    name: str
    start_ns: float
    duration_ns: float


@dataclass
class Line:
    name: str
    events: list = field(default_factory=list)


@dataclass
class Plane:
    name: str
    lines: list = field(default_factory=list)


def host(*events):
    return Plane("/host:CPU", [Line("main", [Ev(*e) for e in events])])


def chip(n, ops, modules=()):
    return Plane(f"/device:TPU:{n}", [
        Line("XLA Modules", [Ev(*m) for m in modules]),
        Line("XLA Ops", [Ev(*o) for o in ops]),
        Line("Async XLA Ops", [Ev("copy-start", 0, 1000)])])


def reduce(planes):
    return trace_reduce.reduce_planes(planes, window_span="bench/window",
                                      span_prefix="bench/")


def test_busy_is_the_union_of_ops_clipped_to_the_window():
    planes = [
        host(("bench/window", 100, 1000)),
        chip(0, [("%a = f()", 50, 100),      # 50..150: 50 inside
                 ("%b = g()", 200, 100),     # 200..300
                 ("%c = h()", 250, 100),     # 250..350 overlaps b
                 ("%d = k()", 1050, 100)]),  # 1050..1150: 50 inside
    ]
    s = reduce(planes)
    assert s.window_s == pytest.approx(1000e-9)
    assert s.busy_s == pytest.approx((50 + 150 + 50) * 1e-9)
    assert s.idle_share == pytest.approx(0.75)


def test_busy_is_averaged_over_the_chips_that_ran():
    planes = [host(("bench/window", 0, 1000)),
              chip(0, [("%a = f()", 0, 400)]),
              chip(1, [("%a = f()", 0, 200)]),
              chip(2, [])]
    assert reduce(planes).busy_s == pytest.approx(300e-9)


def test_top_ops_are_named_by_module_and_summed():
    modules = [("jit_majx_pallas(123)", 0, 300),
               ("jit_scatter(9)", 400, 100)]
    ops = [("%majx_pallas.1 = u32[8] custom-call(x)", 10, 100),
           ("%majx_pallas.1 = u32[8] custom-call(x)", 150, 100),
           ("%fusion = u32[8] fusion(y)", 410, 50)]
    s = reduce([host(("bench/window", 0, 1000)), chip(0, ops, modules)])
    assert s.top_ops == [["jit_majx_pallas:majx_pallas.1", 200e-9],
                         ["jit_scatter:fusion", 50e-9]]


def test_idle_time_goes_to_the_innermost_open_span():
    planes = [
        host(("bench/window", 0, 1000),
             ("bench/service.tick", 0, 600),
             ("bench/session.run_fused", 100, 200),   # 100..300
             ("bench/other", 5000, 10)),               # outside
        chip(0, [("%k = f()", 250, 100)]),             # busy 250..350
    ]
    s = reduce(planes)
    idle = dict((n, t) for n, t in s.idle_gaps)
    # gaps: 0..250 and 350..1000
    assert idle["session.run_fused"] == pytest.approx(150e-9)   # 100..250
    assert idle["service.tick"] == pytest.approx((100 + 250) * 1e-9)
    assert idle["none"] == pytest.approx(400e-9)                # 600..1000
    assert sum(idle.values()) == pytest.approx(s.window_s - s.busy_s)
    assert [n for n, _ in s.idle_gaps] == ["none", "service.tick",
                                           "session.run_fused"]


def test_a_trace_without_the_window_span_is_refused():
    with pytest.raises(ValueError, match="bench/window"):
        reduce([host(("bench/tick", 0, 10)), chip(0, [])])


def _reading(trace, records, peak=819e9):
    window = Window(records, 0.0, 1.0)
    return Reading(window, setup_s=1.0, peaks={"hbm_bytes_per_s": peak},
                   trace=trace)


def test_roofline_is_required_bytes_over_peak_times_busy():
    trace = trace_reduce.TraceSummary(window_s=2.0, busy_s=0.5,
                                      top_ops=[], idle_gaps=[])
    recs = [Record("add", 0, 1, required_bytes=100_000_000),
            Record("add", 0, 1, required_bytes=104_750_000),
            Record("add", 0, 1, required_bytes=10**12, error="lost")]
    r = _reading(trace, recs)
    # 204.75 MB over 819 GB/s x 0.5 s = 0.05%
    assert readings.hbm_roofline_pct(r) == pytest.approx(0.05)
    assert readings.idle_pct(r) == pytest.approx(75.0)


def test_device_readers_are_silent_without_a_device():
    idle = trace_reduce.TraceSummary(window_s=2.0, busy_s=0.0, top_ops=[],
                                     idle_gaps=[])
    recs = [Record("add", 0, 1, required_bytes=12)]
    for trace in (None, idle):
        r = _reading(trace, recs)
        assert readings.hbm_roofline_pct(r) is None
        assert readings.idle_pct(r) is None
    busy = trace_reduce.TraceSummary(window_s=2.0, busy_s=1.0, top_ops=[],
                                     idle_gaps=[])
    assert readings.hbm_roofline_pct(_reading(busy, recs, peak=None)) is None
