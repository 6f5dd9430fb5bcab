"""The reduction of the program's ``pud/`` spans and of every device op,
on small hand-made traces, and the readers of the program's layers."""

import dataclasses
import time
from dataclasses import dataclass, field

import pytest

import harness
import program_trace
import trace_reduce
from harness import Reading, Record, Window

READERS = ("trace_ms.arith", "host_syncs_per_call.arith",
           "session_ms.arith", "level_exec_ms.arith",
           "kernel_device_ms.arith")


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


def host(**lines):
    return Plane("/host:CPU", [Line(n, [Ev(*e) for e in evs])
                               for n, evs in lines.items()])


def chip(n, ops, modules=()):
    return Plane(f"/device:TPU:{n}", [
        Line("XLA Modules", [Ev(*m) for m in modules]),
        Line("XLA Ops", [Ev(*o) for o in ops])])


def reduce(planes):
    return program_trace.reduce_program(planes, window_span="bench/window")


#: One call, 100..900 inside a window of 0..1000, and a second call that
#: starts at 950 and runs past the window's end.
CALL = [("bench/window", 0, 1000),
        ("pud/elementwise", 100, 800),            # 100..900
        ("pud/compile", 110, 290),                # 110..400
        ("pud/compile.sync", 150, 50),            # 150..200
        ("pud/compile.sync", 250, 50),            # 250..300
        ("pud/compile.sync", 300, 0),             # a read of no length
        ("pud/session.run_fused", 400, 450),      # 400..850
        ("pud/backend.run_fused", 500, 300),      # 500..800
        ("pud/elementwise", 950, 200),            # 950..1150: 50 inside
        ("pud/compile", 960, 190),                # 960..1150: 40 inside
        ("pud/compile.sync", 1100, 10)]           # after the window


def test_span_totals_nest_and_clip_to_the_window():
    spans = reduce([host(main=CALL)]).spans
    assert spans["elementwise"].count == 2
    assert spans["elementwise"].inclusive_s == pytest.approx(850e-9)
    # 800 less compile (290) and session.run_fused (450); 50 less 40.
    assert spans["elementwise"].self_s == pytest.approx(60e-9 + 10e-9)
    assert spans["compile"].inclusive_s == pytest.approx(330e-9)
    # 290 less its syncs (100); 40, its sync starting after the window.
    assert spans["compile"].self_s == pytest.approx(190e-9 + 40e-9)
    assert spans["compile.sync"].count == 3
    assert spans["compile.sync"].inclusive_s == pytest.approx(100e-9)
    assert spans["session.run_fused"].self_s == pytest.approx(150e-9)
    assert spans["backend.run_fused"].self_s == pytest.approx(300e-9)


def test_only_spans_on_the_same_thread_line_are_children():
    spans = reduce([host(main=CALL[:2],
                         worker=[("pud/compile", 200, 100)])]).spans
    assert spans["elementwise"].self_s == pytest.approx(800e-9)
    assert spans["compile"].self_s == pytest.approx(100e-9)


def test_idle_time_goes_to_the_innermost_program_span():
    # Busy 500..800: all of backend.run_fused.
    planes = [host(main=CALL), chip(0, [("%k = f()", 500, 300)])]
    spans = reduce(planes).spans
    assert spans["backend.run_fused"].idle_s == 0.0
    assert spans["session.run_fused"].idle_s == pytest.approx(150e-9)
    assert spans["compile.sync"].idle_s == pytest.approx(100e-9)
    # 110..150, 200..250, 300..400, 960..1000
    assert spans["compile"].idle_s == pytest.approx(230e-9)
    # 100..110, 850..900, 950..960
    assert spans["elementwise"].idle_s == pytest.approx(70e-9)
    # The rest, 0..100 and 900..950, had no program span open.
    idle = sum(s.idle_s for s in spans.values())
    assert idle == pytest.approx(1000e-9 - 300e-9 - 150e-9)


def test_op_seconds_cover_every_op():
    n = 2 * trace_reduce.TOP
    ops = [(f"%op{i}.1 = u32[8] fusion(x)", 10 * i, 5) for i in range(n)]
    modules = [("jit_many(1)", 0, 10 * n)]
    planes = [host(main=CALL[:1]), chip(0, ops, modules)]
    op_s = reduce(planes).op_s
    assert len(op_s) == n
    assert op_s["jit_many:op19.1"] == pytest.approx(5e-9)


def _sample_trace():
    modules = [("jit_majx_pallas(1)", 0, 300), ("jit_scatter(2)", 400, 100)]
    ops = [("%majx_csa.1 = u32[8] custom-call(x)", 10, 100),
           ("%fusion = u32[8] fusion(y)", 410, 50)]
    return [host(main=CALL + [("bench/session.run_fused", 400, 450)]),
            chip(0, ops, modules)]


def test_the_wrapped_reduction_keeps_every_existing_field():
    wrapped = trace_reduce.reduce_planes
    plain = wrapped.__wrapped__
    for planes in (_sample_trace(),
                   [host(main=[("bench/window", 0, 1000)]), chip(0, [])]):
        kw = dict(window_span="bench/window", span_prefix="bench/")
        got, want = wrapped(planes, **kw), plain(planes, **kw)
        assert dataclasses.asdict(got) == dataclasses.asdict(want)
        assert got.program == reduce(planes)


def _reading(planes, n_calls=2):
    summary = trace_reduce.reduce_planes(planes, window_span="bench/window",
                                         span_prefix="bench/")
    records = [Record("add", 0, 1) for _ in range(n_calls)]
    return Reading(Window(records, 0.0, 1.0), setup_s=1.0, peaks={},
                   trace=summary)


def _readers():
    spec = harness.load_spec()
    cell = spec["workloads"][0]["name"]
    return harness.resolve(spec, cell, trace=True).readers


def test_readers_give_per_call_values():
    readers = _readers()
    r = _reading(_sample_trace())
    got = {name: readers[name].read(r) for name in READERS}
    assert got == pytest.approx({
        "trace_ms.arith": 330e-9 * 1e3 / 2,
        "host_syncs_per_call.arith": 1.5,
        "session_ms.arith": 150e-9 * 1e3 / 2,
        "level_exec_ms.arith": 300e-9 * 1e3 / 2,
        "kernel_device_ms.arith": 100e-9 * 1e3 / 2})


def test_readers_are_silent_without_the_program_spans():
    readers = _readers()
    planes = [host(main=[("bench/window", 0, 1000),
                         ("bench/session.elementwise", 100, 800)]),
              chip(0, [("%majx_csa.1 = u32[8] custom-call(x)", 10, 100)])]
    for r in (_reading(planes), Reading(Window([Record("add", 0, 1)], 0, 1),
                                        1.0, {}, None)):
        for name in READERS:
            assert readers[name].read(r) is None, name


def test_a_span_that_never_opened_reads_zero():
    readers = _readers()
    planes = [host(main=[("bench/window", 0, 1000),
                         ("pud/elementwise", 100, 800)])]
    r = _reading(planes)
    for name in READERS:
        assert readers[name].read(r) == 0.0, name


@pytest.mark.parametrize("key, kernel", [
    ("jit_majx_pallas:majx_csa.1", True),
    ("jit_fanout_pallas:rowcopy_fanout", True),
    ("jit_run_fused:megakernel_levels.12", True),
    ("jit_mismatch_pallas:mismatch_popcount.3", True),
    ("jit_bitserial_add_pallas:bitserial_add.1", True),
    ("jit_majx_pallas:copy.1", False),
    ("jit_bitserial_add:add.1", False),
    ("?:majx_csa_glue.1", False)])
def test_kernel_ops_are_told_by_op_name(key, kernel):
    assert program_trace.is_kernel_op(key) is kernel


def test_the_kernel_names_are_the_programs():
    from repro.kernels.bitserial import kernel as bitserial
    from repro.kernels.majx import kernel as majx
    from repro.kernels.megakernel import kernel as megakernel
    from repro.kernels.mismatch import kernel as mismatch
    from repro.kernels.rowcopy import kernel as rowcopy

    names = {m.KERNEL_NAME for m in (majx, rowcopy, mismatch, bitserial,
                                     megakernel)}
    assert names == set(program_trace.KERNEL_NAMES)


def test_a_traced_run_reads_every_program_metric():
    """A whole traced run of a cell at a tiny size on the CPU: the five
    readers of the program's layers all read, and the host reads per
    call are the same in every call."""
    spec = harness.load_spec()
    cell = harness.resolve(spec, spec["workloads"][0]["name"], trace=True)
    cell.traffic.update(lanes=4096, pool=2, warm_calls=1)
    out = harness.run_cell(cell, 2**40 + 17, 0.3, True,
                           t_start=time.perf_counter(), log=lambda s: None)
    assert out.correct
    m = {k: v["value"] for k, v in out.metrics.items()}
    assert set(READERS) <= set(m)
    assert m["host_syncs_per_call.arith"] >= 96
    assert m["host_syncs_per_call.arith"] == int(
        m["host_syncs_per_call.arith"])
    assert m["trace_ms.arith"] > 0 and m["level_exec_ms.arith"] > 0
