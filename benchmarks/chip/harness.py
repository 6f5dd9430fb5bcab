"""The benchmark's shared code: find a cell's files, set up, measure, check.

A cell of ``BENCHMARK.json`` names a configuration and a traffic mix.
Everything that belongs to one of them lives in a file of its own, found
by name under this directory:

* ``configs/<config>.json`` — the deployment (session settings, the
  operation, what was assumed);
* ``traffic/<mix>.json`` — the mix's parameters, and under ``driver``
  the name of the general generator that reads them;
* ``drivers/<driver>.py`` — a general generator: builds the system under
  test from the configuration, makes the data from the seed, runs the
  closed loop of clients, and checks every answer against
  ``reference.py``;
* ``metrics/<metric>.py`` — one reader per metric, end-to-end or
  per-layer, with ``read(reading) -> float | None``.

No code here branches on a cell, configuration or metric name: a new
cell is new files plus entries in ``BENCHMARK.json``.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import random
import resource
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Callable, Optional

BENCH_ROOT = Path(__file__).resolve().parent
REPO_ROOT = BENCH_ROOT.parents[1]
SPEC_FILE = REPO_ROOT / "BENCHMARK.json"

#: JAX's monitoring event for one backend compile.
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

#: Every host span the benchmark writes into the profiler's trace starts
#: with this prefix, so the reduction can tell them from JAX's own.
SPAN_PREFIX = "bench/"
WINDOW_SPAN = SPAN_PREFIX + "window"


class SetupError(RuntimeError):
    """The run cannot measure: no chip, an unknown chip, a bad spec."""


# ---------------------------------------------------------------- records
@dataclasses.dataclass
class Record:
    """One request (or call) the window's clients made, as they saw it."""

    kind: str
    t_submit: float            # perf_counter seconds
    t_done: float
    required_bytes: int = 0    # device bytes the work needs at the least
    elements: int = 0          # lanes computed
    error: Optional[str] = None


@dataclasses.dataclass
class Window:
    """What a driver hands back from the measured window."""

    records: list[Record]
    t0: float
    t1: float

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


@dataclasses.dataclass
class Reading:
    """Everything a metric reader may read."""

    window: Window
    setup_s: float
    peaks: dict
    trace: Optional[object] = None   # trace_reduce.TraceSummary

    @property
    def records(self) -> list[Record]:
        return self.window.records


class Reservoir:
    """A sample of at most ``size`` items, drawn from the seed."""

    def __init__(self, size: int, seed: int):
        self.size = size
        self.seen = 0
        self.items: list = []
        self._rng = random.Random(seed)

    def add(self, item) -> None:
        self.seen += 1
        if len(self.items) < self.size:
            self.items.append(item)
            return
        j = self._rng.randrange(self.seen)
        if j < self.size:
            self.items[j] = item


def annotate(obj, attr: str, span: str) -> None:
    """Wrap ``obj.attr`` (an instance's bound method) in a profiler span.

    Only the instance the benchmark built is touched; the program's
    classes stay as they are.
    """
    import jax

    inner = getattr(obj, attr)

    def wrapped(*args, **kwargs):
        with jax.profiler.TraceAnnotation(SPAN_PREFIX + span):
            return inner(*args, **kwargs)

    setattr(obj, attr, wrapped)


def annotate_session(session) -> None:
    """Spans around the layers one ``DramSession`` call passes through:
    validation, the compile cache (schedule, certificate) and the
    backend's fused run."""
    annotate(session, "run_fused", "session.run_fused")
    annotate(session, "_validate", "session.validate")
    annotate(session.backend, "run_fused", "backend.run_fused")
    annotate(session.cache, "schedule_for", "cache.schedule")
    annotate(session.cache, "certificate_for", "cache.certify")


# ------------------------------------------------------------ discovery
def load_module(path: Path):
    """Import one file by path, under a name of its own."""
    if not path.is_file():
        raise SetupError(f"no such file: {path}")
    name = "bench_" + "_".join(path.relative_to(path.parents[1]).with_suffix(
        "").parts).replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _read_json(path: Path) -> dict:
    if not path.is_file():
        raise SetupError(f"no such file: {path}")
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    """One workload of the spec with its files resolved."""

    name: str
    chips: int
    config: dict
    traffic: dict
    driver: object
    metrics: list[dict]       # spec entries of the metrics to print
    readers: dict             # metric name -> reader module


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve(spec: dict, workload: str, *, trace: bool,
            spec_dir: Path = REPO_ROOT, root: Path = BENCH_ROOT) -> Cell:
    """Find the workload's configuration, traffic, driver and readers."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise SetupError(f"unknown workload {workload!r}; the spec has "
                         f"{sorted(cells)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    config = _read_json(spec_dir / configs[cell["config"]]["file"])
    traffic = _read_json(root / "traffic" / f"{cell['traffic']}.json")
    driver = load_module(root / "drivers" / f"{traffic['driver']}.py")
    metrics = [m for m in spec["per_layer" if trace else "end_to_end"]
               if _applies(m, workload)]
    readers = {m["name"]: load_module(root / "metrics" / f"{m['name']}.py")
               for m in metrics}
    return Cell(workload, int(cell["chips"]), config, traffic, driver,
                metrics, readers)


def load_spec(path: Path = SPEC_FILE) -> dict:
    return _read_json(path)


# --------------------------------------------------------------- device
def load_peaks(root: Path = BENCH_ROOT) -> dict:
    return _read_json(root / "peaks.json")["devices"]


def check_device(devices, chips: int, peaks: dict) -> dict:
    """The peaks of the chip the run is on; raises unless it is a TPU
    named in the peaks table and there are enough of them."""
    if not devices or devices[0].platform != "tpu":
        found = devices[0].platform if devices else "nothing"
        raise SetupError(f"no TPU: JAX found {found}")
    kind = devices[0].device_kind
    if kind not in peaks:
        raise SetupError(f"device {kind!r} is not in peaks.json")
    if len(devices) < chips:
        raise SetupError(f"the cell asks for {chips} chips, JAX found "
                         f"{len(devices)}")
    return peaks[kind]


def host_load() -> dict:
    """Counters that tell a slow process's cause apart: this thread's CPU
    seconds and involuntary context switches, and the seconds other
    machines took from this host's CPUs (steal, summed over CPUs)."""
    ru = resource.getrusage(resource.RUSAGE_THREAD)
    steal = 0.0
    try:
        with open("/proc/stat") as f:
            fields = f.readline().split()
        steal = int(fields[8]) / os.sysconf("SC_CLK_TCK")
    except (OSError, IndexError, ValueError):
        pass
    return {"thread_cpu_s": ru.ru_utime + ru.ru_stime,
            "involuntary": ru.ru_nivcsw, "steal_s": steal}


class CompileCounter:
    """Counts backend compiles while registered (``with``)."""

    def __init__(self):
        self.count = 0

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event == COMPILE_EVENT:
            self.count += 1

    def __enter__(self) -> "CompileCounter":
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        return self

    def __exit__(self, *exc) -> None:
        import jax

        jax.monitoring.unregister_event_duration_listener(self._on_event)


# ------------------------------------------------------------ one run
@dataclasses.dataclass
class Outcome:
    """A run's result line, before printing."""

    correct: bool
    attempted: int
    failed: int
    metrics: dict
    device: dict
    checks: dict
    breakdown: Optional[dict] = None

    def line(self) -> dict:
        out = {"correct": self.correct, "attempted": self.attempted,
               "failed": self.failed, "metrics": self.metrics,
               "device": self.device}
        if self.breakdown is not None:
            out["breakdown"] = self.breakdown
        out["checks"] = self.checks
        return out


def _traced_window(run, seconds: float) -> tuple[Window, object]:
    """Measure under the profiler and reduce its trace."""
    import jax

    import trace_reduce

    log_dir = tempfile.mkdtemp(prefix="bench-trace-")
    try:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(log_dir, profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation(WINDOW_SPAN):
                window = run.window(seconds)
        finally:
            jax.profiler.stop_trace()
        summary = trace_reduce.reduce_dir(log_dir, window_span=WINDOW_SPAN,
                                          span_prefix=SPAN_PREFIX)
    finally:
        shutil.rmtree(log_dir, ignore_errors=True)
    return window, summary


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             t_start: float, peaks: Optional[dict] = None,
             control: bool = False,
             log: Callable[[str], None] = lambda s: print(
                 s, file=sys.stderr, flush=True)) -> Outcome:
    """One run of one cell: set up, measure, check.

    ``peaks`` is the chip's row of ``peaks.json`` (``None`` only where a
    test drives the run on the CPU).  ``control`` puts the reference's
    control in the program's place (see ``reference.py``).
    """
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    dev = jax.devices()[0]
    with CompileCounter() as compiles:
        t_build = time.perf_counter()
        run = cell.driver.build(cell.config, cell.traffic, seed,
                                spans=trace)
        if control:
            run.install_control()
        t_warm = time.perf_counter()
        run.warm()
        setup_s = time.perf_counter() - t_start
        before = compiles.count
        log(f"setup: {setup_s:.3f} s (start to build {t_build - t_start:.3f},"
            f" build and data {t_warm - t_build:.3f}, warm-up "
            f"{t_start + setup_s - t_warm:.3f}), {before} compiles, "
            f"cache {cache_dir}")
        load0 = host_load()
        if trace:
            window, summary = _traced_window(run, seconds)
        else:
            window, summary = run.window(seconds), None
        load = {k: v - load0[k] for k, v in host_load().items()}
        in_window = compiles.count - before
    log(f"window: {window.seconds:.3f} s, {len(window.records)} requests, "
        f"{in_window} compiles inside the window")
    log(f"host: main thread on the CPU {load['thread_cpu_s']:.3f} s, "
        f"{load['involuntary']} involuntary switches, steal "
        f"{load['steal_s']:.2f} CPU-s over {os.cpu_count()} CPUs")
    stats = dev.memory_stats() or {}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": int(stats.get("peak_bytes_in_use", 0))}
    run.release()
    checks = run.checks()

    reading = Reading(window, setup_s, peaks or {}, summary)
    metrics = {}
    for m in cell.metrics:
        value = cell.readers[m["name"]].read(reading)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    breakdown = None
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        breakdown = {"device_ops": summary.top_ops,
                     "idle_gaps": summary.idle_gaps}
    failed = sum(1 for r in window.records if r.error is not None)
    correct = bool(window.records) and all(
        c["value"] <= c["limit"] for c in checks.values())
    return Outcome(correct, len(window.records), failed, metrics, device,
                   checks, breakdown)


def main(args, *, t_start: float) -> int:
    """The command line's body: exits 0 with a result line, or nonzero
    with none."""
    sys.path.insert(0, str(REPO_ROOT / "src"))
    try:
        spec = load_spec()
        cell = resolve(spec, args.workload, trace=bool(args.trace))
        try:
            import repro  # noqa: F401  the system under test
        except ImportError as e:
            raise SetupError(f"the system under test is missing: {e}")
        import jax

        peaks = check_device(jax.devices(), cell.chips, load_peaks())
    except SetupError as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    out = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                   t_start=t_start, peaks=peaks, control=bool(args.control))
    for name, c in out.checks.items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out.line()), flush=True)
    return 0

