"""Reduce a profiler trace to device busy time, idle gaps and top ops.

Input is what ``jax.profiler`` writes (an ``.xplane.pb``), read with
``jax.profiler.ProfileData``; :func:`reduce_planes` takes anything of the
same shape (planes with ``name`` and ``lines``, lines with ``name`` and
``events``, events with ``name``, ``start_ns`` and ``duration_ns``), so
it is tested on small hand-made traces.

* The window is the host span named ``window_span``, which the harness
  opens around the measured window.
* Device busy time is, per chip, the union of the intervals in which an
  operation ran on it (its ``XLA Ops`` line), clipped to the window, and
  averaged over the chips that ran anything.
* Idle time is the part of the window in which no chip ran anything.
  Each instant of it goes to the innermost benchmark span
  (``span_prefix``) open on the host then, so the idle time reads as
  what the host was doing while the chip waited; it is summed per span.
* Device time per operation is named ``<XLA module>:<op>``, e.g.
  ``jit_majx_pallas:majx_pallas.1``.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OP_LINE = "XLA Ops"
MODULE_LINE = "XLA Modules"
TOP = 10


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    busy_s: float
    top_ops: list          # [[module:op, device seconds], ...]
    idle_gaps: list        # [[host span, idle seconds], ...]

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def merge(intervals) -> list[tuple[float, float]]:
    """Union of (start, end) intervals, as sorted disjoint intervals."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(s: float, e: float, lo: float, hi: float):
    s, e = max(s, lo), min(e, hi)
    return (s, e) if e > s else None


def _events(line):
    for ev in line.events:
        yield ev.name, float(ev.start_ns), float(ev.start_ns + ev.duration_ns)


def short_op(name: str) -> str:
    """``%majx_pallas.1 = u32[...] custom-call(...)`` -> ``majx_pallas.1``."""
    return name.split(" = ", 1)[0].lstrip("%")


def short_module(name: str) -> str:
    """``jit_majx_pallas(1234)`` -> ``jit_majx_pallas``."""
    return name.split("(", 1)[0]


def idle_by_span(gaps, spans) -> dict[str, float]:
    """Idle time per host span: each instant of a gap goes to the
    innermost (latest-started) benchmark span open then, or to
    ``none``."""
    points = []
    for i, (_, s, e) in enumerate(spans):
        points += [(s, 1, i), (e, -1, i)]
    for s, e in gaps:
        points += [(s, 2, -1), (e, -2, -1)]
    points.sort(key=lambda p: p[0])
    open_spans: dict[int, tuple[float, str]] = {}
    totals: dict[str, float] = {}
    in_gap, last = 0, None
    for t, kind, i in points:
        if in_gap and t > last:
            name = max(open_spans.values())[1] if open_spans else "none"
            totals[name] = totals.get(name, 0.0) + (t - last)
        last = t
        if kind == 1:
            open_spans[i] = (spans[i][1], spans[i][0])
        elif kind == -1:
            open_spans.pop(i, None)
        else:
            in_gap += kind // 2
    return totals


def reduce_planes(planes, *, window_span: str, span_prefix: str,
                  top: int = TOP) -> TraceSummary:
    planes = list(planes)
    window = None
    spans = []
    for plane in planes:
        if DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            for name, s, e in _events(line):
                if name == window_span:
                    window = (s, e)
                elif name.startswith(span_prefix):
                    spans.append((name[len(span_prefix):], s, e))
    if window is None:
        raise ValueError(f"the trace has no {window_span!r} span")
    lo, hi = window

    busy_per_chip, all_busy, per_op = [], [], {}
    for plane in planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        lines = {line.name: line for line in plane.lines}
        modules = sorted(_events(lines[MODULE_LINE]), key=lambda m: m[1]) \
            if MODULE_LINE in lines else []
        starts = [m[1] for m in modules]
        ivs = []
        for name, s, e in (_events(lines[OP_LINE])
                           if OP_LINE in lines else ()):
            c = _clip(s, e, lo, hi)
            if c is None:
                continue
            ivs.append(c)
            k = bisect.bisect_right(starts, s) - 1
            owner = short_module(modules[k][0]) \
                if k >= 0 and s < modules[k][2] else "?"
            key = f"{owner}:{short_op(name)}"
            per_op[key] = per_op.get(key, 0.0) + (c[1] - c[0])
        if ivs:
            merged = merge(ivs)
            busy_per_chip.append(sum(e - s for s, e in merged))
            all_busy.extend(merged)
    busy_ns = (sum(busy_per_chip) / len(busy_per_chip)
               if busy_per_chip else 0.0)

    gaps, cursor = [], lo
    for s, e in merge(all_busy):
        if s > cursor:
            gaps.append((cursor, s))
        cursor = max(cursor, e)
    if hi > cursor:
        gaps.append((cursor, hi))
    idle = sorted(idle_by_span(gaps, spans).items(), key=lambda kv: -kv[1])
    ops = sorted(per_op.items(), key=lambda kv: -kv[1])

    return TraceSummary(
        window_s=(hi - lo) / 1e9,
        busy_s=busy_ns / 1e9,
        top_ops=[[n, t / 1e9] for n, t in ops[:top]],
        idle_gaps=[[n, t / 1e9] for n, t in idle[:top]])


def reduce_dir(log_dir: str, **kwargs) -> TraceSummary:
    """Reduce the one trace ``jax.profiler`` wrote under ``log_dir``."""
    import jax

    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise ValueError(f"expected one trace under {log_dir}, found "
                         f"{len(files)}")
    data = jax.profiler.ProfileData.from_file(files[0])
    return reduce_planes(data.planes, **kwargs)
