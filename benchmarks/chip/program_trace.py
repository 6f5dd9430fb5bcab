"""Reduce the program's own host spans and every device op of a trace.

The program opens host spans under ``pud/`` at its layer boundaries
(``repro.obs``) and names each kernel's device op after its
``pallas_call`` (``KERNEL_NAME`` in each kernel module).  This module
reduces them for the readers of the program's layers:

* ``spans`` maps each span name under the prefix (the prefix left out)
  to :class:`SpanTotals`: ``count`` of the spans that start inside the
  window; ``inclusive_s``, their time clipped to the window; ``self_s``,
  that time less the part covered by the spans opened inside them on
  the same thread line; ``idle_s``, the device-idle time for which the
  span was the innermost one open, as ``trace_reduce.idle_by_span``
  attributes it.  A name appears if the trace holds such a span at all.
* ``op_s`` is device seconds per ``<XLA module>:<op>``, every op.

Both come from ``trace_reduce.reduce_planes`` run over the program's
prefix with no cut on the op list, plus the span totals counted here.

The harness reduces each traced window with
``trace_reduce.reduce_planes`` and hands the summary to the readers.
Importing this module (each reader of a program metric does) wraps that
function, so each summary it returns also carries ``program``: the
:class:`ProgramTrace` of the same planes.  The summary's own fields stay
what the unwrapped function returns, and ``harness.py`` and
``trace_reduce.py`` stay as they are.  A run without a trace loads no
per-layer reader, so nothing is wrapped there.
"""

from __future__ import annotations

import dataclasses
import functools
import re
import sys

import trace_reduce

PROGRAM_PREFIX = "pud/"

#: The span every instrumented call opens; a trace without one comes
#: from a program that has no spans, and its readings are ``None``.
CALL_SPAN = "elementwise"

#: The ``name=`` of every ``pallas_call`` of the program.
KERNEL_NAMES = ("majx_csa", "rowcopy_fanout", "mismatch_popcount",
                "bitserial_add", "megakernel_levels")
_KERNEL_OP = re.compile(r"^(%s)(\.\d+)?$" % "|".join(KERNEL_NAMES))


@dataclasses.dataclass
class SpanTotals:
    count: int = 0
    inclusive_s: float = 0.0
    self_s: float = 0.0
    idle_s: float = 0.0


@dataclasses.dataclass
class ProgramTrace:
    spans: dict       # name under the prefix -> SpanTotals
    op_s: dict        # "<module>:<op>" -> device seconds


def _host_lines(planes, window_span: str, prefix: str):
    """The window and, per host thread line, its prefixed spans as
    (name under the prefix, start ns, end ns)."""
    window, lines = None, []
    for plane in planes:
        if trace_reduce.DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            spans = []
            for name, s, e in trace_reduce._events(line):
                if name == window_span:
                    window = (s, e)
                elif name.startswith(prefix):
                    spans.append((name[len(prefix):], s, e))
            if spans:
                lines.append(spans)
    return window, lines


def _span_totals(lines, lo: float, hi: float) -> dict:
    totals: dict[str, SpanTotals] = {}
    for spans in lines:
        # Parents sort before the children they hold; on one thread the
        # children of a span are disjoint, so each child's clipped time
        # comes off its direct parent's self time once.
        stack: list = []
        for name, s, e in sorted(spans, key=lambda x: (x[1], -x[2])):
            t = totals.setdefault(name, SpanTotals())
            while stack and stack[-1][0] <= s:
                stack.pop()
            inside = lo <= s < hi
            if inside:
                held = (min(e, hi) - s) / 1e9
                t.count += 1
                t.inclusive_s += held
                t.self_s += held
                if stack and stack[-1][1] is not None:
                    stack[-1][1].self_s -= held
            stack.append((e, t if inside else None))
    return totals


def reduce_program(planes, *, window_span: str) -> ProgramTrace:
    planes = list(planes)
    every = _reduce_planes(planes, window_span=window_span,
                           span_prefix=PROGRAM_PREFIX, top=sys.maxsize)
    (lo, hi), lines = _host_lines(planes, window_span, PROGRAM_PREFIX)
    spans = _span_totals(lines, lo, hi)
    for name, idle in every.idle_gaps:
        if name in spans:
            spans[name].idle_s = idle
    return ProgramTrace(spans, dict(every.top_ops))


def _with_program(reduce):
    @functools.wraps(reduce)
    def reduce_planes(planes, **kwargs):
        planes = list(planes)
        summary = reduce(planes, **kwargs)
        summary.program = reduce_program(
            planes, window_span=kwargs["window_span"])
        return summary

    return reduce_planes


_reduce_planes = trace_reduce.reduce_planes
trace_reduce.reduce_planes = _with_program(_reduce_planes)


# ----------------------------------------------------------- readings
def program_of(reading):
    """The reading's :class:`ProgramTrace`, or ``None`` where there is no
    trace or the trace holds no ``pud/elementwise`` span."""
    program = getattr(reading.trace, "program", None)
    if program is None or CALL_SPAN not in program.spans:
        return None
    return program


def span_per_call(reading, name: str, field: str, scale: float = 1.0):
    """``field`` of span ``name`` summed over the window, per call; a
    span that never opened reads 0."""
    program = program_of(reading)
    if program is None or not reading.records:
        return None
    total = getattr(program.spans.get(name, SpanTotals()), field)
    return scale * total / len(reading.records)


def is_kernel_op(key: str) -> bool:
    """Whether ``<module>:<op>`` is one of the program's kernels."""
    return bool(_KERNEL_OP.match(key.partition(":")[2]))


def kernel_ms_per_call(reading):
    """Device milliseconds of the kernels' ops, per call."""
    program = program_of(reading)
    if program is None or not reading.records:
        return None
    total = sum(t for key, t in program.op_s.items() if is_kernel_op(key))
    return 1e3 * total / len(reading.records)
