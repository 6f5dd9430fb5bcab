"""Readings of the scrub's program spans and kernels, per call.

Importing :mod:`program_trace` installs the reduction of the program's
``pud/`` spans and device ops (``summary.program``).  These readings
gate on ``pud/service.scrub``, the span every scrub call opens: a trace
without one comes from a program that has no scrub, and reads nothing.

Each kernel's bytes function is here, beside the reading that divides
by its device time:

* the vote reads ``x`` replica words and writes one vote per word voted,
  ``(x + 1) * 4`` bytes, which is the record's ``required_bytes``; its
  device time is every op of the jitted level walk's modules (the
  modules that run a ``majx_csa`` op: the gather, the kernel and the
  scatter together), since inside the walk the operands of the kernel
  op need not come from HBM;
* ``mismatch_popcount`` compares each replica's tile with the vote, 8
  bytes per compared word and ``x`` compared words per word voted.
"""

from __future__ import annotations

import re

import program_trace

CALL_SPAN = "service.scrub"

_MAJX_OP = re.compile(r"^majx_csa(\.\d+)?$")
_MISMATCH_OP = re.compile(r"^mismatch_popcount(\.\d+)?$")


def program_of(reading):
    """The reading's ``ProgramTrace``, or ``None`` without a trace or
    without a ``pud/service.scrub`` span in it."""
    program = getattr(reading.trace, "program", None)
    if program is None or CALL_SPAN not in program.spans:
        return None
    return program


def _total(program, name: str, field: str) -> float:
    return getattr(program.spans.get(name, program_trace.SpanTotals()),
                   field)


def span_per_call(reading, name: str, field: str, scale: float = 1.0):
    """``field`` of span ``name`` over the window, per call; a span that
    never opened reads 0."""
    program = program_of(reading)
    if program is None or not reading.records:
        return None
    return scale * _total(program, name, field) / len(reading.records)


def host_ms_per_call(reading):
    """Milliseconds of ``pud/service.scrub`` outside the level executor
    (``pud/backend.run_fused``) and the mismatch passes
    (``pud/scrub.verify``), per call."""
    program = program_of(reading)
    if program is None or not reading.records:
        return None
    rest = (_total(program, CALL_SPAN, "inclusive_s")
            - _total(program, "backend.run_fused", "inclusive_s")
            - _total(program, "scrub.verify", "inclusive_s"))
    return 1e3 * rest / len(reading.records)


def _op_name(key: str) -> str:
    return key.partition(":")[2]


def vote_device_s(program) -> float:
    """Device seconds of every op of the modules that run ``majx_csa``."""
    modules = {key.partition(":")[0] for key in program.op_s
               if _MAJX_OP.match(_op_name(key))}
    return sum(t for key, t in program.op_s.items()
               if key.partition(":")[0] in modules)


def mismatch_device_s(program) -> float:
    return sum(t for key, t in program.op_s.items()
               if _MISMATCH_OP.match(_op_name(key)))


def replicas_of(record) -> int:
    """``x``, from ``required_bytes == (x + 1) * 4 * elements``."""
    return record.required_bytes // (4 * record.elements) - 1


def vote_bytes(record) -> int:
    return record.required_bytes


def mismatch_bytes(record) -> int:
    return 8 * replicas_of(record) * record.elements


def _roofline_pct(reading, bytes_of, device_s):
    program = program_of(reading)
    peak = reading.peaks.get("hbm_bytes_per_s")
    if program is None or not peak:
        return None
    t = device_s(program)
    if t <= 0:
        return None
    need = sum(bytes_of(r) for r in reading.records if r.error is None)
    return 100.0 * need / (peak * t)


def vote_roofline_pct(reading):
    """The vote's bytes over peak HBM bandwidth times the walk's device
    seconds, in %."""
    return _roofline_pct(reading, vote_bytes, vote_device_s)


def mismatch_roofline_pct(reading):
    """The mismatch passes' bytes over peak HBM bandwidth times
    ``mismatch_popcount``'s device seconds, in %."""
    return _roofline_pct(reading, mismatch_bytes, mismatch_device_s)
