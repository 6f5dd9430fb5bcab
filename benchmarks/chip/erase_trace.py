"""Readings of the region erase's program spans and kernel, per call.

Importing :mod:`program_trace` installs the reduction of the program's
``pud/`` spans and device ops (``summary.program``).  These readings
gate on ``pud/service.erase``, the span every erase call opens: a trace
without one comes from a program that has no resident erase, and reads
nothing.

The bytes of the ``rowcopy_fanout`` launches are here, beside the
reading that divides by the kernel's device time: each 32-row group's
source row is read once and written to its 31 destinations, so a call
moves 4 bytes for each source word read and 31 times that written, 4
bytes a word of the region (:func:`rowcopy_bytes`).
"""

from __future__ import annotations

import re

import program_trace

CALL_SPAN = "service.erase"

#: Rows one Multi-RowCopy covers: the source and its 31 destinations.
GROUP_ROWS = 32

_ROWCOPY_OP = re.compile(r"^rowcopy_fanout(\.\d+)?$")


def program_of(reading):
    """The reading's ``ProgramTrace``, or ``None`` without a trace or
    without a ``pud/service.erase`` span in it."""
    program = getattr(reading.trace, "program", None)
    if program is None or CALL_SPAN not in program.spans:
        return None
    return program


def span_per_call(reading, name: str, field: str, scale: float = 1.0):
    """``field`` of span ``name`` over the window, per call; a span that
    never opened reads 0."""
    program = program_of(reading)
    if program is None or not reading.records:
        return None
    totals = program.spans.get(name, program_trace.SpanTotals())
    return scale * getattr(totals, field) / len(reading.records)


def rowcopy_device_s(program) -> float:
    return sum(t for key, t in program.op_s.items()
               if _ROWCOPY_OP.match(key.partition(":")[2]))


def rowcopy_bytes(record) -> int:
    """Sources read plus 31 copies of each written: ``elements`` words
    of the region, one source word in every ``GROUP_ROWS``."""
    sources = record.elements // GROUP_ROWS
    return 4 * (sources + (GROUP_ROWS - 1) * sources)


def rowcopy_roofline_pct(reading):
    """The ``rowcopy_fanout`` launches' bytes over peak HBM bandwidth
    times the kernel's device seconds, in %."""
    program = program_of(reading)
    peak = reading.peaks.get("hbm_bytes_per_s")
    if program is None or not peak:
        return None
    t = rowcopy_device_s(program)
    if t <= 0:
        return None
    need = sum(rowcopy_bytes(r) for r in reading.records if r.error is None)
    return 100.0 * need / (peak * t)
