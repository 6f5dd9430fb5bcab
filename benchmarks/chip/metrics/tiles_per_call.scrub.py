"""Tiles voted per call: the count of the program's ``pud/scrub.tile``
spans over the window's calls (profiler trace; see ``scrub_trace.py``)."""

from scrub_trace import span_per_call


def read(reading):
    return span_per_call(reading, "scrub.tile", "count")
