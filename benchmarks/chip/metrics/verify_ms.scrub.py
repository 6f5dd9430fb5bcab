"""Host milliseconds per call in the mismatch passes: the time of the
program's ``pud/scrub.verify`` spans, one per tile, over the window's
calls (profiler trace; see ``scrub_trace.py``)."""

from scrub_trace import span_per_call


def read(reading):
    return span_per_call(reading, "scrub.verify", "inclusive_s", 1e3)
