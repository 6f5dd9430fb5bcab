"""Host milliseconds per call in the backend's level executor: the time
of the program's ``pud/backend.run_fused`` spans, one per tile, over the
window's calls (profiler trace; see ``scrub_trace.py``)."""

from scrub_trace import span_per_call


def read(reading):
    return span_per_call(reading, "backend.run_fused", "inclusive_s", 1e3)
