"""Host milliseconds per call in the backend's level executor: the time
of the program's ``pud/backend.run_fused`` span (image upload, and per
level the gather, kernel and scatter), over the window's calls (profiler
trace; see ``program_trace.py``)."""

from program_trace import span_per_call


def read(reading):
    return span_per_call(reading, "backend.run_fused", "inclusive_s", 1e3)
