"""Host milliseconds per call in the session layer: the self time of the
program's ``pud/session.run_fused`` span (validation, the program's
hash, the compile cache's schedule and certificate), its backend's span
left out, over the window's calls (profiler trace; see
``program_trace.py``)."""

from program_trace import span_per_call


def read(reading):
    return span_per_call(reading, "session.run_fused", "self_s", 1e3)
