"""Host reads of a plane's value per call while tracing the gates: the
count of the program's ``pud/compile.sync`` spans over the window's
calls (profiler trace; see ``program_trace.py``)."""

from program_trace import span_per_call


def read(reading):
    return span_per_call(reading, "compile.sync", "count")
