"""Host milliseconds per call in the compile layer: the time of the
program's ``pud/compile`` span (packing both operands, tracing the
gates, the initial image), over the window's calls (profiler trace; see
``program_trace.py``)."""

from program_trace import span_per_call


def read(reading):
    return span_per_call(reading, "compile", "inclusive_s", 1e3)
