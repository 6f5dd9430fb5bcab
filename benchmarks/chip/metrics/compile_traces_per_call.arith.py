"""Traces of an elementwise program per call: the count of the
program's ``pud/compile.trace`` spans over the window's calls (profiler
trace; see ``program_trace.py``).  0 where every call of the window hits
the compile cache; a program without the span reads 0 too."""

from program_trace import span_per_call


def read(reading):
    return span_per_call(reading, "compile.trace", "count")
