"""Step dispatches per call: the count of the program's ``pud/erase.step``
spans over the window's calls (profiler trace; see ``erase_trace.py``).
One a bank: 16 for the rank."""

from erase_trace import span_per_call


def read(reading):
    return span_per_call(reading, "erase.step", "count")
