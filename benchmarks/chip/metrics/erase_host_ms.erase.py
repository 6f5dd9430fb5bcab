"""Host milliseconds per call in ``pud/service.erase``: queue, admission,
batcher, the session's lookups, one step dispatch a bank and the wait
for the device (profiler trace; see ``erase_trace.py``)."""

from erase_trace import span_per_call


def read(reading):
    return span_per_call(reading, "service.erase", "inclusive_s", 1e3)
