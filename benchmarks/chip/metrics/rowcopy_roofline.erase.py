"""The ``rowcopy_fanout`` launches' share of their HBM roofline, in %:
each group's source read once and written to its 31 destinations, over
819 GB/s times the kernel's device seconds (profiler trace; see
``erase_trace.py``)."""

from erase_trace import rowcopy_roofline_pct as read  # noqa: F401
