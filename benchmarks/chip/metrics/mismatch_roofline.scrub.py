"""The mismatch passes' share of their HBM roofline, in %: 8 bytes per
compared word (``x`` per word voted) over 819 GB/s times the device
seconds of ``mismatch_popcount`` (profiler trace; see
``scrub_trace.py``)."""

from scrub_trace import mismatch_roofline_pct as read  # noqa: F401
