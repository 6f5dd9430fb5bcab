"""The vote's share of its HBM roofline, in %: ``(x + 1) * 4`` bytes per
word voted over 819 GB/s times the device seconds of every op of the
jitted level walk (gather, ``majx_csa`` and scatter together; profiler
trace; see ``scrub_trace.py``)."""

from scrub_trace import vote_roofline_pct as read  # noqa: F401
