"""Host milliseconds per call in the scrub outside the level executor
and the mismatch passes: ``pud/service.scrub`` less
``pud/backend.run_fused`` and ``pud/scrub.verify`` (queue, admission,
batcher, tile images, the session's lookups, write-back), over the
window's calls (profiler trace; see ``scrub_trace.py``)."""

from scrub_trace import host_ms_per_call as read  # noqa: F401
