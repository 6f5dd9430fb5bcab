"""Device milliseconds per call in the program's kernels: the device
time of every op named after a kernel's ``pallas_call``, over the
window's calls (profiler trace; see ``program_trace.py``)."""

from program_trace import kernel_ms_per_call as read  # noqa: F401
