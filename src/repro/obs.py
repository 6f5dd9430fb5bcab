"""Host spans at the layer boundaries of the program.

Every span is a :class:`jax.profiler.TraceAnnotation`: while a profiler
session runs (``jax.profiler.start_trace`` ... ``stop_trace``), the
profiler writes it into the same trace as the device's ``XLA Ops``
lines, on one clock, so each gap in device work can be laid to what the
host was doing.  While no profiler session runs a span is inert: it
keeps nothing and writes nothing.  This module keeps no state, no
buffer, no file and no switch of its own, and no span waits for the
device: a span's duration is host time, and device time comes from the
device's own lines of the same trace.

The spans, with their names stable.  Each opens once per call at its
site, except ``compile.sync``, which opens once per host read; they nest
on the calling thread.  The metric that reads each is a reader of the
benchmark (``benchmarks/chip/metrics``).

``pud/elementwise``
    Opens in ``DramSession.elementwise``: the whole call as the program
    sees it.  Every reader below reads nothing from a trace without it.
``pud/compile``
    Opens in ``compile.trace.compile_elementwise``: packing both
    operands, ``trace_planes``, ``initial_state``.  Read by
    ``trace_ms.arith`` (its time).
``pud/compile.sync``
    Opens in ``compile.trace.Tracer`` around each host read of a
    plane's value (``_key``, and the copy in ``row_of``).  Read by
    ``host_syncs_per_call.arith`` (its count).  The count is of reads
    the tracing mechanism makes, not of bytes moved: a read that JAX
    serves from an array's cached host copy counts as one read.
``pud/session.run_fused``
    Opens in ``DramSession.run_fused``: validation, ``program_key``, the
    compile cache's schedule, lowering and certificate, then the
    backend.  Read by ``session_ms.arith`` (its time outside the
    backend's span).
``pud/backend.run_fused``
    Opens in ``PallasBackend.run_fused``, fused mode and the megakernel
    route alike: the level executor, from the image upload through each
    level's gather, kernel and scatter.  Read by ``level_exec_ms.arith``
    (its time).

The kernels' device time is read from the name each ``pallas_call``
gives its device op (``KERNEL_NAME`` in each kernel module of
:mod:`repro.kernels`), not from a span: ``kernel_device_ms.arith``
reads it.
"""

from __future__ import annotations

import jax

#: Every span the program writes starts with this prefix.
SPAN_PREFIX = "pud/"


def span(name: str) -> jax.profiler.TraceAnnotation:
    """A host span named ``pud/<name>``, for a ``with`` block."""
    return jax.profiler.TraceAnnotation(SPAN_PREFIX + name)
