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
site, except ``compile.trace`` and ``compile.sync``, which open once per
miss of the compile cache, the
two ``backend.levels_*`` spans, of which a fused call opens at most one,
and the ``scrub.*`` and ``erase.*`` spans, which open once per tile;
they nest on the calling thread.  The metric that reads each is a reader of the
benchmark (``benchmarks/chip/metrics``).

``pud/elementwise``
    Opens in ``DramSession.elementwise``: the whole call as the program
    sees it.  Every reader below reads nothing from a trace without it.
``pud/compile``
    Opens in ``compile.trace.compile_elementwise``: the lookup of the
    (op, lanes, tier, n_act) in the compile cache and the dispatch of
    the jitted image build.  Read by ``trace_ms.arith`` (its time).
``pud/compile.trace``
    Opens inside ``pud/compile`` on a miss of the compile cache: tracing
    the op once (``trace_planes``); the image build compiles on its
    first dispatch, after it.  Read by ``compile_traces_per_call.arith``
    (its count).
``pud/compile.sync``
    Opens inside ``pud/compile.trace`` around its one host read, of the
    origin IDs of every gate's operands (``Tracer.allocate``).  Read by
    ``host_syncs_per_call.arith`` (its count).  No operand value is
    read: a call that hits the cache opens neither span.
``pud/session.run_fused``
    Opens in ``DramSession.run_fused``: validation, ``program_key``, the
    compile cache's schedule and certificate, then the backend.  Read by
    ``session_ms.arith`` (its time outside the backend's span).
``pud/backend.run_fused``
    Opens in ``PallasBackend.run_fused``: the level executor, the image
    upload and the level walk (eager, op by op, or one dispatch of the
    jitted walk).  Read by ``level_exec_ms.arith`` (its time).
``pud/backend.levels_build``
    Opens inside ``pud/backend.run_fused`` on the second sighting of a
    schedule: building its jitted level walk (trace and XLA compile) and
    the first run of it.  Read by no metric: the benchmark's cells pay
    it in set-up.
``pud/backend.levels_jit``
    Opens inside ``pud/backend.run_fused`` around each later dispatch of
    a jitted level walk.  Read by ``level_jit_per_call.arith`` (its
    count).  A call that opens neither ran the walk eagerly: the
    schedule's first sighting.

``pud/service.scrub``
    Opens in ``PudService.scrub``: one scrub of a resident replica set,
    the queue, admission and batcher included, and the final read of
    the counts.  Read by ``scrub_host_ms.scrub`` (its time less that of
    ``pud/backend.run_fused`` and ``pud/scrub.verify``, which no longer
    open inside it, so ``level_exec_ms.scrub`` and ``verify_ms.scrub``
    read 0), and every ``*.scrub`` reader reads nothing from a trace
    without it.
``pud/scrub.tile``
    Opens in ``serve.scrub.scrub`` around each tile: its one step and
    the replay of the step's launches on the backend's counters.  Read
    by ``tiles_per_call.scrub`` (its count).
``pud/scrub.step``
    Opens inside ``pud/scrub.tile`` around the tile's one dispatch: the
    jitted step that builds the tile's image, runs the walk, writes the
    vote back and counts each replica's wrong bits.  Read by
    ``steps_per_tile.scrub`` (its count over that of ``pud/scrub.tile``).
``pud/service.erase``
    Opens in ``PudService.erase``: one wipe of a resident region, the
    queue, admission and batcher included, until the device has
    finished it.  Read by ``erase_host_ms.erase`` (its time), and every
    ``*.erase`` reader reads nothing from a trace without it.
``pud/erase.tile``
    Opens in ``serve.region.erase`` around each bank: its one step and
    the replay of the step's launches on the backend's counters.
``pud/erase.step``
    Opens inside ``pud/erase.tile`` around the bank's one dispatch: the
    jitted step that builds the bank's image, runs the walk of the
    bank's erase program and writes the wiped rows back.  Read by
    ``steps_per_call.erase`` (its count).

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
