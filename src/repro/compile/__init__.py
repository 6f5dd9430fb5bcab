"""``repro.compile``: program fusion for PUD instruction streams.

Two layers:

* :mod:`repro.compile.schedule` — partition an addressed
  :class:`~repro.pud.isa.Program` into hazard-respecting dependency
  levels and fuse each level's MAJX / Multi-RowCopy ops into single
  batched kernel dispatches (the plan behind
  :meth:`repro.backends.base.Backend.run_fused`);
* :mod:`repro.compile.trace` — lower §8.1 ``BitSerial`` gate streams to
  addressed, fusable Programs (SSA row allocation over a subarray
  image), traced once per shape by plane origin, never by value.

Consumers: the ``pallas`` backend executes schedules, ``pud.arith``
routes batch-native executors through :func:`compile_elementwise`, the
sweep runner fuses characterization chunks, the serve engine's integrity
vote is one fused program, and ``pud.offload`` prices dispatch-count
reductions.  :class:`repro.session.DramSession` is the layer above:
it memoizes :func:`build_schedule` by program content, so repeated
programs skip straight to fused execution.  See docs/ARCHITECTURE.md
("Program compilation & fusion" and "Session layer").
"""

from repro.compile.schedule import (FusedGroup, Schedule, build_schedule,
                                    dependency_levels)
from repro.compile.trace import (CompiledProgram, Trace, Tracer,
                                 compile_elementwise, trace_planes)

__all__ = [
    "CompiledProgram", "FusedGroup", "Schedule", "Trace", "Tracer",
    "build_schedule", "compile_elementwise", "dependency_levels",
    "trace_planes",
]
