"""``repro.compile``: program fusion for PUD instruction streams.

Three layers:

* :mod:`repro.compile.schedule` — partition an addressed
  :class:`~repro.pud.isa.Program` into hazard-respecting dependency
  levels and fuse each level's MAJX / Multi-RowCopy ops into single
  batched kernel dispatches (the plan behind
  :meth:`repro.backends.base.Backend.run_fused`);
* :mod:`repro.compile.megakernel` — lower a whole Schedule to static
  level tables one Pallas dispatch scans end-to-end
  (``run_fused(mode="megakernel")``), with a VMEM column planner for
  images wider than the on-chip budget;
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

from repro.compile.megakernel import (MegaLowering, VmemPlan,
                                      lower_schedule, plan_vmem)
from repro.compile.schedule import (FusedGroup, Schedule, build_schedule,
                                    dependency_levels)
from repro.compile.trace import (CompiledProgram, Trace, Tracer,
                                 compile_elementwise, trace_planes)

__all__ = [
    "CompiledProgram", "FusedGroup", "MegaLowering", "Schedule", "Trace",
    "Tracer", "VmemPlan", "build_schedule", "compile_elementwise",
    "dependency_levels", "lower_schedule", "plan_vmem", "trace_planes",
]
