"""Static analysis of PUD programs and their compiled artifacts.

Three passes over the compile pipeline's two artifact forms
(:class:`~repro.pud.isa.Program` op streams and the fused
:class:`~repro.compile.schedule.Schedule` levels the level walk
executes):

* **races** (:mod:`repro.analyze.races`) — structural op validation,
  group well-formedness (kinds, MAJ padding arity, MRC fan-out) and
  intra-level RAW/WAW hazards;
* **liveness** (:mod:`repro.analyze.liveness`) — per-row lifetime
  intervals, dead ops, inferred inputs, and
  :class:`~repro.session.rows.RowAllocator` audits (use-after-free,
  leaks);
* **equivalence** (:mod:`repro.analyze.equiv`) — symbolic execution
  over a hash-consed term algebra proving the schedule, run as the
  walk runs it, computes exactly the source program's dataflow
  (including the MAJ arity-padding identity).

:func:`certify` drives all three and freezes a content-hashed
:class:`Certificate`; :class:`~repro.session.cache.CompileCache`
memoizes certificates so every :class:`~repro.session.DramSession`
execution is certified at one-analysis-per-program-content cost.
``python -m repro.analyze`` lints the golden fixtures, serve tick
programs, and sweep chunk programs, and runs the seeded schedule
mutation negative gate (:mod:`repro.analyze.mutate`).
"""

from repro.analyze.cert import (ANALYZER_VERSION, Certificate,
                                CertificationError, analyze, certify,
                                schedule_digest)
from repro.analyze.equiv import (SymbolicDomain, equivalence_findings,
                                 exec_program, exec_schedule)
from repro.analyze.liveness import (RowLifetime, allocator_findings,
                                    lifetimes, liveness_findings)
from repro.analyze.mutate import MUTATIONS, apply_mutation
from repro.analyze.races import (check_ops, iter_level_ops,
                                 schedule_findings)
from repro.analyze.report import (ERROR, WARNING, AnalysisReport, Finding)

__all__ = [
    "ANALYZER_VERSION", "AnalysisReport", "Certificate",
    "CertificationError", "ERROR", "Finding", "MUTATIONS", "RowLifetime",
    "SymbolicDomain", "WARNING", "allocator_findings", "analyze",
    "apply_mutation", "certify", "check_ops", "equivalence_findings",
    "exec_program", "exec_schedule", "iter_level_ops", "lifetimes",
    "liveness_findings", "schedule_digest", "schedule_findings",
]
