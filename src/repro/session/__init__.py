"""``repro.session``: the typed entry point for executing PUD work.

The paper's workloads — MAJX trees, Multi-RowCopy waves, §8.1
bit-serial arithmetic — are programs over subarray rows, and (PULSAR
-style) their value comes from *composing and re-running* those
programs.  :class:`DramSession` packages what every consumer needs for
that: a resolved backend + :class:`~repro.backends.context.
ExecutionContext`, typed :class:`Row`/:class:`PlaneGroup` allocation
with build-time validation, automatic scheduling through
:mod:`repro.compile`, and a content-hashed :class:`CompileCache` so a
repeated program skips straight to fused execution.

>>> from repro.session import DramSession
>>> sess = DramSession("pallas")                # or "oracle" / "sim"
>>> b = sess.program(rows=8)
>>> ops = b.input(planes)                       # typed row handles
>>> out = b.maj(ops[0], ops[1], ops[2])
>>> final = b.run()                             # validate -> cache -> fuse
>>> vals, prog = sess.elementwise("add", a, b_) # §8.1, compile-cached

``repro.backends.get_backend`` remains as the compat layer underneath;
sessions are how examples, the serve engine's integrity hooks, the
sweep runner, and the bench harness execute.
"""

from repro.session.builder import SessionProgram
from repro.session.cache import CacheStats, CompileCache, program_key
from repro.session.rows import (PlaneGroup, Row, RowAllocationError,
                                RowAllocator, SessionError)
from repro.session.session import DramSession
from repro.session.validate import ProgramValidationError, check_program

__all__ = [
    "CacheStats", "CompileCache", "DramSession", "PlaneGroup",
    "ProgramValidationError", "Row", "RowAllocationError", "RowAllocator",
    "SessionError", "SessionProgram", "check_program", "program_key",
]
