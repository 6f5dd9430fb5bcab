"""Dataflow-equivalence certification by symbolic execution.

The differential suites sample random programs; this pass *proves* a
specific compiled artifact.  Both execution forms of a program — the
sequential op stream and the hazard-leveled
:class:`~repro.compile.schedule.Schedule`, executed as the level walk
executes it — are symbolically executed over an abstract dataflow
domain, and the final per-row values must be *structurally identical*
terms.

The domain is a hash-consed term algebra:

* ``Input(r)`` — row ``r``'s initial-state value,
* ``Const0`` / ``Const1`` — the all-zero / all-one planes,
* ``Not(v)`` — bitwise complement, with ``Not(Not(v)) = v`` and
  constant folding,
* ``Maj(v_1..v_k)`` — bit-position majority, canonicalized by operand
  *sort* (majority is symmetric; duplicates are preserved — input
  replication is semantically meaningful), with one sound rewrite,
  **arity-padding cancellation**: matched (Const0, Const1) operand
  pairs are removed — the exact ``MAJ_k == MAJ_{k+2m}(.., 0*m, 1*m)``
  identity the walk relies on when it pads each MAJ op to its group's
  arity (each pair adds one to the popcount and one to the threshold).

The rewrite is a true identity of the concrete semantics, so equal
normal forms imply bit-equal execution on every backend; it is exactly
the transformation the walk performs, so correct compiler output always
normalizes back onto the source program's terms — any surviving
structural difference is a genuine compilation bug (or an injected
mutation: see :mod:`repro.analyze.mutate`).

Hazard semantics match the executors: schedule execution reads the
*level-entry* state and commits writes at level exit, while the
sequential reference commits op by op.  A leveling bug therefore shows
up as a term mismatch here even if the race pass missed it.
"""

from __future__ import annotations

from typing import Optional

from repro.analyze.report import ERROR, Finding
from repro.compile.schedule import Schedule
from repro.pud.isa import Program

_SKIP_KINDS = ("FRAC", "WR", "RD")


class SymbolicDomain:
    """Hash-consed term interner: structural equality is id equality."""

    def __init__(self):
        self._ids: dict[tuple, int] = {}
        self._terms: list[tuple] = []
        self.const0 = self._intern(("const", 0))
        self.const1 = self._intern(("const", 1))

    def _intern(self, key: tuple) -> int:
        vid = self._ids.get(key)
        if vid is None:
            vid = len(self._terms)
            self._ids[key] = vid
            self._terms.append(key)
        return vid

    # ----------------------------------------------------- constructors
    def input(self, row: int) -> int:
        return self._intern(("in", row))

    def not_(self, v: int) -> int:
        if v == self.const0:
            return self.const1
        if v == self.const1:
            return self.const0
        term = self._terms[v]
        if term[0] == "not":
            return term[1]           # Not(Not(v)) = v
        return self._intern(("not", v))

    def maj(self, operands: tuple[int, ...]) -> int:
        """Canonical majority term (see module docstring rewrites)."""
        ops = list(operands)
        # Arity-padding cancellation: drop matched (0, 1) pairs.
        pairs = min(ops.count(self.const0), ops.count(self.const1))
        for _ in range(pairs):
            ops.remove(self.const0)
            ops.remove(self.const1)
        if not ops:
            raise ValueError("majority over zero operands")
        return self._intern(("maj", tuple(sorted(ops))))

    def render(self, v: int, depth: int = 3) -> str:
        """Short human form of a term, for finding messages."""
        kind, *rest = self._terms[v]
        if kind == "const":
            return str(rest[0])
        if kind == "in":
            return f"in[{rest[0]}]"
        if depth <= 0:
            return "..."
        if kind == "not":
            return f"~{self.render(rest[0], depth - 1)}"
        args = ", ".join(self.render(o, depth - 1) for o in rest[0][:5])
        more = ", ..." if len(rest[0]) > 5 else ""
        return f"maj({args}{more})"


def _apply_op(dom: SymbolicDomain, op, read, pad: int = 0) -> Optional[int]:
    """The value an op writes to every destination, reading via ``read``;
    a MAJ votes ``pad`` constant (0, 1) pairs besides its sources."""
    if not op.dsts or op.kind in _SKIP_KINDS:
        return None
    if op.kind == "MAJ":
        return dom.maj(tuple(read(s) for s in op.srcs)
                       + (dom.const0, dom.const1) * pad)
    if op.kind == "NOT":
        return dom.not_(read(op.srcs[0]))
    if op.kind in ("COPY", "MRC"):
        return read(op.srcs[0])
    return None  # unknown kinds are reported by the race pass


def exec_program(dom: SymbolicDomain, program: Program,
                 n_rows: Optional[int] = None) -> list[int]:
    """Sequential symbolic execution — the reference dataflow."""
    n = n_rows if n_rows is not None else program.n_rows()
    state = [dom.input(r) for r in range(n)]
    for op in program.ops:
        v = _apply_op(dom, op, lambda s: state[s])
        if v is None:
            continue
        for d in op.dsts:
            state[d] = v
    return state


def exec_schedule(dom: SymbolicDomain, sched: Schedule,
                  n_rows: int) -> list[int]:
    """Level-at-a-time execution as the walk runs it: entry-state reads,
    exit commits, each MAJ padded to its group's arity (a group that
    cannot be padded so is the race pass's ``SCHED_GROUP`` finding)."""
    state = [dom.input(r) for r in range(n_rows)]
    for lvl in sched.levels:
        entry = list(state)
        for g in lvl:
            for op in g.ops:
                pad = (g.param - len(op.srcs)) // 2 if g.kind == "MAJ" else 0
                v = _apply_op(dom, op, lambda s: entry[s], max(pad, 0))
                if v is None:
                    continue
                for d in op.dsts:
                    state[d] = v
    return state


def equivalence_findings(program: Program, sched: Optional[Schedule] = None,
                         *, where: str = "program") -> list[Finding]:
    """Prove the schedule's dataflow equal to the source program's.

    One shared :class:`SymbolicDomain` interns both executions, so
    comparison is integer equality per row.  Findings carry rendered
    terms for the first few mismatching rows.
    """
    out: list[Finding] = []
    dom = SymbolicDomain()
    n_rows = program.n_rows()
    ref = exec_program(dom, program, n_rows)

    if sched is not None:
        got = exec_schedule(dom, sched, n_rows)
        for r in range(n_rows):
            if got[r] != ref[r]:
                out.append(Finding(
                    "equivalence", ERROR, "EQ_SCHEDULE_ROW",
                    f"{where}: schedule computes row {r} = "
                    f"{dom.render(got[r])}, program computes "
                    f"{dom.render(ref[r])}", where=f"row {r}"))
    return out
