"""Seeded mutations of fused schedules — the analyzer's negative gate.

A verifier that has only ever seen correct compiler output is
untested.  This module applies small, *realistic* corruptions to the
:class:`~repro.compile.schedule.Schedule` the level walk executes —
each one a bug class the scheduler could plausibly grow — and CI
asserts that :func:`repro.analyze.cert.certify` rejects every
applicable mutation on the golden fixtures (``python -m repro.analyze
--mutate``).

Each mutation returns a new schedule (schedules are immutable, so the
input is never modified) or ``None`` when the schedule has no site for
it (e.g. ``shrink_param`` on a program without MAJ ops).  Mutations
prefer sites in the *latest* applicable level.

The six classes and the finding each must trigger:

================  ======================================================
``swap_dst``      two differing ops of one level exchange destinations
                  → ``SCHED_OP_SET`` / ``EQ_SCHEDULE_ROW``
``hoist``         an op moved one level earlier, next to the op it
                  reads from → ``RACE_RAW_LEVEL``
``drop_op``       one op removed → ``SCHED_OP_SET``
``retarget_src``  one source pointed at another row → ``SCHED_OP_SET``
``waw``           one op made to write another same-level op's row
                  with a different value → ``RACE_WAW_LEVEL``
``shrink_param``  a MAJ group's arity below one of its ops' →
                  ``SCHED_GROUP``
================  ======================================================
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Iterator, Optional

from repro.compile.schedule import FusedGroup, Schedule
from repro.pud.isa import PUDOp

Levels = list[list[FusedGroup]]


def _levels(sched: Schedule) -> Levels:
    return [list(lvl) for lvl in sched.levels]


def _schedule(levels: Levels) -> Schedule:
    return Schedule(tuple(tuple(g for g in lvl if g.ops) for lvl in levels
                          if any(g.ops for g in lvl)))


def _sites(sched: Schedule) -> Iterator[tuple[int, int, int, PUDOp]]:
    """(level, group, op index, op), latest level first."""
    for li in range(sched.n_levels - 1, -1, -1):
        for gi, g in enumerate(sched.levels[li]):
            for oi, op in enumerate(g.ops):
                yield li, gi, oi, op


def _replace_op(levels: Levels, li: int, gi: int, oi: int,
                op: Optional[PUDOp]) -> None:
    """Put ``op`` at ``(li, gi, oi)``, or drop the op there when None."""
    g = levels[li][gi]
    ops = list(g.ops)
    if op is None:
        del ops[oi]
    else:
        ops[oi] = op
    levels[li][gi] = dataclasses.replace(g, ops=tuple(ops))


def _value(op: PUDOp) -> tuple:
    return (op.kind, op.x, op.srcs)


def swap_dst(sched: Schedule) -> Optional[Schedule]:
    """Exchange the destinations of two differing ops of one level."""
    for li, gi, oi, op in _sites(sched):
        for gj, g in enumerate(sched.levels[li]):
            for oj, other in enumerate(g.ops):
                if (other.dsts != op.dsts
                        and _value(other) != _value(op)):
                    levels = _levels(sched)
                    _replace_op(levels, li, gi, oi,
                                dataclasses.replace(op, dsts=other.dsts))
                    _replace_op(levels, li, gj, oj,
                                dataclasses.replace(other, dsts=op.dsts))
                    return _schedule(levels)
    return None


def hoist(sched: Schedule) -> Optional[Schedule]:
    """Move an op one level earlier, into the level that writes a row
    it reads (a same-level RAW)."""
    for li, gi, oi, op in _sites(sched):
        if li == 0:
            continue
        below = {d for g in sched.levels[li - 1] for o in g.ops
                 for d in o.dsts}
        if not below & set(op.srcs):
            continue
        levels = _levels(sched)
        _replace_op(levels, li, gi, oi, None)
        g = sched.levels[li][gi]
        for gj, h in enumerate(levels[li - 1]):
            if h.kind == g.kind:
                levels[li - 1][gj] = FusedGroup(
                    g.kind, max(g.param, h.param), (*h.ops, op))
                break
        else:
            levels[li - 1].append(FusedGroup(g.kind, g.param, (op,)))
        return _schedule(levels)
    return None


def drop_op(sched: Schedule) -> Optional[Schedule]:
    """Remove one op: its write silently vanishes."""
    for li, gi, oi, _ in _sites(sched):
        levels = _levels(sched)
        _replace_op(levels, li, gi, oi, None)
        return _schedule(levels)
    return None


def retarget_src(sched: Schedule) -> Optional[Schedule]:
    """Point an op's first source at another row the schedule touches."""
    rows = sorted({r for _, _, _, op in _sites(sched)
                   for r in (*op.srcs, *op.dsts)})
    for li, gi, oi, op in _sites(sched):
        other = next((r for r in rows if r != op.srcs[0]), None)
        if other is None:
            return None
        levels = _levels(sched)
        _replace_op(levels, li, gi, oi, dataclasses.replace(
            op, srcs=(other, *op.srcs[1:])))
        return _schedule(levels)
    return None


def waw(sched: Schedule) -> Optional[Schedule]:
    """Make an op also write the first destination of another op of its
    level that computes a different value."""
    for li, gi, oi, op in _sites(sched):
        for g in sched.levels[li]:
            for other in g.ops:
                d = other.dsts[0]
                if _value(other) != _value(op) and d not in op.dsts:
                    levels = _levels(sched)
                    _replace_op(levels, li, gi, oi, dataclasses.replace(
                        op, dsts=(d, *op.dsts[1:])))
                    return _schedule(levels)
    return None


def shrink_param(sched: Schedule) -> Optional[Schedule]:
    """Give a MAJ group an arity two below its widest op's."""
    for li in range(sched.n_levels - 1, -1, -1):
        for gi, g in enumerate(sched.levels[li]):
            if g.kind == "MAJ":
                levels = _levels(sched)
                levels[li][gi] = dataclasses.replace(g, param=g.param - 2)
                return _schedule(levels)
    return None


#: Name -> mutation, in the order CI reports them.
MUTATIONS: dict[str, Callable[[Schedule], Optional[Schedule]]] = {
    "swap_dst": swap_dst,
    "hoist": hoist,
    "drop_op": drop_op,
    "retarget_src": retarget_src,
    "waw": waw,
    "shrink_param": shrink_param,
}


def apply_mutation(sched: Schedule, name: str) -> Optional[Schedule]:
    """Apply one named mutation; None when the schedule has no site."""
    return MUTATIONS[name](sched)
