"""Differential tests for the program-fusion layer (repro.compile).

The load-bearing guarantee: for ANY addressed Program, fused execution
(`run_fused`, level-batched kernel dispatches on ``pallas``) is
bit-identical to per-op interpretation (`run`) on every backend — the
oracle reference, the ideal behavioural sim, and pallas itself.  The
generator deliberately produces the hazards the scheduler must respect:
destination rows aliasing sources, rows rewritten many times, dead ops
whose results nothing reads, cost-only ops, and mixed MAJ arities inside
one dependency level.
"""

import os

import jax.numpy as jnp
import numpy as np
import pytest

from _proptest import rand_u32, sweep
from repro.backends import ExecutionContext, get_backend
from repro.compile import (build_schedule, compile_elementwise,
                           dependency_levels)
from repro.core import calibration as cal
from repro.pud.isa import Program

IDEAL = ExecutionContext(ideal=True)
ROWS, WORDS = 20, 8


# ------------------------------------------------------------ generator


def rand_program(rng, rows: int = ROWS, n_ops: int = 10) -> Program:
    """Random DAG-shaped addressed Program with deliberate hazards.

    Ops read/write one shared row space with replacement, so source
    aliasing, repeated rewrites of a row, and dead stores all occur;
    cost-only (addressless) and FRAC/WR/RD ops are mixed in to check
    they stay value-neutral under fusion.
    """
    prog = Program()
    for _ in range(n_ops):
        kind = rng.choice(["MAJ", "MAJ", "MAJ", "NOT", "COPY", "MRC",
                           "FRAC", "WR", "cost"])
        if kind == "cost":  # recorded for costing only: no addresses
            prog.emit("MAJ", x=3, n_act=4)
        elif kind == "MAJ":
            x = int(rng.choice([3, 5, 7]))
            srcs = tuple(int(r) for r in rng.integers(0, rows, x))
            n_dst = int(rng.integers(1, 3))
            dsts = tuple(int(r) for r in rng.integers(0, rows, n_dst))
            prog.emit("MAJ", x=x, n_act=cal.min_activation_for(x),
                      srcs=srcs, dsts=dsts)
        elif kind in ("NOT", "COPY"):
            prog.emit(kind, srcs=(int(rng.integers(0, rows)),),
                      dsts=tuple(int(r)
                                 for r in rng.integers(0, rows,
                                                       rng.integers(1, 3))))
        elif kind == "MRC":
            fan = int(rng.integers(1, 8))
            prog.emit("MRC", n_act=8, srcs=(int(rng.integers(0, rows)),),
                      dsts=tuple(int(r) for r in rng.integers(0, rows, fan)))
        elif kind == "FRAC":
            prog.emit("FRAC", dsts=(int(rng.integers(0, rows)),))
        else:
            prog.emit("WR")
    return prog


def _run_everywhere(prog: Program, state) -> dict[str, np.ndarray]:
    outs = {}
    for name in ("oracle", "sim", "pallas"):
        be = get_backend(name, IDEAL)
        outs[f"{name}/per_op"] = np.asarray(be.run(prog, state))
        outs[f"{name}/fused"] = np.asarray(be.run_fused(prog, state))
    return outs


# ----------------------------------------------------- differential sweep


#: Seeds of the differential sweep: cases 0-7 from 0x5EED, 8-15 from
#: 0x3E6A (the seeds of the former all-executors sweep).
_FUSED_RNGS = ([np.random.default_rng((0x5EED, i)) for i in range(8)]
               + [np.random.default_rng((0x3E6A, i)) for i in range(8)])


@pytest.mark.parametrize("rng", _FUSED_RNGS,
                         ids=[f"case{i}" for i in range(len(_FUSED_RNGS))])
def test_random_programs_fused_equals_per_op_everywhere(rng):
    prog = rand_program(rng)
    state = jnp.asarray(rand_u32(rng, ROWS, WORDS))
    outs = _run_everywhere(prog, state)
    want = outs["oracle/per_op"]
    for name, got in outs.items():
        assert (got == want).all(), name


def test_destination_aliasing_program():
    """An op overwriting its own source row, twice over."""
    prog = Program()
    prog.emit("MAJ", x=3, n_act=4, srcs=(0, 1, 2), dsts=(0,))  # dst in srcs
    prog.emit("MAJ", x=3, n_act=4, srcs=(0, 1, 2), dsts=(1,))  # reads new 0
    prog.emit("NOT", srcs=(1,), dsts=(1,))                     # in-place NOT
    prog.emit("MRC", n_act=4, srcs=(1,), dsts=(2, 0, 3))       # clobber 0
    rng = np.random.default_rng(1)
    state = jnp.asarray(rand_u32(rng, 4, WORDS))
    outs = _run_everywhere(prog, state)
    want = outs["oracle/per_op"]
    for name, got in outs.items():
        assert (got == want).all(), name
    # the in-place chain forces strictly increasing levels
    assert len(dependency_levels(prog)) == 4


def test_dead_ops_still_write_their_rows():
    """Dead stores (results never read) must still land in state."""
    prog = Program()
    prog.emit("MAJ", x=3, n_act=4, srcs=(0, 1, 2), dsts=(5,))  # dead
    prog.emit("COPY", srcs=(0,), dsts=(6,))                    # dead
    prog.emit("MAJ", x=3, n_act=4, srcs=(1, 2, 3), dsts=(4,))
    rng = np.random.default_rng(2)
    state = jnp.asarray(rand_u32(rng, 7, WORDS))
    pal = get_backend("pallas", IDEAL)
    got = np.asarray(pal.run_fused(prog, state))
    want = np.asarray(get_backend("oracle", IDEAL).run(prog, state))
    assert (got == want).all()
    assert not (got[5] == np.asarray(state)[5]).all()  # the store happened


def test_cost_only_program_fuses_to_identity():
    prog = Program()
    for _ in range(5):
        prog.emit("MAJ", x=5, n_act=8)
        prog.emit("NOT")
    assert build_schedule(prog).n_levels == 0
    state = jnp.asarray(rand_u32(np.random.default_rng(3), 4, 4))
    got = get_backend("pallas", IDEAL).run_fused(prog, state)
    assert (np.asarray(got) == np.asarray(state)).all()


# --------------------------------------------------- scheduler structure


def test_levels_respect_hazards_by_construction():
    """Every op's sources are written strictly before its level; no two
    same-level ops write one row."""
    rng = np.random.default_rng(4)
    prog = rand_program(rng, n_ops=30)
    levels = dependency_levels(prog)
    write_level: dict[int, int] = {}
    for i, ops in enumerate(levels):
        written_here: set[int] = set()
        for op in ops:
            for s in op.srcs:
                assert write_level.get(s, -1) < i  # RAW
            # WAW within a level: no row written by two *ops* (duplicate
            # dsts inside one op are legal — identical values).
            for d in set(op.dsts):
                assert d not in written_here
                written_here.add(d)
        for d in written_here:
            write_level[d] = i
    assert sum(len(ops) for ops in levels) == sum(
        1 for op in prog.ops
        if op.dsts and op.kind in ("MAJ", "NOT", "COPY", "MRC"))


def test_mixed_arity_level_is_one_dispatch():
    """MAJ3 + MAJ7 in one level fuse via 0/1 pair padding."""
    prog = Program()
    prog.emit("MAJ", x=3, n_act=4, srcs=(0, 1, 2), dsts=(8,))
    prog.emit("MAJ", x=7, n_act=8, srcs=(0, 1, 2, 3, 4, 5, 6), dsts=(9,))
    sched = build_schedule(prog)
    assert sched.n_levels == 1 and sched.n_dispatches() == 1
    rng = np.random.default_rng(5)
    state = jnp.asarray(rand_u32(rng, 10, WORDS))
    pal = get_backend("pallas", IDEAL)
    pal.reset_dispatches()
    got = np.asarray(pal.run_fused(prog, state))
    assert pal.dispatch_count == 1
    want = np.asarray(get_backend("oracle", IDEAL).run(prog, state))
    assert (got == want).all()


@sweep(n_cases=4, seed=0xD1FF)
def test_fused_launches_equal_schedule_groups(rng):
    """One launch per MAJ / MRC group of the schedule, whichever way the
    walk runs: eager, building the jitted walk, and jitted."""
    prog = rand_program(rng, n_ops=14)
    state = jnp.asarray(rand_u32(rng, ROWS, WORDS))
    sched = build_schedule(prog)
    groups = sum(1 for lvl in sched.levels for g in lvl
                 if g.kind in ("MAJ", "MRC"))
    pal = get_backend("pallas", IDEAL)
    want = np.asarray(get_backend("oracle", IDEAL).run(prog, state))
    for sighting in ("eager", "build", "jit"):
        with pal.count_dispatches() as scope:
            got = np.asarray(pal.run_fused(prog, state))
        assert scope.count == groups, sighting
        assert (got == want).all(), sighting


# ------------------------------------------- the acceptance dispatch gate


def test_adder32_dispatch_budget():
    """Fused 32-bit ripple-carry add: <= one dispatch per dependency
    level (vs one per MAJ gate per-op), bit-exact against the oracle."""
    rng = np.random.default_rng(6)
    a = rand_u32(rng, 32)
    b = rand_u32(rng, 32)
    cp = compile_elementwise("add", a, b, tier=5, n_act=32)
    sched = build_schedule(cp.program)

    pal = get_backend("pallas", IDEAL)
    pal.reset_dispatches()
    per_op = np.asarray(pal.run(cp.program, cp.state))
    per_op_dispatches = pal.dispatch_count

    pal.reset_dispatches()
    fused = np.asarray(pal.run_fused(cp.program, cp.state))
    fused_dispatches = pal.dispatch_count

    assert fused_dispatches <= sched.n_levels
    assert fused_dispatches < per_op_dispatches
    assert per_op_dispatches == sum(
        1 for op in cp.program.ops if op.kind == "MAJ")
    assert (fused == per_op).all()
    want = np.asarray(get_backend("oracle", IDEAL).run(cp.program, cp.state))
    assert (fused == want).all()
    assert (np.asarray(cp.outputs(fused)) == (a + b).astype(np.uint32)).all()


def test_fused_elementwise_matches_per_gate_recording():
    """The pallas fused elementwise path returns the same values and op
    histogram as the per-gate executors (and an addressed program)."""
    rng = np.random.default_rng(7)
    a, b = rand_u32(rng, 16), rand_u32(rng, 16)
    out_p, prog_p = get_backend("pallas", IDEAL).elementwise(
        "add", a, b, tier=5, n_act=32)
    out_o, prog_o = get_backend("oracle", IDEAL).elementwise(
        "add", a, b, tier=5, n_act=32)
    assert (np.asarray(out_p) == np.asarray(out_o)).all()
    assert prog_p.histogram() == prog_o.histogram()
    assert all(op.dsts for op in prog_p.ops)      # addressed
    assert not any(op.dsts for op in prog_o.ops)  # cost-only


# ------------------------------------------------- the jitted level walk


def _aliasing_program() -> Program:
    prog = Program()
    prog.emit("MAJ", x=3, n_act=4, srcs=(0, 1, 2), dsts=(0,))
    prog.emit("MAJ", x=3, n_act=4, srcs=(0, 1, 2), dsts=(1,))
    prog.emit("NOT", srcs=(1,), dsts=(1,))
    prog.emit("MRC", n_act=4, srcs=(1,), dsts=(2, 0, 3))
    return prog


def _mixed_arity_program() -> Program:
    prog = Program()
    prog.emit("MAJ", x=3, n_act=4, srcs=(0, 1, 2), dsts=(8, 10))
    prog.emit("MAJ", x=7, n_act=8, srcs=(0, 1, 2, 3, 4, 5, 6), dsts=(9,))
    prog.emit("MAJ", x=5, n_act=8, srcs=(8, 9, 10, 3, 4), dsts=(11,))
    return prog


def _mrc_prefix_program() -> Program:
    """Two MRC ops of different fan-outs in one level: each scatters a
    prefix of the widest fan-out's copies."""
    prog = Program()
    prog.emit("MRC", n_act=8, srcs=(0,), dsts=(4, 5, 6, 7, 8))
    prog.emit("MRC", n_act=4, srcs=(1,), dsts=(9, 10))
    prog.emit("MAJ", x=3, n_act=4, srcs=(4, 9, 2), dsts=(11,))
    return prog


def _not_copy_program() -> Program:
    """NOT / COPY levels only: no kernel launches at all."""
    prog = Program()
    prog.emit("NOT", srcs=(0,), dsts=(1, 2))
    prog.emit("COPY", srcs=(3,), dsts=(4,))
    prog.emit("NOT", srcs=(2,), dsts=(0,))
    prog.emit("COPY", srcs=(1,), dsts=(3, 5))
    return prog


def _adder_program() -> Program:
    rng = np.random.default_rng(8)
    return compile_elementwise("add", rand_u32(rng, 32), rand_u32(rng, 32),
                               tier=5, n_act=32).program


def _dead_ops_program() -> Program:
    """Dead stores (results never read) still land in the image."""
    prog = Program()
    prog.emit("MAJ", x=3, n_act=4, srcs=(0, 1, 2), dsts=(5,))  # dead
    prog.emit("COPY", srcs=(0,), dsts=(6,))                    # dead
    prog.emit("MAJ", x=3, n_act=4, srcs=(1, 2, 3), dsts=(4,))
    return prog


def _maj3579_program() -> Program:
    """MAJ3/5/7/9 in one level: all padded to MAJ9, one launch."""
    prog = Program()
    for i, x in enumerate((3, 5, 7, 9)):
        prog.emit("MAJ", x=x, n_act=cal.min_activation_for(x),
                  srcs=tuple(range(x)), dsts=(10 + i,))
    return prog


def _mrc_fanout31_program() -> Program:
    prog = Program()
    prog.emit("MRC", n_act=32, srcs=(0,), dsts=tuple(range(1, 32)))
    return prog


def _single_not_program() -> Program:
    prog = Program()
    prog.emit("NOT", srcs=(0,), dsts=(1,))
    return prog


def _cost_only_program() -> Program:
    """Addressless ops and WR: no level, no launch, the identity."""
    prog = Program()
    for _ in range(5):
        prog.emit("MAJ", x=5, n_act=8)
        prog.emit("WR")
    return prog


#: Name -> (program, launches a call when the count is pinned).
WALK_CORPUS = {
    "aliasing": (_aliasing_program, None),
    "mixed_arity": (_mixed_arity_program, None),
    "mrc_prefix": (_mrc_prefix_program, None),
    "not_copy": (_not_copy_program, 0),
    "random": (lambda: rand_program(np.random.default_rng(9), n_ops=16),
               None),
    "adder32": (_adder_program, None),
    "dead_ops": (_dead_ops_program, None),
    "maj3579": (_maj3579_program, 1),
    "mrc_fanout31": (_mrc_fanout31_program, 1),
    "single_not": (_single_not_program, 0),
    "cost_only": (_cost_only_program, 0),
}


@pytest.mark.parametrize("name", sorted(WALK_CORPUS))
def test_jitted_walk_matches_eager_and_per_op(name, monkeypatch):
    """First sighting eager, second builds the jitted walk, third and
    later reuse it; every call bit-identical to the per-op interpreter,
    with the eager walk's launches and energy, at two image shapes."""
    from repro import obs

    build_prog, launches = WALK_CORPUS[name]
    prog = build_prog()
    sched = build_schedule(prog)
    if launches is not None:
        assert sched.n_dispatches() == launches
    rng = np.random.default_rng(10)
    opened = []
    span = obs.span
    monkeypatch.setattr(obs, "span",
                        lambda n: opened.append(n) or span(n))

    pal = get_backend("pallas", IDEAL)
    jitted = []
    for words in (WORDS, 2 * WORDS):
        state = jnp.asarray(rand_u32(rng, prog.n_rows() + 1, words))
        want = np.asarray(get_backend("pallas", IDEAL).run(prog, state))
        eager = get_backend("pallas", IDEAL)  # a first sighting
        assert (np.asarray(eager.run_fused(prog, state, sched=sched))
                == want).all()
        for _ in range(3):
            pal.reset_dispatches()
            opened.clear()
            got = np.asarray(pal.run_fused(prog, state, sched=sched))
            assert (got == want).all()
            assert pal.dispatch_count == eager.dispatch_count \
                == sched.n_dispatches()
            assert pal.energy_nj_total == eager.energy_nj_total
            walk = pal._walks[sched]
            jitted.append((walk.jitted, [n for n in opened
                                         if n.startswith("backend.levels")]))
    assert jitted[0] == (None, [])
    build, hit = ["backend.levels_build"], ["backend.levels_jit"]
    fn = jitted[1][0]
    assert fn is not None
    # The new shape under the same schedule reuses the jitted walk.
    assert jitted[1:] == [(fn, build)] + [(fn, hit)] * 4


def test_walk_lru_holds_its_bound():
    from repro.backends import pallas

    pal = get_backend("pallas", IDEAL)
    state = jnp.asarray(rand_u32(np.random.default_rng(11), 4, WORDS))
    progs = []
    for i in range(pallas.WALK_CACHE_SIZE + 2):
        prog = Program()
        prog.emit("NOT", srcs=(i % 3,), dsts=(3,), tag=f"p{i}")
        progs.append(prog)
        out = np.asarray(pal.run_fused(prog, state))
        assert (out[3] == ~np.asarray(state)[i % 3]).all()
    assert len(pal._walks) == pallas.WALK_CACHE_SIZE
    # The two oldest went: the first is a first sighting again.
    assert build_schedule(progs[0]) not in pal._walks
    assert build_schedule(progs[2]) in pal._walks
    pal.run_fused(progs[0], state)
    assert pal._walks[build_schedule(progs[0])].jitted is None
    assert len(pal._walks) == pallas.WALK_CACHE_SIZE


def test_walk_refuses_rows_beyond_the_image():
    prog = Program()
    prog.emit("MAJ", x=3, n_act=4, srcs=(0, 1, 2), dsts=(4,))
    state = jnp.asarray(rand_u32(np.random.default_rng(12), 4, WORDS))
    with pytest.raises(ValueError, match="row 4 of a 4-row image"):
        get_backend("pallas", IDEAL).run_fused(prog, state)


# --------------------------------------------------------- helper hygiene


def test_no_silent_test_helpers():
    """Helper modules under tests/ (anything not matching test_*.py)
    must not define tests, or pytest would silently skip them — the
    failure mode tests/proptest.py had before it became _proptest.py."""
    here = os.path.dirname(__file__)
    for fname in sorted(os.listdir(here)):
        if not fname.endswith(".py") or fname.startswith("test_"):
            continue
        with open(os.path.join(here, fname)) as f:
            src = f.read()
        assert "\ndef test_" not in src and not src.startswith("def test_"), \
            (f"{fname} defines tests but is not collected by pytest; "
             f"rename it to test_*.py or move the tests out")
