"""Differential fuzz + structural tests for the megakernel executor.

The load-bearing guarantee extends test_compile_differential.py by one
mode: for ANY addressed Program,

    oracle per-op == sim == pallas per-op == pallas fused
                  == pallas MEGAKERNEL (one dispatch)

bit-exactly.  The generator produces the hazards the lowering must
survive — aliasing destinations, dead stores, mixed MAJ arities in one
level, wide MRC fan-out — and the structural tests pin the lowering
invariants (table shapes, parity padding, constant-row layout, digest
stability) plus the session-layer lowering cache and the one-dispatch
acceptance gate for the 32-bit adder.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from _proptest import rand_u32, sweep
from repro.backends import ExecutionContext, get_backend
from repro.compile import (MegaLowering, build_schedule, compile_elementwise,
                           lower_schedule, plan_vmem)
from repro.compile.megakernel import N_CONST_ROWS, TRASH_ROW, ZERO_ROW
from repro.kernels.megakernel import run_lowering, schedule_exec_ref
from repro.pud.isa import Program
from repro.session import DramSession
from test_compile_differential import rand_program

IDEAL = ExecutionContext(ideal=True)
ROWS, WORDS = 20, 8


def _oracle_want(prog, state):
    return np.asarray(get_backend("oracle", IDEAL).run(prog, state))


def _all_modes(prog, state) -> dict[str, np.ndarray]:
    outs = {}
    for name in ("oracle", "sim", "pallas"):
        be = get_backend(name, IDEAL)
        outs[f"{name}/fused"] = np.asarray(be.run_fused(prog, state))
        outs[f"{name}/megakernel"] = np.asarray(
            be.run_fused(prog, state, mode="megakernel"))
    outs["pallas/per_op"] = np.asarray(
        get_backend("pallas", IDEAL).run(prog, state))
    return outs


# ------------------------------------------------------ differential fuzz


@sweep(n_cases=8, seed=0x3E6A)
def test_random_programs_all_modes_agree(rng):
    prog = rand_program(rng)
    state = jnp.asarray(rand_u32(rng, ROWS, WORDS))
    want = _oracle_want(prog, state)
    for name, got in _all_modes(prog, state).items():
        assert (got == want).all(), name


@sweep(n_cases=4, seed=0xD1FF)
def test_megakernel_is_one_dispatch_for_any_nonempty_program(rng):
    prog = rand_program(rng, n_ops=14)
    state = jnp.asarray(rand_u32(rng, ROWS, WORDS))
    pal = get_backend("pallas", IDEAL)
    nonempty = build_schedule(prog).n_levels > 0
    with pal.count_dispatches() as scope:
        pal.run_fused(prog, state, mode="megakernel")
    assert scope.count == (1 if nonempty else 0)


def test_destination_aliasing_program_megakernel():
    """In-place rewrites force one level per op; the scan must sample
    level-entry state, never the half-updated image."""
    prog = Program()
    prog.emit("MAJ", x=3, n_act=4, srcs=(0, 1, 2), dsts=(0,))
    prog.emit("MAJ", x=3, n_act=4, srcs=(0, 1, 2), dsts=(1,))
    prog.emit("NOT", srcs=(1,), dsts=(1,))
    prog.emit("MRC", n_act=4, srcs=(1,), dsts=(2, 0, 3))
    state = jnp.asarray(rand_u32(np.random.default_rng(1), 4, WORDS))
    want = _oracle_want(prog, state)
    for name, got in _all_modes(prog, state).items():
        assert (got == want).all(), name


def test_dead_ops_still_write_their_rows_megakernel():
    prog = Program()
    prog.emit("MAJ", x=3, n_act=4, srcs=(0, 1, 2), dsts=(5,))  # dead
    prog.emit("COPY", srcs=(0,), dsts=(6,))                    # dead
    prog.emit("MAJ", x=3, n_act=4, srcs=(1, 2, 3), dsts=(4,))
    state = jnp.asarray(rand_u32(np.random.default_rng(2), 7, WORDS))
    got = np.asarray(get_backend("pallas", IDEAL).run_fused(
        prog, state, mode="megakernel"))
    assert (got == _oracle_want(prog, state)).all()
    assert not (got[5] == np.asarray(state)[5]).all()


def test_mixed_arity_maj3579_single_level_single_dispatch():
    """MAJ3/5/7/9 sharing one level: all pad to x_max=9 with 0/1 pairs."""
    prog = Program()
    prog.emit("MAJ", x=3, n_act=4, srcs=(0, 1, 2), dsts=(10,))
    prog.emit("MAJ", x=5, n_act=8, srcs=(0, 1, 2, 3, 4), dsts=(11,))
    prog.emit("MAJ", x=7, n_act=8, srcs=(0, 1, 2, 3, 4, 5, 6), dsts=(12,))
    prog.emit("MAJ", x=9, n_act=16, srcs=tuple(range(9)), dsts=(13,))
    low = lower_schedule(build_schedule(prog))
    assert (low.n_levels, low.w_max, low.x_max) == (1, 4, 9)
    state = jnp.asarray(rand_u32(np.random.default_rng(3), 14, WORDS))
    pal = get_backend("pallas", IDEAL)
    with pal.count_dispatches() as scope:
        got = np.asarray(pal.run_fused(prog, state, mode="megakernel"))
    assert scope.count == 1
    assert (got == _oracle_want(prog, state)).all()


def test_mrc_fanout31_is_31_identity_slots_one_dispatch():
    prog = Program()
    prog.emit("MRC", n_act=32, srcs=(0,), dsts=tuple(range(1, 32)))
    low = lower_schedule(build_schedule(prog))
    assert (low.n_levels, low.w_max, low.x_max) == (1, 31, 1)
    assert low.level_meta == ((0, 31, 0, 0),)
    state = jnp.asarray(rand_u32(np.random.default_rng(4), 32, WORDS))
    pal = get_backend("pallas", IDEAL)
    with pal.count_dispatches() as scope:
        got = np.asarray(pal.run_fused(prog, state, mode="megakernel"))
    assert scope.count == 1
    assert (got[1:] == np.asarray(state)[0]).all()


def test_single_op_degenerate_schedule():
    prog = Program()
    prog.emit("NOT", srcs=(0,), dsts=(1,))
    state = jnp.asarray(rand_u32(np.random.default_rng(5), 2, WORDS))
    want = _oracle_want(prog, state)
    for name, got in _all_modes(prog, state).items():
        assert (got == want).all(), name


def test_cost_only_program_is_identity_at_zero_dispatches():
    prog = Program()
    for _ in range(5):
        prog.emit("MAJ", x=5, n_act=8)
        prog.emit("WR")
    state = jnp.asarray(rand_u32(np.random.default_rng(6), 4, 4))
    pal = get_backend("pallas", IDEAL)
    with pal.count_dispatches() as scope:
        got = pal.run_fused(prog, state, mode="megakernel")
    assert scope.count == 0
    assert (np.asarray(got) == np.asarray(state)).all()


def test_unknown_mode_rejected_everywhere():
    prog = Program()
    prog.emit("NOT", srcs=(0,), dsts=(1,))
    state = jnp.zeros((2, 4), jnp.uint32)
    for name in ("oracle", "sim", "pallas"):
        with pytest.raises(ValueError, match="unknown run_fused mode"):
            get_backend(name, IDEAL).run_fused(prog, state, mode="warp")


# ------------------------------------------------------ lowering structure


def test_lowering_invariants_random_programs():
    rng = np.random.default_rng(7)
    for _ in range(6):
        prog = rand_program(rng, n_ops=16)
        sched = build_schedule(prog)
        low = lower_schedule(sched)
        assert isinstance(low, MegaLowering)
        assert low.x_max % 2 == 1                       # parity-safe padding
        assert low.src.shape == (low.n_levels, low.w_max, low.x_max)
        assert low.dst.shape == low.inv.shape == (low.n_levels, low.w_max)
        assert low.n_levels == sched.n_levels
        # Every table index addresses the augmented image.
        assert low.src.min() >= 0
        assert low.src.max() < low.n_rows + N_CONST_ROWS
        assert ((low.dst >= N_CONST_ROWS) | (low.dst == TRASH_ROW)).all()
        for li, counts in enumerate(low.level_meta):
            live = sum(counts)
            assert live <= low.w_max
            # Inert padding slots: zero-row gather, trash-row scatter.
            assert (low.dst[li, live:] == TRASH_ROW).all()
            assert (low.src[li, live:] == ZERO_ROW).all()
            assert (low.inv[li, live:] == 0).all()


def test_lowering_digest_is_content_stable_and_sensitive():
    prog = Program()
    prog.emit("MAJ", x=3, n_act=4, srcs=(0, 1, 2), dsts=(3,))
    prog.emit("NOT", srcs=(3,), dsts=(4,))
    d1 = lower_schedule(build_schedule(prog)).digest()
    d2 = lower_schedule(build_schedule(prog)).digest()
    assert d1 == d2
    prog2 = Program()
    prog2.emit("MAJ", x=3, n_act=4, srcs=(0, 1, 2), dsts=(3,))
    prog2.emit("NOT", srcs=(3,), dsts=(5,))             # one address differs
    assert lower_schedule(build_schedule(prog2)).digest() != d1


def test_lowering_is_state_height_independent():
    """Tables depend on program content only — the cacheability contract."""
    prog = Program()
    prog.emit("MAJ", x=3, n_act=4, srcs=(0, 1, 2), dsts=(3,))
    low = lower_schedule(build_schedule(prog))
    for rows in (4, 9, 40):
        state = rand_u32(np.random.default_rng(rows), rows, WORDS)
        got = np.asarray(run_lowering(low, jnp.asarray(state),
                                      interpret=True))
        want = _oracle_want(prog, jnp.asarray(state))
        assert (got == want).all(), rows


def test_run_lowering_rejects_short_state():
    prog = Program()
    prog.emit("MAJ", x=3, n_act=4, srcs=(0, 1, 2), dsts=(9,))
    low = lower_schedule(build_schedule(prog))
    with pytest.raises(ValueError, match="addresses 10 rows"):
        run_lowering(low, jnp.zeros((4, 4), jnp.uint32),
                     interpret=True)


def test_run_lowering_rejects_tables_beyond_smem():
    """Tables that would not fit the chip's SMEM fail loudly, on every
    platform, instead of running some other executor."""
    from repro.compile.megakernel import MegaLowering
    from repro.kernels.megakernel.ops import SMEM_TABLE_LIMIT_BYTES

    levels = SMEM_TABLE_LIMIT_BYTES // (4 * 3 * 8) + 1
    low = MegaLowering(src=np.zeros((levels, 8, 3), np.int32),
                       dst=np.full((levels, 8), 2, np.int32),
                       inv=np.zeros((levels, 8), np.uint32),
                       n_rows=1, level_meta=())
    assert low.table_bytes > SMEM_TABLE_LIMIT_BYTES
    with pytest.raises(ValueError, match="SMEM_TABLE_LIMIT_BYTES"):
        run_lowering(low, jnp.zeros((4, 128), jnp.uint32), interpret=True)


# ----------------------------------------- numpy ref vs the Pallas kernel


@sweep(n_cases=4, seed=0x2EF5)
def test_numpy_ref_executor_matches_pallas_kernel(rng):
    """Separates lowering bugs from kernel bugs: both executors consume
    the SAME tables and must agree bit-exactly (and with the oracle)."""
    prog = rand_program(rng, n_ops=12)
    low = lower_schedule(build_schedule(prog))
    state = rand_u32(rng, ROWS, WORDS)
    want = _oracle_want(prog, jnp.asarray(state))
    ref = schedule_exec_ref(low, state)
    assert (ref == want).all()
    if low.n_levels:
        krn = np.asarray(run_lowering(low, jnp.asarray(state),
                                      interpret=True))
        assert (krn == ref).all()


# --------------------------------------------------- session + lowering cache


def _adder_program(n_bits=8, seed=0xADD):
    rng = np.random.default_rng(seed)
    a = rng.integers(0, 2**16, n_bits, dtype=np.uint32)
    b = rng.integers(0, 2**16, n_bits, dtype=np.uint32)
    return compile_elementwise("add", a, b, tier=5, n_act=32), a, b


def test_session_megakernel_caches_lowering_separately():
    cp, a, b = _adder_program()
    sess = DramSession("pallas", IDEAL)

    fused = np.asarray(sess.run_fused(cp.program, cp.state))
    sched_stats = sess.cache.stats.snapshot()
    assert sess.cache.lowering_stats.lookups == 0

    mega1 = np.asarray(sess.run_fused(cp.program, cp.state,
                                      mode="megakernel"))
    mega2 = np.asarray(sess.run_fused(cp.program, cp.state,
                                      mode="megakernel"))
    assert (mega1 == fused).all() and (mega2 == fused).all()
    assert (np.asarray(cp.outputs(mega1))
            == (a + b).astype(np.uint32)).all()
    # Lowerings account on their own window; schedule stats advance by
    # exactly one (cached) lookup per run, same as fused mode would.
    assert sess.cache.lowering_stats.misses == 1
    assert sess.cache.lowering_stats.hits == 1
    delta = sess.cache.stats.delta(sched_stats)
    assert delta.misses == 0 and delta.hits == 2


def test_session_megakernel_on_fallback_backend_skips_lowering():
    cp, a, b = _adder_program(seed=0xFA11)
    sess = DramSession("oracle", IDEAL)
    got = np.asarray(sess.run_fused(cp.program, cp.state,
                                    mode="megakernel"))
    assert (np.asarray(cp.outputs(got)) == (a + b).astype(np.uint32)).all()
    assert sess.cache.lowering_stats.lookups == 0  # nothing to lower for


# ------------------------------------------- the acceptance dispatch gate


def test_adder32_megakernel_single_dispatch():
    """The gate: a 32-bit ripple-carry add in ONE dispatch, bit-exact."""
    rng = np.random.default_rng(8)
    a, b = rand_u32(rng, 32), rand_u32(rng, 32)
    cp = compile_elementwise("add", a, b, tier=5, n_act=32)
    pal = get_backend("pallas", IDEAL)

    with pal.count_dispatches() as fused_scope:
        fused = np.asarray(pal.run_fused(cp.program, cp.state))
    with pal.count_dispatches() as mega_scope:
        mega = np.asarray(pal.run_fused(cp.program, cp.state,
                                        mode="megakernel"))
    assert mega_scope.count == 1
    assert mega_scope.count < fused_scope.count
    assert (mega == fused).all()
    assert (np.asarray(cp.outputs(mega)) == (a + b).astype(np.uint32)).all()


# --------------------------------------------------- VMEM planning / spill


def test_plan_vmem_properties():
    prog = Program()
    prog.emit("MAJ", x=5, n_act=8, srcs=(0, 1, 2, 3, 4), dsts=(5,))
    low = lower_schedule(build_schedule(prog))
    big = plan_vmem(low, rows=6, words=256, budget_bytes=8 * 2**20)
    assert big.resident and big.block_c % 128 == 0 and big.block_c >= 256
    tiny = plan_vmem(low, rows=6, words=100_000, budget_bytes=4096)
    assert not tiny.resident
    assert tiny.block_c % 128 == 0
    assert tiny.block_c < 100_000
    assert tiny.working_set_bytes > tiny.budget_bytes
    d = tiny.as_dict()
    assert set(d) == {"block_c", "resident", "working_set_bytes",
                      "budget_bytes"}


def test_forced_vmem_spill_is_still_one_exact_dispatch():
    """A starved budget splits the word axis into column blocks streamed
    through the grid — launch count and results must not change."""
    prog = Program()
    prog.emit("MAJ", x=3, n_act=4, srcs=(0, 1, 2), dsts=(3,))
    prog.emit("NOT", srcs=(3,), dsts=(4,))
    prog.emit("MRC", n_act=4, srcs=(4,), dsts=(5, 6, 7))
    state = jnp.asarray(rand_u32(np.random.default_rng(9), 8, 300))
    want = _oracle_want(prog, state)

    starved = get_backend("pallas", IDEAL.replace(vmem_budget_bytes=4096))
    low = lower_schedule(build_schedule(prog))
    plan = plan_vmem(low, 8, 300, starved.ctx.vmem_budget_bytes)
    assert not plan.resident and plan.block_c < 300  # really multi-block
    with starved.count_dispatches() as scope:
        got = np.asarray(starved.run_fused(prog, state, mode="megakernel"))
    assert scope.count == 1
    assert (got == want).all()
