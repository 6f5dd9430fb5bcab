"""TMR voting, secure erase, offload planner, power model."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _proptest import sweep
from repro.core import calibration as cal
from repro.core.power import STANDARD_POWER_W, power_table, simra_power_w
from repro.core.subarray import Subarray
from repro.pud import tmr
from repro.pud.offload import plan_broadcast, plan_vote
from repro.pud.latency import LAT
from repro.pud.secure_erase import (BANK_ROWS, SUBARRAY_ROWS,
                                    destruction_time_ns, erase_program,
                                    erase_subarray, plan_time_ns,
                                    speedup_over_rowclone, subarray_plan)


@sweep(6)
def test_tmr_corrects_single_replica_fault(rng):
    key = jax.random.PRNGKey(int(rng.integers(0, 2**31)))
    x = jax.random.normal(key, (400,), jnp.float32)
    reps = [x, tmr.corrupt(x, key, 0.05), x]  # one heavily corrupted replica
    assert (np.asarray(tmr.vote_array(reps)) == np.asarray(x)).all()


def test_tmr5_corrects_two_faults():
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (256,), jnp.float32)
    reps = [x, tmr.corrupt(x, jax.random.fold_in(key, 1), 0.5),
            tmr.corrupt(x, jax.random.fold_in(key, 2), 0.5), x, x]
    assert (np.asarray(tmr.vote_array(reps)) == np.asarray(x)).all()


def test_tmr_residual_rate_matches_theory():
    key = jax.random.PRNGKey(3)
    x = jnp.zeros((200_000,), jnp.uint32)
    p = 1e-2
    reps = [tmr.corrupt(x, jax.random.fold_in(key, i), p) for i in range(3)]
    voted = tmr.vote_array(reps)
    bad = float(jnp.mean((voted != x).astype(jnp.float32)))
    want = tmr.residual_word_error_rate(p, 3)
    assert bad == pytest.approx(want, rel=0.25)


def test_vote_pytree():
    key = jax.random.PRNGKey(4)
    tree = {"a": jax.random.normal(key, (64,)),
            "b": {"c": jnp.arange(10, dtype=jnp.int32)}}
    reps = [tree,
            jax.tree.map(lambda t: tmr.corrupt(t, key, 0.03), tree),
            tree]
    voted = tmr.vote_pytree(reps)
    for l1, l2 in zip(jax.tree.leaves(voted), jax.tree.leaves(tree)):
        assert (np.asarray(l1) == np.asarray(l2)).all()


# -------------------------------------------------------------- cold boot


def test_fig17_speedups():
    """MRC-based destruction: up to ~20.87x vs RowClone, ~7.55x vs Frac."""
    s32 = speedup_over_rowclone("mrc", 32)
    assert s32 == pytest.approx(cal.COLDBOOT_MAX_SPEEDUP_VS_ROWCLONE, rel=0.02)
    vs_frac = (destruction_time_ns("frac") / destruction_time_ns("mrc", 32))
    assert vs_frac == pytest.approx(cal.COLDBOOT_MAX_SPEEDUP_VS_FRAC, rel=0.02)


def test_fig17_monotone_in_n_act():
    sp = [speedup_over_rowclone("mrc", n) for n in (4, 8, 16, 32)]
    assert sp == sorted(sp)
    assert all(s > 1 for s in sp)


def test_functional_erase():
    sa = Subarray(cols=256, ideal=True)
    sa.fill("0xAA")
    t = erase_subarray(sa, 0)
    assert (np.asarray(sa.planes) == 0).all()
    assert t == plan_time_ns(sa.rows) > 0


def test_the_plan_against_the_fig17_model():
    """A bank by the plan and by the Fig. 17 model.  Both wipe every row
    with 31 rows an MRC.  The model writes one row a bank and counts
    ``ceil((rows - 1) / 31)`` MRCs, as if every MRC's source already held
    the pattern; the plan's groups are disjoint, so it writes each
    group's source (one WR a group) and issues one MRC a group.  The
    seeding is the whole difference: 2,047 more WRs, 67 fewer MRCs."""
    subarrays = BANK_ROWS // SUBARRAY_ROWS
    groups = subarrays * len(subarray_plan())
    model_mrcs = -(-(BANK_ROWS - 1) // 31)
    assert (groups, model_mrcs) == (2048, 2115)
    assert groups * 32 == BANK_ROWS <= model_mrcs * 31 + 1
    assert destruction_time_ns("mrc") == pytest.approx(
        LAT.wr_row + model_mrcs * LAT.mrc)
    assert subarrays * plan_time_ns() == pytest.approx(
        destruction_time_ns("mrc") + (groups - 1) * LAT.wr_row
        - (model_mrcs - groups) * LAT.mrc)
    hist = erase_program(BANK_ROWS).histogram()
    assert hist == {("MRC", 0, 32): groups, ("COPY", 0, 0): groups}


# -------------------------------------------------------------- offload


def test_offload_vote_prefers_pud_for_bulk():
    d = plan_vote(1 << 26)
    assert d.winner == "pud"
    assert d.pud_ns < d.tpu_ns


def test_offload_decision_fields():
    d = plan_broadcast(8192, 31)
    assert d.speedup == pytest.approx(d.tpu_ns / d.pud_ns)
    assert "MRC" in d.detail


# -------------------------------------------------------------- power


def test_obs5_power_anchor():
    """32-row activation draws 21.19 % less power than REF."""
    assert simra_power_w(32) == pytest.approx(
        STANDARD_POWER_W["REF"] * (1 + cal.SIMRA32_POWER_VS_REF), rel=1e-6)


def test_power_monotone_in_n():
    vals = [simra_power_w(n) for n in (2, 4, 8, 16, 32)]
    assert vals == sorted(vals)
    table = power_table()
    assert table["SIMRA_32"] < table["REF"]
