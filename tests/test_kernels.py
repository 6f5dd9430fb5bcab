"""Per-kernel shape/dtype sweeps vs the pure-jnp oracles (interpret mode)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _proptest import rand_u32, sweep
from repro.core import bitplanes as bp
from repro.kernels.bitserial.ops import add_u32, bitserial_add
from repro.kernels.bitserial.ref import bitserial_add_ref
from repro.kernels.majx.ops import majx, vote
from repro.kernels.majx.ref import majx_ref
from repro.kernels.mismatch.ops import mismatch_count, success_rate
from repro.kernels.mismatch.ref import mismatch_count_ref
from repro.kernels.rowcopy.ops import fanout
from repro.kernels.rowcopy.ref import fanout_ref


@pytest.mark.parametrize("n", [3, 5, 7, 9])
@pytest.mark.parametrize("shape", [(1, 64), (8, 128), (13, 700), (32, 2048)])
def test_majx_kernel_shapes(n, shape):
    rng = np.random.default_rng((n, *shape))
    x = jnp.asarray(rand_u32(rng, n, *shape))
    assert (np.asarray(majx(x, interpret=True))
            == np.asarray(majx_ref(x))).all()


@sweep(6)
def test_majx_kernel_random_blocks(rng):
    n = int(rng.choice([3, 5, 7, 9]))
    r = int(rng.integers(1, 40))
    c = int(rng.integers(1, 900))
    x = jnp.asarray(rand_u32(rng, n, r, c))
    br = int(rng.choice([8, 16]))
    bc = int(rng.choice([128, 256, 512]))
    got = majx(x, block_r=br, block_c=bc, interpret=True)
    assert (np.asarray(got) == np.asarray(majx_ref(x))).all()


@pytest.mark.parametrize("nbits", [8, 16, 32])
def test_bitserial_add_widths(nbits):
    rng = np.random.default_rng(nbits)
    a = rand_u32(rng, 4, 300) >> (32 - nbits)
    b = rand_u32(rng, 4, 300) >> (32 - nbits)
    pa = bp.pack_uint_elements(jnp.asarray(a.reshape(-1)), nbits).reshape(
        nbits, -1)
    pb = bp.pack_uint_elements(jnp.asarray(b.reshape(-1)), nbits).reshape(
        nbits, -1)
    got = bitserial_add(pa, pb, interpret=True)
    want = bitserial_add_ref(pa, pb)
    assert (np.asarray(got) == np.asarray(want)).all()


@sweep(6)
def test_add_u32_matches_numpy(rng):
    k = int(rng.integers(1, 700))
    a, b = rand_u32(rng, k), rand_u32(rng, k)
    got = np.asarray(add_u32(a, b, interpret=True))
    assert (got == (a + b)).all()


@pytest.mark.parametrize("fanout_n", [1, 3, 7, 15, 31])
def test_fanout_kernel(fanout_n):
    rng = np.random.default_rng(fanout_n)
    src = jnp.asarray(rand_u32(rng, 9, 300))
    got = fanout(src, fanout_n, interpret=True)
    want = fanout_ref(src, fanout_n)
    assert got.shape == (fanout_n, 9, 300)
    assert (np.asarray(got) == np.asarray(want)).all()


@sweep(6)
def test_mismatch_kernel(rng):
    n = int(rng.integers(1, 3000))
    g, w = rand_u32(rng, n), rand_u32(rng, n)
    got = int(mismatch_count(jnp.asarray(g), jnp.asarray(w),
                             interpret=True))
    want = int(mismatch_count_ref(jnp.asarray(g), jnp.asarray(w)))
    assert got == want
    assert success_rate(g, g, interpret=True) == 1.0


def test_vote_kernel_heals_corruption():
    from repro.pud.tmr import corrupt

    # key chosen so no bit flips in >= 2 replicas (TMR heals single faults
    # only; a double fault is uncorrectable by majority, not a kernel bug)
    key = jax.random.PRNGKey(2)
    x = jax.random.normal(key, (513,), jnp.float32)
    reps = [corrupt(x, jax.random.fold_in(key, i), 1e-3) for i in range(3)]
    healed = vote(reps, interpret=True)
    assert (np.asarray(healed) == np.asarray(x)).all()


def test_vote_kernel_bf16():
    from repro.pud.tmr import corrupt

    key = jax.random.PRNGKey(1)
    x = jax.random.normal(key, (257,), jnp.float32).astype(jnp.bfloat16)
    reps = [corrupt(x, jax.random.fold_in(key, i), 5e-4) for i in range(5)]
    healed = vote(reps, interpret=True)
    assert (np.asarray(healed) == np.asarray(x)).all()
