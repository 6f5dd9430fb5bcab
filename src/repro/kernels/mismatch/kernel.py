"""Pallas TPU kernel: fused XOR + popcount reduction (success-rate counter).

The characterization harness (§3-§6) compares millions of read-back cells
against expected data per trial; the hot loop is "count differing bits".
The kernel fuses XOR, SWAR popcount, and a grid-carried scalar reduction:
each (BR, BC) block contributes its partial sum into a single SMEM
accumulator that every grid step maps to, so HBM sees the operands
exactly once and one int32 comes back.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


#: The ``pallas_call``'s name.  Its device op shows in a profiler
#: trace as ``mismatch_popcount.<n>``, whatever module runs it.
KERNEL_NAME = "mismatch_popcount"


def mismatch_kernel(g_ref, w_ref, o_ref):
    i = pl.program_id(0)
    j = pl.program_id(1)
    x = g_ref[...] ^ w_ref[...]
    x = x - ((x >> 1) & jnp.uint32(0x55555555))
    x = (x & jnp.uint32(0x33333333)) + ((x >> 2) & jnp.uint32(0x33333333))
    x = (x + (x >> 4)) & jnp.uint32(0x0F0F0F0F)
    per_word = ((x * jnp.uint32(0x01010101)) >> 24).astype(jnp.int32)
    partial = jnp.sum(per_word, dtype=jnp.int32)

    @pl.when((i == 0) & (j == 0))
    def _init():
        o_ref[0, 0] = 0

    o_ref[0, 0] += partial


@functools.partial(jax.jit, static_argnames=("block_r", "block_c", "interpret"))
def mismatch_pallas(
    got: jax.Array,
    want: jax.Array,
    *,
    block_r: int = 8,
    block_c: int = 512,
    interpret: bool,
) -> jax.Array:
    """Differing bits of two (R, C) uint32 images, R and C padded to the
    (block_r, block_c) tile with equal words (see ``ops.mismatch_count``)."""
    r, c = got.shape
    grid = (pl.cdiv(r, block_r), pl.cdiv(c, block_c))
    spec = pl.BlockSpec((block_r, block_c), lambda i, j: (i, j))
    return pl.pallas_call(
        mismatch_kernel,
        grid=grid,
        in_specs=[spec, spec],
        out_specs=pl.BlockSpec(memory_space=pltpu.SMEM),
        out_shape=jax.ShapeDtypeStruct((1, 1), jnp.int32),
        interpret=interpret,
        name=KERNEL_NAME,
    )(got, want)[0, 0]
