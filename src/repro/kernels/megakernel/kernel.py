"""Pallas TPU kernel: scan a Schedule's level tables in ONE dispatch.

The per-level executor costs one kernel launch per dependency level;
this kernel runs *every* level of a lowered
:class:`~repro.compile.megakernel.MegaLowering` inside a single
``pallas_call``.  The three level tables (operand indices, destination
rows, complement flags) are staged flat as scalar-prefetch metadata in
SMEM — they are index/control data, not bit-planes — and read one
scalar at a time while a loop walks the level axis over the packed
``uint32`` state block held in VMEM.

One level is one unified primitive, run per write slot:

    read X operand rows  ->  bit-sliced CSA majority over X
    ->  XOR with the slot's complement mask  ->  stage the vote

and, once every slot of the level has voted, the staged votes commit to
their destination rows.  Reads therefore sample the level-entry state
and writes land at level exit — the hazard model the scheduler levels
against — so a WAR pair inside one level stays exact.  Rows move as
dynamic ``pl.ds`` row slices of the VMEM refs.  The vote reuses the
word-packed carry-save counter of the standalone MAJX kernel
(:mod:`repro.kernels.majx.kernel`): the VPU computes 32 bitlines per
word lane per op, the same bulk geometry as the DRAM subarray.

Every op is bitwise per packed word, so word columns are independent:
the grid tiles the word axis, and when the image is wider than one
VMEM-budgeted block the Pallas pipeline streams column slabs through
its double-buffered HBM fetches.  Grid steps are not dispatches — the
launch count stays 1 either way.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.majx.kernel import _csa_accumulate, _ge_threshold


#: The ``pallas_call``'s name.  Its device op shows in a profiler
#: trace as ``megakernel_levels.<n>``, whatever module runs it.
KERNEL_NAME = "megakernel_levels"


def schedule_kernel(src_ref, dst_ref, inv_ref, x_ref, o_ref, vote_ref, *,
                    n_levels: int, w: int, x: int):
    """Execute all levels against one (rows_aug, block_c) column slab.

    ``src_ref`` is the flat (n_levels * w * x,) operand table, ``dst_ref``
    / ``inv_ref`` the flat (n_levels * w,) destination and complement
    tables; ``vote_ref`` is the (w, block_c) VMEM staging area for one
    level's votes.
    """
    o_ref[...] = x_ref[...]

    def level(lv, carry):
        def vote(s, c):
            slot = lv * w + s
            ops = [o_ref[pl.ds(src_ref[slot * x + i], 1), :]
                   for i in range(x)]
            v = _ge_threshold(_csa_accumulate(ops), (x + 1) // 2)
            # inv is 0/1; 0 - 1 == all-ones in uint32, so this is the NOT
            # slots' complement and a no-op everywhere else.
            vote_ref[pl.ds(s, 1), :] = v ^ (jnp.uint32(0) - inv_ref[slot])
            return c

        def commit(s, c):
            row = dst_ref[lv * w + s]
            o_ref[pl.ds(row, 1), :] = vote_ref[pl.ds(s, 1), :]
            return c

        jax.lax.fori_loop(0, w, vote, 0)
        jax.lax.fori_loop(0, w, commit, 0)
        return carry

    jax.lax.fori_loop(0, n_levels, level, 0)


@functools.partial(jax.jit,
                   static_argnames=("n_levels", "w", "x", "block_c",
                                    "interpret"))
def schedule_pallas(
    src: jax.Array,
    dst: jax.Array,
    inv: jax.Array,
    state: jax.Array,
    *,
    n_levels: int,
    w: int,
    x: int,
    block_c: int,
    interpret: bool,
) -> jax.Array:
    """One dispatch over an augmented (rows_aug, C) uint32 image.

    ``src``/``dst``/``inv`` are the *flattened* tables of a
    :class:`~repro.compile.megakernel.MegaLowering` of shape
    (n_levels, w[, x]); ``rows_aug`` and ``C`` must already be padded to
    the (block_r, block_c) tile (see ``ops.run_lowering``).  Programs
    with the same table *shapes* share one compilation — the tables
    themselves are traced operands.
    """
    rows_aug, c = state.shape
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(pl.cdiv(c, block_c),),
        in_specs=[pl.BlockSpec((rows_aug, block_c), lambda j, *_: (0, j))],
        out_specs=pl.BlockSpec((rows_aug, block_c), lambda j, *_: (0, j)),
        scratch_shapes=[pltpu.VMEM((w, block_c), jnp.uint32)],
    )
    return pl.pallas_call(
        functools.partial(schedule_kernel, n_levels=n_levels, w=w, x=x),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((rows_aug, c), jnp.uint32),
        interpret=interpret,
        name=KERNEL_NAME,
    )(src, dst, inv, state)
