"""Host-side wrapper: run a MegaLowering against a bit-plane image.

``run_lowering`` builds the augmented image (three constant rows in
front of the program rows, see :mod:`repro.compile.megakernel`), pads
it to the VPU tile, launches :func:`repro.kernels.megakernel.kernel.
schedule_pallas` exactly once, and crops the program rows back out.

The level tables ride in SMEM as scalar-prefetch operands.  A v5e
core has 1 MiB of SMEM: the chip's compiler accepts tables of
1,036,288 bytes and refuses 1,047,552 (each table is padded to 4 KiB,
and the kernel's own scalars need a few KiB more).
:data:`SMEM_TABLE_LIMIT_BYTES` keeps 64 KiB of that as margin; a larger
lowering raises ``ValueError`` on every platform.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.compile.megakernel import MegaLowering, N_CONST_ROWS, ONE_ROW
from repro.kernels.megakernel.kernel import schedule_pallas
from repro.kernels.tiling import VPU_LANES, VPU_SUBLANES, clamp_block_c

#: Most level-table bytes one megakernel launch may stage in SMEM.
SMEM_TABLE_LIMIT_BYTES = 1024 * 1024 - 64 * 1024


def run_lowering(
    lowering: MegaLowering,
    state: jax.Array,
    *,
    block_c: int = 512,
    interpret: bool,
) -> jax.Array:
    """Execute lowered level tables on a (rows, words) uint32 image.

    One Pallas dispatch regardless of level count or of how many
    ``block_c``-wide column slabs the grid streams.  Rows beyond what
    the lowering addresses ride along untouched (they are gathered
    never, scattered never); an empty lowering is the identity.
    """
    state = jnp.asarray(state, jnp.uint32)
    rows, words = state.shape
    if lowering.n_levels == 0 or lowering.w_max == 0:
        return state
    if lowering.n_rows > rows:
        raise ValueError(
            f"lowering addresses {lowering.n_rows} rows but state has "
            f"only {rows}")
    if lowering.table_bytes > SMEM_TABLE_LIMIT_BYTES:
        raise ValueError(
            f"megakernel level tables need {lowering.table_bytes} bytes of "
            f"SMEM ({lowering.n_levels} levels x {lowering.w_max} slots x "
            f"{lowering.x_max} operands); the limit is "
            f"SMEM_TABLE_LIMIT_BYTES={SMEM_TABLE_LIMIT_BYTES}")

    block_c = clamp_block_c(block_c)
    rows_aug = -(-(rows + N_CONST_ROWS) // VPU_SUBLANES) * VPU_SUBLANES
    cols = -(-words // block_c) * block_c
    aug = jnp.zeros((rows_aug, cols), jnp.uint32)
    # The ones row spans the full padded width so MAJ padding stays
    # exact in the ragged last column block.
    aug = aug.at[ONE_ROW].set(jnp.uint32(0xFFFFFFFF))
    aug = aug.at[N_CONST_ROWS:N_CONST_ROWS + rows, :words].set(state)

    out = schedule_pallas(
        jnp.asarray(lowering.src.reshape(-1)),
        jnp.asarray(lowering.dst.reshape(-1)),
        jnp.asarray(lowering.inv.reshape(-1)),
        aug,
        n_levels=int(lowering.n_levels),
        w=int(lowering.w_max),
        x=int(lowering.x_max),
        block_c=block_c,
        interpret=interpret,
    )
    return out[N_CONST_ROWS:N_CONST_ROWS + rows, :words]


def pick_block_c(words: int, budget_block_c: int) -> int:
    """Snap a planner-chosen block width onto the wrapper's clamp rule."""
    cols = -(-words // VPU_LANES) * VPU_LANES
    return clamp_block_c(min(budget_block_c, cols))
