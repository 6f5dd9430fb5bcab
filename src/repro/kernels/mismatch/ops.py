"""Wrapper for the mismatch/success-rate kernel.

The wrapper is not jitted: its reshape and pad run as eager ops around
the jitted ``mismatch_pallas`` at every call.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import tiling
from repro.kernels.mismatch.kernel import mismatch_pallas
from repro.kernels.mismatch.ref import mismatch_count_ref


def mismatch_count(got: jax.Array, want: jax.Array, *,
                   interpret: bool) -> jax.Array:
    """Number of differing bits between packed arrays of any shape."""
    g = jnp.asarray(got, jnp.uint32).reshape(-1)
    w = jnp.asarray(want, jnp.uint32).reshape(-1)
    width = 512
    # Zero padding on both sides XORs to zero: the tail adds no bits, and
    # the kernel never reads past the arrays into a partial block.
    g2, _ = tiling.pad_to_tile(tiling.words_to_rows(g, width),
                               tiling.VPU_SUBLANES, width)
    w2, _ = tiling.pad_to_tile(tiling.words_to_rows(w, width),
                               tiling.VPU_SUBLANES, width)
    return mismatch_pallas(g2, w2, block_r=tiling.VPU_SUBLANES,
                           block_c=width, interpret=interpret)


def success_rate(got, want, n_bits: int | None = None, *,
                 interpret: bool) -> float:
    g = jnp.asarray(got, jnp.uint32)
    total = int(n_bits) if n_bits else g.size * 32
    bad = int(mismatch_count(got, want, interpret=interpret))
    return 1.0 - bad / total


__all__ = ["mismatch_count", "success_rate", "mismatch_count_ref"]
