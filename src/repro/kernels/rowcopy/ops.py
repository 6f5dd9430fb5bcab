"""Wrapper for the Multi-RowCopy fan-out kernel.

The wrapper is not jitted: its pad and crop run as eager ops around the
jitted ``fanout_pallas`` at every call.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import tiling
from repro.kernels.rowcopy.kernel import fanout_pallas
from repro.kernels.rowcopy.ref import fanout_ref


def fanout(src: jax.Array, fanout_n: int, *, interpret: bool,
           block_r: int = 8, block_c: int = 512) -> jax.Array:
    """Broadcast (R, C) -> (fanout_n, R, C), Multi-RowCopy style."""
    src = jnp.asarray(src)
    squeeze = src.ndim == 1
    if squeeze:
        src = src[None, :]
    padded, rc = tiling.pad_to_tile(src, block_r, block_c)
    out = tiling.crop(
        fanout_pallas(padded, fanout=fanout_n, block_r=block_r,
                      block_c=block_c, interpret=interpret), rc)
    return out[:, 0, :] if squeeze else out


__all__ = ["fanout", "fanout_ref"]
