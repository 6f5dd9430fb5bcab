"""Wrapper: bit-serial add on packed planes or uint element vectors.

The wrappers are not jitted: packing, pad and crop run as eager ops
around the jitted ``bitserial_add_pallas`` at every call.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import bitplanes as bp
from repro.kernels import tiling
from repro.kernels.bitserial.kernel import bitserial_add_pallas
from repro.kernels.bitserial.ref import bitserial_add_ref


def bitserial_add(a_planes: jax.Array, b_planes: jax.Array, *,
                  interpret: bool, block_r: int = 8,
                  block_c: int = 256) -> jax.Array:
    """(NBITS, R, C) or (NBITS, C) packed planes -> sum planes."""
    a = jnp.asarray(a_planes, jnp.uint32)
    b = jnp.asarray(b_planes, jnp.uint32)
    squeeze = a.ndim == 2
    if squeeze:
        a, b = a[:, None, :], b[:, None, :]
    a, rc = tiling.pad_to_tile(a, block_r, block_c)
    b, _ = tiling.pad_to_tile(b, block_r, block_c)
    out = tiling.crop(
        bitserial_add_pallas(a, b, block_r=block_r, block_c=block_c,
                             interpret=interpret), rc)
    return out[:, 0, :] if squeeze else out


def add_u32(a: jax.Array, b: jax.Array, *, interpret: bool) -> jax.Array:
    """uint32 element vectors -> uint32 sums, via the bit-plane kernel."""
    a = jnp.asarray(a, jnp.uint32).reshape(-1)
    b = jnp.asarray(b, jnp.uint32).reshape(-1)
    k = a.shape[0]
    pa = bp.pack_uint_elements(a)
    pb = bp.pack_uint_elements(b)
    out = bitserial_add(pa, pb, interpret=interpret)
    return bp.unpack_uint_elements(out, k)


__all__ = ["bitserial_add", "add_u32", "bitserial_add_ref"]
