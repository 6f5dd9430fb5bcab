"""Public wrapper for the bulk MAJX kernel (+ TMR vote entry point).

The wrapper is not jitted: its pad and crop run as eager ops around the
jitted ``majx_pallas`` at every call.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import bitplanes as bp
from repro.kernels import tiling
from repro.kernels.majx.kernel import majx_pallas
from repro.kernels.majx.ref import majx_ref


def majx(planes: jax.Array, *, interpret: bool,
         block_r: int = 8, block_c: int = 512) -> jax.Array:
    """Bulk MAJX over (N, R, C) packed uint32 planes -> (R, C).

    Pads to VPU-aligned tiles, dispatches the Pallas kernel, crops.
    ``interpret`` selects the Pallas interpreter (the only way the kernel
    runs on a CPU) or the compiled TPU kernel.
    """
    planes = jnp.asarray(planes, jnp.uint32)
    if planes.ndim == 2:
        planes = planes[:, None, :]
        squeeze = True
    else:
        squeeze = False
    block_c = tiling.clamp_block_c(block_c)
    padded, rc = tiling.pad_to_tile(planes, block_r, block_c)
    out = tiling.crop(majx_pallas(padded, block_r=block_r, block_c=block_c,
                                  interpret=interpret), rc)
    return out[0] if squeeze else out


def vote(replicas, *, interpret: bool):
    """TMR/XMR vote over replicas of an arbitrary fixed-width array.

    Bitcasts each replica to packed words, majority-votes them through the
    MAJX kernel, and bitcasts back (see repro.pud.tmr for the digital
    oracle used in tests).
    """
    shape, dtype = None, None
    stacked = []
    for rep in replicas:
        w, shape, dtype = bp.bitcast_to_planes(rep)
        stacked.append(w)
    words = jnp.stack(stacked)  # (X, n_words)
    c = words.shape[1]
    planes = tiling.words_to_rows(words, tiling.MAX_BLOCK_C)
    voted = majx(planes, interpret=interpret).reshape(-1)[:c]
    return bp.bitcast_from_planes(voted, shape, dtype)


__all__ = ["majx", "vote", "majx_ref"]
