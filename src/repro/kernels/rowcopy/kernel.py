"""Pallas TPU kernel: one-to-many row fan-out (Multi-RowCopy, §6).

The paper's Multi-RowCopy writes one source row into up to 31 destinations
in a single command.  The TPU analogue is a broadcast whose *source block
is fetched from HBM once per grid column and fanned out to every
destination block from VMEM* — the BlockSpec index_map pins the source
block regardless of the fan-out grid index, so HBM read traffic is
1/fanout of a naive copy loop (the same traffic asymmetry the DRAM op
exploits).  Used by the checkpoint-restore replicator (repro/ckpt) and the
elastic re-replication path.
"""

from __future__ import annotations

import functools

import jax
from jax.experimental import pallas as pl


#: The ``pallas_call``'s name.  Its device op shows in a profiler
#: trace as ``rowcopy_fanout.<n>``, whatever module runs it.
KERNEL_NAME = "rowcopy_fanout"


def fanout_kernel(src_ref, o_ref):
    o_ref[...] = src_ref[...][None]


@functools.partial(jax.jit, static_argnames=("fanout", "block_r", "block_c",
                                              "interpret"))
def fanout_pallas(
    src: jax.Array,
    *,
    fanout: int,
    block_r: int = 8,
    block_c: int = 512,
    interpret: bool,
) -> jax.Array:
    """src: (R, C) -> (fanout, R, C) broadcast."""
    r, c = src.shape
    grid = (fanout, pl.cdiv(r, block_r), pl.cdiv(c, block_c))
    return pl.pallas_call(
        fanout_kernel,
        grid=grid,
        in_specs=[
            # Source block independent of the fan-out index k: fetched once,
            # reused across the fan-out dimension.
            pl.BlockSpec((block_r, block_c), lambda k, i, j: (i, j)),
        ],
        out_specs=pl.BlockSpec((1, block_r, block_c), lambda k, i, j: (k, i, j)),
        out_shape=jax.ShapeDtypeStruct((fanout, r, c), src.dtype),
        interpret=interpret,
        name=KERNEL_NAME,
    )(src)
