"""Pallas TPU kernel: fused bit-serial MAJ-based ripple-carry adder (§8.1).

The paper's ADD microbenchmark chains 32 majority-based full adders across
DRAM rows.  The TPU adaptation keeps *all 32 bit-planes of both operands in
VMEM at once* and holds the running carry plane in vector registers across
the (trace-time-unrolled) bit loop — the in-VMEM analogue of the subarray
holding every bit-plane under one set of sense amps.  One HBM round trip
total, instead of one per bit-step as a naive plane-at-a-time translation
would incur (a 32x traffic reduction; see benchmarks/bench_kernels.py).

Block geometry: operands (NBITS, R, C) stream as (NBITS, BR, BC) VMEM
blocks; BC a multiple of 128 lanes, BR of 8 sublanes.  VMEM per block =
2 * NBITS * BR * BC * 4B (+ output), so the default (8, 256) tile holds
3 * 32 * 8 * 256 * 4B = 768 KiB — sized for 16 MiB VMEM with double
buffering headroom.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


#: The ``pallas_call``'s name.  Its device op shows in a profiler
#: trace as ``bitserial_add.<n>``, whatever module runs it.
KERNEL_NAME = "bitserial_add"


def bitserial_add_kernel(a_ref, b_ref, o_ref, *, nbits: int):
    carry = jnp.zeros_like(a_ref[0])
    for i in range(nbits):
        a = a_ref[i]
        b = b_ref[i]
        o_ref[i] = a ^ b ^ carry
        # carry' = MAJ3(a, b, c) — the paper's majority carry.
        carry = (a & b) | (b & carry) | (a & carry)


@functools.partial(jax.jit, static_argnames=("block_r", "block_c", "interpret"))
def bitserial_add_pallas(
    a: jax.Array,
    b: jax.Array,
    *,
    block_r: int = 8,
    block_c: int = 256,
    interpret: bool,
) -> jax.Array:
    """a, b: (NBITS, R, C) uint32 planes -> (NBITS, R, C) sum planes."""
    nbits, r, c = a.shape
    grid = (pl.cdiv(r, block_r), pl.cdiv(c, block_c))
    spec = pl.BlockSpec((nbits, block_r, block_c), lambda i, j: (0, i, j))
    return pl.pallas_call(
        functools.partial(bitserial_add_kernel, nbits=nbits),
        grid=grid,
        in_specs=[spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((nbits, r, c), jnp.uint32),
        interpret=interpret,
        name=KERNEL_NAME,
    )(a, b)
