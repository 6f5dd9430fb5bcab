"""Pallas TPU kernel: bulk bitwise MAJX over packed bit-planes.

TPU-native adaptation of the paper's N-row charge-share majority (§5).
Instead of per-bit popcounts (32 shift iterations per word), the kernel
keeps a **bit-sliced carry-save counter** in vector registers: each of the
N operand planes is added into a ceil(log2(N+1))-bit counter whose "digits"
are uint32 planes, using only AND/XOR/OR — the VPU executes 32 bitlines per
word-lane per op, the same bulk-parallel geometry as the DRAM subarray
(every bitline computes simultaneously).

Memory layout: operands are staged as (N, R, C) uint32 in HBM and streamed
through VMEM in (N, BR, BC) blocks; BR/BC are multiples of the (8, 128)
VPU tile.  The majority threshold for odd N is evaluated directly on the
counter digits (no decode step).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


#: The ``pallas_call``'s name.  Its device op shows in a profiler
#: trace as ``majx_csa.<n>``, whatever module runs it.
KERNEL_NAME = "majx_csa"


def _csa_accumulate(planes):
    """Bit-sliced counter: returns digit planes [c0, c1, ...] (LSB first)."""
    max_digits = len(planes).bit_length()
    digits = []
    for w in planes:
        carry = w
        for d in range(len(digits)):
            new_carry = digits[d] & carry
            digits[d] = digits[d] ^ carry
            carry = new_carry
        if len(digits) < max_digits:
            digits.append(carry)
    return digits


def _ge_threshold(digits, thresh: int) -> jax.Array:
    """Bitwise (count >= thresh) from counter digit planes.

    Standard bit-sliced magnitude comparison against a constant, scanned
    MSB-first with greater-so-far / equal-so-far accumulators.
    """
    width = len(digits)
    t_bits = [(thresh >> i) & 1 for i in range(width)]
    ge = None  # strictly-greater-so-far, scanning MSB -> LSB
    eq = None  # equal-so-far
    for i in range(width - 1, -1, -1):
        d = digits[i]
        if t_bits[i]:
            gt_here = jnp.zeros_like(d)
            eq_here = d
        else:
            gt_here = d
            eq_here = ~d
        if ge is None:
            ge, eq = gt_here, eq_here
        else:
            ge = ge | (eq & gt_here)
            eq = eq & eq_here
    return ge | eq


def majx_kernel(x_ref, o_ref, *, n: int):
    planes = [x_ref[i] for i in range(n)]
    digits = _csa_accumulate(planes)
    o_ref[...] = _ge_threshold(digits, (n + 1) // 2)


@functools.partial(jax.jit, static_argnames=("block_r", "block_c", "interpret"))
def majx_pallas(
    planes: jax.Array,
    *,
    block_r: int = 8,
    block_c: int = 512,
    interpret: bool,
) -> jax.Array:
    """planes: (N, R, C) uint32, N odd -> (R, C) uint32 majority."""
    n, r, c = planes.shape
    if n % 2 == 0:
        raise ValueError("MAJX needs odd N")
    grid = (pl.cdiv(r, block_r), pl.cdiv(c, block_c))
    return pl.pallas_call(
        functools.partial(majx_kernel, n=n),
        grid=grid,
        in_specs=[pl.BlockSpec((n, block_r, block_c), lambda i, j: (0, i, j))],
        out_specs=pl.BlockSpec((block_r, block_c), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((r, c), jnp.uint32),
        interpret=interpret,
        name=KERNEL_NAME,
    )(planes)
