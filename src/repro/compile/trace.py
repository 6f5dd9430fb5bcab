"""Lower BitSerial gate streams to addressed, fusable Programs.

The §8.1 bit-serial compiler (:class:`repro.pud.arith.BitSerial`) records
cost-only ops while computing on whatever planes flow through it.  The
:class:`Tracer` here is a :class:`~repro.pud.arith.GateExecutor` that
additionally assigns every gate a *row address*: operands resolve to rows
of a growing subarray image, each gate output gets a fresh (SSA) row, and
the emitted :class:`~repro.pud.isa.Program` carries full ``srcs``/``dsts``
— executable by any backend and fusable by
:mod:`repro.compile.schedule`.

Rows are keyed by plane *value*.  BitSerial freely reshapes, stacks and
re-indexes planes (``jnp.stack(sums)``, ``acc[i:]``), destroying object
identity but never values; because traced rows are written exactly once,
any row holding a value is a valid source for that value forever, so
value-keying is exact.  Planes first seen as gate operands (packed inputs,
``const`` planes) become *input rows* of the initial state image.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import bitplanes as bp
from repro.pud.isa import Program


class Tracer:
    """GateExecutor assigning SSA row addresses while computing oracle
    gate values (the recorded Program is then *re*-executed by a real
    backend, so traced values never leak into backend results)."""

    def __init__(self):
        self.program = Program()
        #: initial value per row; None for gate outputs (written by ops).
        self._init: list[Optional[np.ndarray]] = []
        self._table: dict[bytes, int] = {}

    # ------------------------------------------------------------- rows
    @staticmethod
    def _key(plane) -> bytes:
        with obs.span("compile.sync"):
            return np.asarray(plane, np.uint32).tobytes()

    @property
    def n_rows(self) -> int:
        return len(self._init)

    def row_of(self, plane) -> int:
        """Row holding ``plane``'s value (allocating an input row if the
        value was never produced by a traced gate)."""
        key = self._key(plane)
        row = self._table.get(key)
        if row is None:
            row = len(self._init)
            with obs.span("compile.sync"):
                self._init.append(np.asarray(plane, np.uint32).copy())
            self._table[key] = row
        return row

    def _alloc_output(self, value) -> int:
        row = len(self._init)
        self._init.append(None)
        # Map the value to its newest row: both old and new rows hold it
        # once written (rows are SSA), so either is a valid source.
        self._table[self._key(value)] = row
        return row

    def initial_state(self) -> np.ndarray:
        """(rows, words) uint32 image: input rows hold their traced
        values, gate-output rows start zeroed (their ops overwrite)."""
        width = 0
        for v in self._init:
            if v is not None:
                width = int(np.asarray(v).shape[-1])
                break
        state = np.zeros((len(self._init), width), np.uint32)
        for r, v in enumerate(self._init):
            if v is not None:
                state[r] = v
        return state

    # --------------------------------------------- GateExecutor protocol
    def gate_maj(self, planes: Sequence[jax.Array], x: int,
                 n_act: int) -> jax.Array:
        srcs = tuple(self.row_of(p) for p in planes)
        stack = jnp.stack([jnp.asarray(p, jnp.uint32) for p in planes])
        out = bp.maj3_words(*stack) if len(planes) == 3 else \
            bp.majority(stack, axis=0)
        dst = self._alloc_output(out)
        self.program.emit("MAJ", x=x, n_act=n_act, srcs=srcs, dsts=(dst,))
        return out

    def gate_not(self, p: jax.Array) -> jax.Array:
        src = self.row_of(p)
        out = ~jnp.asarray(p, jnp.uint32)
        dst = self._alloc_output(out)
        self.program.emit("NOT", srcs=(src,), dsts=(dst,))
        return out


@dataclasses.dataclass
class CompiledProgram:
    """A traced computation, ready for :meth:`Backend.run_fused`.

    ``state`` is the initial (rows, words) image; ``out_rows`` index the
    rows holding the result planes after execution; ``n_lanes`` is the
    element count for unpacking elementwise results.
    """

    program: Program
    state: np.ndarray
    out_rows: tuple[int, ...]
    n_lanes: int

    def outputs(self, final_state: jax.Array) -> jax.Array:
        """Unpack the result planes of an executed image into uint32
        elements (inverse of :func:`bitplanes.pack_uint_elements`)."""
        planes = jnp.asarray(final_state, jnp.uint32)[
            np.array(self.out_rows, np.int32)]
        return bp.unpack_uint_elements(planes, self.n_lanes)


def trace_planes(build, tier: int, n_act: int) -> CompiledProgram:
    """Trace ``build(bs, tracer) -> output planes`` into a CompiledProgram.

    ``build`` receives a :class:`~repro.pud.arith.BitSerial` wired to a
    fresh Tracer and returns the stacked output planes ``(nbits, words)``;
    constructions are shared verbatim with the per-gate path, so the
    traced Program's histogram equals the cost-only recording.
    """
    from repro.pud.arith import BitSerial  # deferred: arith lazily imports us

    tracer = Tracer()
    bs = BitSerial(tier=tier, n_act=n_act, executor=tracer)
    out = build(bs)
    out_rows = tuple(tracer.row_of(p) for p in out)
    return CompiledProgram(tracer.program, tracer.initial_state(),
                           out_rows, n_lanes=0)


def compile_elementwise(op: str, a, b, tier: int = 3, n_act: int = 4
                        ) -> CompiledProgram:
    """Compile a §8.1 elementwise microbenchmark to an addressed Program.

    Mirrors :func:`repro.pud.arith.run_elementwise` (same constructions,
    same recorded op stream) but captures row addresses, so the returned
    program executes through :meth:`Backend.run_fused` in level-batched
    kernel dispatches instead of one launch per gate.
    """
    with obs.span("compile"):
        a = jnp.asarray(a, jnp.uint32).reshape(-1)
        b = jnp.asarray(b, jnp.uint32).reshape(-1)
        k = int(a.shape[0])
        A = bp.pack_uint_elements(a)
        B = bp.pack_uint_elements(b)

        def build(bs):
            if op == "and":
                return [bs.and_(A[i], B[i]) for i in range(A.shape[0])]
            if op == "or":
                return [bs.or_(A[i], B[i]) for i in range(A.shape[0])]
            if op == "xor":
                return [bs.xor(A[i], B[i]) for i in range(A.shape[0])]
            if op == "add":
                return list(bs.add(A, B)[0])
            if op == "sub":
                return list(bs.sub(A, B)[0])
            if op == "mul":
                return list(bs.mul(A, B))
            if op == "div":
                return list(bs.div(A, B)[0])
            raise ValueError(f"unknown op {op!r}")

        cp = trace_planes(build, tier=tier, n_act=n_act)
    cp.n_lanes = k
    return cp
