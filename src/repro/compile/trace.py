"""Lower BitSerial gate streams to addressed, fusable Programs.

The §8.1 bit-serial compiler (:class:`repro.pud.arith.BitSerial`) records
cost-only ops while computing on whatever planes flow through it.  The
:class:`Tracer` here is a :class:`~repro.pud.arith.GateExecutor` that
computes nothing: it runs ``BitSerial`` unchanged over width-1 *origin*
planes and assigns every gate a *row address*: operands resolve to rows
of a subarray image, each gate output gets a fresh (SSA) row, and the
emitted :class:`~repro.pud.isa.Program` carries full ``srcs``/``dsts``
— executable by any backend and fusable by
:mod:`repro.compile.schedule`.

Rows are keyed by plane *origin*, never by operand values.  Input plane
``A[i]`` holds the ID ``1 + i``, ``B[i]`` holds ``1 + nbits + i``, the
constants keep their 0 / 0xFFFFFFFF words, and each gate returns a fresh
ID.  BitSerial reshapes, stacks and re-indexes planes (``jnp.stack(sums)``,
``acc[i:]``), which keeps each plane's ID as it would keep its value, so
the recorded gates are resolved to rows after the build, from one host
read of every operand's ID.  Origins first seen as gate operands become
*input rows* of the initial image, in order of first use.

The program therefore depends only on (op, nbits, tier, n_act), and
:func:`compile_elementwise` traces each (op, lanes, tier, n_act) once
(span ``pud/compile.trace``), then builds every call's image on the
device with one jitted function of the operands.
"""

from __future__ import annotations

import collections
import dataclasses
import threading
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import bitplanes as bp
from repro.pud.isa import FrozenProgram, Program

#: The IDs of the constant planes: ``BitSerial.const`` fills with them.
ZERO_ID, ONE_ID = 0, 0xFFFFFFFF

#: Bit-planes of an elementwise operand: its lanes are ``uint32``.
ELEMENT_BITS = 32


class Tracer:
    """GateExecutor that records each gate over origin planes and
    computes nothing: every gate returns a plane holding a fresh ID."""

    def __init__(self, first_id: int):
        self._next_id = first_id
        #: (kind, x, n_act, operand planes, output ID) per gate, in order.
        self._gates: list[tuple] = []

    def _gate(self, kind: str, planes: Sequence, x: int = 0,
              n_act: int = 0) -> np.ndarray:
        out = self._next_id
        self._next_id += 1
        self._gates.append((kind, x, n_act, tuple(planes), out))
        return np.full((1,), out, np.uint32)

    # --------------------------------------------- GateExecutor protocol
    def gate_maj(self, planes: Sequence[jax.Array], x: int,
                 n_act: int) -> np.ndarray:
        return self._gate("MAJ", planes, x, n_act)

    def gate_not(self, p: jax.Array) -> np.ndarray:
        return self._gate("NOT", (p,))

    # ------------------------------------------------------------- rows
    def allocate(self, outputs: Sequence, nbits: int) -> "Trace":
        """Resolve the recorded gates to rows: SSA rows for gate outputs,
        input rows in order of first use (operands before the gate's own
        output), then the rows of ``outputs``."""
        with obs.span("compile.sync"):
            operands, outputs = jax.device_get(
                ([g[3] for g in self._gates], list(outputs)))
        program = Program()
        rows: dict[int, int] = {}
        sources: list[int] = []    # per row: its plane of [A; B; 0; 1]
        zero_src, one_src = 2 * nbits, 2 * nbits + 1

        def row_of(plane) -> int:
            origin = int(np.asarray(plane).reshape(-1)[0])
            row = rows.get(origin)
            if row is None:
                row = rows[origin] = len(sources)
                sources.append(zero_src if origin == ZERO_ID else
                               one_src if origin == ONE_ID else origin - 1)
            return row

        for (kind, x, n_act, _, out), planes in zip(self._gates, operands):
            srcs = tuple(row_of(p) for p in planes)
            dst = rows[out] = len(sources)
            sources.append(zero_src)   # gate rows start at zero
            program.emit(kind, x=x, n_act=n_act, srcs=srcs, dsts=(dst,))
        out_rows = tuple(row_of(p) for p in outputs)
        return Trace(program.freeze(), tuple(sources), out_rows)


@dataclasses.dataclass(frozen=True)
class Trace:
    """A traced computation over two ``nbits``-plane operands.

    ``sources[r]`` is the plane row ``r`` starts from, an index into
    the stack ``[A; B; all-0; all-1]`` (gate-output rows start at
    all-0, their ops overwrite them); ``out_rows`` index the rows
    holding the result planes after execution.
    """

    program: FrozenProgram
    sources: tuple[int, ...]
    out_rows: tuple[int, ...]

    def image(self, A: jax.Array, B: jax.Array) -> jax.Array:
        """The (rows, words) initial image from operand planes ``A`` and
        ``B`` (nbits, words); eager or under ``jax.jit`` alike."""
        words = A.shape[-1]
        planes = jnp.concatenate([
            jnp.asarray(A, jnp.uint32), jnp.asarray(B, jnp.uint32),
            jnp.zeros((1, words), jnp.uint32),
            jnp.full((1, words), 0xFFFFFFFF, jnp.uint32)])
        return planes[np.array(self.sources, np.int32)]


def trace_planes(build, nbits: int, tier: int, n_act: int) -> Trace:
    """Trace ``build(bs, A, B) -> output planes`` into a :class:`Trace`.

    ``build`` receives a :class:`~repro.pud.arith.BitSerial` wired to a
    fresh Tracer and the two operands as ``(nbits, 1)`` origin planes;
    constructions are shared verbatim with the per-gate path, so the
    traced Program's histogram equals the cost-only recording.
    """
    from repro.pud.arith import BitSerial  # deferred: arith lazily imports us

    ids = np.arange(1, 2 * nbits + 1, dtype=np.uint32)[:, None]
    tracer = Tracer(first_id=2 * nbits + 1)
    bs = BitSerial(tier=tier, n_act=n_act, executor=tracer)
    out = build(bs, ids[:nbits], ids[nbits:])
    return tracer.allocate(out, nbits)


#: Each elementwise op over operand planes ``A``, ``B``: its result planes.
OPS: dict[str, Callable] = {
    "and": lambda bs, A, B: [bs.and_(A[i], B[i]) for i in range(len(A))],
    "or": lambda bs, A, B: [bs.or_(A[i], B[i]) for i in range(len(A))],
    "xor": lambda bs, A, B: [bs.xor(A[i], B[i]) for i in range(len(A))],
    "add": lambda bs, A, B: list(bs.add(A, B)[0]),
    "sub": lambda bs, A, B: list(bs.sub(A, B)[0]),
    "mul": lambda bs, A, B: list(bs.mul(A, B)),
    "div": lambda bs, A, B: list(bs.div(A, B)[0]),
}


@dataclasses.dataclass
class CompiledProgram:
    """A traced computation, ready for :meth:`Backend.run_fused`.

    ``state`` is the initial (rows, words) image on the device;
    ``out_rows`` index the rows holding the result planes after
    execution; ``n_lanes`` is the element count for unpacking
    elementwise results.
    """

    program: FrozenProgram
    state: jax.Array
    out_rows: tuple[int, ...]
    n_lanes: int
    _unpack: Callable = dataclasses.field(repr=False)

    def outputs(self, final_state: jax.Array) -> jax.Array:
        """Unpack the result planes of an executed image into uint32
        elements (inverse of :func:`bitplanes.pack_uint_elements`)."""
        return self._unpack(final_state)


@dataclasses.dataclass(frozen=True)
class _Compiled:
    """One (op, lanes, tier, n_act): its trace and its two jitted
    functions, operands -> image and final image -> elements."""

    trace: Trace
    image: Callable
    unpack: Callable


def _compile(op: str, lanes: int, tier: int, n_act: int) -> _Compiled:
    trace = trace_planes(OPS[op], ELEMENT_BITS, tier, n_act)
    out_rows = np.array(trace.out_rows, np.int32)

    def image(a, b):
        return trace.image(bp.pack_uint_elements(a.reshape(-1)),
                           bp.pack_uint_elements(b.reshape(-1)))

    def unpack(state):
        return bp.unpack_uint_elements(state[out_rows], lanes)

    return _Compiled(trace, jax.jit(image), jax.jit(unpack))


#: Compiled shapes a process keeps, least recently used first; the same
#: bound as the backend's level walks.  Every caller may share an entry:
#: it is a pure function of its key.
COMPILE_CACHE_SIZE = 128
_compiled: collections.OrderedDict = collections.OrderedDict()
_compiled_lock = threading.Lock()


def _compiled_for(op: str, lanes: int, tier: int, n_act: int) -> _Compiled:
    key = (op, lanes, tier, n_act)
    with _compiled_lock:
        entry = _compiled.get(key)
        if entry is not None:
            _compiled.move_to_end(key)
            return entry
        with obs.span("compile.trace"):
            entry = _compiled[key] = _compile(*key)
        while len(_compiled) > COMPILE_CACHE_SIZE:
            _compiled.popitem(last=False)
        return entry


def compile_elementwise(op: str, a, b, tier: int = 3, n_act: int = 4
                        ) -> CompiledProgram:
    """Compile a §8.1 elementwise microbenchmark to an addressed Program.

    Mirrors :func:`repro.pud.arith.run_elementwise` (same constructions,
    same recorded op stream) but captures row addresses, so the returned
    program executes through :meth:`Backend.run_fused` in level-batched
    kernel dispatches instead of one launch per gate.  The trace is made
    once per (op, lanes, tier, n_act) and reads no operand; each call
    dispatches one jitted function that packs ``a`` and ``b`` into the
    image on the device.
    """
    if op not in OPS:
        raise ValueError(f"unknown op {op!r}")
    with obs.span("compile"):
        a = jnp.asarray(a, jnp.uint32)
        b = jnp.asarray(b, jnp.uint32)
        if a.size != b.size:
            raise ValueError(
                f"operands of {a.size} and {b.size} lanes differ")
        entry = _compiled_for(op, int(a.size), tier, n_act)
        state = entry.image(a, b)
    return CompiledProgram(entry.trace.program, state, entry.trace.out_rows,
                           int(a.size), entry.unpack)
