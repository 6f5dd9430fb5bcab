"""``pallas``: the bulk TPU-kernel backend.

Dispatches the Pallas kernels of :mod:`repro.kernels` (bit-sliced CSA
MAJX, fan-out Multi-RowCopy, fused XOR+popcount mismatch, fused
bit-serial adder) through the shared VPU tiling helper
(:mod:`repro.kernels.tiling`).  How the kernels run is not a knob:
:func:`interpret_mode` compiles them on a TPU and runs the Pallas
interpreter everywhere else, and the backend fixes that mode once, at
construction.
Batch dispatch is vmapped over the kernel wrappers — one fused launch
per batch, not a python loop.

Program execution: :meth:`run_fused` overrides the per-op interpreter
with the :mod:`repro.compile` schedule — every dependency level of the
program becomes at most one MAJX dispatch (mixed arities padded with
constant 0/1 plane pairs, an exact identity) plus at most one
Multi-RowCopy dispatch, while NOT/COPY levels are pure gather/scatter.
The level walk is written once, as a pure function of the image over
the group indices the backend builds once per schedule, and runs two
ways.  The first time the backend sees a schedule it runs eagerly, op by
op, so a program that runs once pays no XLA compile.  The second time,
the walk is jitted with its indices baked in as constants, and from
then on each call is one dispatch of that compiled program.  The
backend keeps the walks of its last ``WALK_CACHE_SIZE`` schedules.
:meth:`run_fused` opens the host span ``pud/backend.run_fused``
(:mod:`repro.obs`) around the level executor: the image upload and the
level walk; inside it, ``pud/backend.levels_build`` wraps building and
first running a jitted walk, and ``pud/backend.levels_jit`` each later
dispatch of one.  :meth:`walk_fn` hands a caller the same walk, as a
traceable function, to build into a jitted program of its own (the
scrub's tile step); the caller replays its launches.
``self.dispatch_count`` counts MAJX / Multi-RowCopy launches (one per
schedule group, however the walk runs), a structural count and not a
device time; each launch also accrues
:data:`repro.core.costmodel.COST`-priced energy (launch round-trip at
board power + HBM traffic) into ``self.energy_nj_total``, so fusion's
dispatch savings show up in modelled joules too.
"""

from __future__ import annotations

import collections
import copy
import dataclasses
import functools
import threading
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.backends.base import Backend, Capabilities
from repro.backends.context import ExecutionContext
from repro.core import calibration as cal
from repro.core.costmodel import COST
from repro.kernels.bitserial.ops import bitserial_add
from repro.kernels.majx.ops import majx as majx_kernel
from repro.kernels.mismatch.ops import mismatch_count
from repro.kernels.rowcopy.ops import fanout
from repro.pud.isa import Program


def interpret_mode() -> bool:
    """Whether Pallas kernels run in the interpreter on this process.

    On a TPU the kernels run compiled, never interpreted.  Elsewhere the
    interpreter is the only way a Pallas TPU kernel runs at all.
    """
    return jax.default_backend() != "tpu"


class PallasBackend(Backend):
    name = "pallas"

    def __init__(self, ctx: Optional[ExecutionContext] = None):
        super().__init__(ctx)
        self.interpret = interpret_mode()
        #: Schedule -> :class:`_LevelWalk`, least recently used first.
        self._walks: collections.OrderedDict = collections.OrderedDict()
        self._walks_lock = threading.Lock()
        #: Set on a :meth:`tracer` twin: the bytes of each launch it
        #: traces, for the replay.
        self._log: Optional[list] = None

    def capabilities(self) -> Capabilities:
        return Capabilities(
            name=self.name,
            description="bulk Pallas TPU kernels (CSA bit-sliced MAJX, "
                        "fan-out MRC, fused mismatch, bit-serial add)",
            stochastic=False,
            device_model=False,
            accelerated=True,
            max_majx=1_000_000,
            n_act_levels=cal.N_ACT_LEVELS,
            native_batch=True,
        )

    def _launch(self, n_bytes: float) -> None:
        """Account one kernel launch: bump the dispatch counter and
        accrue its CostModel energy — the launch round-trip at board
        power plus the HBM access energy of the kernel's ``n_bytes`` of
        operand + result traffic.  A :meth:`tracer` twin logs the launch
        instead."""
        if self._log is not None:
            self._log.append(n_bytes)
            return
        self.dispatch_count += 1
        self.energy_nj_total += (COST.dispatch_energy_nj(1)
                                 + COST.hbm_energy_nj(n_bytes))

    def majx(self, planes: jax.Array, x: Optional[int] = None,
             n_act: Optional[int] = None) -> jax.Array:
        out_words = planes.size // planes.shape[0]
        self._launch((planes.size + out_words) * 4)
        return majx_kernel(planes, interpret=self.interpret,
                           block_r=self.ctx.block_r,
                           block_c=self.ctx.block_c)

    def majx_batch(self, planes: jax.Array) -> jax.Array:
        """(B, X, R, C) -> (B, R, C) in one vmapped kernel dispatch."""
        planes = jnp.asarray(planes, jnp.uint32)
        self._launch((planes.size + planes.size // planes.shape[1]) * 4)
        fn = functools.partial(majx_kernel, interpret=self.interpret,
                               block_r=self.ctx.block_r,
                               block_c=self.ctx.block_c)
        return jax.vmap(fn)(planes)

    def rowcopy(self, src: jax.Array, n_dst: int) -> jax.Array:
        self._launch(src.size * (1 + n_dst) * 4)
        return fanout(src, n_dst, interpret=self.interpret,
                      block_r=self.ctx.block_r, block_c=self.ctx.block_c)

    def mismatch(self, a: jax.Array, b: jax.Array) -> jax.Array:
        self._launch((jnp.asarray(a).size + jnp.asarray(b).size) * 4)
        return mismatch_count(a, b, interpret=self.interpret)

    def add_planes(self, a: jax.Array, b: jax.Array) -> jax.Array:
        self._launch(3 * jnp.asarray(a).size * 4)
        return bitserial_add(a, b, interpret=self.interpret)

    # ------------------------------------------------- fused program path
    def run_fused(self, program: Program, state: jax.Array, *,
                  sched=None) -> jax.Array:
        """Level-batched program execution (see module docstring).

        Reads sample the level-entry state and writes commit at level
        exit, matching the hazard model the scheduler levels against;
        WAW leveling guarantees the per-level scatters hit disjoint
        rows.  A prebuilt ``sched`` (the session compile cache) skips
        the scheduling pass entirely.

        Runs the schedule's level walk (:meth:`_walk`): eager the first
        time the backend sees the schedule, then as one jitted
        function, built on the second sighting (span
        ``pud/backend.levels_build``) and dispatched from then on (span
        ``pud/backend.levels_jit``).  Either way ``dispatch_count`` and
        ``energy_nj_total`` advance by one launch per MAJ/MRC group.
        """
        from repro.compile.schedule import build_schedule

        with obs.span("backend.run_fused"):
            if sched is None:
                sched = build_schedule(program)
            state = jnp.asarray(state, jnp.uint32)
            walk, first = self._level_walk(sched)
            if walk.max_row >= state.shape[0]:
                raise ValueError(
                    f"program addresses row {walk.max_row} of a "
                    f"{state.shape[0]}-row image")
            if first:
                return self._walk(walk, state)
            if walk.jitted is None:
                with obs.span("backend.levels_build"):
                    walk.jitted = jax.jit(walk.fn)
                    out = walk.jitted(state)
            else:
                with obs.span("backend.levels_jit"):
                    out = walk.jitted(state)
            self._replay(walk, state.shape[1])
            return out

    def walk_fn(self, program: Program, sched, rows: int):
        """The schedule's level walk as a traceable function of a
        ``(rows, words)`` image, and ``replay(width)``: the walk
        :meth:`run_fused` jits, from the same LRU entry, whose launches
        the caller replays after each call (see
        :meth:`Backend.walk_fn`)."""
        walk, _ = self._level_walk(sched)
        if walk.max_row >= rows:
            raise ValueError(f"program addresses row {walk.max_row} of a "
                             f"{rows}-row image")
        return walk.fn, functools.partial(self._replay, walk)

    def tracer(self) -> tuple["PallasBackend", Callable[[], None]]:
        """A twin that logs the launches traced on it, and the replay of
        that log on this backend (see :meth:`Backend.tracer`)."""
        twin = copy.copy(self)
        twin._log = log = []

        def replay() -> None:
            for n_bytes in log:
                self._launch(n_bytes)

        return twin, replay

    def _replay(self, walk: "_LevelWalk", width: int) -> None:
        """One call's launches of ``walk`` on a ``width``-word image."""
        for words in walk.launch_words:
            self._launch(words * width * 4)

    def _level_walk(self, sched) -> tuple["_LevelWalk", bool]:
        """The schedule's :class:`_LevelWalk` from the LRU, built and
        admitted on its first sighting (then ``True``)."""
        with self._walks_lock:
            walk = self._walks.get(sched)
            if walk is not None:
                self._walks.move_to_end(sched)
                return walk, False
            walk = self._walks[sched] = _LevelWalk(sched)
            # Traced on a copy of the backend: the launches it counts
            # while tracing are dropped, and each call's are replayed.
            walk.fn = functools.partial(copy.copy(self)._walk, walk)
            while len(self._walks) > WALK_CACHE_SIZE:
                self._walks.popitem(last=False)
            return walk, True

    def _walk(self, walk: "_LevelWalk", state: jax.Array) -> jax.Array:
        """Every level of the schedule, a pure function of ``state``.

        Runs eagerly or under ``jax.jit`` alike: the group indices are
        NumPy constants of ``walk``.  The all-0 and all-1 rows that pad
        narrow MAJ ops are appended below the image once, as rows -2 and
        -1, and cropped at the end; no ``dst`` addresses them.  Each
        group gathers from the level-entry image and scatters into the
        running one.
        """
        rows, width = state.shape
        image = jnp.concatenate([
            state,
            jnp.zeros((1, width), jnp.uint32),
            jnp.full((1, width), 0xFFFFFFFF, jnp.uint32)])
        for level in walk.levels:
            entry = image
            for g in level:
                if g.kind == "MAJ":
                    # (B, X) gather -> (X, B, W): one MAJX launch.
                    batch = jnp.swapaxes(entry[g.srcs], 0, 1)
                    vals = self.majx(batch)[g.sel]
                elif g.kind == "MRC":
                    copies = self.rowcopy(entry[g.srcs], g.param)
                    vals = copies[g.sel_copy, g.sel]
                elif g.kind == "NOT":
                    vals = self._not(entry[g.srcs])
                else:
                    vals = self._copy(entry[g.srcs])
                image = image.at[g.dsts].set(vals)
        return image[:rows]


#: Level walks a backend keeps (schedules seen, most recent last); the
#: same bound as the session's ``CompileCache``.
WALK_CACHE_SIZE = 128

#: Rows of the constant planes the walk appends below the image.
ZERO_ROW, ONE_ROW = -2, -1


@dataclasses.dataclass(frozen=True, eq=False)
class _GroupPlan:
    """One schedule group's gather and scatter indices.

    MAJ: ``srcs`` is the (B, X) source matrix, narrower ops padded to
    the group's arity X with constant (all-0, all-1) plane *pairs*, each
    pair adding one to the popcount and one to the majority threshold,
    so ``MAJ_k(x..) == MAJ_X(x.., 0*m, 1*m)`` exactly; ``sel`` is the op
    each of ``dsts`` takes.  MRC: ``srcs`` is the (B,) source rows, one
    fan-out to the widest destination count serves them all, and each
    op scatters the prefix of copies its own ``dsts`` ask for (copies
    are identical, so a prefix is exact): ``sel_copy`` / ``sel`` pick
    (copy, op) per dst.  NOT / COPY: ``srcs`` is one source row per dst.
    ``launch_words`` is the kernel's operand + result words per image
    word (0 without a kernel).
    """

    kind: str
    param: int
    srcs: np.ndarray
    dsts: np.ndarray
    sel: Optional[np.ndarray] = None
    sel_copy: Optional[np.ndarray] = None
    launch_words: int = 0


def _plan_group(group) -> _GroupPlan:
    ops = group.ops
    dsts = np.array([d for op in ops for d in op.dsts])
    sel = np.array([i for i, op in enumerate(ops) for _ in op.dsts])
    if group.kind == "MAJ":
        x_max = group.param
        srcs = np.empty((len(ops), x_max), np.int32)
        for i, op in enumerate(ops):
            k = len(op.srcs)
            if (x_max - k) % 2:
                raise ValueError(
                    f"cannot pad MAJ{k} to MAJ{x_max}: parity differs")
            pad = (x_max - k) // 2
            srcs[i, :k] = op.srcs
            srcs[i, k:k + pad] = ZERO_ROW
            srcs[i, k + pad:] = ONE_ROW
        return _GroupPlan("MAJ", x_max, srcs, dsts, sel=sel,
                          launch_words=len(ops) * (x_max + 1))
    if group.kind == "MRC":
        sel_copy = np.array([j for op in ops for j in range(len(op.dsts))])
        return _GroupPlan("MRC", group.param,
                          np.array([op.srcs[0] for op in ops]), dsts,
                          sel=sel, sel_copy=sel_copy,
                          launch_words=len(ops) * (1 + group.param))
    # NOT / COPY: one gather (+ complement) + scatter, no kernel.
    return _GroupPlan(group.kind, group.param,
                      np.array([op.srcs[0] for op in ops
                                for _ in op.dsts]), dsts)


class _LevelWalk:
    """A schedule's group plans, built once, its walk as a traceable
    function (``fn``), and its jitted walk, built on the schedule's
    second sighting."""

    def __init__(self, sched):
        self.levels = tuple(tuple(_plan_group(g) for g in level)
                            for level in sched.levels)
        groups = [g for level in self.levels for g in level]
        #: Per MAJ/MRC group in walk order: what one launch moves.
        self.launch_words = tuple(g.launch_words for g in groups
                                  if g.launch_words)
        self.max_row = max((int(a.max()) for g in groups
                            for a in (g.srcs, g.dsts)), default=-1)
        self.fn = None
        self.jitted = None
