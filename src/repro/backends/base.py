"""The Backend protocol: one Program, interchangeable executors.

A backend executes PUD work at three granularities through one
interface:

* **bulk entry points** — ``majx(planes, x, n_act)``,
  ``rowcopy(src, n_dst)``, ``mismatch(a, b)``, ``add_planes(a, b)`` on
  packed uint32 bit-planes (the layout of :mod:`repro.core.bitplanes`);
* **programs** — ``run(program, state)`` interprets a
  :class:`repro.pud.isa.Program` whose ops carry row addresses against a
  ``(rows, words)`` subarray image, and ``run_fused(program, state)``
  executes the same program through the :mod:`repro.compile` fusion
  scheduler (bit-identical results; batch-native backends collapse each
  dependency level into one kernel dispatch);
* **compiled arithmetic** — ``elementwise(op, a, b)`` drives the §8.1
  bit-serial compiler with this backend as the gate executor, so the
  recorded Program and the computed values come from the same run.

All knobs live in one shared :class:`~repro.backends.context.ExecutionContext`.
Implementations: ``oracle`` (pure bitwise reference), ``sim``
(behavioural subarray with calibrated error injection), ``pallas``
(bulk TPU kernels).  Consumers name one backend and execute through a
:class:`repro.session.DramSession` — a backend is a one-string config
choice, which is what makes regime comparisons (PULSAR/FCDRAM-style
reliability-vs-throughput tradeoffs) apples-to-apples; the session adds
typed row allocation, build-time validation, and schedule caching on
top of this protocol.
"""

from __future__ import annotations

import abc
import contextlib
import dataclasses
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp

from repro.backends.context import ExecutionContext
from repro.pud.isa import Program


class DispatchScope:
    """A window over a backend's kernel-launch and energy counters.

    Produced by :meth:`Backend.count_dispatches`: ``.count`` is the
    launches issued since the scope opened and ``.energy_nj`` the
    modelled energy accrued (CostModel-priced: per-dispatch launch
    energy + HBM traffic on accelerated backends, per-DRAM-command
    Fig. 5 energy on the device-model backend), both frozen when the
    ``with`` block exits — so two workloads (figure rows, tests) each
    read their own window of the monotonic counters instead of sharing
    one mutable total that leaks across resets.
    """

    def __init__(self, backend: "Backend"):
        self._backend = backend
        self._start = backend.dispatch_count
        self._stop: Optional[int] = None
        self._energy_start = backend.energy_nj_total
        self._energy_stop: Optional[float] = None

    @property
    def count(self) -> int:
        end = (self._backend.dispatch_count if self._stop is None
               else self._stop)
        return end - self._start

    @property
    def energy_nj(self) -> float:
        end = (self._backend.energy_nj_total if self._energy_stop is None
               else self._energy_stop)
        return end - self._energy_start


@dataclasses.dataclass(frozen=True)
class Capabilities:
    """What a backend models / how it executes.

    Consumers branch on this instead of on backend names: the sweep
    planner batches chunks for ``native_batch`` executors, the offload
    planner checks ``accelerated``, and characterization filters grids
    by ``max_majx`` / ``n_act_levels``.

    Attributes:
        name: registry name the backend was instantiated under.
        description: one-line human summary of the execution model.
        stochastic: True when the paper-calibrated per-cell error
            surfaces (Obs 1-18) are injected; exact digital results
            otherwise.  ``ExecutionContext(ideal=True)`` forces False.
        device_model: True when ops execute through the behavioural
            ``Subarray``/``PUDDevice`` APA/PRE/ACT command model rather
            than closed-form boolean semantics.  Such ops keep host
            state per command, so they never run under ``jax.jit``: a
            caller that builds a backend's program into a jitted one of
            its own (:meth:`Backend.walk_fn`) runs it eagerly there.
        accelerated: True when bulk ops dispatch Pallas TPU kernels
            (compiled on a TPU, the Pallas interpreter elsewhere).
        max_majx: widest MAJ arity this backend can execute.  For the
            calibrated ``sim`` backend this is the manufacturer limit
            (fn 11: 9 for Mfr H, 7 for Mfr M); digital backends are
            unbounded in arity (reported as a large sentinel).
        n_act_levels: reachable simultaneous-activation counts
            (§4 Limitation 2: powers of two up to 32).
        native_batch: True when ``majx_batch`` is a single vmapped
            kernel dispatch rather than a python loop — the property
            the sweep planner exploits to fuse a chunk of grid points
            into one launch.
    """

    name: str
    description: str
    stochastic: bool
    device_model: bool
    accelerated: bool
    max_majx: int
    n_act_levels: tuple[int, ...]
    native_batch: bool


class Backend(abc.ABC):
    """Abstract executor for PUD operations (see module docstring)."""

    name: str = "?"

    def __init__(self, ctx: Optional[ExecutionContext] = None):
        self.ctx = ctx or ExecutionContext()
        #: Kernel launches issued so far (bulk-op or program execution).
        #: Only accelerated backends increment it; it is the structural
        #: count the fusion layer reduces (a count, not a device time).
        self.dispatch_count = 0
        #: Modelled energy (nJ) accrued so far, priced by
        #: :data:`repro.core.costmodel.COST`: the ``pallas`` backend
        #: accrues launch + HBM-traffic energy per kernel dispatch, the
        #: ``sim`` backend Fig. 5 command energy per DRAM op.  The
        #: ``oracle`` reference accrues nothing (it models no hardware).
        self.energy_nj_total = 0.0

    def reset_dispatches(self) -> None:
        """Zero the process-lifetime counters (launches AND energy).

        Prefer :meth:`count_dispatches` for measurement — resetting a
        shared counter inside someone else's measurement window corrupts
        their count; a scope never does.
        """
        self.dispatch_count = 0
        self.energy_nj_total = 0.0

    @contextlib.contextmanager
    def count_dispatches(self):
        """Scoped kernel-launch and energy counting.

        Yields a :class:`DispatchScope` whose ``.count`` is the
        launches issued — and ``.energy_nj`` the modelled energy accrued
        — inside the ``with`` block (frozen at exit).  Scopes nest and
        sequence independently, so concurrent workloads and tests
        cannot leak counts into each other.

        >>> with backend.count_dispatches() as scope:
        ...     backend.run_fused(program, state)
        >>> scope.count                # launches of that run alone
        """
        scope = DispatchScope(self)
        try:
            yield scope
        finally:
            scope._stop = self.dispatch_count
            scope._energy_stop = self.energy_nj_total

    # ------------------------------------------------------------ protocol
    @abc.abstractmethod
    def capabilities(self) -> Capabilities:
        """Self-description for capability-based dispatch.

        May depend on ``self.ctx`` (e.g. ``sim`` reports the active
        manufacturer's MAJ arity limit, and ``stochastic=False`` under
        an ideal context).
        """

    @abc.abstractmethod
    def majx(self, planes: jax.Array, x: Optional[int] = None,
             n_act: Optional[int] = None) -> jax.Array:
        """MAJX over X packed operand planes.

        ``planes``: (X, words) or (X, R, C) uint32, X odd.  ``x`` defaults
        to ``planes.shape[0]``; ``n_act`` (>= x, a reachable activation
        level) defaults to ``ctx.n_act`` and selects the replication
        ladder of §5 — it changes the *success rate*, never the logical
        result.  Returns the majority plane, shape ``planes.shape[1:]``.
        """

    @abc.abstractmethod
    def rowcopy(self, src: jax.Array, n_dst: int) -> jax.Array:
        """Multi-RowCopy: replicate one row image to ``n_dst`` rows.

        ``src``: (words,) or (R, C) uint32.  Returns ``(n_dst, *src.shape)``.
        """

    @abc.abstractmethod
    def mismatch(self, a: jax.Array, b: jax.Array) -> jax.Array:
        """Total differing bits between two packed arrays (any shape)."""

    @abc.abstractmethod
    def add_planes(self, a: jax.Array, b: jax.Array) -> jax.Array:
        """Bit-serial ripple add over (NBITS, ...) packed planes."""

    # ------------------------------------------------- derived bulk helpers
    def majx_batch(self, planes: jax.Array) -> jax.Array:
        """Batched MAJX: (B, X, R, C) -> (B, R, C).

        Default is a python loop; backends with native batch dispatch
        (``pallas``) override with a vmapped kernel call.
        """
        return jnp.stack([self.majx(p) for p in planes])

    def success_rate(self, got: jax.Array, want: jax.Array,
                     n_bits: Optional[int] = None) -> float:
        """Fraction of matching bits — the paper's §3.1 metric."""
        total = int(n_bits) if n_bits else jnp.asarray(got).size * 32
        return 1.0 - int(self.mismatch(got, want)) / total

    # -------------------------------------------------- program execution
    def run(self, program: Program, state: jax.Array) -> jax.Array:
        """Execute an addressed Program against a (rows, words) image.

        Ops without destination addresses (cost-only streams recorded by
        the bit-serial compiler) are skipped.  Returns the new image.
        """
        state = jnp.asarray(state, jnp.uint32)
        for op in program.ops:
            state = self._exec_op(op, state)
        return state

    def run_fused(self, program: Program, state: jax.Array, *,
                  sched=None) -> jax.Array:
        """Execute an addressed Program through the fusion scheduler.

        Semantically identical to :meth:`run` (verified adversarially in
        tests/test_compile_differential.py).  The default falls back
        to per-op interpretation, so device-model and reference backends
        keep their exact command-level semantics; backends with native
        batch dispatch (``pallas``) override this with level-batched
        kernel launches (see :mod:`repro.compile.schedule`).

        ``sched`` optionally supplies a prebuilt schedule (the session
        layer's content-hash cache skips re-scheduling on repeated
        programs).  Backends that interpret per-op ignore it.
        """
        return self.run(program, state)

    def walk_fn(self, program: Program, sched, rows: int):
        """``program`` as a function of a ``(rows, words)`` image, for a
        caller to build into a program of its own, and ``replay(width)``,
        which advances this backend's counters as one call of it on a
        ``width``-word image does.

        The per-op default is :meth:`run` of the program, plain jnp, so
        it traces under ``jax.jit`` unless ``capabilities().device_model``
        (then it runs eagerly and counts its own commands); it counts
        nothing to replay.  ``sched`` and ``rows`` are the session's
        schedule and the image's row count, which backends with a fused
        path (``pallas``) build their walk from and check against.
        """
        return _PerOpRun(self, program.content_key(), program), _no_replay

    def tracer(self) -> tuple["Backend", Callable[[], None]]:
        """A twin of this backend for ``jax.jit`` to trace bulk ops on,
        and ``replay``, which advances this backend's counters as one
        call of the traced program advanced the twin's.  The default
        twin is the backend itself: its traced ops count nothing."""
        return self, lambda: None

    def _exec_op(self, op, state: jax.Array) -> jax.Array:
        if not op.dsts:
            return state  # cost-only op: nothing addressable to do
        dsts = jnp.asarray(op.dsts)
        if op.kind == "MAJ":
            out = self.majx(state[jnp.asarray(op.srcs)], x=op.x,
                            n_act=op.n_act or None)
            return state.at[dsts].set(out)
        if op.kind == "NOT":
            return state.at[dsts].set(self._not(state[op.srcs[0]]))
        if op.kind == "COPY":
            return state.at[dsts].set(self._copy(state[op.srcs[0]]))
        if op.kind == "MRC":
            rows = self.rowcopy(state[op.srcs[0]], len(op.dsts))
            return state.at[dsts].set(rows)
        if op.kind == "FRAC":
            return self._frac(dsts, state)
        if op.kind in ("WR", "RD"):
            return state  # I/O accounting ops: no in-array effect
        raise ValueError(f"unknown op kind {op.kind}")

    # Per-op hooks the device-model backend overrides with command-level
    # execution (RowClone / complement copy with calibrated errors).
    def _not(self, plane: jax.Array) -> jax.Array:
        return ~jnp.asarray(plane, jnp.uint32)

    def _copy(self, plane: jax.Array) -> jax.Array:
        return jnp.asarray(plane, jnp.uint32)

    def _frac(self, dsts: jax.Array, state: jax.Array) -> jax.Array:
        return state  # neutral rows don't vote; value-wise a no-op

    # ------------------------------------------- §8.1 compiled arithmetic
    def elementwise(self, op: str, a, b, tier: Optional[int] = None,
                    n_act: Optional[int] = None):
        """Run a §8.1 microbenchmark through this backend's gates.

        Returns (uint32 results, recorded Program) — the Program prices
        latency/energy under the shared calibration regardless of which
        backend computed the values.
        """
        from repro.pud.arith import run_elementwise

        return run_elementwise(
            op, a, b, tier=tier or self.ctx.tier,
            n_act=n_act or self.ctx.n_act, executor=self)

    # GateExecutor protocol (repro.pud.arith) -----------------------------
    def gate_maj(self, planes: Sequence[jax.Array], x: int,
                 n_act: int) -> jax.Array:
        return self.majx(jnp.stack([jnp.asarray(p, jnp.uint32)
                                    for p in planes]), x=x, n_act=n_act)

    def gate_not(self, p: jax.Array) -> jax.Array:
        return self._not(p)


@dataclasses.dataclass(frozen=True)
class _PerOpRun:
    """:meth:`Backend.run` of one program, as a function of the image;
    equal for one backend and equal program content, so a caller may key
    a cache on it."""

    backend: Backend
    key: str
    program: Program = dataclasses.field(compare=False)

    def __call__(self, state: jax.Array) -> jax.Array:
        return self.backend.run(self.program, state)


def _no_replay(width: int) -> None:
    """A per-op run counts its own commands: nothing to replay."""
