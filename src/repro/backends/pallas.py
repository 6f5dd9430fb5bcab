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
``run_fused(mode="megakernel")`` goes further: the whole schedule
lowers to static level tables (:mod:`repro.compile.megakernel`) that
ONE ``pallas_call`` scans end-to-end, VMEM-resident, column-blocked
against ``Capabilities.vmem_budget_bytes`` when the image is too wide.
:meth:`run_fused` opens the host span ``pud/backend.run_fused``
(:mod:`repro.obs`) around the level executor in both modes: the image
upload and, per level, the gather, kernel and scatter glue (fused), or
the one megakernel launch with its padding and crop.
``self.dispatch_count`` tracks real kernel launches, which is the
structural metric ``benchmarks/bench.py`` and the CI perf gate assert
on; each launch also accrues :data:`repro.core.costmodel.COST`-priced
energy (launch round-trip at board power + HBM traffic) into
``self.energy_nj_total``, so fusion's dispatch savings show up in
joules too.
"""

from __future__ import annotations

import functools
from typing import Optional

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
            megakernel=True,
            vmem_budget_bytes=self.ctx.vmem_budget_bytes,
        )

    def _launch(self, n_bytes: float) -> None:
        """Account one kernel launch: bump the dispatch counter and
        accrue its CostModel energy — the launch round-trip at board
        power plus the HBM access energy of the kernel's ``n_bytes`` of
        operand + result traffic."""
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
                  sched=None, mode: str = "fused",
                  lowering=None) -> jax.Array:
        """Level-batched program execution (see module docstring).

        Reads sample the level-entry state and writes commit at level
        exit, matching the hazard model the scheduler levels against;
        WAW leveling guarantees the per-level scatters hit disjoint
        rows.  Prebuilt ``sched`` / ``lowering`` artifacts (the session
        compile cache) skip the scheduling and lowering passes entirely.

        ``mode="megakernel"`` routes to :meth:`run_megakernel` — the
        whole schedule in one dispatch.
        """
        from repro.compile.schedule import build_schedule

        with obs.span("backend.run_fused"):
            if mode == "megakernel":
                return self.run_megakernel(program, state, sched=sched,
                                           lowering=lowering)
            if mode != "fused":
                raise ValueError(f"unknown run_fused mode {mode!r}")
            if sched is None:
                sched = build_schedule(program)
            state = jnp.asarray(state, jnp.uint32)
            for level in sched.levels:
                entry = state
                for group in level:
                    state = self._exec_group(group, entry, state)
            return state

    def run_megakernel(self, program: Program, state: jax.Array, *,
                       sched=None, lowering=None) -> jax.Array:
        """The whole schedule in ONE Pallas dispatch.

        Lowers the program's Schedule to static level tables
        (:mod:`repro.compile.megakernel`), plans VMEM column blocking
        against ``ctx.vmem_budget_bytes``, and scans every level inside
        a single ``pallas_call``.  Value-neutral programs (no write
        slots) are the identity at zero dispatches — there is nothing
        to launch, matching what the empty fused walk does.
        """
        from repro.compile.megakernel import lower_schedule, plan_vmem
        from repro.compile.schedule import build_schedule
        from repro.kernels.megakernel.ops import run_lowering

        if lowering is None:
            if sched is None:
                sched = build_schedule(program)
            lowering = lower_schedule(sched)
        state = jnp.asarray(state, jnp.uint32)
        if lowering.n_levels == 0 or lowering.w_max == 0:
            return state
        rows, words = state.shape
        plan = plan_vmem(lowering, rows, words, self.ctx.vmem_budget_bytes,
                         block_r=self.ctx.block_r)
        self._launch(2 * rows * words * 4)  # image in + image out
        return run_lowering(lowering, state, block_c=plan.block_c,
                            interpret=self.interpret)

    def _exec_group(self, group, entry: jax.Array,
                    state: jax.Array) -> jax.Array:
        if group.kind == "MAJ":
            return self._fused_maj(group, entry, state)
        if group.kind == "MRC":
            return self._fused_mrc(group, entry, state)
        # NOT / COPY: one gather (+ complement) + scatter, no kernel.
        srcs = np.array([op.srcs[0] for op in group.ops
                         for _ in op.dsts])
        dsts = np.array([d for op in group.ops for d in op.dsts])
        vals = entry[srcs]
        if group.kind == "NOT":
            vals = self._not(vals)
        else:
            vals = self._copy(vals)
        return state.at[dsts].set(vals)

    def _fused_maj(self, group, entry: jax.Array,
                   state: jax.Array) -> jax.Array:
        """All MAJ ops of a level in ONE kernel dispatch.

        Narrower ops are padded to the level's widest arity X with
        constant (all-0, all-1) plane *pairs* — each pair adds one to
        the popcount and one to the majority threshold, so
        ``MAJ_k(x..) == MAJ_X(x.., 0*m, 1*m)`` exactly.  The batch is
        laid out (X, B, W): every op is one row-image of the tile, so a
        single non-vmapped MAJX launch covers the whole level with
        minimal VPU padding.
        """
        x_max = group.param
        width = entry.shape[-1]
        # Augment the image with one all-0 and one all-1 row, then build
        # the whole (B, X) source-index matrix on the host: padding slots
        # point at the constant rows, and a single fancy-index gather
        # assembles the batch (no per-op jnp traffic).
        zero_row, one_row = entry.shape[0], entry.shape[0] + 1
        aug = jnp.concatenate([
            entry,
            jnp.zeros((1, width), jnp.uint32),
            jnp.full((1, width), 0xFFFFFFFF, jnp.uint32)])
        idx = np.empty((len(group.ops), x_max), np.int32)
        for i, op in enumerate(group.ops):
            k = len(op.srcs)
            if (x_max - k) % 2:
                raise ValueError(
                    f"cannot pad MAJ{k} to MAJ{x_max}: parity differs")
            pad = (x_max - k) // 2
            idx[i, :k] = op.srcs
            idx[i, k:k + pad] = zero_row
            idx[i, k + pad:] = one_row
        batch = jnp.swapaxes(aug[idx], 0, 1)          # (X, B, W)
        out = self.majx(batch)                        # (B, W), 1 dispatch
        dsts = np.array([d for op in group.ops for d in op.dsts])
        sel = np.array([i for i, op in enumerate(group.ops)
                        for _ in op.dsts])
        return state.at[dsts].set(out[sel])

    def _fused_mrc(self, group, entry: jax.Array,
                   state: jax.Array) -> jax.Array:
        """All Multi-RowCopy ops of a level in ONE fan-out dispatch.

        Sources stack into a (B, W) block treated as one (R=B, C=W)
        image; a single fan-out to the widest destination count yields
        (n, B, W), and each op scatters the prefix of copies its own
        ``dsts`` ask for (copies are identical, so a prefix is exact).
        """
        n_max = group.param
        srcs = np.array([op.srcs[0] for op in group.ops])
        copies = self.rowcopy(entry[srcs], n_max)     # (n_max, B, W)
        dsts = np.array([d for op in group.ops for d in op.dsts])
        sel_copy = np.array([j for op in group.ops
                             for j in range(len(op.dsts))])
        sel_op = np.array([i for i, op in enumerate(group.ops)
                           for _ in op.dsts])
        return state.at[dsts].set(copies[sel_copy, sel_op])
