#!/usr/bin/env python3
"""Chip smoke test: the PUD stack's main paths, compiled, on a TPU.

Drives the stack once through the entry points a user calls, in one
process, at 65,536-bit DRAM rows (2,048 ``uint32`` words):

* ``session`` — ``DramSession("pallas")`` against ``DramSession("oracle")``
  on the same seeded data: MAJ3/5/7/9 over 1,024 rows, Multi-RowCopy
  1->31 of 512 rows, ``mismatch`` over 64 MiB, and the §8.1 add32
  program on 2^20 lanes (``run_fused`` twice: the eager level walk, then
  the jitted one, and ``elementwise("add")``);
* ``service`` — ``PudService`` on ``pallas`` with 4 tenants: one heal of
  3 x 1,024 rows each with one replica corrupted at a 1e-5 bit-error
  rate, one integrity check each, and one erase of 1,024 rows at
  fan-out 31;
* ``sweep`` — a MAJX campaign (MAJ3-9 x n_act 4-32, ideal) through
  ``run_sweep`` into a fresh record store;
* ``engine`` — ``xlstm-125m`` at full width with seeded random weights:
  ``heal_params`` from 3 replicas (one corrupted), ``verify_params``,
  and ``generate`` for 4 prompts of 16 tokens, 16 new tokens each.

Each kernel phase asserts that the lowered call holds a
``tpu_custom_call``: the kernel ran compiled, not in the interpreter.
Each phase prints its sizes, compile seconds and wall seconds; the last
line is a JSON object naming the device.  A failed check or phase exits
nonzero.  With no TPU the script exits nonzero before any phase.

Usage::

    python chip_smoke.py             # one chip, every phase
    python chip_smoke.py --chips 4   # only the sweep's mesh path over a
                                     # 2x2 ("data", "model") mesh and its
                                     # one-device control
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "src"))

#: JAX's event for one compile (or one load from the persistent cache).
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
BER = 1e-5


@dataclasses.dataclass(frozen=True)
class Sizes:
    """Operand sizes of every phase (defaults: the chip run)."""

    words: int = 2048                 # one 65,536-bit DRAM row
    maj_rows: int = 1024
    mrc_rows: int = 512
    mismatch_bytes: int = 64 * 2**20
    add_lanes: int = 2**20
    tenants: int = 4
    heal_rows: int = 1024
    erase_rows: int = 1024
    sweep_rows: int = 8
    smoke_model: bool = False         # True: the config's SMOKE variant
    prompts: int = 4
    prompt_len: int = 16
    new_tokens: int = 16


class Smoke:
    """Shared state of one run: sizes, seed, output directory, whether
    kernels must be compiled, and the compile-seconds counter, which
    counts while the object is entered as a context."""

    def __init__(self, sizes: Sizes, seed: int, out_dir: str, *,
                 require_kernels: bool):
        self.sizes = sizes
        self.seed = seed
        self.out_dir = out_dir
        self.require_kernels = require_kernels
        self.compile_s = 0.0

    def __enter__(self) -> "Smoke":
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        return self

    def __exit__(self, *exc) -> None:
        import jax

        jax.monitoring.unregister_event_duration_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event == COMPILE_EVENT:
            self.compile_s += duration

    def assert_kernel(self, what: str, fn, *shapes) -> None:
        """The jitted call lowers to a Mosaic kernel (``tpu_custom_call``)
        when kernels must run compiled; ``shapes`` are ShapeDtypeStructs."""
        import jax

        if not self.require_kernels:
            return
        text = jax.jit(fn).lower(*shapes).as_text()
        if "tpu_custom_call" not in text:
            raise AssertionError(f"{what}: no tpu_custom_call in the "
                                 f"lowered call; the kernel would not run "
                                 f"compiled")

    def phase(self, name: str, fn) -> None:
        c0, t0 = self.compile_s, time.perf_counter()
        sizes = fn(self)
        wall = time.perf_counter() - t0
        print(f"[{name}] {sizes} | compile_s={self.compile_s - c0:.3f} "
              f"wall_s={wall:.3f}", flush=True)


def check(ok, what: str) -> None:
    if not bool(ok):
        raise AssertionError(f"check failed: {what}")


def _u32(shape):
    import jax
    import jax.numpy as jnp

    return jax.ShapeDtypeStruct(shape, jnp.uint32)


def _popcount(a, b) -> int:
    import numpy as np

    return int(np.bitwise_count(np.asarray(a) ^ np.asarray(b)).sum())


def _bytes(x):
    """The raw bytes of an array of any dtype, as a flat uint8 array."""
    import numpy as np

    return np.ascontiguousarray(np.asarray(x)).reshape(-1).view(np.uint8)


def _corrupt(clean, rng):
    """``clean`` (uint32) with i.i.d. bit flips at rate :data:`BER`."""
    import numpy as np

    out = clean.copy().reshape(-1)
    n_bits = out.size * 32
    pos = rng.choice(n_bits, rng.binomial(n_bits, BER), replace=False)
    np.bitwise_xor.at(out, pos // 32,
                      np.left_shift(np.uint32(1), (pos % 32).astype(
                          np.uint32)))
    return out.reshape(clean.shape)


# ------------------------------------------------------------- phases
def session_phase(sm: Smoke) -> str:
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.backends import ExecutionContext
    from repro.compile import compile_elementwise
    from repro.kernels.majx.ops import majx
    from repro.kernels.mismatch.ops import mismatch_count
    from repro.kernels.rowcopy.ops import fanout
    from repro.session import DramSession

    s = sm.sizes
    ctx = ExecutionContext(ideal=True)
    pal = DramSession("pallas", ctx)
    ora = DramSession("oracle", ctx)
    be = pal.backend
    key = jax.random.PRNGKey(sm.seed)

    for x in (3, 5, 7, 9):
        shape = (x, s.maj_rows, s.words)
        sm.assert_kernel(f"majx X={x}", functools.partial(
            majx, interpret=be.interpret, block_r=ctx.block_r,
            block_c=ctx.block_c), _u32(shape))
        planes = jax.random.bits(jax.random.fold_in(key, x), shape,
                                 jnp.uint32)
        check(jnp.array_equal(pal.majx(planes, x=x), ora.majx(planes, x=x)),
              f"MAJ{x} pallas == oracle")

    src = jax.random.bits(jax.random.fold_in(key, 31),
                          (s.mrc_rows, s.words), jnp.uint32)
    sm.assert_kernel("rowcopy 1->31", functools.partial(
        fanout, fanout_n=31, interpret=be.interpret, block_r=ctx.block_r,
        block_c=ctx.block_c), _u32(src.shape))
    check(jnp.array_equal(pal.rowcopy(src, 31), ora.rowcopy(src, 31)),
          "Multi-RowCopy 1->31 pallas == oracle")

    n_words = s.mismatch_bytes // 4
    ka, kb = jax.random.split(jax.random.fold_in(key, 64))
    a = jax.random.bits(ka, (n_words,), jnp.uint32)
    b = jax.random.bits(kb, (n_words,), jnp.uint32)
    sm.assert_kernel("mismatch", functools.partial(
        mismatch_count, interpret=be.interpret), _u32(a.shape),
        _u32(b.shape))
    bad = int(pal.mismatch(a, b))
    check(bad == int(ora.mismatch(a, b)) and bad > 0,
          "mismatch pallas == oracle")

    rng = np.random.default_rng(sm.seed)
    xa = rng.integers(0, 2**32, s.add_lanes, dtype=np.uint32)
    xb = rng.integers(0, 2**32, s.add_lanes, dtype=np.uint32)
    want = xa + xb
    cp = compile_elementwise("add", xa, xb, tier=ctx.tier, n_act=ctx.n_act)
    ref = ora.run_fused(cp.program, cp.state)
    rows, words = cp.state.shape
    for walk in ("eager", "jitted"):
        final = pal.run_fused(cp.program, cp.state)
        check(jnp.array_equal(final, ref), f"add32 {walk} pallas == oracle")
        check(np.array_equal(np.asarray(cp.outputs(final)), want),
              f"add32 {walk} == numpy")
    got, _ = pal.elementwise("add", xa, xb)
    ref_el, _ = ora.elementwise("add", xa, xb)
    check(np.array_equal(np.asarray(got), np.asarray(ref_el))
          and np.array_equal(np.asarray(got), want),
          "elementwise add pallas == oracle == numpy")
    return (f"MAJ3/5/7/9 {s.maj_rows}x{s.words} | MRC 1->31 "
            f"{s.mrc_rows}x{s.words} | mismatch {s.mismatch_bytes} B "
            f"({bad} bits differ) | add32 {s.add_lanes} lanes, "
            f"{len(cp.program.ops)} ops, "
            f"{pal.schedule_for(cp.program).n_levels} levels, "
            f"{rows}x{words} image")


def service_phase(sm: Smoke) -> str:
    import functools

    import numpy as np

    from repro.kernels.majx.ops import majx
    from repro.kernels.mismatch.ops import mismatch_count
    from repro.kernels.rowcopy.ops import fanout
    from repro.serve import (EraseRequest, HealRequest, IntegrityRequest,
                             PudService, ServiceConfig)

    s = sm.sizes
    svc = PudService(ServiceConfig(backend="pallas"))
    be = svc.sessions[0].backend
    heal_rows = s.tenants * s.heal_rows
    sm.assert_kernel("service heal vote", functools.partial(
        majx, interpret=be.interpret), _u32((3, heal_rows, s.words)))
    sm.assert_kernel("service mismatch", functools.partial(
        mismatch_count, interpret=be.interpret),
        _u32((s.heal_rows, s.words)), _u32((s.heal_rows, s.words)))
    sm.assert_kernel("service erase", functools.partial(
        fanout, fanout_n=31, interpret=be.interpret), _u32((1, s.words)))

    rng = np.random.default_rng(sm.seed + 1)
    tenants = [f"tenant{i}" for i in range(s.tenants)]
    clean, bad = {}, {}
    for t in tenants:
        clean[t] = rng.integers(0, 2**32, (s.heal_rows, s.words),
                                dtype=np.uint32)
        bad[t] = _corrupt(clean[t], rng)
    heals = svc.serve([
        HealRequest(replicas=np.stack([bad[t], clean[t], clean[t]]),
                    tenant=t) for t in tenants])
    flips = 0
    for t, res in zip(tenants, heals):
        n = _popcount(bad[t], clean[t])
        flips += n
        check(np.array_equal(res.healed, clean[t]), f"{t} healed == clean")
        check(res.fixed_bits == n, f"{t} fixed_bits {res.fixed_bits} == {n}")

    pattern = 0xA5A5A5A5
    rest = svc.serve(
        [IntegrityRequest(live=bad[t], reference=clean[t], tenant=t)
         for t in tenants]
        + [EraseRequest(rows=s.erase_rows, words=s.words, pattern=pattern,
                        fanout=31, tenant=tenants[0])])
    for t, res in zip(tenants, rest):
        n = _popcount(bad[t], clean[t])
        check(res.mismatch_bits == n,
              f"{t} mismatch_bits {res.mismatch_bits} == {n}")
    wiped = rest[-1].wiped
    check(wiped.shape == (s.erase_rows, s.words)
          and (wiped == np.uint32(pattern)).all(), "erase wrote the pattern")
    return (f"{s.tenants} tenants x heal 3x{s.heal_rows}x{s.words} "
            f"({flips} bits flipped at BER {BER}) + integrity | erase "
            f"{s.erase_rows}x{s.words} fan-out 31")


def _sweep_spec(sizes: Sizes, name: str):
    from repro.sweep import SweepSpec

    return SweepSpec(name=name, op="majx", backends=("pallas",),
                     x_values=(3, 5, 7, 9), n_act=(4, 8, 16, 32),
                     ideal=True, rows=sizes.sweep_rows, words=sizes.words)


def _fresh_store(sm: Smoke, name: str) -> str:
    root = os.path.join(sm.out_dir, "sweeps", name)
    shutil.rmtree(root, ignore_errors=True)
    return root


def sweep_phase(sm: Smoke) -> str:
    import functools

    from repro.backends import ExecutionContext
    from repro.backends.pallas import PallasBackend
    from repro.kernels.majx.ops import majx
    from repro.sweep import run_sweep

    s = sm.sizes
    spec = _sweep_spec(s, "chip-smoke-majx")
    # The runner builds a fresh backend per chunk; one built the same way
    # resolves to the same mode.
    interpret = PallasBackend(ExecutionContext(ideal=True)).interpret
    sm.assert_kernel("sweep vote", functools.partial(
        majx, interpret=interpret), _u32((9, 4 * s.sweep_rows, s.words)))
    result = run_sweep(spec, _fresh_store(sm, "single"))
    check(result.executed_chunks > 0 and result.cached_chunks == 0,
          "sweep ran every chunk")
    check(len(result.records) == spec.n_points(), "one record per point")
    check(all(r["success"] == 1.0 for r in result.records),
          "every MAJX record succeeds against the oracle")
    return (f"MAJ3-9 x n_act 4-32: {len(result.records)} points, "
            f"{result.executed_chunks} chunks, {s.sweep_rows}x{s.words} "
            f"per point")


def mesh_sweep_phase(sm: Smoke, n_devices: int = 4) -> str:
    """The sweep's mesh path over a ("data", "model") mesh and its
    one-device control, on the same spec."""
    import jax

    from repro.backends import ExecutionContext
    from repro.launch.mesh import make_mesh
    from repro.session import DramSession
    from repro.sweep import run_sweep
    from repro.sweep.runner import sharded_majx_batch

    s = sm.sizes
    check(len(jax.devices()) >= n_devices,
          f"{n_devices} devices (found {len(jax.devices())})")
    model = 2 if n_devices % 2 == 0 else 1
    mesh = make_mesh((n_devices // model, model), ("data", "model"))
    spec = _sweep_spec(s, "chip-smoke-mesh")
    if sm.require_kernels:
        sess = DramSession("pallas", ExecutionContext(ideal=True))
        vote, sharding = sharded_majx_batch(
            sess, mesh, (4, 3, s.sweep_rows, s.words))
        text = jax.jit(vote).lower(jax.ShapeDtypeStruct(
            (4, 3, s.sweep_rows, s.words), "uint32",
            sharding=sharding)).compile().as_text()
        check("tpu_custom_call" in text, "mesh vote runs the Pallas kernel")
        check("all-gather" not in text, "mesh vote gathers no batch")
    meshed = run_sweep(spec, _fresh_store(sm, "mesh"), mesh=mesh).records
    control = run_sweep(spec, _fresh_store(sm, "control")).records

    def key(r):
        return (r["x"], r["n_act"], r["seed"], r["pattern"])

    check(len(meshed) == spec.n_points(), "one mesh record per point")
    check(sorted(meshed, key=key) == sorted(control, key=key),
          "mesh records == one-device control records")
    check(all(r["success"] == 1.0 for r in meshed),
          "every mesh record succeeds against the oracle")
    return (f"{len(meshed)} points over a {dict(mesh.shape)} mesh == "
            f"one-device control, {s.sweep_rows}x{s.words} per point")


def engine_phase(sm: Smoke) -> str:
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs.registry import get_config
    from repro.kernels.majx.ops import majx
    from repro.models import model as M
    from repro.pud.tmr import corrupt
    from repro.serve import scrub
    from repro.serve.engine import Engine, Request

    s = sm.sizes
    cfg = get_config("xlstm-125m", smoke=s.smoke_model)
    key = jax.random.PRNGKey(sm.seed)
    params, _ = M.init(key, cfg)
    leaves, treedef = jax.tree.flatten(params)
    bad = jax.tree.unflatten(treedef, [
        corrupt(leaf, jax.random.fold_in(key, 1000 + i), BER)
        for i, leaf in enumerate(leaves)])
    flips = sum(_popcount(_bytes(a), _bytes(b))
                for a, b in zip(leaves, jax.tree.leaves(bad)))
    n_bytes = sum(leaf.size * leaf.dtype.itemsize for leaf in leaves)

    engine = Engine(params, cfg, max_seq=s.prompt_len + s.new_tokens,
                    seed=sm.seed)
    rows = scrub.layout_of(params).tile_rows
    sm.assert_kernel("engine heal vote", functools.partial(
        majx, interpret=engine.pud.backend.interpret),
        _u32((3, rows, scrub.ROW_WORDS)))
    clean = jax.tree.map(jnp.copy, params)
    fixed = engine.heal_params([bad, params, clean])
    for a, b in zip(jax.tree.leaves(engine.params), leaves):
        check(np.array_equal(_bytes(a), _bytes(b)),
              "healed params == clean params, bit for bit")
    check(fixed == flips, f"heal fixed {fixed} bits == {flips} flipped")
    check(engine.verify_params(params) == 1.0, "verify_params == 1.0")

    rng = np.random.default_rng(sm.seed + 2)
    prompts = rng.integers(0, cfg.vocab_size, (s.prompts, s.prompt_len),
                           dtype=np.int32)
    logits, _ = engine._prefill(engine.params,
                                {"tokens": jnp.asarray(prompts)})
    check(jnp.isfinite(logits.astype(jnp.float32)).all(),
          "prefill logits are finite")
    reqs = engine.generate([Request(rid=i, prompt=p,
                                    max_new_tokens=s.new_tokens)
                            for i, p in enumerate(prompts)])
    for r in reqs:
        toks = np.asarray(r.out_tokens)
        check(len(toks) == s.new_tokens
              and ((toks >= 0) & (toks < cfg.vocab_size)).all(),
              f"request {r.rid}: {s.new_tokens} tokens in the vocabulary")
    return (f"{cfg.name}: {len(leaves)} leaves, {n_bytes} B, heal 3 "
            f"replicas x {rows} rows ({flips} bits flipped at BER {BER}) | "
            f"generate {s.prompts} x {s.prompt_len}+{s.new_tokens} tokens")


PHASES = (("session", session_phase), ("service", service_phase),
          ("sweep", sweep_phase), ("engine", engine_phase))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the sweep's mesh path and its control")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=os.path.join(HERE, "chiprun_out",
                                                  "chip_smoke"),
                    help="directory for the sweep's fresh record stores")
    args = ap.parse_args(argv)

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, found platform {dev.platform!r}",
              file=sys.stderr)
        return 1
    from repro.launch.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    print(f"device: {dev.device_kind} x {len(jax.devices())} | compile "
          f"cache: {cache}", flush=True)
    with Smoke(Sizes(), args.seed, args.out, require_kernels=True) as sm:
        if args.chips == 4:
            sm.phase("mesh-sweep", mesh_sweep_phase)
        else:
            for name, fn in PHASES:
                sm.phase(name, fn)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
