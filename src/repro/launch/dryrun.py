import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"
# ^ MUST precede any jax-importing import: jax locks the device count at
# first backend initialization.  The dry-run is the ONLY entry point that
# fakes 512 devices; tests and benches see the real (1-CPU) topology.
# It is a CPU lowering tool: it never opens an accelerator, so it cannot
# compete for a chip with the process that holds it.

"""Multi-pod dry-run: lower + compile every (arch x shape) cell.

For each cell this lowers the production step function with ShapeDtypeStruct
inputs on the requested mesh, compiles it, and records:
  * memory_analysis()  — proves the cell fits per-chip HBM,
  * cost_analysis()    — FLOPs / bytes for the roofline,
  * the optimized HLO's collective schedule (parsed wire bytes),
  * cost-mode composition points (see launch/roofline.py).

Usage:
  python -m repro.launch.dryrun --arch mixtral-8x22b --shape train_4k
  python -m repro.launch.dryrun --all --multipod --out results.json
"""

import argparse
import dataclasses
import json
import sys
import time
import traceback

import jax

from repro.configs.base import TrainConfig
from repro.configs.registry import SHAPES, all_configs, get_config, shape_applicable
from repro.launch import roofline as rl
from repro.launch import specs as sp
from repro.launch.mesh import make_production_mesh
from repro.models import attention as attention_mod
from repro.models import model as M
from repro.train.step import make_train_step


def _train_fn(cfg, microbatches: int = 1):
    tc = TrainConfig(microbatches=microbatches, compression="none")
    return make_train_step(cfg, tc)


def _prefill_fn(cfg, shape):
    def prefill_step(params, batch):
        logits, cache = M.prefill(params, batch, cfg, shape.seq_len)
        return logits

    return prefill_step


def _decode_fn(cfg):
    def decode_step(params, tokens, cache):
        return M.decode(params, tokens, cache, cfg)

    return decode_step


def lower_cell(cfg, shape, mesh, *, donate: bool = True,
               microbatches: int = 1, serve_rules: bool = False):
    """Returns (lowered, compiled) for one cell on one mesh.

    ``serve_rules=True`` lowers decode cells under the activation-
    stationary SERVE_RULES (see repro.dist.sharding; §Perf H3).
    """
    from repro.dist import sharding as shd
    import contextlib

    rules_ctx = (shd.use_rules(shd.SERVE_RULES)
                 if serve_rules else contextlib.nullcontext())
    with jax.set_mesh(mesh), rules_ctx:
        if shape.kind == "train":
            state_abs, state_shard, _ = sp.state_specs(cfg, mesh)
            batch_abs, batch_shard = sp.batch_specs(cfg, shape, mesh, True)
            fn = jax.jit(
                _train_fn(cfg, microbatches),
                in_shardings=(state_shard, batch_shard),
                out_shardings=(state_shard, None),
                donate_argnums=(0,) if donate else (),
            )
            lowered = fn.lower(state_abs, batch_abs)
        elif shape.kind == "prefill":
            params_abs, params_shard, _ = sp.params_specs(cfg, mesh)
            batch_abs, batch_shard = sp.batch_specs(cfg, shape, mesh, False)
            fn = jax.jit(_prefill_fn(cfg, shape),
                         in_shardings=(params_shard, batch_shard))
            lowered = fn.lower(params_abs, batch_abs)
        else:  # decode
            params_abs, params_shard, _ = sp.params_specs(cfg, mesh)
            tok_abs, cache_abs, tok_shard, cache_shard = sp.decode_specs(
                cfg, shape, mesh)
            fn = jax.jit(
                _decode_fn(cfg),
                in_shardings=(params_shard, tok_shard, cache_shard),
                out_shardings=(None, cache_shard),
                donate_argnums=(2,) if donate else (),
            )
            lowered = fn.lower(params_abs, tok_abs, cache_abs)
        compiled = lowered.compile()
    return lowered, compiled


def _cost_points(cfg, shape, mesh):
    """Cost-mode compile points for the roofline composition."""
    attention_mod.FORCE_DENSE = True
    try:
        points = {}
        if cfg.family in ("dense", "moe", "audio", "vlm"):
            depths = (0, 1)
        elif cfg.family == "hybrid":
            depths = (0, cfg.attn_every, cfg.attn_every + 1)
        else:  # ssm: S-composition at full depth
            pts = {}
            for s_small in (64, 128):
                sh = dataclasses.replace(shape, seq_len=s_small)
                _, comp = lower_cell(cfg, sh, mesh, donate=False)
                ca = comp.cost_analysis()
                pts[s_small] = rl.CostPoint(ca.get("flops", 0.0),
                                            ca.get("bytes accessed", 0.0))
            if shape.kind == "decode":
                # decode for ssm is python-unrolled: exact, no composition
                _, comp = lower_cell(cfg, shape, mesh, donate=False)
                ca = comp.cost_analysis()
                return rl.CostPoint(ca.get("flops", 0.0),
                                    ca.get("bytes accessed", 0.0))
            return rl.compose_seq(shape.seq_len, pts)
        for d in depths:
            cfg_d = dataclasses.replace(cfg, n_layers=d, remat="none")
            _, comp = lower_cell(cfg_d, shape, mesh, donate=False)
            ca = comp.cost_analysis()
            points[d] = rl.CostPoint(ca.get("flops", 0.0),
                                     ca.get("bytes accessed", 0.0))
        return rl.compose(cfg, points)
    finally:
        attention_mod.FORCE_DENSE = False


def run_cell(arch: str, shape_name: str, multi_pod: bool = False,
             skip_cost: bool = False, verbose: bool = True,
             microbatches: int = 4, serve_rules: bool = False) -> dict:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    ok, why = shape_applicable(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name, "status": "skipped",
                "reason": why}
    mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_name = "x".join(str(s) for s in mesh.devices.shape)
    mb = microbatches if shape.kind == "train" else 1
    t0 = time.time()
    lowered, compiled = lower_cell(cfg, shape, mesh, microbatches=mb,
                                   serve_rules=serve_rules)
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    hlo = compiled.as_text()
    trips = [max(cfg.n_layers, 1)] if mb == 1 else [mb, max(cfg.n_layers, 1)]
    coll = rl.collective_bytes(hlo, loop_trips=trips)
    ca = compiled.cost_analysis()
    deploy_cost = rl.CostPoint(ca.get("flops", 0.0),
                               ca.get("bytes accessed", 0.0))

    if skip_cost:
        cost = deploy_cost
    else:
        try:
            cost = _cost_points(cfg, shape, mesh)
        except Exception:
            traceback.print_exc()
            cost = deploy_cost

    n_chips = mesh.devices.size
    # donated inputs alias outputs: count aliased bytes once
    mem_per_chip = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
                    + mem.output_size_in_bytes - mem.alias_size_in_bytes
                    + mem.generated_code_size_in_bytes)
    report = rl.RooflineReport(
        arch=arch, shape=shape_name, mesh=mesh_name, n_chips=n_chips,
        flops_per_chip=cost.flops, bytes_per_chip=cost.bytes_accessed,
        coll_bytes_per_chip=coll.total_bytes,
        coll_dominant_kind=coll.dominant,
        model_flops_global=rl.model_flops(cfg, shape),
        mem_per_chip_bytes=mem_per_chip,
    )
    out = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name,
        "status": "ok", "compile_s": round(t_compile, 1),
        "memory": {
            "argument_gb": mem.argument_size_in_bytes / 2**30,
            "output_gb": mem.output_size_in_bytes / 2**30,
            "temp_gb": mem.temp_size_in_bytes / 2**30,
            "alias_gb": mem.alias_size_in_bytes / 2**30,
            "total_gb": mem_per_chip / 2**30,
        },
        "collectives": {
            "per_kind_gb": {k: v / 2**30 for k, v in coll.bytes_by_kind.items()},
            "total_gb": coll.total_bytes / 2**30,
            "n_ops": coll.n_ops,
        },
        "deploy_cost": dataclasses.asdict(deploy_cost),
        "roofline": report.row(),
    }
    if verbose:
        r = out["roofline"]
        print(f"[dryrun] {arch:24s} {shape_name:12s} mesh={mesh_name:10s} "
              f"mem={out['memory']['total_gb']:.2f}GB "
              f"tC={r['t_compute_s']:.3e} tM={r['t_memory_s']:.3e} "
              f"tX={r['t_collective_s']:.3e} bound={r['bottleneck']:<10s} "
              f"frac={r['roofline_fraction']:.3f} compile={t_compile:.0f}s",
              flush=True)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multipod", action="store_true")
    ap.add_argument("--skip-cost", action="store_true",
                    help="skip cost-mode composition compiles (faster)")
    ap.add_argument("--microbatches", type=int, default=4,
                    help="grad-accumulation microbatches for train cells")
    ap.add_argument("--serve-rules", action="store_true",
                    help="decode cells: activation-stationary SERVE_RULES")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    cells = []
    if args.all:
        for arch in all_configs():
            for shape_name in SHAPES:
                cells.append((arch, shape_name))
    else:
        if not args.arch or not args.shape:
            ap.error("--arch and --shape required (or --all)")
        cells = [(args.arch, args.shape)]

    results, failures = [], 0
    for arch, shape_name in cells:
        try:
            results.append(run_cell(arch, shape_name, args.multipod,
                                    skip_cost=args.skip_cost,
                                    microbatches=args.microbatches,
                                    serve_rules=args.serve_rules))
        except Exception as e:
            failures += 1
            traceback.print_exc()
            results.append({"arch": arch, "shape": shape_name,
                            "status": "error", "error": str(e)[:500]})
    if args.out:
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1)
        print(f"[dryrun] wrote {args.out}")
    print(f"[dryrun] {sum(1 for r in results if r['status']=='ok')} ok, "
          f"{sum(1 for r in results if r['status']=='skipped')} skipped, "
          f"{failures} failed")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
