"""Perf-iteration harness: lower one cell with config overrides and print
the roofline terms + top collectives (the §Perf hypothesis loop tool).

  PYTHONPATH=src python results/hillclimb.py --arch chatglm3-6b \
      --shape train_4k --microbatches 4 --set remat=dots
  PYTHONPATH=src python results/hillclimb.py --arch mixtral-8x22b \
      --shape decode_32k --serve-rules
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"  # a CPU lowering tool: never the chip

import argparse
import dataclasses
import re
import time

from repro.configs.registry import SHAPES, get_config
from repro.launch import roofline as rl
from repro.launch.dryrun import lower_cell
from repro.launch.mesh import make_production_mesh


def top_collectives(txt, trips, n=8):
    rows = []
    for line in txt.splitlines():
        m = re.search(r'= (\(?[a-z0-9]+\[[0-9,]*\])[^ ]* '
                      r'(all-reduce|all-gather|all-to-all|reduce-scatter|'
                      r'collective-permute)\(', line)
        if not m or "-done(" in line:
            continue
        shp, kind = m.group(1).lstrip("("), m.group(2)
        dt = shp.split("[")[0]
        dims = shp.split("[")[1].rstrip("]")
        nelem = 1
        for d in dims.split(","):
            if d:
                nelem *= int(d)
        b = nelem * {"bf16": 2, "f32": 4, "u32": 4, "s32": 4}.get(dt, 4)
        opn = re.search(r'op_name="([^"]*)"', line)
        depth = opn.group(1).count("while/") if opn else 0
        mult = 1
        for t in trips[:depth]:
            mult *= t
        rows.append((b * mult, kind, shp,
                     (opn.group(1)[-70:] if opn else "")))
    rows.sort(reverse=True)
    return rows[:n]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--microbatches", type=int, default=4)
    ap.add_argument("--serve-rules", action="store_true")
    ap.add_argument("--multipod", action="store_true")
    ap.add_argument("--set", action="append", default=[],
                    help="cfg override key=value")
    ap.add_argument("--show-collectives", action="store_true")
    args = ap.parse_args()

    cfg = get_config(args.arch)
    for kv in args.set:
        k, v = kv.split("=")
        field = {f.name: f for f in dataclasses.fields(cfg)}[k]
        typ = field.type if callable(field.type) else type(getattr(cfg, k))
        cast = type(getattr(cfg, k))
        val = cast(v) if cast is not bool else v.lower() in ("1", "true")
        cfg = dataclasses.replace(cfg, **{k: val})
    shape = SHAPES[args.shape]
    mesh = make_production_mesh(multi_pod=args.multipod)
    mb = args.microbatches if shape.kind == "train" else 1
    t0 = time.time()
    lowered, compiled = lower_cell(cfg, shape, mesh, microbatches=mb,
                                   serve_rules=args.serve_rules)
    dt = time.time() - t0
    mem = compiled.memory_analysis()
    txt = compiled.as_text()
    trips = [max(cfg.n_layers, 1)] if mb == 1 else [mb, max(cfg.n_layers, 1)]
    coll = rl.collective_bytes(txt, loop_trips=trips)
    ca = compiled.cost_analysis()
    mem_total = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
                 + mem.output_size_in_bytes - mem.alias_size_in_bytes) / 2**30
    print(f"cell={args.arch}/{args.shape} mb={mb} "
          f"serve_rules={args.serve_rules} overrides={args.set}")
    print(f"compile={dt:.0f}s mem={mem_total:.2f}GB "
          f"(arg {mem.argument_size_in_bytes/2**30:.2f} temp "
          f"{mem.temp_size_in_bytes/2**30:.2f})")
    print(f"flops/chip={ca.get('flops', 0):.3e} "
          f"bytes/chip={ca.get('bytes accessed', 0):.3e}")
    print(f"collectives: total={coll.total_bytes/2**30:.2f}GB "
          f"t_coll={coll.total_bytes/rl.ICI_BW:.3f}s "
          f"by kind={ {k: round(v/2**30, 2) for k, v in coll.bytes_by_kind.items()} }")
    if args.show_collectives:
        for b, kind, shp, opn in top_collectives(txt, trips):
            print(f"  {b/2**30:8.2f}GB {kind:14s} {shp:26s} ...{opn}")


if __name__ == "__main__":
    main()
