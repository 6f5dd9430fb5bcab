"""``benchmarks.serve_bench``: offered load vs SLO under batching.

Drives a :class:`~repro.serve.service.PudService` with a mixed
integrity workload (X-replica MAJX heals + Multi-RowCopy erases) at a
ladder of offered loads, in two modes over the *same* requests:

* ``sequential`` — coalescing off: every request is its own fused
  Program and its own dispatch set (the one-at-a-time baseline the old
  engine hook was);
* ``batched`` — continuous batching on: same-shape requests coalesce
  into one fused Program per tick, so N tenants' votes cost one
  schedule-cache lookup and one batched MAJX dispatch.

Each (load, mode) point records wall time, throughput, p50/p99 request
latency, executed batches, batch occupancy, *structural* dispatch
counts and CostModel-priced energy (per-batch ``DispatchScope`` windows
summed by the SLO monitor) and the schedule-cache window.  A nonzero
``--tick-window`` exercises ``ServiceConfig.tick_window_s`` — the
cross-tick coalescing wait — on the sync ``serve()`` path.  Results
land in ``BENCH_serve.json`` (schema ``repro-bench/serve-v2``,
documented in ``docs/BENCH.md``); ``scripts/ci.sh`` gates on the
structural columns — batched throughput >= sequential, batched
dispatches AND energy < sequential, cache hit rate > 0, p99 recorded.

Usage::

    python -m benchmarks.serve_bench --smoke       # CI-size, ~seconds
    python -m benchmarks.serve_bench               # full load ladder
    python -m benchmarks.serve_bench --backend oracle --loads 4 16
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.dirname(__file__))

from _bench_io import default_out, execution_stamp, write_bench_json

SCHEMA = "repro-bench/serve-v2"
DEFAULT_OUT = default_out("BENCH_serve.json")


def _requests(offered: int, rows: int, words: int, seed: int):
    """A deterministic mixed workload: ``offered`` heals + erases.

    Every round rebuilds fresh request objects (requests are stamped at
    admission) from the same seed, so batched and sequential modes
    serve bit-identical work.
    """
    import numpy as np

    from repro.serve import EraseRequest, HealRequest

    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(offered):
        base = rng.integers(0, 2**32, (rows, words), dtype=np.uint32)
        replicas = np.stack([base, base, base])
        # one replica suffers a few flipped bits, as in a real heal
        replicas[i % 3, rng.integers(rows), rng.integers(words)] ^= 0b101
        reqs.append(HealRequest(replicas=replicas, tenant=f"tenant[{i}]"))
    for i in range(offered):
        reqs.append(EraseRequest(rows=31, words=words, pattern=0xDEADBEEF,
                                 tenant=f"tenant[{i}]"))
    return reqs


def bench_point(offered: int, mode: str, backend: str, rows: int,
                words: int, rounds: int,
                tick_window_s: float = 0.0) -> dict:
    import time

    from repro.serve import PudService, ServiceConfig

    svc = PudService(ServiceConfig(
        backend=backend, pool_size=2, coalesce=(mode == "batched"),
        max_batch=2 * offered, queue_depth=max(4 * offered, 64),
        tick_window_s=tick_window_s))
    svc.serve(_requests(offered, rows, words, seed=0))  # warm-up round
    svc.reset_slo()

    t0 = time.perf_counter()
    for r in range(rounds):
        results = svc.serve(_requests(offered, rows, words, seed=r))
        assert all(not isinstance(x, Exception) for x in results)
    wall = time.perf_counter() - t0

    snap = svc.snapshot()
    return {
        "offered": offered,
        "mode": mode,
        "rounds": rounds,
        "wall_s": wall,
        "completed": snap.completed,
        "throughput_rps": snap.completed / wall,
        "p50_ms": (None if snap.p50_latency_s is None
                   else snap.p50_latency_s * 1e3),
        "p99_ms": (None if snap.p99_latency_s is None
                   else snap.p99_latency_s * 1e3),
        "batches": snap.batches,
        "batch_occupancy": snap.batch_occupancy,
        "dispatches": snap.dispatches,
        "energy_nj": snap.energy_nj,
        "energy_per_req_nj": snap.energy_nj / max(snap.completed, 1),
        "tick_window_s": tick_window_s,
        "cache": snap.cache,
        "shed": snap.shed,
        "slo": snap.to_dict(),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m benchmarks.serve_bench", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--smoke", action="store_true",
                    help="CI-size loads, tiny tiles, 2 rounds")
    ap.add_argument("--out", default=DEFAULT_OUT,
                    help="output JSON path (default results/BENCH_serve.json)")
    ap.add_argument("--backend", default="pallas",
                    help="service backend (oracle | sim | pallas)")
    ap.add_argument("--loads", nargs="+", type=int, default=None,
                    help="offered concurrent requests per class per round")
    ap.add_argument("--rounds", type=int, default=None,
                    help="timed rounds per point (default: 2 smoke, 3 full)")
    ap.add_argument("--tick-window", type=float, default=None,
                    help="ServiceConfig.tick_window_s coalescing wait "
                         "(default: 1 ms smoke — exercising the sync-path "
                         "window — 0 full)")
    args = ap.parse_args(argv)

    loads = args.loads or ([2, 8] if args.smoke else [4, 16, 64])
    rounds = args.rounds or (2 if args.smoke else 3)
    rows, words = (4, 64) if args.smoke else (8, 256)
    tick_window = (args.tick_window if args.tick_window is not None
                   else (0.001 if args.smoke else 0.0))

    points = []
    for offered in loads:
        for mode in ("sequential", "batched"):
            print(f"[serve-bench] offered={offered} mode={mode} ...",
                  flush=True)
            points.append(bench_point(offered, mode, args.backend,
                                      rows, words, rounds,
                                      tick_window_s=tick_window))

    doc = {
        "schema": SCHEMA,
        "smoke": args.smoke,
        "backend": args.backend,
        **execution_stamp(),
        "rounds": rounds,
        "tick_window_s": tick_window,
        "workload": {
            "classes": ["heal(x3)", "erase(mrc31)"],
            "heal_rows": rows,
            "erase_rows": 31,
            "words": words,
        },
        "points": points,
    }
    write_bench_json(args.out, doc)

    for p in points:
        occ = p["batch_occupancy"] or 0.0
        print(f"  load {p['offered']:4d} [{p['mode']:10s}] "
              f"{p['throughput_rps']:8.1f} req/s | p50 "
              f"{p['p50_ms']:7.1f} ms p99 {p['p99_ms']:7.1f} ms | "
              f"{p['dispatches']:4d} disp / {p['batches']:3d} batches "
              f"(occ {occ:4.1f}) | {p['energy_per_req_nj']/1e3:6.1f} "
              f"uJ/req | cache {p['cache']['hit_rate']*100:3.0f}%")

    # Structural self-check (the CI gate re-asserts this from the JSON).
    bad = []
    for offered in loads:
        seq = next(p for p in points
                   if p["offered"] == offered and p["mode"] == "sequential")
        bat = next(p for p in points
                   if p["offered"] == offered and p["mode"] == "batched")
        if bat["dispatches"] >= seq["dispatches"]:
            bad.append(f"load {offered}: batched dispatches "
                       f"{bat['dispatches']} >= sequential "
                       f"{seq['dispatches']}")
        if bat["energy_nj"] > seq["energy_nj"]:
            bad.append(f"load {offered}: batched energy "
                       f"{bat['energy_nj']:.0f} nJ > sequential "
                       f"{seq['energy_nj']:.0f} nJ")
    if bad:
        print("[serve-bench] STRUCTURAL REGRESSION:", *bad, sep="\n  ")
    return 1 if bad else 0


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    sys.exit(main())
