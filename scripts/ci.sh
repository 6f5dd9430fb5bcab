#!/usr/bin/env bash
# CI entry point: tier-1 suite + sweep/bench/quickstart smokes + docs check
# + backend-parity smoke.
set -euo pipefail
cd "$(dirname "$0")/.."

echo "== lint (ruff, correctness tier — skipped when unavailable) =="
if command -v ruff >/dev/null 2>&1; then
    # Config lives in pyproject.toml ([tool.ruff]): pyflakes + E9 only.
    ruff check src tests benchmarks scripts examples
else
    echo "ruff not installed in this image; lint config still applies in editors"
fi

echo "== tier-1: full test suite =="
python -m pytest -x -q

echo "== sweep smoke (<=16 grid points, interpret) + resume check =="
SWEEP_CI_ROOT=$(mktemp -d)
PYTHONPATH=src python -m repro.sweep.run --smoke --root "$SWEEP_CI_ROOT" --quiet
# identical spec, second invocation: every chunk must come from the store
PYTHONPATH=src python -m repro.sweep.run --smoke --root "$SWEEP_CI_ROOT" --quiet --expect-cached
rm -rf "$SWEEP_CI_ROOT"

echo "== adaptive smoke: boundary search economy + grid parity + resume =="
ADAPT_CI_ROOT=$(mktemp -d)
PYTHONPATH=src python - "$ADAPT_CI_ROOT" <<'PY'
import filecmp, os, sys

from repro.sweep import presets, run_adaptive, run_sweep

root = sys.argv[1]
aspec = presets.adaptive_smoke_spec()

dense = run_sweep(aspec.base, os.path.join(root, "dense"))
adaptive = run_adaptive(aspec, os.path.join(root, "adaptive"))

# Economy gate: the boundary search must consult <= 40% of the ladder.
assert adaptive.points_covered <= 0.4 * adaptive.n_grid_points, \
    (adaptive.points_covered, adaptive.n_grid_points)

# Cliff parity: each located bracket must match a dense first-below scan.
by_idx = {r["index"]: r["success"] for r in dense.records}
ladder = sorted(by_idx)
for c in adaptive.crossings:
    assert c.crossed and c.direction == "falling", c
    first_below = next(i for i in ladder if by_idx[i] < c.threshold)
    assert (c.lo_index, c.hi_index) == (first_below - 1, first_below), \
        (c, first_below)

# Store parity: every chunk file both modes produced is byte-identical.
d_dir = os.path.join(dense.store_path, "chunks")
a_dir = os.path.join(adaptive.store_path, "chunks")
chunk_files = sorted(set(os.listdir(d_dir)) & set(os.listdir(a_dir)))
assert chunk_files, (os.listdir(d_dir), os.listdir(a_dir))
for f in chunk_files:
    assert filecmp.cmp(os.path.join(d_dir, f), os.path.join(a_dir, f),
                       shallow=False), f

print(f"adaptive gate OK: {adaptive.points_covered}/"
      f"{adaptive.n_grid_points} points probed, "
      f"{len(adaptive.crossings)} crossings match dense scan, "
      f"{len(chunk_files)} shared chunk files byte-identical")
PY
# identical campaign, second invocation: the search must replay entirely
# from the store (zero chunks executed).
PYTHONPATH=src python -m repro.sweep.run --adaptive \
    --root "$ADAPT_CI_ROOT/adaptive" --quiet --expect-cached
rm -rf "$ADAPT_CI_ROOT"

echo "== fault-tolerant sweep smoke (elastic workers) =="
FT_CI_ROOT=$(mktemp -d)
PYTHONPATH=src python -m repro.sweep.run --smoke --workers 3 \
    --root "$FT_CI_ROOT" --quiet
PYTHONPATH=src python -m repro.sweep.run --smoke --workers 3 \
    --root "$FT_CI_ROOT" --quiet --expect-cached
rm -rf "$FT_CI_ROOT"

echo "== program-fusion differential + golden + megakernel suites =="
PYTHONPATH=src python -m pytest -x -q tests/test_compile_differential.py \
    tests/test_compile_golden.py tests/test_megakernel_differential.py

echo "== bench smoke: per-op vs fused (structural dispatch gate) =="
BENCH_CI_ROOT=$(mktemp -d)
PYTHONPATH=src python -m benchmarks.bench --smoke \
    --out "$BENCH_CI_ROOT/BENCH_fused.json"
PYTHONPATH=src python - "$BENCH_CI_ROOT/BENCH_fused.json" <<'PY'
import json, sys

doc = json.load(open(sys.argv[1]))
assert doc["schema"] == "repro-bench/fused-v4", doc["schema"]
rows = {(r["name"], r["backend"]): r for r in doc["workloads"]}
assert len({n for n, _ in rows}) >= 3, sorted(rows)
add = rows[("add32", "pallas")]
# Structural perf gate (no timing stability needed): the fused 32-bit
# adder must launch fewer kernels than per-op, within its level budget.
assert add["fused"]["dispatches"] < add["per_op"]["dispatches"], add
assert add["fused"]["dispatches"] <= add["n_levels"], add
assert all(r["per_op"]["parity"] and r["fused"]["parity"]
           and r["megakernel"]["parity"] for r in doc["workloads"])
# Energy gate: every row carries CostModel-priced energy; on the pallas
# executor it is positive and ordered megakernel <= fused <= per-op for
# add32 (fewer launches -> fewer joules, the PULSAR amortization story).
for r in doc["workloads"]:
    for m in ("per_op", "fused", "megakernel"):
        assert "energy_nj" in r[m] and r[m]["energy_nj"] >= 0, (r["name"], m)
    assert r["offload"]["pud_energy_nj"] > 0, r["name"]
    assert r["offload"]["winner_energy"] in ("pud", "tpu"), r["name"]
    if r["backend"] == "pallas":
        for m in ("per_op", "fused", "megakernel"):
            assert r[m]["energy_nj"] > 0, (r["name"], m)
add_e = {m: add[m]["energy_nj"] for m in ("per_op", "fused", "megakernel")}
assert 0 < add_e["megakernel"] <= add_e["fused"] <= add_e["per_op"], add_e
print(f"energy gate OK: add32 per-op {add_e['per_op']/1e3:.0f} uJ >= "
      f"fused {add_e['fused']/1e3:.0f} uJ >= megakernel "
      f"{add_e['megakernel']/1e3:.0f} uJ; offload winner_energy "
      f"{add['offload']['winner_energy']}")
# Session compile cache: repeated programs must re-use their schedule.
cc = doc["compile_cache"]
assert cc["hits"] >= 1, cc
print(f"bench gate OK: add32 fused {add['fused']['dispatches']} vs "
      f"per-op {add['per_op']['dispatches']} dispatches "
      f"({add['n_levels']} levels); compile cache {cc['hits']} hits / "
      f"{cc['misses']} misses")

# Megakernel gate: whole-schedule execution must collapse the deep
# workloads (add32: ~34 levels, mul8: ~36) to at most 2 launches and
# never launch more than the level-fused path; lowered tables must be
# cache-reused across reps.  No wall time is gated: on the CPU it times
# the Pallas interpreter, which no deployment runs.
for wl in ("add32", "mul8"):
    r = rows[(wl, "pallas")]
    mega, fused = r["megakernel"], r["fused"]
    assert mega["dispatches"] <= 2, (wl, mega)
    assert mega["dispatches"] <= fused["dispatches"], (wl, r)
    assert mega["launch_overhead_ns"] <= fused["launch_overhead_ns"], (wl, r)
    assert mega["parity"], (wl, mega)
    assert mega["vmem"] is not None and mega["vmem"]["block_c"] % 128 == 0
add_mega = rows[("add32", "pallas")]["megakernel"]
lc = doc["lowering_cache"]
assert lc["hits"] >= 1, lc
print(f"megakernel gate OK: add32 {add_mega['dispatches']} dispatch; "
      f"lowering cache {lc['hits']} hits / {lc['misses']} misses")
PY
rm -rf "$BENCH_CI_ROOT"

echo "== serve bench smoke: coalesced batching vs sequential (SLO gate) =="
SERVE_CI_ROOT=$(mktemp -d)
PYTHONPATH=src python -m benchmarks.serve_bench --smoke \
    --out "$SERVE_CI_ROOT/BENCH_serve.json"
PYTHONPATH=src python - "$SERVE_CI_ROOT/BENCH_serve.json" <<'PY'
import json, sys

doc = json.load(open(sys.argv[1]))
assert doc["schema"] == "repro-bench/serve-v2", doc["schema"]
points = {(p["offered"], p["mode"]): p for p in doc["points"]}
loads = sorted({o for o, _ in points})
assert loads, points
# The smoke run exercises the sync-path coalescing window (bugfix:
# tick_window_s used to be honored only on the asyncio path).
assert doc["tick_window_s"] > 0, doc["tick_window_s"]
for o in loads:
    seq, bat = points[(o, "sequential")], points[(o, "batched")]
    # Structural gate (no timing stability needed): coalescing must cut
    # the kernel-dispatch count and actually fill batches.
    assert bat["dispatches"] < seq["dispatches"], \
        (o, bat["dispatches"], seq["dispatches"])
    assert bat["batch_occupancy"] > 1.0, (o, bat["batch_occupancy"])
    # p99 latency must be recorded (non-null) at every point.
    assert seq["p99_ms"] is not None and bat["p99_ms"] is not None, o
    assert seq["shed"] == 0 and bat["shed"] == 0, o
    # Energy gate: present and positive at every point, and coalescing
    # must save joules, not just dispatches.
    assert seq["energy_nj"] > 0 and bat["energy_nj"] > 0, o
    assert seq["energy_per_req_nj"] > 0 and bat["energy_per_req_nj"] > 0, o
    assert bat["energy_nj"] < seq["energy_nj"], \
        (o, bat["energy_nj"], seq["energy_nj"])
    assert all(p["tick_window_s"] == doc["tick_window_s"]
               for p in (seq, bat)), o
# No throughput is gated: on the CPU it times the Pallas interpreter.
o = loads[-1]
seq, bat = points[(o, "sequential")], points[(o, "batched")]
# The batched service must be hitting the shared schedule cache.
assert bat["cache"]["hit_rate"] > 0, bat["cache"]
print(f"serve gate OK: load {o} batched {bat['throughput_rps']:.0f} req/s"
      f" / {bat['dispatches']} dispatches / "
      f"{bat['energy_per_req_nj']/1e3:.0f} uJ/req vs sequential "
      f"{seq['throughput_rps']:.0f} req/s / {seq['dispatches']} / "
      f"{seq['energy_per_req_nj']/1e3:.0f} uJ/req; "
      f"occupancy {bat['batch_occupancy']:.1f}, cache hit rate "
      f"{bat['cache']['hit_rate']*100:.0f}%, tick window "
      f"{doc['tick_window_s']*1e3:.0f} ms")
PY
rm -rf "$SERVE_CI_ROOT"

echo "== analyzer gate: certify goldens/serve/sweep + seeded mutations =="
# --all = positive certification of every golden fixture, serve tick
# program, and sweep chunk program; the negative gate (every applicable
# seeded table corruption must be REJECTED); and the certificate-cache
# check (repeat certification of a cached program is a pure hit — zero
# re-analysis).  Nonzero exit on any hole.
PYTHONPATH=src python -m repro.analyze --all

echo "== docs check (module paths in docs/*.md resolve) =="
python scripts/check_docs.py

echo "== quickstart smoke (session API end-to-end) =="
PYTHONPATH=src python examples/quickstart.py

# This smoke deliberately exercises the raw registry (get_backend), the
# compat layer under repro.session — it is the one place outside tests
# that should keep doing so.
echo "== backend-parity smoke (oracle / sim / pallas) =="
PYTHONPATH=src python - <<'PY'
import numpy as np
import jax.numpy as jnp
from repro.backends import ExecutionContext, available_backends, get_backend
from repro.pud.isa import Program

rng = np.random.default_rng(0)
ideal = ExecutionContext(ideal=True)
backends = {n: get_backend(n, ideal) for n in ("oracle", "sim", "pallas")}
ref = backends["oracle"]

for x in (3, 5, 7, 9):
    planes = jnp.asarray(rng.integers(0, 2**32, (x, 2, 24), dtype=np.uint32))
    want = np.asarray(ref.majx(planes))
    for n, be in backends.items():
        assert (np.asarray(be.majx(planes, n_act=32)) == want).all(), (n, x)

src = jnp.asarray(rng.integers(0, 2**32, (24,), dtype=np.uint32))
for n_dst in (1, 7, 31):
    want = np.asarray(ref.rowcopy(src, n_dst))
    for n, be in backends.items():
        assert (np.asarray(be.rowcopy(src, n_dst)) == want).all(), (n, n_dst)

prog = Program()
prog.emit("MAJ", x=3, n_act=4, srcs=(0, 1, 2), dsts=(3,))
prog.emit("MRC", n_act=4, srcs=(3,), dsts=(4, 5, 6))
state = jnp.asarray(rng.integers(0, 2**32, (7, 8), dtype=np.uint32))
want = np.asarray(ref.run(prog, state))
for n, be in backends.items():
    assert (np.asarray(be.run(prog, state)) == want).all(), n

a = rng.integers(0, 2**32, 8, dtype=np.uint32)
b = rng.integers(0, 2**32, 8, dtype=np.uint32)
for n, be in backends.items():
    out, _ = be.elementwise("add", a, b, tier=5, n_act=32)
    assert (np.asarray(out) == (a + b).astype(np.uint32)).all(), n

print(f"backend parity OK across {sorted(backends)} "
      f"(registry: {available_backends()})")
PY

echo "CI OK"
