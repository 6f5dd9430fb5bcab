#!/usr/bin/env bash
# CI entry point: tier-1 suite + sweep/quickstart smokes + analyzer gate
# + docs check + backend-parity smoke.
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

echo "== program-fusion differential + golden suites =="
PYTHONPATH=src python -m pytest -x -q tests/test_compile_differential.py \
    tests/test_compile_golden.py

echo "== analyzer gate: certify goldens/serve/sweep + seeded mutations =="
# --all = positive certification of every golden fixture, serve tick
# program, and sweep chunk program; the negative gate (every applicable
# seeded schedule corruption must be REJECTED); and the certificate-cache
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
