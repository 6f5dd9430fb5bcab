"""Golden-program regression tests.

tests/golden/*.json freeze canonical serialized Programs (ripple-carry
adders, MAJ5/7/9 reduction trees, fan-out-31 Multi-RowCopy waves) with
their expected output bitplanes under fixed seeds — regenerate with
``tests/golden/generate.py`` only on intentional semantic changes.  A
scheduler change that reorders ops but alters results fails here loudly,
on every backend, per-op and fused, and in the pallas level walk's
eager, building and jitted runs; each fixture additionally pins the
walk's structure (per level, each group's kind, padded arity or
fan-out, op count and launch words), so a silent regrouping fails even
when the final state happens to agree.
"""

import glob
import hashlib
import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from repro.backends import ExecutionContext, get_backend
from repro.backends.pallas import _LevelWalk
from repro.compile import build_schedule
from repro.pud.isa import Program

IDEAL = ExecutionContext(ideal=True)
GOLDEN_DIR = os.path.join(os.path.dirname(__file__), "golden")
GOLDEN_FILES = sorted(glob.glob(os.path.join(GOLDEN_DIR, "*.json")))


def _load(path):
    with open(path) as f:
        doc = json.load(f)
    prog = Program()
    for raw in doc["ops"]:
        prog.emit(raw["kind"], x=raw["x"], n_act=raw["n_act"],
                  tag=raw["tag"], srcs=tuple(raw["srcs"]),
                  dsts=tuple(raw["dsts"]))
    rng = np.random.default_rng((doc["seed"], 0x601D))
    state = rng.integers(0, 2 ** 32, (doc["rows"], doc["words"]),
                         dtype=np.uint32)
    expected = np.array(
        [[int(row[i:i + 8], 16) for i in range(0, len(row), 8)]
         for row in doc["expected"]], dtype=np.uint32)
    return doc, prog, state, expected


def test_fixture_set_is_complete():
    names = {os.path.basename(p)[:-5] for p in GOLDEN_FILES}
    assert {"add8", "add16", "add32", "maj5_tree", "maj7_tree",
            "maj9_tree", "mrc_fanout31"} <= names


@pytest.mark.parametrize(
    "path", GOLDEN_FILES, ids=[os.path.basename(p)[:-5]
                               for p in GOLDEN_FILES])
def test_golden_program_all_backends_both_paths(path):
    doc, prog, state, expected = _load(path)
    assert prog.n_rows() == doc["rows"]
    state = jnp.asarray(state)
    for name in ("oracle", "sim", "pallas"):
        be = get_backend(name, IDEAL)
        for mode, run in (("per_op", be.run), ("fused", be.run_fused)):
            got = np.asarray(run(prog, state))
            assert (got == expected).all(), (doc["name"], name, mode)


@pytest.mark.parametrize(
    "path", GOLDEN_FILES, ids=[os.path.basename(p)[:-5]
                               for p in GOLDEN_FILES])
def test_golden_fused_dispatch_budget(path):
    """Fused execution of every golden stays within its level budget and
    never exceeds the per-op launch count."""
    _, prog, state, _ = _load(path)
    sched = build_schedule(prog)
    pal = get_backend("pallas", IDEAL)
    pal.reset_dispatches()
    pal.run_fused(prog, jnp.asarray(state))
    assert pal.dispatch_count == sched.n_dispatches()
    assert pal.dispatch_count <= sched.n_levels or sched.n_levels == 0
    assert sched.n_dispatches() <= sched.per_op_dispatches()


@pytest.mark.parametrize(
    "path", GOLDEN_FILES, ids=[os.path.basename(p)[:-5]
                               for p in GOLDEN_FILES])
def test_golden_level_walk_structure(path):
    """The frozen walk structure: per level, each group's kind, padded
    arity or fan-out, op count and launch words, plus the digest of the
    expected final image, must reproduce exactly."""
    doc, prog, _, expected = _load(path)
    frozen = doc["walk"]
    sched = build_schedule(prog)
    walk = _LevelWalk(sched)
    assert [[[p.kind, p.param, len(g.ops), p.launch_words]
             for g, p in zip(lvl, plans)]
            for lvl, plans in zip(sched.levels, walk.levels)] \
        == frozen["levels"]
    assert hashlib.sha256(
        np.ascontiguousarray(expected).tobytes()).hexdigest() \
        == frozen["final_digest"]


@pytest.mark.parametrize(
    "path", GOLDEN_FILES, ids=[os.path.basename(p)[:-5]
                               for p in GOLDEN_FILES])
def test_golden_jitted_walk(path):
    """Three sightings of one schedule on one backend — eager, building
    the jitted walk, dispatching it — each equal to ``expected`` and
    each at the schedule's launch budget."""
    _, prog, state, expected = _load(path)
    sched = build_schedule(prog)
    pal = get_backend("pallas", IDEAL)
    state = jnp.asarray(state)
    for sighting in ("eager", "build", "jit"):
        with pal.count_dispatches() as scope:
            got = np.asarray(pal.run_fused(prog, state, sched=sched))
        assert (got == expected).all(), sighting
        assert scope.count == sched.n_dispatches(), sighting
    assert pal._walks[sched].jitted is not None


def test_serialization_roundtrip():
    _, prog, _, _ = _load(GOLDEN_FILES[0])
    again = Program.from_json(prog.to_json())
    assert again.ops == prog.ops
    assert again.histogram() == prog.histogram()


@pytest.mark.parametrize(
    "path", GOLDEN_FILES, ids=[os.path.basename(p)[:-5]
                               for p in GOLDEN_FILES])
def test_golden_certificate_is_frozen_and_deterministic(path):
    """The frozen certificate section must reproduce bit-for-bit.

    Recomputes the full static analysis (races, liveness, symbolic
    equivalence over the schedule) and compares against the
    fixture's pinned digest: an analyzer change that silently alters
    what is checked — or a compiler change that alters the artifacts —
    moves this digest and must go through fixture regeneration.
    """
    from repro.analyze import certify

    doc, prog, _, _ = _load(path)
    sched = build_schedule(prog)
    cert = certify(prog, sched=sched)
    frozen = doc["certificate"]
    assert cert.to_dict() == frozen
    # Determinism: a second independent run lands on the same digest.
    assert certify(prog, sched=sched).digest == frozen["digest"]
