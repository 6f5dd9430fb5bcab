"""Regenerate the golden-program fixtures (tests/golden/*.json).

Run after an *intentional* semantic change to program construction or
execution::

    PYTHONPATH=src:tests python tests/golden/generate.py

Each fixture freezes (a) a canonical serialized Program, (b) the seed of
its random initial (rows, words) state, (c) the expected final state
computed by the per-op oracle interpreter, (d) a ``walk`` section
pinning the level walk's structure (per level, each group's kind,
padded arity or fan-out, op count and launch words) plus a digest of
the expected final state, and (e) a ``certificate`` section freezing
the static analyzer's verdict (:func:`repro.analyze.certify` digest +
per-pass error/warning counts) — so an analyzer change that silently
alters what is checked, or a compiler change that alters the
schedule, moves a pinned digest.  tests/test_compile_golden.py replays
every fixture through per-op and fused execution on all backends and
through the pallas walk's eager and jitted runs: a scheduler change
that reorders ops but alters results — or silently regroups the walk
— fails loudly against these bytes.  Review regenerated diffs
op-by-op — a changed ``expected`` row means changed semantics, not
formatting.
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..",
                                "src"))

import numpy as np  # noqa: E402

WORDS = 4  # state width of every fixture (uint32 words per row)


def _adder(nbits: int):
    """Traced tier-5 ripple-carry adder over nbits-plane operands."""
    from repro.compile import trace_planes

    return trace_planes(lambda bs, A, B: list(bs.add(A, B)[0]), nbits,
                        tier=5, n_act=32).program


def _maj_tree(x: int):
    """Two-level MAJ_x reduction tree: x*x leaf rows -> x -> 1."""
    from repro.core import calibration as cal
    from repro.pud.isa import Program

    prog = Program()
    n_act = cal.min_activation_for(x)
    leaves = x * x
    for i in range(x):
        prog.emit("MAJ", x=x, n_act=n_act, tag=f"tree/l1[{i}]",
                  srcs=tuple(range(i * x, (i + 1) * x)),
                  dsts=(leaves + i,))
    prog.emit("MAJ", x=x, n_act=n_act, tag="tree/root",
              srcs=tuple(range(leaves, leaves + x)),
              dsts=(leaves + x,))
    return prog


def _mrc_fanout31():
    """Fan-out-31 Multi-RowCopy waves + a vote over the copies."""
    from repro.pud.isa import Program

    prog = Program()
    prog.emit("WR", tag="stage/pattern")
    prog.emit("MRC", n_act=32, tag="wave0", srcs=(0,),
              dsts=tuple(range(1, 32)))
    prog.emit("NOT", tag="complement", srcs=(16,), dsts=(32,))
    prog.emit("MRC", n_act=32, tag="wave1", srcs=(32,),
              dsts=tuple(range(33, 64)))
    prog.emit("MAJ", x=3, n_act=4, tag="vote", srcs=(1, 31, 33),
              dsts=(64,))
    return prog


FIXTURES = {
    "add8": lambda: _adder(8),
    "add16": lambda: _adder(16),
    "add32": lambda: _adder(32),
    "maj5_tree": lambda: _maj_tree(5),
    "maj7_tree": lambda: _maj_tree(7),
    "maj9_tree": lambda: _maj_tree(9),
    "mrc_fanout31": _mrc_fanout31,
}


def _walk_section(prog, final: np.ndarray) -> dict:
    """Freeze the level walk's structure + the final-state digest."""
    import hashlib

    from repro.backends.pallas import _LevelWalk
    from repro.compile import build_schedule

    sched = build_schedule(prog)
    walk = _LevelWalk(sched)
    return {
        "levels": [[[p.kind, p.param, len(g.ops), p.launch_words]
                    for g, p in zip(lvl, plans)]
                   for lvl, plans in zip(sched.levels, walk.levels)],
        "final_digest": hashlib.sha256(
            np.ascontiguousarray(final).tobytes()).hexdigest(),
    }


def _certificate_section(prog) -> dict:
    """Freeze the analyzer's certificate for the program's schedule.

    Deterministic: the digest covers program content, the schedule
    digest, the analyzer version, and the per-pass finding counts —
    ``python -m repro.analyze --golden`` and
    ``tests/test_compile_golden.py`` both recompute and compare it.
    """
    from repro.analyze import certify
    from repro.compile import build_schedule

    return certify(prog, sched=build_schedule(prog)).to_dict()


def main() -> None:
    from repro.backends import ExecutionContext, get_backend

    oracle = get_backend("oracle", ExecutionContext(ideal=True))
    out_dir = os.path.dirname(os.path.abspath(__file__))
    for name, build in FIXTURES.items():
        prog = build()
        seed = sum(ord(c) for c in name)  # stable, content-derived
        rng = np.random.default_rng((seed, 0x601D))
        state = rng.integers(0, 2 ** 32, (prog.n_rows(), WORDS),
                             dtype=np.uint32)
        final = np.asarray(oracle.run(prog, state))
        doc = {
            "name": name,
            "seed": seed,
            "rows": prog.n_rows(),
            "words": WORDS,
            "ops": json.loads(prog.to_json()),
            "expected": ["".join(f"{w:08x}" for w in row) for row in final],
            "walk": _walk_section(prog, final),
            "certificate": _certificate_section(prog),
        }
        path = os.path.join(out_dir, f"{name}.json")
        with open(path, "w") as f:
            json.dump(doc, f, indent=1)
        print(f"wrote {path}: {len(prog.ops)} ops, {prog.n_rows()} rows")


if __name__ == "__main__":
    main()
