"""Shared result-file hygiene for the bench harnesses.

Both benches (``benchmarks/bench.py``, ``benchmarks/serve_bench.py``)
write schema'd JSON documents.  The rules they share live here instead
of being duplicated:

* ``--out`` is always honored; the repo-relative ``results/`` path is
  only a *default* for interactive runs — CI must pass a temp-dir
  ``--out`` and never writes into ``results/`` (see ``scripts/ci.sh``);
* writes are atomic (tmp file + ``os.replace``), so a killed bench
  never leaves a half-written results document for a gate to parse.
"""

from __future__ import annotations

import json
import os

_RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "results")


def execution_stamp() -> dict:
    """``interpret``: whether the Pallas kernels ran in the interpreter
    (on any platform but a TPU); ``device``: where the numbers were
    measured, as JAX reports it."""
    import jax

    from repro.backends.pallas import interpret_mode

    dev = jax.devices()[0]
    return {"interpret": interpret_mode(),
            "device": {"platform": dev.platform, "kind": dev.device_kind,
                       "count": len(jax.devices())}}


def default_out(filename: str) -> str:
    """Default (non-CI) output path: ``results/<filename>``."""
    return os.path.join(_RESULTS_DIR, filename)


def write_bench_json(out_path: str, doc: dict) -> str:
    """Atomically write ``doc`` to ``out_path``; returns the abspath."""
    out_path = os.path.abspath(out_path)
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    tmp = out_path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(doc, f, indent=2)
    os.replace(tmp, out_path)
    print(f"[bench] wrote {out_path}")
    return out_path
