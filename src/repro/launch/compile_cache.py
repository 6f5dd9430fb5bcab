"""JAX's persistent compilation cache, placed from outside the program.

The entry points that compile for the chip (``chip_smoke.py``,
``benchmarks/bench.py``, ``benchmarks/serve_bench.py`` and
``python -m repro.sweep.run``) call :func:`enable_compile_cache` before
their first compile, so processes that run one after another share
compiled kernels.

* Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
  this module sets no directory.
* Otherwise the cache lives at :data:`CHECKOUT_CACHE_DIR`, a fixed path
  inside the checkout (``.jax_cache/``, ignored by git).  A path that
  moved from run to run would never be hit again.

The PUD kernels compile in about a second, under JAX's default floor of
one second for what the cache keeps, so the floor is lowered to zero.
"""

from __future__ import annotations

import os

import jax

CHECKOUT_CACHE_DIR = os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "..", "..", ".jax_cache"))


def enable_compile_cache() -> str:
    """Turn on the persistent cache; returns the directory in use."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = CHECKOUT_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
