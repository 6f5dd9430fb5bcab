"""Kernel micro-benchmarks through the session API: a ``pallas``
session vs the ``oracle`` reference on identical inputs,
plus the analytic TPU-side traffic model for each kernel.  Swapping the
one-string backend name re-prices every row on a different executor."""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.backends import ExecutionContext
from repro.session import DramSession

#: One-string config choice: which executor the benchmark rows measure.
BENCH_BACKEND = "pallas"
REF_BACKEND = "oracle"


def _timeit(fn, reps=3):
    r = fn()
    jax.block_until_ready(r)
    t0 = time.perf_counter()
    for _ in range(reps):
        r = fn()
    jax.block_until_ready(r)
    return (time.perf_counter() - t0) / reps * 1e6


def kernel_benchmarks(backend: str = BENCH_BACKEND):
    ctx = ExecutionContext()
    be = DramSession(backend, ctx)
    ref = DramSession(REF_BACKEND, ctx)
    rng = np.random.default_rng(0)
    rows = []

    x = jnp.asarray(rng.integers(0, 2**32, (9, 64, 2048), dtype=np.uint32))
    us_ref = _timeit(lambda: ref.majx(x))
    us_k = _timeit(lambda: be.majx(x))
    # HBM traffic model on TPU: read 9 planes + write 1
    traffic = x.nbytes * 10 / 9
    rows.append((f"kernel_majx9_64x2048[{backend}]", us_k,
                 f"ref_us={us_ref:.0f};tpu_bytes={traffic:.0f}"))

    a = jnp.asarray(rng.integers(0, 2**32, (32, 16, 512), dtype=np.uint32))
    b = jnp.asarray(rng.integers(0, 2**32, (32, 16, 512), dtype=np.uint32))
    us_ref = _timeit(lambda: ref.add_planes(a, b))
    us_k = _timeit(lambda: be.add_planes(a, b))
    # fused kernel: one round trip; naive plane-at-a-time: 32 round trips
    rows.append((f"kernel_bitserial_add_32x16x512[{backend}]", us_k,
                 f"ref_us={us_ref:.0f};traffic_reduction=32x"))

    src = jnp.asarray(rng.integers(0, 2**32, (8, 4096), dtype=np.uint32))
    us_k = _timeit(lambda: be.rowcopy(src, 31))
    rows.append((f"kernel_fanout31_8x4096[{backend}]", us_k,
                 f"hbm_read_bytes={src.nbytes};write={src.nbytes*31}"))

    g = jnp.asarray(rng.integers(0, 2**32, (1 << 18,), dtype=np.uint32))
    w = jnp.asarray(rng.integers(0, 2**32, (1 << 18,), dtype=np.uint32))
    us_ref = _timeit(lambda: ref.mismatch(g, w))
    us_k = _timeit(lambda: be.mismatch(g, w))
    rows.append((f"kernel_mismatch_1M_cells[{backend}]", us_k,
                 f"ref_us={us_ref:.0f}"))

    # vmapped batch dispatch (native on pallas, loop elsewhere)
    xb = jnp.asarray(rng.integers(0, 2**32, (4, 5, 16, 512), dtype=np.uint32))
    us_k = _timeit(lambda: be.majx_batch(xb))
    rows.append((f"kernel_majx5_batch4_16x512[{backend}]", us_k,
                 f"native_batch={be.capabilities().native_batch}"))
    return rows
