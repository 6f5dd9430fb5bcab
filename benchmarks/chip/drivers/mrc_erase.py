"""General generator for the in-place wipe of a resident region: one
caller in a closed loop.

The configuration gives the deployment: the service's ``backend`` and
``ctx``, and the region, ``banks`` of ``bank_rows`` rows of ``words``
``uint32`` words, in subarrays of ``subarray_rows`` rows.  The region's
content is random bits made from the seed on the device, bank by bank,
and installed through ``PudService.install_region``: no host copy.

The traffic file gives:

``warm_calls``
    untimed calls made in set-up, after which every program the window
    runs is compiled.
``check_rows``
    rows read back after each call, outside its record, at positions
    drawn from the seed, and compared with the call's pattern.
``lanes`` and ``pool``
    (optional; the cell's traffic file leaves both out) a cut for tests
    on the CPU: the region keeps ``pool`` banks of whole subarrays, its
    subarrays a bank and its words a row cut by one factor, so that it
    holds about ``lanes`` words.

A call is ``PudService.erase`` of the whole region with the call's
pattern, drawn from the seed: every byte nonzero and different from the
same byte of the previous call's pattern.  The service returns once the
device has finished.  The call's record counts the region's words as
``elements`` and :func:`required_bytes` as ``required_bytes``.

Checks, each with limit 0: ``wrong_words``, the words of the region
after the window that differ from the last call's pattern, read back a
bank at a time by ``reference_erase.py``; and ``wrong_sampled_rows``,
the rows read back after the calls (warm-up included) that differ from
their call's pattern.
"""

from __future__ import annotations

import functools
import math
import time

import numpy as np

import reference_erase
from harness import Record, Window, annotate

#: Rows a Multi-RowCopy group covers: one source, 31 destinations.
GROUP_ROWS = 32


def required_bytes(words: int) -> int:
    """Every word written once, and each group's source word read once."""
    return 4 * words + 4 * (words // GROUP_ROWS)


def next_pattern(rng, previous: int) -> int:
    """Four bytes, each nonzero and different from ``previous``'s."""
    out = 0
    for k in range(4):
        prev = (previous >> (8 * k)) & 0xFF
        byte = int(rng.integers(1, 256 if prev == 0 else 255))
        out |= (byte + (byte >= prev > 0)) << (8 * k)
    return out


@functools.cache
def _device_fns():
    import jax
    import jax.numpy as jnp

    @functools.partial(jax.jit, donate_argnums=0)
    def fill(rows, key, t):
        bank = jax.random.bits(jax.random.fold_in(key, t), rows.shape[1:],
                               jnp.uint32)
        return jax.lax.dynamic_update_index_in_dim(rows, bank, t, 0)

    @jax.jit
    def take(rows, banks, idx):
        return rows[banks, idx]

    @functools.partial(jax.jit, donate_argnums=0)
    def write(rows, bank, t):
        return jax.lax.dynamic_update_index_in_dim(rows, bank, t, 0)

    return fill, take, write


class EraseRun:
    def __init__(self, config: dict, traffic: dict, seed: int,
                 spans: bool):
        import jax
        import jax.numpy as jnp

        from repro.backends import ExecutionContext
        from repro.serve import PudService, ServiceConfig

        self.seed, self.traffic = seed, traffic
        self.sub = int(config["subarray_rows"])
        banks, rows, words = (int(config[k]) for k in
                              ("banks", "bank_rows", "words"))
        if "lanes" in traffic:
            banks = int(traffic.get("pool", 2))
            f = math.sqrt(traffic["lanes"] / (banks * rows * words))
            rows = self.sub * max(1, round(rows // self.sub * f))
            words = max(1, round(words * f))
        self.shape = (banks, rows, words)
        self.svc = PudService(ServiceConfig(
            backend=config["backend"], ctx=ExecutionContext(
                **config["ctx"]), pool_size=1))
        install = self.svc.install_region
        fill = _device_fns()[0]
        key = jax.random.key(int(np.random.SeedSequence(seed)
                                 .generate_state(1)[0]))
        region = jnp.zeros(self.shape, jnp.uint32)
        for t in range(banks):
            region = fill(region, key, t)
        self.region = install(jax.block_until_ready(region),
                              subarray_rows=self.sub)
        if spans:
            annotate(self.svc, "erase", "service.erase")
            annotate(self, "_sample", "sample")
        self.calls = self.wrong_rows = self.pattern = 0

    @property
    def words(self) -> int:
        return math.prod(self.shape)

    def _sample(self, pattern: int) -> None:
        """Read ``check_rows`` seeded rows back; count those that differ
        from ``pattern``."""
        take = _device_fns()[1]
        rng = np.random.default_rng([self.seed, 2, self.calls])
        flat = rng.integers(0, self.shape[0] * self.shape[1],
                            self.traffic["check_rows"])
        banks, idx = np.divmod(flat, self.shape[1])
        rows = np.asarray(take(self.region.rows, banks.astype(np.int32),
                               idx.astype(np.int32)))
        self.wrong_rows += reference_erase.wrong_rows(rows, pattern)

    def _call(self) -> tuple[float, float]:
        rng = np.random.default_rng([self.seed, 3, self.calls])
        pattern = next_pattern(rng, self.pattern)
        t_sub = time.perf_counter()
        self.svc.erase(self.region, pattern)
        t_done = time.perf_counter()
        self.pattern = pattern
        self._sample(pattern)
        self.calls += 1
        return t_sub, t_done

    def warm(self) -> None:
        for _ in range(self.traffic["warm_calls"]):
            self._call()

    def window(self, seconds: float) -> Window:
        records = []
        t0 = time.perf_counter()
        t_end = t0 + seconds
        while time.perf_counter() < t_end:
            t_sub, t_done = self._call()
            records.append(Record("erase", t_sub, t_done,
                                  required_bytes=required_bytes(self.words),
                                  elements=self.words))
        return Window(records, t0, time.perf_counter())

    def release(self) -> None:
        """The service goes; the region stays for the check."""
        self.svc = None

    def install_control(self) -> None:
        """Wipe with the reference's control, bank by bank on the host,
        in the service's place."""
        import jax.numpy as jnp

        write = _device_fns()[2]

        def erase(region, pattern):
            for t in range(region.banks):
                bank = np.asarray(region.rows[t])[None]
                new = reference_erase.control_erase(bank, pattern, self.sub)
                region.rows = write(region.rows, jnp.asarray(new[0]), t)

        self.svc.erase = erase

    def checks(self) -> dict:
        blocks = (np.asarray(self.region.rows[t])
                  for t in range(self.shape[0]))
        return {"wrong_words": {"value": reference_erase.wrong_words(
                    blocks, self.pattern), "limit": 0},
                "wrong_sampled_rows": {"value": self.wrong_rows,
                                       "limit": 0}}


def build(config: dict, traffic: dict, seed: int, *,
          spans: bool) -> EraseRun:
    return EraseRun(config, traffic, seed, spans)
