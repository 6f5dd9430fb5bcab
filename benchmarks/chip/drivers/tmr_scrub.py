"""General generator for the TMR scrub of resident replicas: one caller
in a closed loop.

The configuration gives the deployment: the service's ``backend`` and
``ctx``, the replica count ``x``, and under ``leaves`` the stage's leaf
list.  Each leaf's ``shape`` entries and ``count`` are expressions over
the configuration's own keys (integers, ``+``, ``-``, ``*``, ``//`` and
``len(key)``), so the list reads straight off the published config and
this driver knows no leaf by name.  Leaves are made from the seed on
the host, leaf by leaf, and installed through
``PudService.install_replicas``: the device never holds a copy of the
tree beside the replicas.

The traffic file gives:

``flips``
    single-bit flips planted before each call, at distinct word
    positions drawn from the seed, each in one replica drawn from the
    seed, so the majority stays clean.  Planting is a donated in-place
    update of the replicas, its own work, outside the call's record.
``warm_calls``
    untimed calls made in set-up, after which every program the window
    runs is compiled.
``lanes``
    (optional; the cells' traffic files leave it out) a cut for tests on
    the CPU: every integer key the shapes read is scaled down so a
    replica holds about this many words, the counts kept, and ``flips``
    is held to a quarter of the words.

A call is ``PudService.scrub`` of the whole stage, and waits for the
replicas (``block_until_ready``).  Its record counts the leaf words
voted per replica as ``elements`` and ``(x + 1) * 4`` bytes per word as
``required_bytes``.

Checks, each with limit 0: ``wrong_bits``, the bits of the replicas
after the window that differ from the clean stage made again leaf by
leaf from the seed and packed by ``reference_scrub.py`` (rows past the
last leaf must be zero); and ``miscounted_calls``, the calls (warm-up
included) whose per-replica counts differ from the flips planted for
them.
"""

from __future__ import annotations

import ast
import functools
import math
import operator
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

import reference_scrub
from harness import Record, Window, annotate

_OPS = {ast.Add: operator.add, ast.Sub: operator.sub,
        ast.Mult: operator.mul, ast.FloorDiv: operator.floordiv}


def evaluate(expr: str, keys: dict) -> int:
    """An integer expression over the configuration's keys."""
    def ev(node):
        if isinstance(node, ast.Expression):
            return ev(node.body)
        if isinstance(node, ast.Constant) and isinstance(node.value, int):
            return node.value
        if isinstance(node, ast.Name):
            return int(keys[node.id])
        if isinstance(node, ast.BinOp) and type(node.op) in _OPS:
            return _OPS[type(node.op)](ev(node.left), ev(node.right))
        if (isinstance(node, ast.Call) and isinstance(node.func, ast.Name)
                and node.func.id == "len" and len(node.args) == 1
                and isinstance(node.args[0], ast.Name)):
            return len(keys[node.args[0].id])
        raise ValueError(f"unsupported expression {expr!r}")
    return ev(ast.parse(expr, mode="eval"))


def stage_leaves(config: dict, scale: float = 1.0) -> list[tuple]:
    """``(shape, dtype)`` of every leaf instance, in packing order;
    ``scale`` multiplies every integer key the shapes read."""
    import jax.numpy as jnp

    scaled = {k: max(1, round(v * scale)) if isinstance(v, int)
              and not isinstance(v, bool) else v for k, v in config.items()}
    out = []
    for leaf in config["leaves"]:
        shape = tuple(evaluate(e, scaled) for e in leaf["shape"])
        out += [(shape, jnp.dtype(leaf["dtype"]))] * evaluate(
            leaf["count"], config)
    return out


def leaf_words(spec) -> int:
    shape, dtype = spec
    return -(-math.prod(shape) * np.dtype(dtype).itemsize // 4)


def leaf_values(seed: int, i: int, spec) -> np.ndarray:
    """Leaf ``i`` of the stage, random bits drawn from the seed."""
    shape, dtype = spec
    nbytes = math.prod(shape) * np.dtype(dtype).itemsize
    raw = np.random.Generator(np.random.SFC64([seed, i])).integers(
        0, 2**64, -(-nbytes // 8), dtype=np.uint64)
    return raw.view(np.uint8)[:nbytes].view(dtype).reshape(shape)


def make_leaves(seed: int, specs) -> list[np.ndarray]:
    with ThreadPoolExecutor() as pool:
        return list(pool.map(lambda a: leaf_values(seed, *a),
                             enumerate(specs)))


def required_bytes(x: int, words: int) -> int:
    """``x`` replicas read and one vote written per word."""
    return (x + 1) * 4 * words


@functools.cache
def _device_fns():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def words_at(replicas, rows, cols):
        return jnp.stack([r[rows, cols] for r in replicas])

    # One word at a time: the chip's scatter would copy a whole replica
    # first, where a loop of one-word updates stays in place.
    @functools.partial(jax.jit, donate_argnums=0)
    def put(replicas, rows, cols, words):
        def one(k, reps):
            return tuple(jax.lax.dynamic_update_slice(
                r, w[k].reshape(1, 1), (rows[k], cols[k]))
                for r, w in zip(reps, words))
        return jax.lax.fori_loop(0, rows.shape[0], one, replicas)

    @functools.partial(jax.jit, donate_argnums=0)
    def write(replicas, block, row):
        return tuple(jax.lax.dynamic_update_slice(r, b, (row, 0))
                     for r, b in zip(replicas, block))

    @jax.jit
    def wrong_bits(replica, clean, row):
        got = jax.lax.dynamic_slice(replica, (row, 0), clean.shape)
        return jnp.sum(jax.lax.population_count(got ^ clean), axis=1,
                       dtype=jnp.int32)

    return words_at, put, write, wrong_bits


class ScrubRun:
    def __init__(self, config: dict, traffic: dict, seed: int,
                 spans: bool):
        import jax

        from repro.backends import ExecutionContext
        from repro.serve import PudService, ScrubResult, ServiceConfig

        self.ScrubResult = ScrubResult
        self.seed, self.x = seed, int(config["x"])
        self.traffic = traffic
        self.specs = stage_leaves(config)
        self.flips = int(traffic["flips"])
        if "lanes" in traffic:
            full = sum(map(leaf_words, self.specs))
            self.specs = stage_leaves(
                config, math.sqrt(traffic["lanes"] / full))
        self.words = sum(map(leaf_words, self.specs))
        if "lanes" in traffic:
            self.flips = min(self.flips, self.words // 4)
        self.rows = reference_scrub.leaf_offsets(self.specs)
        self.first_word = np.cumsum([0] + [leaf_words(s)
                                           for s in self.specs])
        self.svc = PudService(ServiceConfig(
            backend=config["backend"], ctx=ExecutionContext(
                **config["ctx"]), pool_size=1))
        self.rs = self.svc.install_replicas(
            make_leaves(seed, self.specs), x=self.x)
        jax.block_until_ready(self.rs.replicas)
        if spans:
            annotate(self.svc, "scrub", "service.scrub")
            annotate(self, "_plant", "plant")
        self.calls = self.miscounted = 0

    def _plant(self, call: int) -> tuple[int, ...]:
        """Flip ``flips`` bits in place; returns the flips per replica."""
        import jax

        rng = np.random.default_rng([self.seed, 1, call])
        word = rng.choice(self.words, self.flips, replace=False)
        bit = rng.integers(0, 32, self.flips, dtype=np.uint32)
        rep = rng.integers(0, self.x, self.flips)
        leaf = np.searchsorted(self.first_word, word, side="right") - 1
        within = word - self.first_word[leaf]
        rows = np.asarray(self.rows)[leaf] + within // \
            reference_scrub.ROW_WORDS
        cols = within % reference_scrub.ROW_WORDS
        masks = np.zeros((self.x, self.flips), np.uint32)
        masks[rep, np.arange(self.flips)] = np.uint32(1) << bit
        words_at, put = _device_fns()[:2]
        rows, cols = rows.astype(np.int32), cols.astype(np.int32)
        words = words_at(self.rs.replicas, rows, cols)
        self.rs.replicas = put(self.rs.replicas, rows, cols, words ^ masks)
        jax.block_until_ready(self.rs.replicas)
        return tuple(int(c) for c in np.bincount(rep, minlength=self.x))

    def _call(self) -> None:
        import jax

        planted = self._plant(self.calls)
        self.t_sub = time.perf_counter()
        result = self.svc.scrub(self.rs)
        jax.block_until_ready(self.rs.replicas)
        self.calls += 1
        if tuple(result.corrected) != planted:
            self.miscounted += 1

    def warm(self) -> None:
        for _ in range(self.traffic["warm_calls"]):
            self._call()

    def window(self, seconds: float) -> Window:
        records = []
        t0 = time.perf_counter()
        t_end = t0 + seconds
        while time.perf_counter() < t_end:
            self._call()
            records.append(Record(
                "scrub", self.t_sub, time.perf_counter(),
                required_bytes=required_bytes(self.x, self.words),
                elements=self.words))
        return Window(records, t0, time.perf_counter())

    def release(self) -> None:
        """The service goes; the replicas stay for the check."""
        self.svc = None

    def install_control(self) -> None:
        """Scrub with the reference's control, block by block on the
        host, in the service's place."""
        import jax.numpy as jnp

        write = _device_fns()[2]
        block_rows = 256 * reference_scrub.CONTROL_TILE_ROWS

        def scrub(rs):
            counts = np.zeros(rs.x, np.int64)
            n = int(rs.replicas[0].shape[0])
            for lo in range(0, n, block_rows):
                block = np.stack([np.asarray(r[lo:lo + block_rows])
                                  for r in rs.replicas])
                new, c = reference_scrub.control_scrub(
                    block, lo // reference_scrub.CONTROL_TILE_ROWS)
                counts += c
                rs.replicas = write(rs.replicas, jnp.asarray(new), lo)
            return self.ScrubResult(corrected=tuple(int(c) for c in counts),
                                    tiles=0, words=self.words)

        self.svc.scrub = scrub

    def checks(self) -> dict:
        import jax.numpy as jnp

        wrong_bits = _device_fns()[3]
        wrong = 0
        for i, clean in enumerate(make_leaves(self.seed, self.specs)):
            block = jnp.asarray(reference_scrub.pack_leaf(clean))
            for r in self.rs.replicas:
                wrong += int(np.asarray(wrong_bits(r, block, self.rows[i]),
                                        np.int64).sum())
        used = self.rows[-1] + reference_scrub.leaf_rows(
            leaf_words(self.specs[-1]) * 4) if self.specs else 0
        for r in self.rs.replicas:
            wrong += int(np.bitwise_count(np.asarray(r[used:])).sum())
        return {"wrong_bits": {"value": wrong, "limit": 0},
                "miscounted_calls": {"value": self.miscounted, "limit": 0}}


def build(config: dict, traffic: dict, seed: int, *,
          spans: bool) -> ScrubRun:
    return ScrubRun(config, traffic, seed, spans)
