"""Resident replica sets and their tile-by-tile scrub
(``repro.serve.scrub``), against the benchmark's plain numpy reference
(``benchmarks/chip/reference_scrub.py``).

The leaf list is a tiny stand-in for a Zamba2 stage, with its odd
shapes: a bf16 matrix, grouped-conv weights (rows, 1, 4), 7-element
vectors, a rank-2 adapter pair, and a bf16 vector of odd length.  The
tile is cut to 8 rows so that the embedding spans two tiles and the row
count is not a multiple of the tile before padding.
"""

import glob
import os
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np
import pytest

from repro import obs
from repro.serve import PudService, ScrubRequest, ServeError, ServiceConfig
from repro.serve import scrub as scrub_mod

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "benchmarks"
                       / "chip"))
import reference_scrub  # noqa: E402

BF16 = np.dtype(ml_dtypes.bfloat16)

#: (shape, dtype) of each leaf, in packing order.
SPECS = [((59, 16), BF16),               # in_proj
         ((30, 1, 4), BF16),             # grouped conv weights
         ((7,), BF16), ((7,), BF16),     # dt_bias, A_log
         ((16, 2), BF16), ((2, 16), BF16),   # LoRA adapter pair
         ((5 * 4096 + 5, 2), BF16),      # "embedding": rows 6-11, 2 tiles
         ((4097,), BF16)]                # odd length, spills into a row


@pytest.fixture
def small_tiles(monkeypatch):
    monkeypatch.setattr(scrub_mod, "TILE_ROWS", 8)


def random_leaves(seed, specs=SPECS):
    rng = np.random.default_rng(seed)
    out = []
    for shape, dtype in specs:
        n = int(np.prod(shape)) * dtype.itemsize
        out.append(rng.integers(0, 256, n, dtype=np.uint8).view(dtype)
                   .reshape(shape))
    return out


def host(rs):
    return np.stack([np.asarray(r) for r in rs.replicas])


def test_the_layout_matches_the_reference(small_tiles):
    leaves = random_leaves(0)
    layout = scrub_mod.layout_of(leaves)
    assert layout.tile_rows == 8
    assert [s.row for s in layout.slots] == \
        reference_scrub.leaf_offsets(leaves)
    used = len(reference_scrub.pack(leaves))
    assert used % 8 and layout.rows == -(-used // 8) * 8
    embed = layout.slots[6]
    assert embed.row // 8 != (embed.row + embed.rows - 1) // 8
    packed = np.asarray(scrub_mod.pack(leaves, layout))
    np.testing.assert_array_equal(packed[:used],
                                  reference_scrub.pack(leaves))
    assert not packed[used:].any()


@pytest.mark.parametrize("backend", ["oracle", "pallas", "sim"])
def test_scrub_equals_the_reference_majority(backend, small_tiles):
    """Every replica differs from the vote, in both tiles; ``sim``
    (a device model) runs the tile step eagerly, the others jitted."""
    svc = PudService(ServiceConfig(backend=backend, pool_size=1))
    trees = [random_leaves(s) for s in (1, 2, 3)]
    rs = svc.install_replica_trees(trees)
    want, counts = reference_scrub.scrub(host(rs))
    result = svc.scrub(rs)
    assert result.corrected == tuple(counts)
    assert result.tiles == rs.layout.tiles == 2
    assert result.words == sum(s.words for s in rs.layout.slots)
    np.testing.assert_array_equal(host(rs), want)
    # The vote holds: a second scrub finds nothing to correct.
    assert svc.scrub(rs).corrected == (0, 0, 0)


@pytest.mark.parametrize("backend", ["oracle", "pallas"])
def test_counts_equal_the_planted_flips(backend, small_tiles):
    svc = PudService(ServiceConfig(backend=backend, pool_size=1))
    leaves = random_leaves(4)
    rs = svc.install_replicas(leaves, x=3)
    reps = host(rs)
    rng = np.random.default_rng(5)
    used = len(reference_scrub.pack(leaves))
    words = rng.choice(used * scrub_mod.ROW_WORDS, 300, replace=False)
    owner = rng.integers(0, 3, 300)
    bits = rng.integers(0, 32, 300, dtype=np.uint32)
    rows, cols = np.divmod(words, scrub_mod.ROW_WORDS)
    reps[owner, rows, cols] ^= np.uint32(1) << bits
    rs.replicas = tuple(jnp.asarray(r) for r in reps)
    result = svc.scrub(rs)
    assert result.corrected == tuple(np.bincount(owner, minlength=3))
    for got, want in zip(jax.tree.leaves(svc.live(rs)), leaves):
        np.testing.assert_array_equal(np.asarray(got).view(np.uint8),
                                      want.view(np.uint8))


def test_live_round_trips_every_dtype():
    rng = np.random.default_rng(6)
    tree = {"f32": rng.standard_normal((3, 5)).astype(np.float32),
            # Random bits: subnormals and NaN payloads among them.
            "bf16": np.append(rng.integers(0, 2**16, 6, np.uint16),
                              [0x0001, 0x7F81, 0xFFC3]).astype(
                                  np.uint16).view(BF16),
            "bf16_dev": jnp.asarray(rng.integers(0, 2**16, (3, 3),
                                                 np.uint16)).view(BF16),
            "f16": rng.standard_normal((2, 3)).astype(np.float16),
            "i32": rng.integers(-9, 9, 4, dtype=np.int32),
            "u32": rng.integers(0, 2**32, 5000, dtype=np.uint32),
            "i16": rng.integers(-9, 9, 3, dtype=np.int16),
            "i8": rng.integers(-9, 9, (5,), dtype=np.int8),
            "u8": rng.integers(0, 255, 3, dtype=np.uint8)}
    svc = PudService(ServiceConfig(backend="oracle", pool_size=1))
    rs = svc.install_replicas(tree)
    assert rs.x == 3 and svc.scrub(rs).corrected == (0, 0, 0)
    live = svc.live(rs)
    for k, want in tree.items():
        want = np.asarray(want)
        assert live[k].dtype == want.dtype and live[k].shape == want.shape
        np.testing.assert_array_equal(np.asarray(live[k]).view(np.uint8),
                                      want.view(np.uint8))
    leaves = [np.asarray(leaf) for leaf in jax.tree.leaves(tree)]
    np.testing.assert_array_equal(
        host(rs)[0, :len(reference_scrub.pack(leaves))],
        reference_scrub.pack(leaves))


def test_one_program_per_tile_shape_through_the_service(small_tiles):
    """Every tile of every scrub runs one frozen Program, resolved once
    a scrub: one schedule miss and one certificate, then a hit per
    scrub; each scrub is a request."""
    svc = PudService(ServiceConfig(backend="pallas", pool_size=1))
    rs = svc.install_replicas(random_leaves(7), tenant="t")
    for _ in range(2):
        svc.scrub(rs)
    assert rs.layout.tiles == 2
    assert svc.cache.stats.misses == 1
    assert svc.cache.stats.hits == 1
    assert svc.cache.certificate_stats.misses == 1
    assert svc.snapshot().tenants["t"]["completed"] == 2
    prog = scrub_mod.tile_program(3, 8)
    assert prog is scrub_mod.tile_program(3, 8) and len(prog.ops) == 8
    with pytest.raises(TypeError):
        prog.emit("MAJ")


def test_a_scrub_request_needs_a_replica_set_and_an_odd_count():
    svc = PudService(ServiceConfig(backend="oracle", pool_size=1))
    with pytest.raises(ServeError, match="required"):
        ScrubRequest()
    with pytest.raises(ServeError, match="odd"):
        svc.install_replicas({"w": np.zeros(4, np.float32)}, x=2)
    with pytest.raises(ServeError, match="odd"):
        svc.install_replica_trees([{"w": np.zeros(4, np.float32)}] * 4)
    with pytest.raises(ValueError, match="structure"):
        svc.install_replica_trees([{"w": np.zeros(4, np.float32)},
                                   {"w": np.zeros(5, np.float32)},
                                   {"w": np.zeros(4, np.float32)}])


def test_engine_heal_params_is_the_reference_vote(make_tiny_pud_engine):
    """``heal_params`` installs, scrubs and unpacks: the healed params
    are the bitwise majority of the replicas, and it returns the bits
    replica 0 had wrong, as the one-tile heal before it did."""
    eng, params = make_tiny_pud_engine(pud_backend="pallas")
    rng = np.random.default_rng(8)
    reps = [{k: v.copy() for k, v in params.items()} for _ in range(3)]
    for rep in reps:
        for v in rep.values():
            v.reshape(-1).view(np.uint32)[rng.integers(0, v.size, 3)] ^= \
                np.uint32(1) << rng.integers(0, 32, 3, dtype=np.uint32)
    want = {k: reference_scrub.majority(
        [r[k].view(np.uint32) for r in reps]) for k in params}
    fixed = eng.heal_params(reps)
    assert fixed == sum(int(np.bitwise_count(
        reps[0][k].view(np.uint32) ^ want[k]).sum()) for k in params)
    for k in params:
        np.testing.assert_array_equal(
            np.asarray(eng.params[k]).view(np.uint32), want[k])
    assert eng.pud_decisions[-1] is not None


def test_scrub_spans_nest_inside_the_service_call(tmp_path, small_tiles):
    svc = PudService(ServiceConfig(backend="pallas", pool_size=1))
    rs = svc.install_replicas(random_leaves(9))
    svc.scrub(rs)
    jax.profiler.start_trace(str(tmp_path))
    try:
        svc.scrub(rs)
        jax.block_until_ready(rs.replicas)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                        recursive=True)
    events = [(ev.name[len(obs.SPAN_PREFIX):], ev.start_ns,
               ev.start_ns + ev.duration_ns)
              for plane in jax.profiler.ProfileData.from_file(path).planes
              for line in plane.lines for ev in line.events
              if ev.name.startswith(obs.SPAN_PREFIX)]
    names = [e[0] for e in events]
    tiles = rs.layout.tiles
    assert names.count("service.scrub") == 1
    for name in ("scrub.tile", "scrub.step"):
        assert names.count(name) == tiles, name
    # The walk and the mismatch passes run inside the step's program.
    for name in ("scrub.verify", "session.run_fused", "backend.run_fused"):
        assert names.count(name) == 0, name
    (call,) = [e for e in events if e[0] == "service.scrub"]
    assert all(call[1] <= s and e <= call[2] for _, s, e in events)
    spans = {name: [(s, e) for n, s, e in events if n == name]
             for name in ("scrub.tile", "scrub.step")}
    for s, e in spans["scrub.step"]:
        assert sum(ts <= s and e <= te for ts, te in spans["scrub.tile"]) \
            == 1


def parent_scrub(session, rs):
    """The tile loop before the one-step tile, the reference for the
    counters: the walk through ``run_fused``, the write-back, and one
    ``mismatch`` call per replica, each its own dispatch."""
    x, tile = rs.x, rs.layout.tile_rows
    prog = scrub_mod.tile_program(x, tile)
    counts = []
    for t in range(rs.layout.tiles):
        start = t * tile
        image = session.run_fused(
            prog, scrub_mod._tile_image(rs.replicas, start, tile))
        rs.replicas, before, voted = scrub_mod._commit(
            tuple(map(jnp.copy, rs.replicas)), image, start)
        counts.append([int(session.mismatch(b, voted)) for b in before])
    return tuple(int(c) for c in np.sum(counts, 0))


@pytest.mark.parametrize("backend", ["pallas", "sim"])
def test_counters_advance_as_the_tile_loop_did(backend, small_tiles):
    """``dispatch_count`` and ``energy_nj_total`` advance per scrub
    exactly as the loop of separate dispatches did: a launch per group
    of the walk and one per replica's mismatch, in that order, a tile
    (``pallas``: the walk's eager first sighting and jitted later ones
    count alike); and the votes and counts agree."""
    trees = [random_leaves(s) for s in (11, 12, 13)]
    new, old = (PudService(ServiceConfig(backend=backend, pool_size=1))
                for _ in range(2))
    rs_new, rs_old = (svc.install_replica_trees(trees) for svc in (new, old))
    for _ in range(2):
        got = scrub_mod.scrub(new.sessions[0], rs_new)
        want = parent_scrub(old.sessions[0], rs_old)
        assert got == want and any(want)
        np.testing.assert_array_equal(host(rs_new), host(rs_old))
        a, b = new.sessions[0].backend, old.sessions[0].backend
        assert a.energy_nj_total > 0
        assert (a.dispatch_count, a.energy_nj_total) == \
            (b.dispatch_count, b.energy_nj_total)
        trees = [random_leaves(s) for s in (14, 15, 16)]
        rs_new.replicas = tuple(jnp.asarray(r) for r in host(
            new.install_replica_trees(trees)))
        rs_old.replicas = tuple(map(jnp.copy, rs_new.replicas))


def test_the_count_sum_is_exact_past_2_to_32():
    """Per-tile counts near ``2**32`` sum exactly: the buffer is summed
    in ``int64`` on the host, not in ``uint32``."""
    counts = jnp.asarray([[2**32 - 1, 2**32 - 2, 2**31],
                          [2**32 - 1, 1, 2**31],
                          [2**32 - 5, 2**32 - 1, 2**31 + 3]], jnp.uint32)
    assert scrub_mod._sum_counts(counts) == (
        3 * 2**32 - 7, 2**33 - 2, 3 * 2**31 + 3)
