"""The TMR scrub cell: its configuration's leaf list, its reference, whole
runs at a tiny leaf list on the CPU, and its readers on hand-made
traces.

A sound run is correct; the run comes out not correct with the
reference's control in the program's place, and with each fault the
scrub can have planted: the state returned unscrubbed (no vote written
back), half the tiles skipped, and one voted bit flipped.
"""

import time

import jax.numpy as jnp
import numpy as np
import pytest

import harness
import reference_scrub
import scrub_trace
from harness import Reading, Record, Window
from repro.backends.pallas import PallasBackend
from repro.serve import scrub as scrub_mod
from test_program_trace import chip, host

CELL = "scrub-zamba2-7b-stage0"

READERS = ("device_idle.scrub", "hbm_roofline.scrub", "level_exec_ms.scrub",
           "verify_ms.scrub", "scrub_host_ms.scrub", "tiles_per_call.scrub",
           "vote_roofline.scrub", "mismatch_roofline.scrub")

#: Zamba2-like keys at tiny widths, with its odd shapes: an in_proj of
#: 2*2*6 + 2*1*3 + 5 = 35 rows, conv weights (15, 1, 4), 5-element
#: vectors, an odd-length bf16 vector, and an embedding over two tiles.
TINY_KEYS = {"vocab_size": 4101, "hidden_size": 6, "mamba_expand": 2,
             "mamba_ngroups": 1, "mamba_d_state": 3, "n_mamba_heads": 5,
             "mamba_d_conv": 4, "adapter_rank": 2, "num_hidden_layers": 2,
             "hybrid_layer_ids": [1]}
TINY_LEAVES = [
    {"name": "m.in_proj", "shape": ["2*mamba_expand*hidden_size + 2*"
                                    "mamba_ngroups*mamba_d_state + "
                                    "n_mamba_heads", "hidden_size"],
     "dtype": "bfloat16", "count": "num_hidden_layers"},
    {"name": "m.conv", "shape": ["mamba_expand*hidden_size + mamba_d_state",
                                 "1", "mamba_d_conv"],
     "dtype": "bfloat16", "count": "num_hidden_layers"},
    {"name": "m.D", "shape": ["n_mamba_heads"], "dtype": "bfloat16",
     "count": "num_hidden_layers"},
    {"name": "h.adapter", "shape": ["adapter_rank", "hidden_size"],
     "dtype": "bfloat16", "count": "len(hybrid_layer_ids)"},
    {"name": "embed", "shape": ["vocab_size", "hidden_size"],
     "dtype": "bfloat16", "count": "1"}]


def tiny():
    cell = harness.resolve(harness.load_spec(), CELL, trace=False)
    cell.config = dict(cell.config, **TINY_KEYS, leaves=TINY_LEAVES)
    cell.traffic.update(flips=256, warm_calls=1)
    return cell


def run(cell, control=False, trace=False):
    return harness.run_cell(cell, 2**40 + 29, 0.3, trace,
                            t_start=time.perf_counter(), control=control,
                            log=lambda s: None)


@pytest.fixture
def small_tiles(monkeypatch):
    monkeypatch.setattr(scrub_mod, "TILE_ROWS", 8)


# ------------------------------------------------------ configuration
def test_the_stage_is_zamba2_7b_layers_0_to_11():
    cell = harness.resolve(harness.load_spec(), CELL, trace=False)
    driver = cell.driver
    specs = driver.stage_leaves(cell.config)
    params = sum(int(np.prod(shape)) for shape, _ in specs)
    assert params == 1_757_849_536
    assert sum(map(driver.leaf_words, specs)) == 878_924_768
    shapes = {shape for shape, _ in specs}
    assert {(14704, 3584), (7424, 1, 4), (112,), (32000, 3584),
            (128, 3584), (28672, 128), (7168, 7168)} <= shapes
    assert cell.config["layers_block_type"].count("hybrid") == \
        len(cell.config["hybrid_layer_ids"]) == 2
    assert set(cell.config["reduced"]) == {
        "num_hidden_layers", "layers_block_type", "hybrid_layer_ids"}


def test_the_expression_language():
    driver = harness.resolve(harness.load_spec(), CELL,
                             trace=False).driver
    keys = {"a": 3, "b": 4, "ids": [1, 5]}
    assert driver.evaluate("2*a + b//3 - len(ids)", keys) == 5
    for bad in ("a**2", "open('x')", "a.b", "1.5"):
        with pytest.raises(ValueError):
            driver.evaluate(bad, keys)


# ----------------------------------------------------------- reference
def test_the_reference_packs_and_votes():
    rng = np.random.default_rng(1)
    leaves = [rng.integers(0, 2**16, 2 * 4096 + 1, np.uint16),
              rng.standard_normal((3, 5)).astype(np.float32),
              rng.integers(0, 9, 3, np.int8)]
    packed = reference_scrub.pack(leaves)
    assert packed.shape == (4, reference_scrub.ROW_WORDS)
    assert reference_scrub.leaf_offsets(leaves) == [0, 2, 3]
    for got, want in zip(reference_scrub.unpack(
            packed, [(x.shape, x.dtype) for x in leaves]), leaves):
        np.testing.assert_array_equal(got, want)
    a, b, c = (rng.integers(0, 2**32, (4, 9), np.uint32) for _ in range(3))
    voted, counts = reference_scrub.scrub(np.stack([a, b, c]))
    assert (voted == ((a & b) | (a & c) | (b & c))).all()
    assert counts[0] == int(np.bitwise_count(a ^ voted[0]).sum())


def test_the_control_votes_every_other_tile():
    t = reference_scrub.CONTROL_TILE_ROWS
    rng = np.random.default_rng(2)
    reps = rng.integers(0, 2**32, (3, 4 * t, 2), np.uint32)
    voted, _ = reference_scrub.control_scrub(reps)
    full, _ = reference_scrub.scrub(reps)
    for tile in range(4):
        rows = slice(tile * t, (tile + 1) * t)
        want = full if tile % 2 == 0 else reps
        assert (voted[:, rows] == want[:, rows]).all(), tile
    shifted, _ = reference_scrub.control_scrub(reps, first_tile=1)
    assert (shifted[:, :t] == reps[:, :t]).all()


# ---------------------------------------------------------- whole runs
def test_a_sound_run_is_correct(small_tiles):
    cell = tiny()
    out = run(cell)
    assert out.correct, out.checks
    assert out.attempted > 0 and out.failed == 0
    assert set(out.checks) == {"wrong_bits", "miscounted_calls"}


def test_the_control_is_not_correct():
    out = run(tiny(), control=True)
    assert not out.correct
    assert out.checks["wrong_bits"]["value"] > 0
    assert out.checks["miscounted_calls"]["value"] > 0


def state_unscrubbed(mp):
    commit = scrub_mod._commit

    def keep(replicas, image, start):
        return (replicas, *commit(tuple(map(jnp.copy, replicas)), image,
                                  start)[1:])

    mp.setattr(scrub_mod, "_commit", keep)


def half_the_tiles(mp):
    mp.setattr(scrub_mod.PackedLayout, "tiles",
               property(lambda self: self.rows // self.tile_rows // 2))


def voted_bit_flipped(mp):
    majx = PallasBackend.majx

    def majx_flip(self, planes, x=None, n_act=None):
        out = majx(self, planes, x, n_act)
        return out.at[0, 0].set(out[0, 0] ^ 1)

    mp.setattr(PallasBackend, "majx", majx_flip)


FAULTS = {"state_unscrubbed": state_unscrubbed,
          "half_the_tiles": half_the_tiles,
          "voted_bit_flipped": voted_bit_flipped}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_fault_is_not_correct(fault, small_tiles, monkeypatch):
    FAULTS[fault](monkeypatch)
    out = run(tiny())
    assert not out.correct, (fault, out.checks)


def test_a_traced_run_reads_the_host_metrics(small_tiles):
    """On the CPU the trace has no device lines: the four readers of host
    spans read, and the device readers are silent."""
    cell = tiny()
    traced = harness.resolve(harness.load_spec(), CELL, trace=True)
    cell.metrics, cell.readers = traced.metrics, traced.readers
    assert {m["name"] for m in cell.metrics} == set(READERS)
    out = run(cell, trace=True)
    assert out.correct, out.checks
    m = {k: v["value"] for k, v in out.metrics.items()}
    assert set(m) == {"level_exec_ms.scrub", "verify_ms.scrub",
                      "scrub_host_ms.scrub", "tiles_per_call.scrub"}
    assert m["tiles_per_call.scrub"] == 2
    assert m["level_exec_ms.scrub"] > 0 and m["verify_ms.scrub"] > 0
    assert m["scrub_host_ms.scrub"] > 0


# ------------------------------------------------------------- readers
#: One scrub call of two tiles in a window of 0..1000 ns.
SPANS = [("bench/window", 0, 1000),
         ("pud/service.scrub", 100, 800),            # 100..900
         ("pud/scrub.tile", 110, 390),               # 110..500
         ("pud/session.run_fused", 120, 200),        # 120..320
         ("pud/backend.run_fused", 150, 150),        # 150..300
         ("pud/scrub.verify", 330, 100),             # 330..430
         ("pud/scrub.tile", 500, 390),               # 500..890
         ("pud/session.run_fused", 510, 200),
         ("pud/backend.run_fused", 540, 150),
         ("pud/scrub.verify", 720, 100)]

#: The walk's module runs a gather, the kernel and a scatter; the
#: mismatch kernel runs in its own module; a plant runs elsewhere.
MODULES = [("jit__walk(1)", 0, 300), ("jit_mismatch_pallas(2)", 300, 100),
           ("jit_flip(3)", 500, 100)]
OPS = [("%gather.1 = u32[8] gather(x)", 10, 40),
       ("%majx_csa.3 = u32[8] custom-call(x)", 60, 20),
       ("%scatter.2 = u32[8] scatter(x)", 100, 60),
       ("%mismatch_popcount.1 = s32[] custom-call(x)", 310, 50),
       ("%scatter.9 = u32[8] scatter(x)", 510, 30)]

PEAKS = {"hbm_bytes_per_s": 819e9}


def _reading(planes, words=1000, x=3):
    import trace_reduce

    summary = trace_reduce.reduce_planes(planes, window_span="bench/window",
                                         span_prefix="bench/")
    rec = Record("scrub", 0, 1, required_bytes=(x + 1) * 4 * words,
                 elements=words)
    return Reading(Window([rec], 0.0, 1.0), setup_s=1.0, peaks=PEAKS,
                   trace=summary)


def _readers():
    return harness.resolve(harness.load_spec(), CELL, trace=True).readers


def test_readers_give_per_call_values():
    readers = _readers()
    r = _reading([host(main=SPANS), chip(0, OPS, MODULES)])
    got = {name: readers[name].read(r) for name in READERS}
    busy = 40 + 20 + 60 + 50 + 30
    assert got == pytest.approx({
        "device_idle.scrub": 100 * (1 - busy / 1000),
        "hbm_roofline.scrub": 100 * 16_000 / (819e9 * busy * 1e-9),
        "level_exec_ms.scrub": 300e-9 * 1e3,
        "verify_ms.scrub": 200e-9 * 1e3,
        "scrub_host_ms.scrub": (800 - 300 - 200) * 1e-9 * 1e3,
        "tiles_per_call.scrub": 2,
        "vote_roofline.scrub": 100 * 16_000 / (819e9 * 120e-9),
        "mismatch_roofline.scrub": 100 * 8 * 3 * 1000 / (819e9 * 50e-9)})


def test_the_bytes_functions():
    rec = Record("scrub", 0, 1, required_bytes=6 * 4 * 10, elements=10)
    assert scrub_trace.replicas_of(rec) == 5
    assert scrub_trace.vote_bytes(rec) == 240
    assert scrub_trace.mismatch_bytes(rec) == 8 * 5 * 10


def test_program_readers_are_silent_without_the_scrub_span():
    readers = _readers()
    no_scrub = [("bench/window", 0, 1000), ("pud/elementwise", 100, 800),
                ("pud/backend.run_fused", 150, 150)]
    for r in (_reading([host(main=no_scrub), chip(0, OPS, MODULES)]),
              Reading(Window([Record("scrub", 0, 1)], 0, 1), 1.0, PEAKS,
                      None)):
        for name in READERS[2:]:
            assert readers[name].read(r) is None, name


def test_a_walk_without_device_ops_reads_nothing():
    readers = _readers()
    r = _reading([host(main=SPANS)])
    for name in ("vote_roofline.scrub", "mismatch_roofline.scrub"):
        assert readers[name].read(r) is None
