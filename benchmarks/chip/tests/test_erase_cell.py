"""The rank wipe cell: its configuration, its reference, whole runs at a
tiny rank on the CPU, and its readers on hand-made traces.

A sound run is correct; the run comes out not correct with the
reference's control in the program's place, and with each fault the
wipe can have planted: the state returned unchanged (the walk the step
runs hands its image back), half of each ``rowcopy`` output zeroed, and
one bit of its output flipped.
"""

import time

import numpy as np
import pytest

import erase_trace
import harness
import reference_erase
from harness import Reading, Record, Window
from repro.backends.pallas import PallasBackend
from test_program_trace import chip, host

CELL = "erase-ddr4-rank-mrc31"

READERS = ("device_idle.erase", "hbm_roofline.erase",
           "rowcopy_roofline.erase", "erase_host_ms.erase",
           "steps_per_call.erase")


def tiny():
    """2 banks of 2 subarrays of 512 rows, 32 words a row."""
    cell = harness.resolve(harness.load_spec(), CELL, trace=False)
    cell.traffic.update(lanes=2 * 2 * 512 * 32, pool=2, warm_calls=1)
    return cell


def run(cell, control=False, trace=False):
    return harness.run_cell(cell, 2**40 + 31, 0.3, trace,
                            t_start=time.perf_counter(), control=control,
                            log=lambda s: None)


# ------------------------------------------------------ configuration
def test_the_rank_is_a_whole_ddr4_rank():
    cell = harness.resolve(harness.load_spec(), CELL, trace=False)
    c = cell.config
    words = c["banks"] * c["bank_rows"] * c["words"]
    assert (c["banks"], c["bank_rows"], c["subarray_rows"], c["words"]) \
        == (16, 65536, 512, 2048)
    assert words == 2_147_483_648 and 4 * words == 8 * 2**30
    assert c["reduced"] == [] and cell.chips == 1
    assert cell.driver.required_bytes(words) == 4 * words + 4 * words // 32


def test_patterns_differ_from_the_last_in_every_byte():
    driver = tiny().driver
    rng = np.random.default_rng(0)
    prev = 0
    for _ in range(2000):
        p = driver.next_pattern(rng, prev)
        assert 0 < p < 2**32
        for k in range(4):
            byte = (p >> 8 * k) & 0xFF
            assert byte and byte != (prev >> 8 * k) & 0xFF
        prev = p


# ----------------------------------------------------------- reference
def test_the_control_keeps_one_group_of_every_subarray():
    rng = np.random.default_rng(1)
    region = rng.integers(0, 2**32, (2, 1024, 3), dtype=np.uint32)
    out = reference_erase.control_erase(region, 7, 512)
    kept = np.any(out != 7, axis=2)
    group = reference_erase.decoder_group(512)
    assert group[:8].tolist() == [0, 1, 2, 3, 8, 9, 10, 11]
    for sub in (0, 512):
        assert kept[:, sub + group].all()
    assert kept.sum() == 2 * 2 * 32
    assert reference_erase.wrong_words([out], 7) == 2 * 2 * 32 * 3
    assert reference_erase.wrong_rows(out[0], 7) == 2 * 32
    assert (reference_erase.erase(region, 7) == 7).all()


# ---------------------------------------------------------- whole runs
def test_a_sound_run_is_correct():
    out = run(tiny())
    assert out.correct, out.checks
    assert out.attempted > 0 and out.failed == 0
    assert set(out.checks) == {"wrong_words", "wrong_sampled_rows"}


def test_the_control_is_not_correct():
    out = run(tiny(), control=True)
    assert not out.correct
    assert out.checks["wrong_words"]["value"] > 0
    assert out.checks["wrong_sampled_rows"]["value"] > 0


def state_unchanged(mp):
    mp.setattr(PallasBackend, "_walk", lambda self, walk, state: state)


def half_the_copies(mp):
    rowcopy = PallasBackend.rowcopy

    def rowcopy_half(self, src, n_dst):
        out = rowcopy(self, src, n_dst)                     # (n, rows, words)
        return out.at[:, out.shape[1] // 2:].set(0)

    mp.setattr(PallasBackend, "rowcopy", rowcopy_half)


def copied_bit_flipped(mp):
    rowcopy = PallasBackend.rowcopy

    def rowcopy_flip(self, src, n_dst):
        out = rowcopy(self, src, n_dst)
        return out.at[0, 0, 0].set(out[0, 0, 0] ^ 1)

    mp.setattr(PallasBackend, "rowcopy", rowcopy_flip)


FAULTS = {"state_unchanged": state_unchanged,
          "half_the_copies": half_the_copies,
          "copied_bit_flipped": copied_bit_flipped}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_fault_is_not_correct(fault, monkeypatch):
    FAULTS[fault](monkeypatch)
    out = run(tiny())
    assert not out.correct, (fault, out.checks)


def test_a_traced_run_reads_the_host_metrics():
    """On the CPU the trace has no device lines: the two readers of host
    spans read, and the device readers are silent."""
    cell = tiny()
    traced = harness.resolve(harness.load_spec(), CELL, trace=True)
    cell.metrics, cell.readers = traced.metrics, traced.readers
    assert {m["name"] for m in cell.metrics} == set(READERS)
    out = run(cell, trace=True)
    assert out.correct, out.checks
    m = {k: v["value"] for k, v in out.metrics.items()}
    assert set(m) == {"erase_host_ms.erase", "steps_per_call.erase"}
    assert m["steps_per_call.erase"] == 2 and m["erase_host_ms.erase"] > 0


# ------------------------------------------------------------- readers
#: One erase call of two banks in a window of 0..1000 ns.
SPANS = [("bench/window", 0, 1000),
         ("pud/service.erase", 100, 800),            # 100..900
         ("pud/erase.tile", 110, 390),
         ("pud/erase.step", 120, 300),
         ("pud/erase.tile", 500, 390),
         ("pud/erase.step", 510, 300)]

#: The step's module runs the image, the kernel and the scatter; a
#: read-back runs elsewhere.
MODULES = [("jit__step(1)", 0, 400), ("jit_take(2)", 500, 100)]
OPS = [("%concatenate.1 = u32[8] concatenate(x)", 10, 40),
       ("%rowcopy_fanout.1 = u32[8] custom-call(x)", 60, 20),
       ("%fusion.3 = u32[8] scatter(x)", 100, 60),
       ("%rowcopy_fanout.2 = u32[8] custom-call(x)", 200, 30),
       ("%gather.9 = u32[8] gather(x)", 510, 30)]

PEAKS = {"hbm_bytes_per_s": 819e9}


def _reading(planes, words=32 * 1000):
    import trace_reduce

    summary = trace_reduce.reduce_planes(planes, window_span="bench/window",
                                         span_prefix="bench/")
    rec = Record("erase", 0, 1, required_bytes=4 * words + 4 * words // 32,
                 elements=words)
    return Reading(Window([rec], 0.0, 1.0), setup_s=1.0, peaks=PEAKS,
                   trace=summary)


def _readers():
    return harness.resolve(harness.load_spec(), CELL, trace=True).readers


def test_readers_give_per_call_values():
    readers = _readers()
    r = _reading([host(main=SPANS), chip(0, OPS, MODULES)])
    got = {name: readers[name].read(r) for name in READERS}
    busy = 40 + 20 + 60 + 30 + 30
    words = 32 * 1000
    assert got == pytest.approx({
        "device_idle.erase": 100 * (1 - busy / 1000),
        "hbm_roofline.erase": 100 * (4 * words + 4 * words // 32)
        / (819e9 * busy * 1e-9),
        "rowcopy_roofline.erase": 100 * 4 * words / (819e9 * 50e-9),
        "erase_host_ms.erase": 800e-9 * 1e3,
        "steps_per_call.erase": 2})


def test_the_bytes_function():
    rec = Record("erase", 0, 1, elements=32 * 10)
    assert erase_trace.rowcopy_bytes(rec) == 4 * (10 + 31 * 10)


def test_readers_are_silent_without_the_erase_span():
    readers = _readers()
    no_erase = [("bench/window", 0, 1000), ("pud/service.scrub", 100, 800),
                ("pud/erase.step", 150, 150)]
    for r in (_reading([host(main=no_erase), chip(0, OPS, MODULES)]),
              Reading(Window([Record("erase", 0, 1)], 0, 1), 1.0, PEAKS,
                      None)):
        for name in READERS[2:]:
            assert readers[name].read(r) is None, name


def test_a_wipe_without_device_ops_reads_no_roofline():
    r = _reading([host(main=SPANS)])
    assert _readers()["rowcopy_roofline.erase"].read(r) is None
