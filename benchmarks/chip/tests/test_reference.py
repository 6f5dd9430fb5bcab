"""The plain reference, its control, the required-bytes function, and
the comparison rejecting a single flipped bit."""

import numpy as np
import pytest

import reference
from harness import BENCH_ROOT, load_module

elementwise = load_module(BENCH_ROOT / "drivers" / "elementwise.py")


@pytest.fixture
def rng():
    return np.random.default_rng(5)


def test_add_wraps_at_32_bits():
    a = np.array([0xFFFFFFFF, 7, 0x0000FFFF], np.uint32)
    b = np.array([1, 8, 1], np.uint32)
    np.testing.assert_array_equal(reference.add(a, b), [0, 15, 0x10000])


def test_the_control_drops_the_carry_out_of_bit_15(rng):
    a = np.array([0x0000FFFF, 0x00010000], np.uint32)
    b = np.array([1, 1], np.uint32)
    np.testing.assert_array_equal(reference.control_add(a, b),
                                  [0, 0x00010001])
    a, b = rng.integers(0, 2**32, (2, 1024), np.uint32)
    assert (reference.control_add(a, b) != reference.add(a, b)).mean() > 0.3


def test_required_bytes():
    assert elementwise.required_bytes(2**20) == 12 * 2**20


class _Sample:
    def __init__(self, items):
        self.items = items


def test_the_check_rejects_one_flipped_bit(rng):
    """The elementwise driver's comparison, fed answers with one bit
    wrong."""
    run = elementwise.ElementwiseRun.__new__(elementwise.ElementwiseRun)
    run.op, run.lanes = "add", 64
    run.pairs = [tuple(rng.integers(0, 2**32, (2, 64), np.uint32))
                 for _ in range(2)]
    good = [(j, reference.add(*run.pairs[j])) for j in (0, 1, 0)]
    run.sample = _Sample(good)
    assert run.checks() == {"wrong_lanes": {"value": 0, "limit": 0}}
    bad = good[1][1].copy()
    bad[17] ^= 1 << 31
    run.sample = _Sample(good[:1] + [(1, bad)])
    assert run.checks()["wrong_lanes"]["value"] == 1
    run.sample = _Sample([(0, good[0][1][:32])])
    assert run.checks()["wrong_lanes"]["value"] == 64
