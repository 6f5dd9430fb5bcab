"""Whole runs of each cell at a tiny size on the CPU, without the
harness's look for a chip: a sound run is correct, and the run comes out
not correct with the reference's control in the program's place, and
with each fault a single-chip cell can have planted in the timed path:

* a step that returns its state unchanged (the fused run returns the
  image it was given);
* half of the batch left out (each kernel computes the first half of its
  rows);
* an answer altered where it is produced (one bit of a kernel's output
  flipped).

The cells run on one chip each, so there is no exchange between chips to
leave out.
"""

import time

import jax.numpy as jnp
import pytest

import harness
from repro.backends.pallas import PallasBackend

#: Every cell of the spec.
CELLS = tuple(w["name"] for w in harness.load_spec()["workloads"])


def tiny(name: str) -> harness.Cell:
    cell = harness.resolve(harness.load_spec(), name, trace=False)
    cell.traffic.update(lanes=4096, pool=2, warm_calls=1)
    return cell


def run(name: str, control: bool = False) -> harness.Outcome:
    return harness.run_cell(tiny(name), 2**40 + 17, 0.3, False,
                            t_start=time.perf_counter(), control=control,
                            log=lambda s: None)


def state_unchanged(mp):
    mp.setattr(PallasBackend, "run_fused",
               lambda self, program, state, **kw: jnp.asarray(state,
                                                              jnp.uint32))


def half_batch(mp):
    majx, rowcopy = PallasBackend.majx, PallasBackend.rowcopy

    def majx_half(self, planes, x=None, n_act=None):
        out = majx(self, planes, x, n_act)                  # (rows, words)
        return out.at[out.shape[0] // 2:].set(0)

    def rowcopy_half(self, src, n_dst):
        out = rowcopy(self, src, n_dst)                     # (n, rows, words)
        return out.at[:, out.shape[1] // 2:].set(0)

    mp.setattr(PallasBackend, "majx", majx_half)
    mp.setattr(PallasBackend, "rowcopy", rowcopy_half)


def answer_altered(mp):
    majx, rowcopy = PallasBackend.majx, PallasBackend.rowcopy

    def majx_flip(self, planes, x=None, n_act=None):
        out = majx(self, planes, x, n_act)
        return out.at[0, 0].set(out[0, 0] ^ 1)

    def rowcopy_flip(self, src, n_dst):
        out = rowcopy(self, src, n_dst)
        return out.at[0, 0, 0].set(out[0, 0, 0] ^ 1)

    mp.setattr(PallasBackend, "majx", majx_flip)
    mp.setattr(PallasBackend, "rowcopy", rowcopy_flip)


FAULTS = {"state_unchanged": state_unchanged, "half_batch": half_batch,
          "answer_altered": answer_altered}


@pytest.mark.parametrize("name", CELLS)
def test_a_sound_run_is_correct(name):
    out = run(name)
    assert out.correct, out.checks
    assert out.attempted > 0 and out.failed == 0


@pytest.mark.parametrize("name", CELLS)
def test_the_control_is_not_correct(name):
    out = run(name, control=True)
    assert not out.correct
    assert any(c["value"] > c["limit"] for c in out.checks.values())


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("name", CELLS)
def test_a_fault_is_not_correct(name, fault, monkeypatch):
    FAULTS[fault](monkeypatch)
    out = run(name)
    assert not out.correct, (fault, out.checks)
