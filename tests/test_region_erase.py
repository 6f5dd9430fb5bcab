"""Resident regions and their in-place wipe (``repro.serve.region``),
along the row decoder's groups (``repro.pud.secure_erase``), against the
benchmark's plain numpy reference (``benchmarks/chip/reference_erase.py``).

The rank is tiny: 2 banks of 4 subarrays of 512 rows, 128 words a row,
seeded random content.
"""

import glob
import os
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.compile import build_schedule
from repro.core import rowcopy as rc
from repro.core.decoder import RowDecoder
from repro.core.subarray import Subarray
from repro.pud.secure_erase import (erase_program, erase_subarray,
                                    subarray_plan)
from repro.serve import (PudService, RegionEraseRequest, ServeError,
                         ServiceConfig)
from repro.serve import region as region_mod

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "benchmarks"
                       / "chip"))
import reference_erase  # noqa: E402

BANKS, SUBARRAYS, SUB, WORDS = 2, 4, 512, 128


def random_rank(seed):
    rng = np.random.default_rng(seed)
    return rng.integers(0, 2**32, (BANKS, SUBARRAYS * SUB, WORDS),
                        dtype=np.uint32)


# ----------------------------------------------------------------- plan
@pytest.mark.parametrize("rows,n_act", [(512, 32), (512, 8), (1024, 32),
                                        (1024, 2)])
def test_the_plan_partitions_a_subarray(rows, n_act):
    plan = subarray_plan(rows, n_act)
    decoder = RowDecoder.for_subarray(rows)
    assert len(plan) == rows // n_act
    covered = [r for g in plan for r in g.rows]
    assert sorted(covered) == list(range(rows))
    for g in plan:
        assert g.rows == decoder.apa_activated_rows(g.src, g.partner)
        assert g.src in g.rows and len(g.dsts) == n_act - 1
    if (rows, n_act) == (512, 32):
        assert plan[0].rows[:8] == (0, 1, 2, 3, 8, 9, 10, 11)
        assert plan[1].rows[:8] == (4, 5, 6, 7, 12, 13, 14, 15)


def test_the_reference_decoder_groups_are_the_plans():
    for rows in (512, 1024):
        for g in subarray_plan(rows):
            np.testing.assert_array_equal(
                reference_erase.decoder_group(rows, g.src), g.rows)


def test_every_mrc_is_one_decoder_group_of_one_subarray():
    """The bank program: a WR (a COPY from the pattern row) of each
    group's source, then one MRC of 31 destinations per group, at
    ``n_act`` 32, inside one subarray; its MRC row sets are the
    decoder's groups, subarray by subarray."""
    rows = SUBARRAYS * SUB
    prog = erase_program(rows, SUB)
    plan = subarray_plan(SUB)
    mrcs = [op for op in prog.ops if op.kind == "MRC"]
    copies = [op for op in prog.ops if op.kind == "COPY"]
    assert len(mrcs) == len(copies) == SUBARRAYS * len(plan)
    assert len(prog.ops) == len(mrcs) + len(copies)
    got = set()
    for op in mrcs:
        assert op.n_act == 32 and len(op.srcs) == 1 and len(op.dsts) == 31
        s = op.srcs[0] // SUB
        local = sorted(r - s * SUB for r in op.srcs + op.dsts)
        assert all(0 <= r < SUB for r in local)
        got.add((s, op.srcs[0] - s * SUB, tuple(local)))
    want = {(s, g.src, g.rows) for s in range(SUBARRAYS) for g in plan}
    assert got == want
    assert {op.srcs for op in copies} == {(rows,)}
    assert sorted(op.dsts[0] for op in copies) == \
        sorted(op.srcs[0] for op in mrcs)
    sched = build_schedule(prog)
    assert [[(g.kind, g.param, len(g.ops)) for g in level]
            for level in sched.levels] == [[("COPY", 0, len(copies))],
                                           [("MRC", 31, len(mrcs))]]


# ------------------------------------------------------------- service
@pytest.mark.parametrize("backend", ["pallas", "oracle", "sim"])
def test_erase_equals_the_reference(backend):
    """Seeded random content, two wipes: every word holds the last
    pattern, as the reference says; ``pallas`` runs a jitted step a
    bank, ``oracle`` and ``sim`` the same step eagerly."""
    svc = PudService(ServiceConfig(backend=backend, pool_size=1))
    content = random_rank(1)
    region = svc.install_region(jnp.asarray(content), tenant="t")
    for pattern in (0xA5A5A5A5, 0x3C3C3C3C):
        result = svc.erase(region, pattern)
        np.testing.assert_array_equal(
            np.asarray(region.rows),
            reference_erase.erase(content, pattern))
    assert (result.banks, result.words) == (BANKS, content.size)
    assert svc.snapshot().tenants["t"]["completed"] == 2


def test_erase_counts_one_launch_a_bank_and_stays_on_the_device():
    """``pallas``: one MRC launch a bank on the backend's counters (the
    COPY level has no kernel), priced as the kernel's bytes; the region
    never leaves the device in a call."""
    svc = PudService(ServiceConfig(backend="pallas", pool_size=1))
    region = svc.install_region(jnp.asarray(random_rank(2)))
    backend = svc.sessions[0].backend
    for _ in range(2):
        with backend.count_dispatches() as scope:
            with jax.transfer_guard_device_to_host("disallow"):
                svc.erase(region, 0x0F0F0F0F)
        assert scope.count == BANKS and scope.energy_nj > 0
    assert svc.cache.stats.misses == 1


def test_erase_subarray_and_the_service_leave_the_same_subarray(
        monkeypatch):
    """The behavioural wipe and the service's, from one content: the
    same groups, and the same subarray after."""
    pattern = 0x5A5AA5A5
    content = random_rank(3)[0, :SUB]
    sa = Subarray(cols=WORDS * 32, ideal=True)
    sa.planes = jnp.asarray(content)
    issued = []
    mrc = rc.multi_rowcopy

    def spy(sa, src_data, n_act, **kw):
        rf, dests = mrc(sa, src_data, n_act, **kw)
        issued.append((rf, dests))
        return rf, dests

    monkeypatch.setattr(rc, "multi_rowcopy", spy)
    erase_subarray(sa, pattern)
    svc = PudService(ServiceConfig(backend="pallas", pool_size=1))
    region = svc.install_region(jnp.asarray(content[None]))
    svc.erase(region, pattern)
    np.testing.assert_array_equal(np.asarray(sa.planes),
                                  np.asarray(region.rows[0]))
    assert (np.asarray(sa.planes) == pattern).all()
    prog = erase_program(SUB, SUB)
    assert issued == [(op.srcs[0], op.dsts) for op in prog.ops
                      if op.kind == "MRC"]


def test_erase_spans_nest_inside_the_service_call(tmp_path):
    svc = PudService(ServiceConfig(backend="pallas", pool_size=1))
    region = svc.install_region(jnp.asarray(random_rank(4)))
    svc.erase(region, 1)
    jax.profiler.start_trace(str(tmp_path))
    try:
        svc.erase(region, 2)
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
    assert names.count("service.erase") == 1
    for name in ("erase.tile", "erase.step"):
        assert names.count(name) == BANKS, name
    for name in ("session.run_fused", "backend.run_fused"):
        assert names.count(name) == 0, name
    (call,) = [e for e in events if e[0] == "service.erase"]
    assert all(call[1] <= s and e <= call[2] for _, s, e in events)
    tiles = [(s, e) for n, s, e in events if n == "erase.tile"]
    for n, s, e in events:
        if n == "erase.step":
            assert sum(ts <= s and e <= te for ts, te in tiles) == 1


def test_a_region_and_a_pattern_are_checked():
    svc = PudService(ServiceConfig(backend="oracle", pool_size=1))
    with pytest.raises(ServeError, match="uint32"):
        svc.install_region(jnp.zeros((1, 512, 4), jnp.int32))
    with pytest.raises(ServeError, match="uint32"):
        svc.install_region(jnp.zeros((512, 4), jnp.uint32))
    with pytest.raises(ServeError, match="whole number"):
        svc.install_region(jnp.zeros((1, 700, 4), jnp.uint32))
    region = svc.install_region(jnp.zeros((1, 512, 4), jnp.uint32))
    with pytest.raises(ServeError, match="required"):
        RegionEraseRequest()
    for bad in (-1, 2**32):
        with pytest.raises(ServeError, match="uint32"):
            svc.erase(region, bad)
    assert isinstance(region, region_mod.Region) and region.size == 2048
