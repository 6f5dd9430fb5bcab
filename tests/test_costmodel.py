"""The unified CostModel: one pricing authority for latency, power, and
TPU constants.

Load-bearing claims: (1) the Fig. 5 / Obs 5 power anchors are pinned
(32-row SiMRA draws 21.19 % less than REF) and W x ns = nJ exactly;
(2) the TPU machine constants have ONE source — ``repro.pud.offload``
and ``repro.launch.roofline`` re-export ``repro.core.costmodel``'s
values, and ``repro.pud.latency`` is a pure shim; (3) Program costing
delegates to COST bit-identically, preserving the historical retry
semantics (NOT/COPY energy prices one clean issue while its latency is
retry-aware); (4) offload decisions carry an energy verdict next to
the latency verdict; (5) backend dispatch scopes meter energy — zero
on the oracle, positive on sim, and ordered fused <= per-op on
pallas; (6) the serve layer threads energy into its SLO
snapshots and the sync ``serve()`` path honors ``tick_window_s``.
"""

import time

import numpy as np
import pytest

from _proptest import rand_u32
from repro.core import calibration as cal
from repro.core import power as pw
from repro.core.costmodel import COST, LAT, CostModel, majx_issue_ns
from repro.core.errormodel import ErrorModel
from repro.backends import ExecutionContext, get_backend
from repro.pud.isa import Program
from repro.serve import PudService, ServiceConfig
from repro.session import DramSession
from test_serve_service import heal_req

IDEAL = ExecutionContext(ideal=True)


# --------------------------------------------------- Fig. 5 / Obs 5 anchors


def test_obs5_simra32_vs_ref_pinned():
    """The paper's one pinned power relationship: 32-row SiMRA draws
    21.19 % less than REF (Obs 5)."""
    want = pw.STANDARD_POWER_W["REF"] * (1.0 + cal.SIMRA32_POWER_VS_REF)
    assert pw.simra_power_w(32) == pytest.approx(want, rel=1e-12)
    assert cal.SIMRA32_POWER_VS_REF == -0.2119
    assert COST.simra_power_w(32) == pw.simra_power_w(32)


def test_simra_power_monotonic_in_n_act():
    """Wordline/CSL driver load grows with asserted wordlines: power is
    strictly increasing over the measured activation counts."""
    series = [pw.simra_power_w(n) for n in cal.N_ACT_LEVELS]
    assert all(a < b for a, b in zip(series, series[1:]))
    assert pw.simra_power_w(2) > pw.STANDARD_POWER_W["ACT_PRE"]


def test_energy_is_watts_times_ns():
    """1 W held for 1 ns is exactly 1 nJ."""
    assert pw.energy_nj("REF", 12.5) == pytest.approx(1.80 * 12.5)
    assert pw.energy_nj("ACT_PRE", 1.0) == pw.STANDARD_POWER_W["ACT_PRE"]
    # CostModel's duration path is the same table.
    assert COST.energy_nj("REF", 12.5) == pw.energy_nj("REF", 12.5)
    assert COST.power_w("WR") == pw.STANDARD_POWER_W["WR"]


def test_energy_unknown_series_names_valid_ops():
    """The bugfix: a clear ValueError (not a bare KeyError) listing the
    calibrated series."""
    with pytest.raises(ValueError, match="valid ops") as ei:
        pw.energy_nj("SIMRA_3", 10.0)
    assert "SIMRA_32" in str(ei.value) and "REF" in str(ei.value)
    with pytest.raises(ValueError, match="valid ops"):
        COST.power_w("BOGUS")


def test_power_table_cached_and_copy_safe():
    """The table is built once but handed out as fresh copies: a caller
    mutating its copy cannot corrupt later pricing."""
    t1 = pw.power_table()
    t1["REF"] = 0.0
    t1["EVIL"] = 99.0
    t2 = pw.power_table()
    assert t2["REF"] == pw.STANDARD_POWER_W["REF"]
    assert "EVIL" not in t2
    assert pw.energy_nj("REF", 1.0) == pw.STANDARD_POWER_W["REF"]
    assert set(f"SIMRA_{n}" for n in cal.N_ACT_LEVELS) <= set(t2)


# ------------------------------------------------- single-source constants


def test_tpu_constants_single_source():
    """offload and roofline must re-export COST's values, never carry
    their own copies."""
    from repro.launch import roofline
    from repro.pud import offload

    assert offload.PEAK_FLOPS == roofline.PEAK_FLOPS == COST.peak_flops
    assert offload.HBM_BYTES_PER_S == roofline.HBM_BW == COST.hbm_bytes_per_s
    assert offload.KERNEL_LAUNCH_NS == COST.kernel_launch_ns
    assert roofline.ICI_BW == COST.ici_bytes_per_s
    assert COST.dispatch_overhead(3) == 3 * COST.kernel_launch_ns


def test_latency_module_is_a_shim():
    """repro.pud.latency re-exports the costmodel objects unchanged."""
    from repro.pud import latency

    assert latency.LAT is LAT
    assert latency.majx_issue_ns is majx_issue_ns
    assert latency.ROW_BITS == 65536


# ----------------------------------------------------- per-op / per-program


def test_unknown_op_kind_raises():
    with pytest.raises(ValueError, match="unknown op kind"):
        COST.latency_ns("XOR")
    with pytest.raises(ValueError, match="unknown op kind"):
        COST.energy_nj("XOR")


def test_maj_energy_is_simra_power_times_retry_latency():
    em = ErrorModel("H")
    t = COST.latency_ns("MAJ", x=3, n_act=32, errors=em)
    assert t > majx_issue_ns(3, 32)  # retries lengthen the issue
    want = pw.simra_power_w(32) * t
    assert COST.energy_nj("MAJ", x=3, n_act=32, errors=em) == \
        pytest.approx(want, rel=1e-12)


def test_support_op_energy_prices_one_clean_issue():
    """Historical §8 semantics: NOT/COPY *latency* is retry-aware but
    their *energy* charges a single clean RowClone at ACT+PRE power."""
    em = ErrorModel("H")
    clean = pw.energy_nj("ACT_PRE", LAT.rowclone)
    assert COST.energy_nj("NOT", errors=em) == pytest.approx(clean)
    assert COST.energy_nj("COPY") == pytest.approx(clean)
    assert COST.latency_ns("COPY", errors=em) > COST.latency_ns("COPY")


def test_program_costing_delegates_to_cost():
    prog = Program()
    prog.emit("WR", dsts=(0,))
    prog.emit("MAJ", x=3, n_act=32, srcs=(0, 1, 2), dsts=(3,))
    prog.emit("MRC", n_act=8, srcs=(3,), dsts=tuple(range(4, 11)))
    prog.emit("NOT", srcs=(3,), dsts=(11,))
    prog.emit("FRAC", dsts=(12,))
    prog.emit("RD", srcs=(3,))
    em = ErrorModel("H")
    assert prog.latency_ns(em) == \
        pytest.approx(COST.program_latency_ns(prog, em), rel=1e-12)
    assert prog.energy_nj(em) == \
        pytest.approx(COST.program_energy_nj(prog, em), rel=1e-12)
    per_op = sum(COST.energy_nj(op.kind, x=op.x, n_act=op.n_act, errors=em)
                 for op in prog.ops)
    assert prog.energy_nj(em) == pytest.approx(per_op, rel=1e-12)
    assert prog.energy_nj(em) > 0


def test_costmodel_replace_for_what_if():
    """Frozen dataclass: what-if variants via dataclasses.replace."""
    import dataclasses

    slow = dataclasses.replace(COST, kernel_launch_ns=4000.0)
    assert slow.dispatch_energy_nj(1) == 2 * COST.dispatch_energy_nj(1)
    assert isinstance(slow, CostModel)
    with pytest.raises(dataclasses.FrozenInstanceError):
        COST.kernel_launch_ns = 0.0


# ------------------------------------------------- offload energy verdicts


def test_offload_decision_carries_energy():
    from repro.pud.offload import plan_broadcast, plan_vote

    d = plan_vote(1 << 20)
    assert d.tpu_energy_nj > 0 and d.pud_energy_nj > 0
    assert d.winner_energy in ("pud", "tpu")
    assert d.energy_savings == \
        pytest.approx(d.tpu_energy_nj / d.pud_energy_nj)
    b = plan_broadcast(1 << 20, fanout=31)
    assert b.winner_energy in ("pud", "tpu")
    assert b.pud_energy_nj > 0


# ----------------------------------------------- backend energy metering


def test_dispatch_scope_energy_oracle_zero_sim_positive():
    rng = np.random.default_rng(0)
    planes = rand_u32(rng, 3, 4, 8)
    oracle = get_backend("oracle", IDEAL)
    with oracle.count_dispatches() as scope:
        oracle.majx(planes, n_act=32)
    assert scope.energy_nj == 0.0

    sim = get_backend("sim", IDEAL)
    with sim.count_dispatches() as scope:
        sim.majx(planes, n_act=32)
    assert scope.energy_nj > 0.0
    frozen = scope.energy_nj
    sim.majx(planes, n_act=32)  # outside the window
    assert scope.energy_nj == frozen
    sim.reset_dispatches()
    assert sim.energy_nj_total == 0.0


def test_pallas_energy_ordering_mega_fused_per_op():
    """Fusion's joule story mirrors its dispatch story: launch energy
    amortizes, so fused <= per-op, on the eager walk and the jitted one
    alike."""
    sess = DramSession("pallas")
    rng = np.random.default_rng(1)
    a = rng.integers(0, 2**32, 8, dtype=np.uint32)
    b = rng.integers(0, 2**32, 8, dtype=np.uint32)
    _, prog = sess.elementwise("add", a, b, tier=5, n_act=32)
    state = np.zeros((prog.n_rows(), 1), np.uint32)
    nj = {}
    for mode, run in (
            ("per_op", lambda: sess.run(prog, state)),
            ("fused", lambda: sess.run_fused(prog, state)),
            ("jitted", lambda: sess.run_fused(prog, state))):
        with sess.count_dispatches() as scope:
            run()
        nj[mode] = scope.energy_nj
        assert scope.energy_nj > 0
    assert nj["jitted"] == pytest.approx(nj["fused"], rel=1e-12)
    assert nj["fused"] < nj["per_op"]


# ------------------------------------------------------- serve-layer energy


def test_slo_snapshot_carries_energy():
    svc = PudService(ServiceConfig(backend="sim"))
    rng = np.random.default_rng(2)
    svc.serve([heal_req(rng)])
    snap = svc.snapshot()
    assert snap.energy_nj > 0.0
    assert snap.to_dict()["energy_nj"] == snap.energy_nj
    svc.reset_slo()
    assert svc.snapshot().energy_nj == 0.0


def test_sync_serve_honors_tick_window():
    """The bugfix: tick_window_s used to be honored only on the asyncio
    path — the sync serve() must pay the coalescing wait too."""
    window = 0.05
    svc = PudService(ServiceConfig(backend="oracle", tick_window_s=window))
    rng = np.random.default_rng(3)
    t0 = time.monotonic()
    svc.serve([heal_req(rng)])
    assert time.monotonic() - t0 >= window
