"""Reproduction of every SiMRA-DRAM figure/table as benchmark functions.

Each function returns a list of CSV rows (name, us_per_call, derived)
where ``derived`` carries the figure's headline quantity.  benchmarks/run.py
prints them; EXPERIMENTS.md §Paper-validation quotes them.

The characterization figures (3, 4, 6-12) are formatted views over
`repro.sweep` records: each figure's grid is a preset
:class:`~repro.sweep.spec.SweepSpec` (``repro.sweep.presets``) whose
records are produced — or loaded from the resumable store under
``results/sweeps`` — by the sweep engine.  The remaining figures
(power, SPICE, §8 case studies) are analytic models, not
characterization grids, and stay direct.
"""

from __future__ import annotations

import time

import jax
import numpy as np

from repro.session import DramSession
from repro.core import calibration as cal
from repro.core import chargeshare as cs
from repro.core import power as pw
from repro.core.errormodel import ErrorModel
from repro.pud import latency as lat
from repro.pud.secure_erase import destruction_time_ns, speedup_over_rowclone
from repro.sweep import default_root, presets, records_for, run_adaptive

#: Sweep record stores for the figure grids (resumable across runs;
#: repo-relative default shared with the CLI and make_tables).
SWEEP_ROOT = default_root()


def _records(spec):
    return records_for(spec, root=SWEEP_ROOT, progress=False)


def _timeit(fn, reps=3):
    fn()
    t0 = time.perf_counter()
    for _ in range(reps):
        fn()
    return (time.perf_counter() - t0) / reps * 1e6


# Fig 3: SiMRA success vs (t1, t2) x N -----------------------------------


def fig3_simra_timing():
    recs = sorted(_records(presets.fig3_spec()),
                  key=lambda r: (r["t1"], r["t2"], r["n_act"]))
    return [(f"fig3_simra_n{r['n_act']}_t1_{r['t1']}_t2_{r['t2']}", 0.0,
             f"success={r['success']:.4f}") for r in recs]


# Fig 4: SiMRA temperature / voltage -------------------------------------


def fig4_simra_temp_vpp():
    recs = _records(presets.fig4_spec())
    rows = [(f"fig4a_simra32_T{r['temp_c']:.0f}", 0.0,
             f"success={r['success']:.4f}")
            for r in recs if r["vpp_v"] == 2.5]
    rows += [(f"fig4b_simra32_V{r['vpp_v']:.1f}", 0.0,
              f"success={r['success']:.4f}")
             for r in recs if r["temp_c"] == 50.0]
    return rows


# Fig 5: power ------------------------------------------------------------


def fig5_power():
    rows = []
    for op, w in pw.power_table().items():
        rows.append((f"fig5_power_{op}", 0.0, f"watts={w:.3f}"))
    rows.append(("fig5_simra32_vs_ref", 0.0,
                 f"delta={pw.simra_power_w(32)/pw.STANDARD_POWER_W['REF']-1:+.4f}"))
    return rows


# Fig 6: MAJ3 vs timing x N (incl. the replication ladder) -----------------


def fig6_maj3_timing():
    spec = presets.fig6_spec()
    order = {t: i for i, t in enumerate(spec.timings)}
    recs = sorted(_records(spec),
                  key=lambda r: (order[(r["t1"], r["t2"])], r["n_act"]))
    return [(f"fig6_maj3_n{r['n_act']}_t1_{r['t1']}_t2_{r['t2']}", 0.0,
             f"success={r['success']:.4f}") for r in recs]


def fig6_cliff_adaptive():
    """Obs 7 cliff located by boundary search instead of a dense ladder.

    Runs the adaptive smoke campaign (20-step t1 ladder, MAJ3@32) and
    reports each threshold bracket plus the point economy — the
    fraction of the dense ladder the search actually executed.  The
    store is shared with dense runs of the same spec, so records here
    are byte-identical to a grid campaign's.
    """
    result = run_adaptive(presets.adaptive_smoke_spec(), root=SWEEP_ROOT)
    rows = []
    for c in result.crossings:
        if not c.crossed:
            continue
        rows.append((f"fig6_cliff_t1_at_{c.threshold:g}", 0.0,
                     f"bracket={c.lo_value[0]:g}..{c.hi_value[0]:g}ns"))
    rows.append(("fig6_cliff_economy", 0.0,
                 f"probed={result.n_probed}/{result.n_grid_points}"))
    return rows


# Fig 7: MAJX x data pattern ----------------------------------------------


def fig7_majx_patterns():
    return [(f"fig7_maj{r['x']}_{r['pattern'].replace('/', '_')}", 0.0,
             f"success={r['success']:.4f}")
            for r in _records(presets.fig7_spec())]


# Fig 8/9: MAJX temperature / voltage -------------------------------------


def fig8_majx_temperature():
    recs = _records(presets.fig8_spec())
    wanted = {(x, n) for x in (3, 5, 7, 9)
              for n in (cal.min_activation_for(x), 32)}
    recs = sorted((r for r in recs if (r["x"], r["n_act"]) in wanted),
                  key=lambda r: (r["x"], r["temp_c"], r["n_act"]))
    return [(f"fig8_maj{r['x']}_n{r['n_act']}_T{r['temp_c']:.0f}", 0.0,
             f"success={r['success']:.4f}") for r in recs]


def fig9_majx_voltage():
    return [(f"fig9_maj{r['x']}_V{r['vpp_v']:.1f}", 0.0,
             f"success={r['success']:.4f}")
            for r in _records(presets.fig9_spec())]


# Fig 10-12: Multi-RowCopy -------------------------------------------------


def fig10_mrc_timing():
    recs = sorted(_records(presets.fig10_spec()),
                  key=lambda r: (r["t1"], r["n_dest"]))
    return [(f"fig10_mrc{r['n_dest']}_t1_{r['t1']}", 0.0,
             f"success={r['success']:.5f}") for r in recs]


def fig11_mrc_patterns():
    order = {"0x00": 0, "0xFF": 1, "random": 2}
    recs = sorted(_records(presets.fig11_spec()),
                  key=lambda r: (order[r["pattern"]], r["n_dest"]))
    return [(f"fig11_mrc{r['n_dest']}_{r['pattern']}", 0.0,
             f"success={r['success']:.5f}") for r in recs]


def fig12_mrc_temp_vpp():
    recs = _records(presets.fig12_spec())
    rows = [(f"fig12a_mrc31_T{r['temp_c']:.0f}", 0.0,
             f"success={r['success']:.5f}")
            for r in recs if r["vpp_v"] == 2.5]
    rows += [(f"fig12b_mrc31_V{r['vpp_v']:.1f}", 0.0,
              f"success={r['success']:.5f}")
             for r in recs if r["temp_c"] == 50.0]
    return rows


# Fig 15: SPICE Monte-Carlo ------------------------------------------------


def fig15_spice_mc():
    key = jax.random.PRNGKey(0)
    out = cs.spice_study(key, iters=4000)
    rows = []
    for (n, pv), d in out.items():
        us = 0.0
        rows.append((f"fig15_n{n}_pv{int(pv*100)}", us,
                     f"dev={d['dev_mean']:.4f};success={d['success_rate']:.4f}"))
    gain = cs.deviation_mean(32) / cs.deviation_mean(4) - 1
    rows.append(("fig15_dev_gain_32_over_4", 0.0, f"gain={gain:+.4f}"))
    return rows


# Fig 16: the seven microbenchmarks ---------------------------------------

#: active subarrays pipelining MAJX issues (bank-level parallelism; the
#: paper schedules across 16 banks x 3 subarrays — 5 concurrently active
#: keeps the model within a DDR4 power budget, cf. Fig 5).
ACTIVE_SUBARRAYS = 5


def _microbench_time_ns(op: str, mfr: str, tier: int) -> float:
    """Analytical §8.1 model: time = max(critical path, op-issue time /
    active subarrays), with best-group success retries."""
    em = ErrorModel(mfr)
    rng = np.random.default_rng(0)
    a = rng.integers(0, 2**32, 8, dtype=np.uint32)
    b = np.maximum(rng.integers(0, 2**32, 8, dtype=np.uint32), 1)
    n_act = 4 if tier == 3 else 32
    # Programs are backend-invariant; the oracle is the cheapest compiler.
    _, prog = DramSession("oracle").elementwise(op, a, b, tier=tier,
                                                n_act=n_act)
    bg = cal.MAJX_BEST_GROUP_SUCCESS[mfr]
    bg3_baseline = cal.MAJ3_4ROW_BEST_GROUP_SUCCESS[mfr]

    def op_time(x: int) -> float:
        s = bg.get(x, 0.005) if tier > 3 else bg3_baseline
        return lat.LAT.majx_apa / max(s, 1e-3)

    total = 0.0
    crit = 0.0
    n_maj = {3: 0, 5: 0, 7: 0, 9: 0}
    for o in prog.ops:
        if o.kind == "MAJ":
            total += op_time(o.x)
            n_maj[o.x] += 1
        elif o.kind in ("NOT", "COPY"):
            total += lat.LAT.rowclone
    # critical path: the serial carry chain (adds/sub/mul/div);
    # tier>=7 halves its depth via the MAJ7 two-position skip.
    if op in ("add", "sub"):
        chain = 32
    elif op == "mul":
        chain = 32 * 32
    elif op == "div":
        chain = 33 * 32
    else:
        chain = 3
    if tier >= 7:
        chain /= 2
    worst_x = max((x for x, c in n_maj.items() if c), default=3)
    crit = chain * op_time(worst_x if tier >= 7 else 3)
    return max(crit, total / ACTIVE_SUBARRAYS)


def fig16_microbench_speedups():
    rows = []
    for mfr in ("M", "H"):
        tiers = (5, 7) if mfr == "M" else (5, 7, 9)
        speedups = {t: [] for t in tiers}
        for op in cal.MICROBENCHMARKS:
            base = _microbench_time_ns(op, mfr, tier=3)
            for t in tiers:
                sp = base / _microbench_time_ns(op, mfr, tier=t)
                speedups[t].append(sp)
                rows.append((f"fig16_{mfr}_{op}_maj{t}", 0.0,
                             f"speedup={sp:.3f}"))
        for t in tiers:
            rows.append((f"fig16_{mfr}_avg_maj{t}", 0.0,
                         f"speedup={np.mean(speedups[t]):.3f}"))
    return rows


# Fig 17: cold-boot content destruction ------------------------------------


def fig17_cold_boot():
    rows = []
    for n in (2, 4, 8, 16, 32):
        rows.append((f"fig17_mrc{n}", destruction_time_ns("mrc", n) / 1e3,
                     f"speedup={speedup_over_rowclone('mrc', n):.2f}"))
    rows.append(("fig17_frac", destruction_time_ns("frac") / 1e3,
                 f"speedup={speedup_over_rowclone('frac'):.2f}"))
    rows.append(("fig17_rowclone", destruction_time_ns("rowclone") / 1e3,
                 "speedup=1.00"))
    return rows


# Fig 18 (extension): energy per execution mode ---------------------------


def fig18_energy_modes():
    """Kill-Llama-style energy-savings view over the execution paths.

    Runs the same §8.1 programs (32-bit adder, 8-bit multiplier)
    through the ``pallas`` session's two executors — per-op and fused —
    and reports the CostModel-priced TPU-side energy each
    accrues (launch round-trips at board power + HBM traffic), next to
    the DRAM-side energy of executing the identical program in-situ
    under the Fig. 5 power model.  The headline ratios mirror the
    dispatch-reduction story in joules: fusion amortizes launch energy
    exactly as SiMRA amortizes activation energy (Obs 5 / PULSAR).
    """
    from repro.core.costmodel import COST

    session = DramSession("pallas", name="fig18")
    errors = ErrorModel("H")
    rng = np.random.default_rng(0)
    rows = []
    for wl, op, nbits, lanes in (("add32", "add", 32, 64),
                                 ("mul8", "mul", 8, 64)):
        a = rng.integers(0, 2**nbits, lanes, dtype=np.uint32)
        b = rng.integers(0, 2**nbits, lanes, dtype=np.uint32)
        if op == "mul":
            a, b = a & 0xFF, b & 0xFF
        _, prog = session.elementwise(op, a, b, tier=5, n_act=32)
        state = np.zeros((prog.n_rows(), (lanes + 31) // 32), np.uint32)
        energies = {}
        for mode, run in (
                ("per_op", lambda: session.run(prog, state)),
                ("fused", lambda: session.run_fused(prog, state))):
            with session.count_dispatches() as scope:
                run()
            energies[mode] = scope.energy_nj
            rows.append((f"fig18_{wl}_{mode}", 0.0,
                         f"energy_nj={scope.energy_nj:.1f};"
                         f"dispatches={scope.count}"))
        pud_nj = prog.energy_nj(errors)
        rows.append((f"fig18_{wl}_pud_dram", 0.0, f"energy_nj={pud_nj:.1f}"))
        rows.append((f"fig18_{wl}_savings", 0.0,
                     f"fused_vs_per_op="
                     f"{energies['per_op']/energies['fused']:.2f};"
                     f"pud_vs_per_op={energies['per_op']/pud_nj:.2f}"))
    rows.append(("fig18_dispatch_energy_nj", 0.0,
                 f"per_launch={COST.dispatch_energy_nj(1):.1f}"))
    return rows


# Table 1/2: tested devices ------------------------------------------------


def table1_devices():
    rows = []
    for (mfr, rev), d in cal.TABLE1.items():
        rows.append((f"table1_{mfr}_{rev}", 0.0,
                     f"chips={d['chips']};density={d['density']};"
                     f"subarrays={d['subarray_sizes']}"))
    return rows
