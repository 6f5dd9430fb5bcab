"""One function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV.  Roofline rows for the LM
architectures come from prior dry-run artifacts (results/dryrun*.json),
since the dry-run needs the 512-device environment.
"""

from __future__ import annotations

import glob
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def main() -> None:
    from benchmarks import paper_figures as pf

    rows = []
    for fn in (
        pf.fig3_simra_timing,
        pf.fig4_simra_temp_vpp,
        pf.fig5_power,
        pf.fig6_maj3_timing,
        pf.fig6_cliff_adaptive,
        pf.fig7_majx_patterns,
        pf.fig8_majx_temperature,
        pf.fig9_majx_voltage,
        pf.fig10_mrc_timing,
        pf.fig11_mrc_patterns,
        pf.fig12_mrc_temp_vpp,
        pf.fig15_spice_mc,
        pf.fig16_microbench_speedups,
        pf.fig17_cold_boot,
        pf.fig18_energy_modes,
        pf.table1_devices,
    ):
        rows.extend(fn())

    for path in sorted(glob.glob(os.path.join(
            os.path.dirname(__file__), "..", "results", "dryrun*.json"))):
        try:
            for r in json.load(open(path)):
                if r.get("status") != "ok":
                    continue
                rl = r["roofline"]
                rows.append((
                    f"roofline_{r['arch']}_{r['shape']}_{r['mesh']}", 0.0,
                    f"bound={rl['bottleneck']};frac={rl['roofline_fraction']:.4f};"
                    f"mem_gb={rl['mem_per_chip_gb']:.2f}"))
        except Exception:
            pass

    print("name,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.2f},{derived}")


if __name__ == "__main__":
    main()
