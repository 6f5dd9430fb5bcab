#!/usr/bin/env python3
"""One run of one benchmark cell on the chip.

    python3 benchmarks/chip/run.py --workload arith-add32-1M --seed 7 \
        --seconds 20 --trace 0

Run from the root of a checkout.  The cell is an entry of
``BENCHMARK.json``; its configuration, traffic, driver and metric readers
are files under this directory (see ``harness.py``).  The run sets up
(compile cache, data from the seed, every shape warmed), measures for
``--seconds``, checks every answer against ``reference.py``, and prints
one JSON line last on standard output.  With ``--trace 1`` the window
runs under the profiler and the line carries the per-layer metrics, the
device's busy seconds and a breakdown.  Without a TPU named in
``peaks.json``, or with fewer chips than the cell asks for, it exits 2
and prints no result.

``--control 1`` puts the reference's control (``reference.py``) in the
program's place for the whole run; such a run must come out not
correct.  It gives the upper reading of each number compared, and the
benchmark's own runs never ask for it.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402


def parse(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n")[1])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--control", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import harness

    sys.exit(harness.main(parse(), t_start=T_START))
