"""Puts the benchmark's directory and the program's ``src`` on the path.

Run from the root of the repository:

    python -m pytest benchmarks/chip/tests
"""

import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
for p in (BENCH, BENCH.parents[1] / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))
