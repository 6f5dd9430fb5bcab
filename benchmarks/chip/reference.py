"""The plain reference: what each call must return.

Straight numpy over ``uint32`` lanes, written from the semantics the
configurations state and from nothing of the program under test (it
imports no ``repro`` module): an ``add`` returns the lanes' sums modulo
2**32.

Each guarantee has a *control* beside it: the reference with that one
guarantee broken, in the way a shortcut would break it.  Put in the
program's place, a control must make the run come out not correct.
"""

from __future__ import annotations

import numpy as np


def add(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    return np.asarray(a, np.uint32) + np.asarray(b, np.uint32)


# --------------------------------------------------------------- controls
def control_add(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """16-bit lanes: the carry out of bit 15 is dropped."""
    a = np.asarray(a, np.uint32)
    b = np.asarray(b, np.uint32)
    lo = (a & 0xFFFF) + (b & 0xFFFF)
    hi = (a >> 16) + (b >> 16)
    return ((hi & 0xFFFF) << 16 | (lo & 0xFFFF)).astype(np.uint32)
