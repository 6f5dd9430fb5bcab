"""The plain reference of the rank wipe: what an erase must leave behind.

Straight numpy over ``uint32`` words, written from the semantics the
configuration states and from nothing of the program under test (it
imports no ``repro`` module).

* A wipe with pattern ``p`` leaves every word of the region equal to
  ``p`` (:func:`erase`); :func:`wrong_words` counts the words of a
  region, read back one block at a time, that differ from it.
* The row decoder (SiMRA §7.1) splits a subarray's row address over five
  predecoders, lowest bits first: for 2^9 rows A = 1 bit and B..E = 2
  bits each, for 2^10 rows 2 bits each.  A 32-row activation latches two
  codes on every predecoder, which differ in the predecoder's lowest
  bit, and asserts their Cartesian product: the rows that agree with
  the first address on every other bit (:func:`decoder_group`).

The control, :func:`control_erase`, breaks the guarantee the way a wipe
that skips a group would: it leaves one decoder group of every
subarray, the group of the subarray's first row, as it was.  Put in the
program's place, it must make the run come out not correct.
"""

from __future__ import annotations

from typing import Iterable

import numpy as np

#: Predecoder widths (lowest bits first) per subarray row count.
PREDECODER_BITS = {512: (1, 2, 2, 2, 2), 1024: (2, 2, 2, 2, 2)}


def erase(region: np.ndarray, pattern: int) -> np.ndarray:
    """The region after a wipe with ``pattern``."""
    return np.full_like(np.asarray(region, np.uint32), np.uint32(pattern))


def wrong_words(blocks: Iterable[np.ndarray], pattern: int) -> int:
    """Words of the blocks that differ from ``pattern``."""
    return sum(int(np.count_nonzero(np.asarray(b) != np.uint32(pattern)))
               for b in blocks)


def wrong_rows(rows: np.ndarray, pattern: int) -> int:
    """Rows of ``(n, words)`` that hold a word other than ``pattern``."""
    return int(np.any(np.asarray(rows) != np.uint32(pattern), axis=1).sum())


def decoder_group(subarray_rows: int, row: int = 0) -> np.ndarray:
    """The rows of a subarray that one 32-row activation asserts
    together with ``row``."""
    low, lo = 0, 0
    for width in PREDECODER_BITS[subarray_rows]:
        low |= 1 << lo
        lo += width
    rows = np.arange(subarray_rows)
    return rows[(rows & ~low) == (row & ~low)]


# --------------------------------------------------------------- controls
def control_erase(region: np.ndarray, pattern: int,
                  subarray_rows: int) -> np.ndarray:
    """``(banks, rows, words)`` wiped but for the decoder group of every
    subarray's first row, which keeps its content."""
    region = np.asarray(region, np.uint32)
    out = erase(region, pattern)
    starts = np.arange(0, region.shape[1], subarray_rows)
    kept = (starts[:, None] + decoder_group(subarray_rows)).reshape(-1)
    out[:, kept] = region[:, kept]
    return out
