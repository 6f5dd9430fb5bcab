"""The plain reference of the TMR scrub: what a scrub must leave behind.

Straight numpy over ``uint32`` words, written from the semantics the
configuration states and from nothing of the program under test (it
imports no ``repro`` module).

* The packed layout of a leaf list: leaf after leaf in list order, each
  from a row of its own.  A row of ``ROW_WORDS`` words holds ``per *
  ROW_WORDS`` consecutive elements of its leaf (``per = 4 // itemsize``,
  the last row zero-padded): element ``k * ROW_WORDS + j`` of the row is
  bits ``8 * itemsize * k`` and up of word ``j`` (:func:`pack`,
  :func:`unpack`).  Rows past the last leaf are zero.
* A scrub votes every word of the replicas by bitwise majority and
  writes the vote into every replica; replica ``j``'s count is the bits
  in which it differed from the vote (:func:`scrub`).

The control, :func:`control_scrub`, breaks one guarantee the way a
shortcut would: it votes only every other tile of ``CONTROL_TILE_ROWS``
rows.  Put in the program's place, it must make the run come out not
correct.
"""

from __future__ import annotations

import numpy as np

#: Words per row of the packed layout.
ROW_WORDS = 4096

#: Rows per tile of the control's every-other-tile vote.
CONTROL_TILE_ROWS = 8


def leaf_rows(nbytes: int) -> int:
    """Rows a leaf of ``nbytes`` bytes takes."""
    return -(-nbytes // (4 * ROW_WORDS))


def _uint(itemsize: int):
    return np.dtype(f"<u{itemsize}")


def pack_leaf(values: np.ndarray) -> np.ndarray:
    """One leaf as ``(rows, ROW_WORDS)`` words."""
    values = np.ascontiguousarray(values)
    size = values.dtype.itemsize
    per = 4 // size
    rows = leaf_rows(values.size * size)
    x = np.zeros(rows * per * ROW_WORDS, _uint(size))
    x[:values.size] = values.reshape(-1).view(_uint(size))
    x = x.reshape(rows, per, ROW_WORDS).astype(np.uint32)
    words = np.zeros((rows, ROW_WORDS), np.uint32)
    for k in range(per):
        words |= x[:, k] << np.uint32(8 * size * k)
    return words


def leaf_offsets(leaves) -> list[int]:
    """First row of each leaf; ``leaves`` are arrays or (shape, dtype)."""
    rows, row = [], 0
    for leaf in leaves:
        shape, dtype = (leaf.shape, leaf.dtype) if hasattr(
            leaf, "dtype") else leaf
        rows.append(row)
        row += leaf_rows(int(np.prod(shape)) * np.dtype(dtype).itemsize)
    return rows


def pack(leaves) -> np.ndarray:
    """The leaf list as ``(rows, ROW_WORDS)`` words."""
    blocks = [pack_leaf(leaf) for leaf in leaves]
    return np.concatenate(blocks) if blocks else \
        np.zeros((0, ROW_WORDS), np.uint32)


def unpack(packed: np.ndarray, specs) -> list[np.ndarray]:
    """The leaves ``(shape, dtype)`` that ``packed`` holds."""
    out = []
    for row, (shape, dtype) in zip(leaf_offsets(specs), specs):
        dtype = np.dtype(dtype)
        size, n = dtype.itemsize, int(np.prod(shape))
        words = np.asarray(packed[row:row + leaf_rows(n * size)], np.uint32)
        mask = np.uint32((1 << (8 * size)) - 1)
        x = np.stack([(words >> np.uint32(8 * size * k)) & mask
                      for k in range(4 // size)], axis=1)
        out.append(x.astype(_uint(size)).reshape(-1)[:n].view(dtype)
                   .reshape(shape))
    return out


def majority(replicas) -> np.ndarray:
    """Bitwise majority of three replicas' words."""
    a, b, c = (np.asarray(r, np.uint32) for r in replicas)
    return (a & b) | (a & c) | (b & c)


def _vote(replicas, rows) -> tuple[np.ndarray, list[int]]:
    new = np.array(replicas, np.uint32)
    voted = majority(new[:, rows])
    counts = [int(np.bitwise_count(r[rows] ^ voted).sum()) for r in new]
    new[:, rows] = voted
    return new, counts


def scrub(replicas) -> tuple[np.ndarray, list[int]]:
    """Every word voted: (the replicas after, the bits each had wrong)."""
    return _vote(replicas, slice(None))


# --------------------------------------------------------------- controls
def control_scrub(replicas, first_tile: int = 0
                  ) -> tuple[np.ndarray, list[int]]:
    """Votes only tiles ``first_tile``, ``first_tile + 2``, ... of
    ``CONTROL_TILE_ROWS`` rows: the others keep their flipped bits, and
    no count sees them.  ``first_tile`` is the parity of the tile the
    replicas start at, for a caller that hands over one block at a
    time."""
    rows = np.asarray(replicas).shape[1]
    tile = np.arange(rows) // CONTROL_TILE_ROWS + first_tile
    return _vote(replicas, np.flatnonzero(tile % 2 == 0))
