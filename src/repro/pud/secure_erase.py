"""Cold-boot-attack content destruction (paper §8.2).

Three strategies, exactly as the paper schedules them:

1. **RowClone-based**: WR a predetermined pattern to one row, then RowClone
   it to every other row (one op per destination row).
2. **Frac-based**: Frac every row to the neutral VDD/2 state.
3. **Multi-RowCopy-based**: WR one row, then fan it out with N-row
   activation (N in 2..32), destroying N-1 rows per op.

`destruction_time_ns` is the analytical bank-wipe model behind Fig. 17.

A Multi-RowCopy can only copy within the rows one APA asserts: the
Cartesian product of the two addresses' predecoder codes
(:meth:`~repro.core.decoder.RowDecoder.apa_activated_rows`), not a
contiguous span.  :func:`subarray_plan` is the one grouping of a
subarray into such sets; everything that wipes by MRC takes its groups
from it:

* :func:`erase_subarray` performs the wipe on the behavioural model;
* :func:`erase_program` is the addressed program that wipes a bank of
  such subarrays, which the service runs over a resident region
  (:mod:`repro.serve.region`).

Seeding: the groups of a subarray are disjoint, so no group holds the
pattern before its own MRC, and each group's source is written with the
pattern (WR) before the group is fanned out.  The Fig. 17 model counts
one WR a bank and ``ceil((rows - 1) / (N - 1))`` MRCs; the plan issues
one WR and one MRC per group (:func:`plan_time_ns`).
"""

from __future__ import annotations

import dataclasses
import functools

import jax.numpy as jnp

from repro.core import calibration as cal
from repro.core.decoder import RowDecoder
from repro.core.subarray import Subarray
from repro.core import rowcopy as rc
from repro.pud.isa import FrozenProgram, Program
from repro.pud.latency import LAT

#: rows per DDR4 bank (2^16, §7.1) and per subarray (512, Mfr H).
BANK_ROWS = 65536
SUBARRAY_ROWS = 512


def destruction_time_ns(strategy: str, n_act: int = 32,
                        bank_rows: int = BANK_ROWS) -> float:
    """Total time to overwrite every row in a bank (Fig. 17 model)."""
    if strategy == "rowclone":
        return LAT.wr_row + (bank_rows - 1) * LAT.rowclone
    if strategy == "frac":
        return bank_rows * LAT.frac
    if strategy == "mrc":
        if n_act not in cal.N_ACT_LEVELS:
            raise ValueError(f"n_act must be one of {cal.N_ACT_LEVELS}")
        # Each MRC issue wipes n_act-1 rows (the source is already wiped).
        ops = -(-(bank_rows - 1) // (n_act - 1))
        return LAT.wr_row + ops * LAT.mrc
    raise ValueError(f"unknown strategy {strategy!r}")


def speedup_over_rowclone(strategy: str, n_act: int = 32) -> float:
    return destruction_time_ns("rowclone") / destruction_time_ns(strategy, n_act)


@dataclasses.dataclass(frozen=True)
class DecoderGroup:
    """The rows ``APA(src, partner)`` asserts; ``src`` is the MRC's
    source, the other rows its destinations."""

    src: int
    partner: int
    rows: tuple[int, ...]

    @property
    def dsts(self) -> tuple[int, ...]:
        return tuple(r for r in self.rows if r != self.src)


@functools.lru_cache(maxsize=16)
def subarray_plan(rows: int = SUBARRAY_ROWS,
                  n_act: int = 32) -> tuple[DecoderGroup, ...]:
    """The decoder groups of ``n_act`` rows that partition a subarray of
    ``rows`` rows, in order of their first row: each row's group is the
    one :meth:`~repro.core.decoder.RowDecoder.pair_for_n_rows` gives for
    the lowest row not yet covered.  Raises ``ValueError`` if such
    groups cannot cover the subarray exactly."""
    decoder = RowDecoder.for_subarray(rows)
    groups, covered = [], set()
    for base in range(rows):
        if base in covered:
            continue
        rf, rs = decoder.pair_for_n_rows(n_act, base)
        group = decoder.apa_activated_rows(rf, rs)
        if len(group) != n_act or covered.intersection(group):
            raise ValueError(f"{n_act}-row groups do not partition a "
                             f"{rows}-row subarray (row {base})")
        covered.update(group)
        groups.append(DecoderGroup(rf, rs, group))
    return tuple(groups)


def plan_time_ns(rows: int = SUBARRAY_ROWS, n_act: int = 32) -> float:
    """Modelled time of one subarray's wipe by its plan: one WR of each
    group's source and one MRC per group."""
    return len(subarray_plan(rows, n_act)) * (LAT.wr_row + LAT.mrc)


def erase_subarray(sa: Subarray, pattern_word: int = 0, n_act: int = 32) -> float:
    """Functionally destroy a subarray's content with Multi-RowCopy fan-out.

    Walks the subarray's plan: each group's source is written with the
    pattern and fanned out to the rest of its group.  Returns the
    modeled wall time (ns, :func:`plan_time_ns`).
    """
    src = jnp.full((sa.n_words,), jnp.uint32(pattern_word), jnp.uint32)
    for g in subarray_plan(sa.rows, n_act):
        rc.multi_rowcopy(sa, src, n_act, base_row=g.src)
    return plan_time_ns(sa.rows, n_act)


@functools.lru_cache(maxsize=16)
def erase_program(bank_rows: int, subarray_rows: int = SUBARRAY_ROWS,
                  n_act: int = 32) -> FrozenProgram:
    """The wipe of a bank of ``bank_rows`` rows, subarray by subarray
    along :func:`subarray_plan`, over an image of ``bank_rows + 1``
    rows whose last row holds the pattern.

    Per group: a COPY of the pattern row into the group's source (the
    WR; the ISA's ``WR`` op carries no data, so the data the WR drives
    is the image's last row), then one MRC of ``n_act - 1``
    destinations.  Two dependency levels: every source, then every
    fan-out."""
    if bank_rows % subarray_rows:
        raise ValueError(f"a bank of {bank_rows} rows is not a whole "
                         f"number of {subarray_rows}-row subarrays")
    plan = subarray_plan(subarray_rows, n_act)
    prog = Program()
    for s in range(bank_rows // subarray_rows):
        base = s * subarray_rows
        for k, g in enumerate(plan):
            prog.emit("COPY", tag=f"erase/wr[{s}.{k}]", srcs=(bank_rows,),
                      dsts=(base + g.src,))
            prog.emit("MRC", n_act=n_act, tag=f"erase/mrc[{s}.{k}]",
                      srcs=(base + g.src,),
                      dsts=tuple(base + d for d in g.dsts))
    return prog.freeze()
