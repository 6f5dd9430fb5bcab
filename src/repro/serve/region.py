"""A region of DRAM rows resident on the device, and its wipe in place.

Cold-boot-attack content destruction (SiMRA §8.2) of a whole rank: the
region is one ``(banks, bank_rows, words)`` ``uint32`` device array, each
bank a whole number of subarrays of ``subarray_rows`` rows.  It is
installed once (:func:`install`) and wiped in place with a pattern per
call (:func:`erase`); no copy of it goes to the host.

Every bank is wiped by one frozen program,
:func:`~repro.pud.secure_erase.erase_program`: each decoder group of each
subarray (:func:`~repro.pud.secure_erase.subarray_plan`) has its source
written with the pattern and is fanned out by one Multi-RowCopy to the
other rows its APA asserts.  The program is built, hashed, scheduled and
certified once, and resolved once per erase by
:meth:`~repro.session.DramSession.walk_fn`, which hands back the
backend's walk of it as a function of a bank's image: the bank's rows
with the pattern as one more row.  A bank is the tile: each bank is one
dispatch of one step (:func:`_step`), which takes the region, the
pattern and the bank's index and, inside one program, builds the bank's
image, runs the walk (the ``rowcopy_fanout`` kernel on ``pallas``) and
writes the wiped rows back.  On an accelerated backend the step is
compiled once per walk and region shape, with the region donated, so the
write-back is in place; the host replays each bank's launches on the
backend's counters.  Other backends (``oracle``, ``sim``) run the same
step eagerly.  An erase returns once the device has finished it.

Spans (:mod:`repro.obs`): ``pud/erase.tile`` around each bank, and
inside it ``pud/erase.step`` around the bank's one dispatch.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.pud.secure_erase import SUBARRAY_ROWS, erase_program, subarray_plan
from repro.serve.queue import ServeError


@dataclasses.dataclass(eq=False)
class Region:
    """``(banks, bank_rows, words)`` ``uint32`` rows resident on the
    device.

    ``rows`` is replaced (not copied) by every erase, which donates the
    old buffer; hold the region, not its array.
    """

    rows: jax.Array
    subarray_rows: int = SUBARRAY_ROWS
    tenant: str = "default"

    @property
    def banks(self) -> int:
        return int(self.rows.shape[0])

    @property
    def bank_rows(self) -> int:
        return int(self.rows.shape[1])

    @property
    def words(self) -> int:
        """Words a row."""
        return int(self.rows.shape[2])

    @property
    def size(self) -> int:
        """Words of the whole region."""
        return self.banks * self.bank_rows * self.words


def install(rows, subarray_rows: int = SUBARRAY_ROWS,
            tenant: str = "default") -> Region:
    """Hold ``rows`` as a resident region.  A device array is held as it
    is, not copied."""
    if rows.dtype != np.uint32 or rows.ndim != 3:
        raise ServeError(f"a region is (banks, bank_rows, words) uint32, "
                         f"got {rows.dtype} {rows.shape}")
    if rows.shape[1] % subarray_rows:
        raise ServeError(f"a bank of {rows.shape[1]} rows is not a whole "
                         f"number of {subarray_rows}-row subarrays")
    try:
        subarray_plan(subarray_rows)
    except ValueError as e:
        raise ServeError(str(e)) from e
    return Region(jnp.asarray(rows), subarray_rows, tenant)


def _image(rows, pattern, t):
    """Bank ``t`` with the pattern as one more row below it."""
    bank = jax.lax.dynamic_index_in_dim(rows, t, keepdims=False)
    return jnp.concatenate(
        [bank, jnp.full((1, rows.shape[2]), pattern, jnp.uint32)])


def _step(walk, rows, pattern, t):
    """Bank ``t`` of ``rows`` wiped with ``pattern`` by ``walk``."""
    image = walk(_image(rows, pattern, t))
    return jax.lax.dynamic_update_index_in_dim(rows, image[:-1], t, 0)


@functools.lru_cache(maxsize=16)
def _compiled_step(walk, shape: tuple):
    """:func:`_step` compiled for a region of ``shape``, the region
    donated."""
    step = jax.jit(functools.partial(_step, walk), donate_argnums=0)
    return step.lower(jax.ShapeDtypeStruct(shape, jnp.uint32),
                      jax.ShapeDtypeStruct((), jnp.uint32),
                      jax.ShapeDtypeStruct((), jnp.int32)).compile()


def erase(session, region: Region, pattern: int) -> None:
    """Every word of ``region`` set to ``pattern``, one step a bank (see
    the module docstring); returns once the device has finished."""
    word = np.uint32(pattern)
    prog = erase_program(region.bank_rows, region.subarray_rows)
    walk, replay = session.walk_fn(prog, region.bank_rows + 1)
    if session.capabilities().accelerated:
        step = _compiled_step(walk, tuple(region.rows.shape))
    else:
        step = functools.partial(_step, walk)
    for t in range(region.banks):
        with obs.span("erase.tile"):
            with obs.span("erase.step"):
                region.rows = step(region.rows, word, np.int32(t))
            replay(region.words)
    jax.block_until_ready(region.rows)
