"""Replica sets resident on the device, and their tile-by-tile scrub.

Triple (or X-fold) modular redundancy for a served model's weights
(SiMRA §8.1): the params pytree is packed once into the service's
layout and held as ``x`` device arrays, one per replica, each
``(rows, ROW_WORDS)`` ``uint32``.  Every leaf starts on a row of its own
(its last row zero-padded), so a leaf's words are a row range and
:func:`unpack` takes one leaf without touching the others.  A row holds
``per * ROW_WORDS`` consecutive elements of its leaf, ``per = 4 //
itemsize``: element ``k * ROW_WORDS + j`` of the row sits in bits
``8 * itemsize * k`` of word ``j``, so packing and unpacking move
lane-aligned slices and never shuffle lanes.  The row count is a whole
number of tiles.

A scrub votes the replicas tile by tile.  Every tile has one shape, so
one frozen :class:`~repro.pud.isa.Program` of ``tile_rows`` MAJ ops
(:func:`tile_program`) serves every tile of every scrub: built, hashed,
scheduled and certified once, and resolved once per scrub by
:meth:`~repro.session.DramSession.walk_fn`, which hands back the
backend's walk of it as a function of the tile's image.  Each tile is
then one dispatch of one jitted step (:func:`_step`), which takes the
replicas, a ``(tiles, x)`` ``uint32`` count buffer and the tile's index,
donates the first two, and inside one program:

* builds the tile's image, the ``x`` replica tiles stacked above a
  zeroed output group (:func:`_tile_image`);
* runs the walk (the ``majx_csa`` kernel on ``pallas``);
* writes the voted rows back into every replica, in place
  (:func:`_commit`);
* counts each replica's wrong bits against the vote with the backend's
  ``mismatch`` (``mismatch_popcount`` on ``pallas``) into the buffer's
  row for the tile.

The host replays each tile's kernel launches on the backend's counters
(one per schedule group of the walk, one per replica's mismatch), and
reads the count buffer once per scrub, summing it in ``int64``: a tile's
count fits ``uint32``, a whole scrub's need not.  Working memory beyond
the replicas is a few tile images.  A backend whose ops cannot be traced
(``Capabilities.device_model``) runs the same step eagerly.

``TILE_ROWS`` is a constant, not a knob: 1,024 rows of 4,096 words
(16 MiB a replica tile).  A replica set smaller than a tile gets one
tile of its own size, rounded up to whole sublanes.

Spans (:mod:`repro.obs`): ``pud/scrub.tile`` around each tile, and
inside it ``pud/scrub.step`` around the tile's one dispatch.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import calibration as cal
from repro.kernels import tiling
from repro.pud.isa import FrozenProgram, Program

#: Words per row of the packed layout.
ROW_WORDS = tiling.MAX_BLOCK_C

#: Rows per scrub tile (see the module docstring).
TILE_ROWS = 1024


@dataclasses.dataclass(frozen=True)
class LeafSlot:
    """Where one leaf lives in a replica: its first row and its words."""

    row: int
    words: int
    shape: tuple
    dtype: np.dtype

    @property
    def rows(self) -> int:
        return -(-self.words // ROW_WORDS)


@dataclasses.dataclass(frozen=True)
class PackedLayout:
    """The packed layout of one pytree structure (see module docstring)."""

    treedef: object
    slots: tuple[LeafSlot, ...]
    rows: int
    tile_rows: int

    @property
    def words(self) -> int:
        """Words that hold leaf bits (row padding left out)."""
        return sum(s.words for s in self.slots)

    @property
    def tiles(self) -> int:
        return self.rows // self.tile_rows


def layout_of(tree) -> PackedLayout:
    """The layout ``tree`` packs into, from leaf shapes and dtypes alone."""
    leaves, treedef = jax.tree.flatten(tree)
    slots, row = [], 0
    for leaf in leaves:
        shape, dtype = tuple(np.shape(leaf)), jnp.dtype(leaf.dtype)
        words = -(-int(np.prod(shape)) * dtype.itemsize // 4)
        slot = LeafSlot(row, words, shape, dtype)
        slots.append(slot)
        row += slot.rows
    sub = tiling.VPU_SUBLANES
    tile = min(TILE_ROWS, -(-max(row, 1) // sub) * sub)
    return PackedLayout(treedef, tuple(slots), -(-max(row, 1) // tile) * tile,
                        tile)


#: The unsigned type of each element size.
_UINT = {1: jnp.uint8, 2: jnp.uint16, 4: jnp.uint32}


def _to_rows(bits) -> jax.Array:
    """A leaf's :func:`_bits` as ``(rows, ROW_WORDS)`` words (see the
    module docstring).

    Each row's ``per`` lane-aligned slices of elements are shifted into
    place and or-ed: no lane shuffle and no trailing axis of size
    ``per``, which the chip would pad to 128 lanes.
    """
    size = bits.dtype.itemsize
    per = 4 // size
    x = bits.reshape(-1)
    m = -(-x.size // (per * ROW_WORDS))
    x = jnp.pad(x, (0, m * per * ROW_WORDS - x.size)).reshape(
        m, per * ROW_WORDS)
    words = x[:, :ROW_WORDS].astype(jnp.uint32)
    for k in range(1, per):
        words |= x[:, k * ROW_WORDS:(k + 1) * ROW_WORDS].astype(
            jnp.uint32) << (8 * size * k)
    return words


def _from_rows(words: jax.Array, slot: LeafSlot) -> jax.Array:
    """Inverse of :func:`_to_rows`."""
    size = slot.dtype.itemsize
    per, utype = 4 // size, _UINT[size]
    x = jnp.concatenate([(words >> (8 * size * k)).astype(utype)
                         for k in range(per)], axis=1)
    n = int(np.prod(slot.shape))
    return jax.lax.bitcast_convert_type(x.reshape(-1)[:n].reshape(slot.shape),
                                        slot.dtype)


def _bits(leaf):
    """A leaf as unsigned integers of its width.  Float bits never pass
    through a float op (a reshape may copy through one), which on a TPU
    flushes subnormals and rewrites NaN payloads: a host leaf is viewed
    before it leaves the host, a device leaf bitcast before anything
    else."""
    if leaf.dtype.itemsize not in _UINT:
        raise TypeError(f"cannot pack a leaf of dtype {leaf.dtype}: "
                        f"1, 2 or 4 bytes an element")
    utype = _UINT[leaf.dtype.itemsize]
    if isinstance(leaf, np.ndarray):
        return leaf.view(np.dtype(utype))
    return jax.lax.bitcast_convert_type(leaf, utype)


@functools.partial(jax.jit, donate_argnums=0)
def _place(packed, bits, row):
    return jax.lax.dynamic_update_slice(packed, _to_rows(bits), (row, 0))


def pack(tree, layout: PackedLayout) -> jax.Array:
    """``tree`` in ``layout`` on the device, leaf by leaf: a host leaf
    goes to the device on its own, and no copy of the whole tree is
    made."""
    leaves, treedef = jax.tree.flatten(tree)
    if treedef != layout.treedef or any(
            tuple(np.shape(leaf)) != s.shape or jnp.dtype(leaf.dtype)
            != s.dtype for leaf, s in zip(leaves, layout.slots)):
        raise ValueError("the tree does not match the layout's structure, "
                         "shapes and dtypes")
    packed = jnp.zeros((layout.rows, ROW_WORDS), jnp.uint32)
    for leaf, slot in zip(leaves, layout.slots):
        if slot.words:
            packed = _place(packed, _bits(leaf), slot.row)
    return packed


@functools.partial(jax.jit, static_argnums=(2,))
def _take(packed, row, slot: LeafSlot):
    block = jax.lax.dynamic_slice(packed, (row, 0), (slot.rows, ROW_WORDS))
    return _from_rows(block, slot)


def unpack(packed: jax.Array, layout: PackedLayout):
    """The pytree one packed replica holds."""
    leaves = [_take(packed, s.row, dataclasses.replace(s, row=0))
              if s.words else jnp.zeros(s.shape, s.dtype)
              for s in layout.slots]
    return jax.tree.unflatten(layout.treedef, leaves)


@dataclasses.dataclass(eq=False)
class ReplicaSet:
    """``x`` packed replicas of one pytree, resident on the device.

    ``replicas`` is replaced (not copied) by every scrub, which donates
    the old buffers; hold the set, not its arrays.
    """

    replicas: tuple
    layout: PackedLayout
    tenant: str = "default"

    @property
    def x(self) -> int:
        return len(self.replicas)


def install(trees: Sequence, tenant: str = "default") -> ReplicaSet:
    """Pack each of ``trees`` (one structure) as a replica."""
    layout = layout_of(trees[0])
    return ReplicaSet(tuple(pack(t, layout) for t in trees), layout,
                      tenant)


@functools.lru_cache(maxsize=16)
def tile_program(x: int, tile_rows: int) -> FrozenProgram:
    """One MAJX per row of a tile: rows ``j * tile_rows + r`` of the
    ``x`` replica groups vote into row ``x * tile_rows + r``."""
    n_act = cal.min_activation_for(max(max(cal.N_ACT_LEVELS), x))
    prog = Program()
    for r in range(tile_rows):
        prog.emit("MAJ", x=x, n_act=n_act, tag=f"serve/scrub/row[{r}]",
                  srcs=tuple(j * tile_rows + r for j in range(x)),
                  dsts=(x * tile_rows + r,))
    return prog.freeze()


def _tile_image(replicas, start, tile_rows: int):
    """The ``x`` replica tiles from row ``start``, stacked above a zeroed
    output group."""
    tiles = [jax.lax.dynamic_slice_in_dim(r, start, tile_rows)
             for r in replicas]
    return jnp.concatenate(tiles + [jnp.zeros_like(tiles[0])])


def _commit(replicas, image, start):
    """The voted rows of a tile's image written into every replica;
    returns the replicas, each replica's tile as it was, and the vote."""
    x = len(replicas)
    tile = image.shape[0] // (x + 1)
    *before, voted = (image[j * tile:(j + 1) * tile] for j in range(x + 1))
    return (tuple(jax.lax.dynamic_update_slice_in_dim(r, voted, start, 0)
                  for r in replicas), before, voted)


def _step(walk, mismatch, tile_rows: int, replicas, counts, t):
    """Tile ``t`` voted by ``walk`` and written back into every replica;
    row ``t`` of ``counts`` set to each replica's wrong bits."""
    start = t * tile_rows
    image = walk(_tile_image(replicas, start, tile_rows))
    replicas, before, voted = _commit(replicas, image, start)
    row = jnp.stack([mismatch(b, voted) for b in before]).astype(jnp.uint32)
    return replicas, jax.lax.dynamic_update_slice(counts, row[None], (t, 0))


@functools.lru_cache(maxsize=16)
def _compiled_step(walk, backend, shape: tuple, x: int, tile_rows: int,
                   tiles: int):
    """:func:`_step` compiled for ``x`` replicas of ``shape``, with the
    replicas and the count buffer donated, and the replay of one call's
    mismatch launches.  Traced on the backend's twin, so the launches
    the trace makes are replayed, not counted."""
    twin, replay = backend.tracer()
    step = jax.jit(functools.partial(_step, walk, twin.mismatch, tile_rows),
                   donate_argnums=(0, 1))
    compiled = step.lower(
        (jax.ShapeDtypeStruct(shape, jnp.uint32),) * x,
        jax.ShapeDtypeStruct((tiles, x), jnp.uint32),
        jax.ShapeDtypeStruct((), jnp.int32)).compile()
    return compiled, replay


def _sum_counts(counts) -> tuple[int, ...]:
    """Each replica's total over a ``(tiles, x)`` count buffer, summed in
    ``int64`` (the total can pass ``2**32``)."""
    return tuple(int(c) for c in np.asarray(counts, np.int64).sum(0))


def _counted_eagerly() -> None:
    """An eager step's ops count themselves: nothing to replay."""


def scrub(session, rs: ReplicaSet) -> tuple[int, ...]:
    """Vote every tile of ``rs``, one step a tile (see the module
    docstring), and write the votes back into every replica; returns the
    bits each replica had wrong."""
    x, tile, tiles = rs.x, rs.layout.tile_rows, rs.layout.tiles
    walk, replay_walk = session.walk_fn(tile_program(x, tile), (x + 1) * tile)
    if session.capabilities().device_model:
        step = functools.partial(_step, walk, session.mismatch, tile)
        replay_mismatch = _counted_eagerly
    else:
        step, replay_mismatch = _compiled_step(
            walk, session.backend, rs.replicas[0].shape, x, tile, tiles)
    counts = jnp.zeros((tiles, x), jnp.uint32)
    for t in range(tiles):
        with obs.span("scrub.tile"):
            with obs.span("scrub.step"):
                rs.replicas, counts = step(rs.replicas, counts, np.int32(t))
            replay_walk(ROW_WORDS)
            replay_mismatch()
    return _sum_counts(counts)


def plan(session, rs: ReplicaSet):
    """The offload planner's verdict for one tile's vote (advisory)."""
    from repro.pud.offload import plan_program

    prog = tile_program(rs.x, rs.layout.tile_rows)
    return plan_program(prog, ROW_WORDS * 4, ctx=session.ctx,
                        sched=session.schedule_for(prog))
