"""Logical-axis sharding: one rule table maps model axes to mesh axes.

Model code annotates tensors with *logical* axes (``"batch"``, ``"fsdp"``,
``"tp"``, ``"sp"``, ``"expert"``, ``"kv_batch"``); this module owns the
single mapping from those names onto the physical mesh axes (``pod``,
``data``, ``model``).  Swapping the active :class:`AxisRules` re-lays-out
the whole model without touching a single layer definition — that is how
serving flips to the activation-stationary layout (§Perf H3) and how the
elastic re-mesh path recomputes every sharding after a topology change.

Key invariants:

* **No mesh, no constraint** — outside a mesh context every helper
  degrades to a no-op / replicated sharding, so unit tests on one CPU
  device never pay a layout cost.
* **Indivisible dims replicate** — a logical axis whose mesh extent does
  not divide the tensor dim is dropped (replicated), never erroring
  (e.g. ``long_500k``'s global batch of 1 on a 16-way data axis).
* **Each physical axis is used at most once per spec** (SPMD requirement).
"""

from __future__ import annotations

import contextlib
import threading
from typing import Mapping, Optional, Sequence

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

#: Logical axis annotation: a tuple of logical names (or None) per dim.
Axes = Sequence[Optional[str]]


class AxisRules:
    """An immutable logical-axis -> physical-mesh-axes mapping."""

    def __init__(self, name: str, mapping: Mapping[str, tuple[str, ...]]):
        self.name = name
        self.mapping = dict(mapping)

    def physical(self, logical: Optional[str]) -> tuple[str, ...]:
        """Physical mesh axes a logical axis shards over ('' -> none)."""
        if logical is None:
            return ()
        return tuple(self.mapping.get(logical, ()))

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return f"AxisRules({self.name!r})"


#: Training layout: batch-family axes over the data-parallel grid
#: (pod x data), weight/tensor axes over the model grid.  ``sp`` is the
#: sequence-parallel fallback when a head count does not divide TP.
DEFAULT_RULES = AxisRules("default", {
    "batch": ("pod", "data"),
    "kv_batch": ("pod", "data"),
    "fsdp": ("pod", "data"),
    "tp": ("model",),
    "sp": ("model",),
    "expert": ("model",),
})

#: Serving layout (activation-stationary, §Perf H3): per-token activations
#: replicate (their resharding is KBs but happens every decode step) while
#: the KV cache stays sharded over the data grid (gathering it is GBs).
SERVE_RULES = AxisRules("serve", {
    "batch": (),
    "kv_batch": ("pod", "data"),
    "fsdp": ("pod", "data"),
    "tp": ("model",),
    "sp": ("model",),
    "expert": ("model",),
})


_STATE = threading.local()


def _active_rules() -> AxisRules:
    return getattr(_STATE, "rules", DEFAULT_RULES)


@contextlib.contextmanager
def use_rules(rules: AxisRules):
    """Swap the active rule table inside the context (thread-local)."""
    prev = getattr(_STATE, "rules", None)
    _STATE.rules = rules
    try:
        yield rules
    finally:
        if prev is None:
            del _STATE.rules
        else:
            _STATE.rules = prev


def _current_mesh() -> Optional[jax.sharding.AbstractMesh]:
    """The (abstract) mesh entered via ``with jax.set_mesh(mesh):``, or
    None outside any; readable inside ``jax.jit`` tracing too."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return None
    return mesh


def axis_extent(logical: str, rules: Optional[AxisRules] = None,
                mesh: Optional[Mesh] = None) -> int:
    """Product of mesh extents a logical axis shards over (1 off-mesh)."""
    mesh = mesh if mesh is not None else _current_mesh()
    if mesh is None:
        return 1
    rules = rules or _active_rules()
    extent = 1
    for a in rules.physical(logical):
        if a in mesh.axis_names:
            extent *= mesh.shape[a]
    return extent


def _spec_entries(axes: Axes, mesh: Mesh, rules: AxisRules,
                  shape: Optional[Sequence[int]] = None) -> list:
    """PartitionSpec entries for one tensor; drops unusable mappings."""
    entries: list = []
    used: set[str] = set()
    for i, logical in enumerate(axes):
        phys = [a for a in rules.physical(logical)
                if a in mesh.axis_names and a not in used]
        extent = 1
        for a in phys:
            extent *= mesh.shape[a]
        if not phys or extent <= 1:
            entries.append(None)
            continue
        if shape is not None and shape[i] % extent != 0:
            entries.append(None)  # indivisible: replicate this dim
            continue
        used.update(phys)
        entries.append(tuple(phys) if len(phys) > 1 else phys[0])
    return entries


def sharding_for(shape: Sequence[int], axes: Axes, mesh: Mesh,
                 rules: Optional[AxisRules] = None) -> NamedSharding:
    """NamedSharding for a concrete shape (indivisible dims replicate)."""
    rules = rules or _active_rules()
    return NamedSharding(
        mesh, P(*_spec_entries(tuple(axes), mesh, rules, tuple(shape))))


def _is_axes_leaf(x) -> bool:
    return isinstance(x, tuple) and all(
        a is None or isinstance(a, str) for a in x)


def tree_shardings(axes_tree, mesh: Mesh,
                   rules: Optional[AxisRules] = None):
    """Map a pytree of logical-axes tuples to NamedShardings.

    Leaves are tuples of logical names / None (the empty tuple is a
    scalar leaf -> fully replicated).  Shape-unaware: divisibility is the
    annotator's contract here (shape-aware callers use
    :func:`sharding_for`).
    """
    rules = rules or _active_rules()

    def to_sharding(axes):
        return NamedSharding(mesh, P(*_spec_entries(axes, mesh, rules)))

    return jax.tree.map(to_sharding, axes_tree, is_leaf=_is_axes_leaf)


def constraint(x: jax.Array, axes: Axes) -> jax.Array:
    """Apply a logical-axes sharding constraint (no-op outside a mesh)."""
    mesh = _current_mesh()
    if mesh is None:
        return x
    rules = _active_rules()
    entries = _spec_entries(tuple(axes), mesh, rules, x.shape)
    if all(e is None for e in entries):
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(*entries)))
