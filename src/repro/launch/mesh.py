"""Production mesh construction.

Single pod: (16, 16) = 256 chips as (data, model).
Multi-pod:  (2, 16, 16) = 512 chips as (pod, data, model); the ``pod``
axis carries only data parallelism + ZeRO sharding, so its collectives
(DP all-reduce, FSDP all-gather) are the only cross-DCN traffic — the
layout that scales past one ICI domain.

A FUNCTION, not a module-level constant: importing this module must never
touch jax device state (smoke tests run on 1 CPU device).
"""

from __future__ import annotations

import jax


def _make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    return _make_mesh(shape, axes)


def make_test_mesh(n_devices: int | None = None, model: int = 2):
    """Small mesh over available devices (tests / examples)."""
    n = n_devices or jax.device_count()
    model = min(model, n)
    return make_mesh((n // model, model), ("data", "model"))
