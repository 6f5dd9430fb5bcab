"""Unified execution backends for PUD operations.

One :class:`~repro.pud.isa.Program`, three interchangeable executors:

>>> from repro.backends import ExecutionContext, get_backend
>>> be = get_backend("oracle")                  # or "sim" / "pallas"
>>> out = be.majx(planes, x=3, n_act=32)
>>> copies = be.rowcopy(src, 31)
>>> bad_bits = be.mismatch(out, want)

Every backend takes the same :class:`ExecutionContext` (calibration
point, timings, temperature/voltage, tiling flags), so a
backend is a one-string config choice everywhere.  New executors
(multi-device sharded sim, compiled-TPU) register with
:func:`register_backend` and inherit every consumer for free.

This registry is the **compat layer**: consumers execute through
:class:`repro.session.DramSession` (typed row allocation, build-time
validation, compile-cached fused execution), which resolves its backend
here via :func:`resolve_backend`.  Reach for :func:`get_backend`
directly only when implementing backend-layer machinery or tests.
"""

from __future__ import annotations

from typing import Optional, Type

from repro.backends.base import Backend, Capabilities  # noqa: F401
from repro.backends.context import ExecutionContext, Timings  # noqa: F401

_REGISTRY: dict[str, Type[Backend]] = {}


def register_backend(name: str):
    """Class decorator: register a Backend implementation under a name."""

    def deco(cls: Type[Backend]) -> Type[Backend]:
        cls.name = name
        _REGISTRY[name] = cls
        return cls

    return deco


def available_backends() -> tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


def get_backend(name: str, ctx: Optional[ExecutionContext] = None) -> Backend:
    """Instantiate a registered backend with a shared ExecutionContext."""
    try:
        cls = _REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"unknown backend {name!r}; available: {available_backends()}"
        ) from None
    return cls(ctx)


def resolve_backend(backend: "str | Backend",
                    ctx: Optional[ExecutionContext] = None) -> Backend:
    """Name -> registry lookup; instance -> passed through unchanged.

    The session layer's resolution hook: ``DramSession("sim", ctx)``
    and ``DramSession(prebuilt_backend)`` both land here.  A ``ctx``
    alongside an already-constructed instance must match the instance's
    own context (a backend is constructed *under* its context; silently
    swapping would change semantics mid-flight).
    """
    if isinstance(backend, Backend):
        if ctx is not None and ctx != backend.ctx:
            raise ValueError(
                f"backend instance {backend.name!r} already carries an "
                f"ExecutionContext; pass ctx only when resolving by name")
        return backend
    return get_backend(backend, ctx)


# Register the three shipped implementations.
from repro.backends.oracle import OracleBackend  # noqa: E402
from repro.backends.pallas import PallasBackend  # noqa: E402
from repro.backends.sim import SimBackend  # noqa: E402

register_backend("oracle")(OracleBackend)
register_backend("sim")(SimBackend)
register_backend("pallas")(PallasBackend)

__all__ = [
    "Backend", "Capabilities", "ExecutionContext", "Timings",
    "available_backends", "get_backend", "register_backend",
    "resolve_backend",
]
