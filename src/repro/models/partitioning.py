"""Lightweight activation-sharding constraints for model internals.

The launch layer registers the active mesh (+ the batch axes) here; model
code calls :func:`constrain` at GSPMD decision points (MoE dispatch/combine
being the critical one — without a constraint the combine scatter tends to
come out replicated over the model axis, inflating activation memory by the
TP degree).  With no mesh registered (unit tests, single-device smoke runs)
``constrain`` is a no-op.
"""
from __future__ import annotations

import contextlib
from typing import Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_STATE = {"mesh": None, "batch_axes": ("data",)}


def set_mesh(mesh: Optional[Mesh], batch_axes: Tuple[str, ...] = ("data",)):
    _STATE["mesh"] = mesh
    _STATE["batch_axes"] = tuple(batch_axes)


@contextlib.contextmanager
def use_mesh(mesh: Optional[Mesh], batch_axes: Tuple[str, ...] = ("data",)):
    prev = (_STATE["mesh"], _STATE["batch_axes"])
    set_mesh(mesh, batch_axes)
    try:
        yield
    finally:
        set_mesh(*prev)


def batch_axes() -> Tuple[str, ...]:
    return _STATE["batch_axes"]


def axis_size(name: str) -> int:
    """Devices along the registered mesh's axis ``name`` (1 without one)."""
    mesh = _STATE["mesh"]
    if mesh is None or name not in mesh.shape:
        return 1
    return int(mesh.shape[name])


def constrain(x, *spec):
    """with_sharding_constraint(x, P(*spec)) if a mesh is registered.

    Spec entries: None, a mesh axis name, 'BATCH' (expands to the registered
    batch axes), or a tuple of axis names."""
    mesh = _STATE["mesh"]
    if mesh is None:
        return x
    resolved = []
    for s in spec:
        if s == "BATCH":
            resolved.append(_STATE["batch_axes"])
        else:
            resolved.append(s)
    try:
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, P(*resolved)))
    except Exception:
        return x
