"""Production mesh construction (multi-pod dry-run requirement).

``make_production_mesh`` is a FUNCTION so importing this module never touches
jax device state.  The dry-run's ``main()`` sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` and the CPU
platform before the first backend use; everything else sees the real
devices.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]):
    """Arbitrary mesh for tests/examples (e.g. (1, 1) on one CPU device).

    Axes are ``Auto``: the model code relies on GSPMD propagation (plus
    ``partitioning.constrain`` hints), not on explicit-sharding types."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def batch_axes(mesh) -> Tuple[str, ...]:
    """Axes the global batch is sharded over."""
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def fsdp_axis(mesh) -> Optional[str]:
    """Axis parameters/optimizer state are fully-sharded over (ZeRO-3)."""
    return "data" if "data" in mesh.axis_names else None


def named(mesh, *spec) -> NamedSharding:
    return NamedSharding(mesh, P(*spec))
