"""Device meshes.

Every helper is a FUNCTION (importing this module never touches jax device
state).  ``make_production_mesh`` builds the dry-run shapes: one v5e pod =
(16, 16) over ("data", "model"); two pods = (2, 16, 16) over ("pod",
"data", "model"), the "pod" axis crossing DCN.  ``make_host_mesh`` builds
the mesh of the devices this process sees, which is what a real run uses.
"""
from __future__ import annotations

from typing import Tuple

import jax
from jax.sharding import AbstractMesh, AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...], devices=None):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def abstract_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]):
    """Device-free mesh for sharding-rule tests."""
    return AbstractMesh(tuple(shape), tuple(axes))


def host_mesh_shape(n_devices: int) -> Tuple[Tuple[int, ...], Tuple[str, ...]]:
    """(shape, axes) of the local mesh over ``n_devices``: four chips form a
    2x2 ("data", "model") mesh, so the planner can give the model axis a
    role; any other count is one "data" axis."""
    if n_devices == 4:
        return (2, 2), ("data", "model")
    return (n_devices,), ("data",)


def make_host_mesh(devices=None):
    """The mesh over ``devices`` (default: every device this process sees)."""
    devices = list(jax.devices() if devices is None else devices)
    return make_mesh(*host_mesh_shape(len(devices)), devices=devices)
