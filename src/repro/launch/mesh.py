"""Production meshes (dry-run target: TPU v5e, 256 chips/pod).

Defined as FUNCTIONS so importing this module never touches jax device
state (jax locks the device count on first backend init).

Every axis is ``AxisType.Auto``: the model code annotates shardings with
``with_sharding_constraint`` and leaves propagation to the compiler, which
explicit-sharding axes (``jax.make_mesh``'s default) would refuse.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape: tuple, axes: tuple):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def data_axes(mesh) -> tuple:
    """The batch-parallel axes of a production mesh."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def make_debug_mesh(data: int = 2, model: int = 2):
    """Small host mesh for tests (requires forced host device count)."""
    return _auto_mesh((data, model), ("data", "model"))
