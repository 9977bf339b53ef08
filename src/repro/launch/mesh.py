"""Production mesh construction (kept as functions — importing this module
never touches jax device state).

Meshes use ``Auto`` axes: ``jax.make_mesh`` defaults to ``Explicit`` axes,
under which eager scatters into sharded state (the sharded stream
backend's ``clear_rows``) and ``with_sharding_constraint`` are rejected.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes):
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def data_axes(mesh) -> tuple:
    """Axes carrying the batch dimension (DP domain)."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def model_axes(mesh) -> tuple:
    return tuple(a for a in mesh.axis_names if a == "model")
