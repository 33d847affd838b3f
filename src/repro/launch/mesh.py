"""Production meshes. Defined as FUNCTIONS so importing this module never
touches jax device state (the dry-run sets the fake-device count before
any jax initialization)."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape, axes):
    # Auto axes: the model code shards by annotation (with_sharding_
    # constraint), which Explicit axes, jax.make_mesh's default, reject
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_debug_mesh(n_data: int = 2, n_model: int = 2):
    """Small mesh for subprocess multi-device tests (8 host devices)."""
    return _mesh((n_data, n_model), ("data", "model"))
