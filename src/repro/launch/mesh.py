"""Production mesh construction.

A function, not a module-level constant, so importing this module never
touches jax device state (smoke tests must keep seeing 1 CPU device).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh_kwargs(n_axes: int) -> dict:
    return {"axis_types": (AxisType.Auto,) * n_axes}


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, **_mesh_kwargs(len(axes)))


def make_local_mesh():
    """Single-device mesh for CPU smoke runs of the distributed code path."""
    n = jax.device_count()
    return jax.make_mesh((n, 1), ("data", "model"), **_mesh_kwargs(2))


def batch_axes(mesh) -> tuple:
    """Mesh axes that carry the batch dimension."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)
