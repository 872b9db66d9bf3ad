"""Production mesh construction.

A function (never a module-level constant) so importing this module never
touches jax device state — the dry-run sets XLA_FLAGS before first init.

Production topology (TPU v5e):
  single-pod: (data=16, model=16)            = 256 chips
  multi-pod : (pod=2, data=16, model=16)     = 512 chips
DP runs over ("pod", "data"); TP/EP/sequence-CP over "model".
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape, axes):
    # Auto axes: the model code places its tensor-parallel layouts with
    # sharding constraints that the partitioner propagates (DESIGN.md §5);
    # jax's default Explicit axes would make every such layout a type
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1, pod: int = 0):
    """Small meshes for tests/examples on whatever devices exist."""
    if pod:
        return _mesh((pod, data, model), ("pod", "data", "model"))
    return _mesh((data, model), ("data", "model"))


def dp_axes(mesh) -> tuple:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def dp_size(mesh) -> int:
    out = 1
    for a in dp_axes(mesh):
        out *= mesh.shape[a]
    return out
