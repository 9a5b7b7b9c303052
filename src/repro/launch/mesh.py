"""Production meshes (DESIGN.md §5).

Single pod: (data=16, model=16) = 256 v5e chips. Multi-pod adds a leading
DCN-connected ``pod`` axis: (pod=2, data=16, model=16) = 512 chips.
Functions, not module constants — importing this module never touches jax
device state (the dry-run sets XLA_FLAGS before first jax init).
"""
from __future__ import annotations

import jax


def make_mesh(shape, axes):
    """A device mesh over ``jax.devices()`` whose axes are all Auto, so the
    partitioner places what sharding constraints leave open."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh():
    """1-chip mesh with the production axis names (CPU smoke tests)."""
    return make_mesh((1, 1), ("data", "model"))


def batch_axes(mesh) -> tuple:
    """The axes a leading batch/client dimension shards over."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def batch_size(mesh) -> int:
    n = 1
    for a in batch_axes(mesh):
        n *= mesh.shape[a]
    return n


def model_size(mesh) -> int:
    return mesh.shape.get("model", 1)
