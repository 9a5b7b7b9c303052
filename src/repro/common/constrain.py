"""Soft sharding constraints: no-ops without an ambient mesh.

Model code stays mesh-agnostic — constraints only bind when the caller
established a mesh with ``jax.set_mesh`` (the dry-run / production /
sharded-engine path); CPU unit tests and single-device runs are untouched.
A constraint that names only axes of the ambient mesh is applied as is: if
the partitioner refuses it, the error surfaces.
"""
from __future__ import annotations

import jax
from jax.sharding import PartitionSpec as P


def _ambient_mesh():
    """The mesh bound by ``jax.set_mesh``, or None."""
    mesh = jax.sharding.get_abstract_mesh()
    return None if mesh.empty else mesh


def _ambient_axes():
    mesh = _ambient_mesh()
    return tuple(mesh.axis_names) if mesh is not None else ()


def constrain(x, *spec):
    """with_sharding_constraint(x, P(*spec)) iff every named axis in spec
    exists in the ambient mesh; otherwise identity."""
    axes = _ambient_axes()
    if not axes:
        return x
    for s in spec:
        for name in ((s,) if isinstance(s, str) else (s or ())):
            if name not in axes:
                return x
    return jax.lax.with_sharding_constraint(x, P(*spec))


def constrain_batch(x, ax: int = 0):
    """Constrain axis ``ax`` of ``x`` over the ambient BATCH axes — the
    (pod, data) subset of the active mesh. The gather/scatter boundaries of
    the compacted steps use this: compacted rows, per-row state and logits
    partition over the batch axes while the frozen base stays on its own
    tensor/FSDP plan. Identity when no mesh is ambient, when the mesh has
    no batch axis, or when the axis length doesn't divide."""
    mesh = _ambient_mesh()
    if mesh is None:
        return x
    baxes = tuple(a for a in mesh.axis_names if a in ("pod", "data"))
    if not baxes:
        return x
    size = 1
    for a in baxes:
        size *= dict(mesh.shape)[a]
    if size <= 1 or x.ndim <= ax or x.shape[ax] % size:
        return x
    spec = [None] * x.ndim
    spec[ax] = baxes if len(baxes) > 1 else baxes[0]
    return constrain(x, *spec)
