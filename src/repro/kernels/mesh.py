"""Kernel calls inside a sharded program.

XLA cannot partition a Mosaic (Pallas TPU) kernel, so under an ambient mesh
(``jax.set_mesh``) every kernel wrapper runs its body per device through
``shard_map``. Rows shard over the mesh's batch axes (``pod``, ``data``)
and heads over ``model``, each only where the mesh has the axis and it
divides the dimension; every other dimension is whole on each device, so
whatever arrives split along it (a page pool sharded by page, activations
sharded by feature) is gathered first. The jnp twins take the same path,
so CPU runs on host meshes partition the ops exactly as the chip does.
"""
from __future__ import annotations

import math

import jax
from jax.sharding import PartitionSpec as P

ROWS = "rows"           # a dimension that shards over the batch axes
HEADS = "heads"         # a dimension that shards over ``model``


def _axes(mesh, role):
    if role == ROWS:
        names = tuple(a for a in mesh.axis_names if a in ("pod", "data"))
    elif role == HEADS:
        names = tuple(a for a in mesh.axis_names if a == "model")
    else:
        names = ()
    return names, math.prod(mesh.shape[a] for a in names)


def per_device(fn, roles, out_roles, *args):
    """``fn(*args)``, run per device under the ambient mesh (if any).

    ``roles[i]`` names, per dimension of ``args[i]``, ``ROWS``, ``HEADS``
    or None; ``out_roles`` does the same for the output, whose ROWS/HEADS
    dimensions must have the sizes of the inputs' (a role shards all of its
    dimensions alike or none of them)."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or mesh.size == 1:
        return fn(*args)
    split = {}
    for role in (ROWS, HEADS):
        names, size = _axes(mesh, role)
        dims = [x.shape[d] for r, x in zip(roles, args)
                for d, rd in enumerate(r) if rd == role]
        ok = size > 1 and all(n % size == 0 for n in dims)
        split[role] = (names if len(names) > 1 else names[0]) if ok else None

    def spec(r):
        return P(*(split.get(rd) for rd in r))

    return jax.shard_map(fn, mesh=mesh, in_specs=tuple(spec(r) for r in roles),
                         out_specs=spec(out_roles), check_vma=False)(*args)
