"""Seeded weights in the layout the program consumes.

Every leaf is drawn from its own key, ``fold_in`` of the run's key by the
leaf's name and then by each leading index (client, layer). So the whole
tree comes from one jitted call on the device, and the plain reference
makes the same weights again from the seed alone, the base with the same
call and each adapter layer by itself (the same key gives the same numbers,
to float32 rounding, whether the draw is vmapped over layers or made once).

Leaf kinds:

- ``linear``: uniform in +-1/sqrt(fan_in), as a frozen projection;
- ``embed``: normal, std 0.02;
- ``norm``: 1 + 0.05 * normal, so a misapplied norm scale shows;
- adapter leaves (``lora_a``, ``lora_b``, ``ia3``, ``prefix``): seeded noise
  large enough that every method moves the logits.
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
import numpy as np

from bench import harness


def root_key(seed: int):
    """A threefry key from any seed that fits in 64 bits."""
    seed = int(seed) % (1 << 64)
    return jax.random.wrap_key_data(
        np.array([seed >> 32, seed & 0xFFFFFFFF], np.uint32))


def _name_id(name: str) -> int:
    return zlib.crc32(name.encode()) & 0x7FFFFFFF


def draw(key, name: str, kind: str, shape, dtype, fan_in: int = 1):
    """One leaf (no leading indices) of ``kind``."""
    k = jax.random.fold_in(key, _name_id(name))
    return _draw(k, kind, tuple(shape), jnp.dtype(dtype), fan_in)


def _draw(k, kind, shape, dtype, fan_in):
    if kind == "linear":
        s = float(np.float32(1.0 / np.sqrt(fan_in)))
        return jax.random.uniform(k, shape, jnp.float32, -s, s).astype(dtype)
    if kind == "embed":
        return (0.02 * jax.random.normal(k, shape, jnp.float32)).astype(dtype)
    if kind == "norm":
        return (1.0 + 0.05 * jax.random.normal(k, shape, jnp.float32)
                ).astype(dtype)
    if kind == "lora_a":
        return (jax.random.normal(k, shape, jnp.float32)
                * np.float32(1.0 / np.sqrt(fan_in))).astype(dtype)
    if kind == "lora_b":
        return (0.1 * jax.random.normal(k, shape, jnp.float32)).astype(dtype)
    if kind == "ia3":
        return (1.0 + 0.1 * jax.random.normal(k, shape, jnp.float32)
                ).astype(dtype)
    if kind == "prefix":
        return jax.random.normal(k, shape, jnp.float32).astype(dtype)
    raise ValueError(f"unknown leaf kind {kind!r}")


def draw_indexed(key, name: str, kind: str, lead, shape, dtype,
                 fan_in: int = 1):
    """Leaf stacked over leading indices ``lead`` (e.g. (clients, layers)):
    element ``[i, j]`` is the draw from ``fold_in(fold_in(k, i), j)``."""
    k = jax.random.fold_in(key, _name_id(name))

    def one(*idx):
        kk = k
        for i in idx:
            kk = jax.random.fold_in(kk, i)
        return _draw(kk, kind, tuple(shape), jnp.dtype(dtype), fan_in)

    fn = one
    for n in reversed(lead):
        fn = _vmap_first(fn, n)
    return fn()


def _vmap_first(fn, n):
    """Wrap ``fn(*idx)`` into ``g(*outer)`` that vmaps a new leading index
    of size ``n`` in front of ``fn``'s own."""
    def g(*outer):
        return jax.vmap(lambda i: fn(*outer, i))(jnp.arange(n))
    return g


# ---------------------------------------------------------------------------
# The base parameters
# ---------------------------------------------------------------------------

def _set(tree, path, value):
    node = tree
    for p in path[:-1]:
        node = node.setdefault(p, {})
    node[path[-1]] = value


def make_base(cfg: dict, seed: int, family=None):
    """The whole base tree of the configuration's family (its
    ``base_leaves``), in the served dtype, from one jitted call (the key is
    an argument, so every seed runs the one compiled program)."""
    dtype = jnp.dtype(cfg["param_dtype"])
    leaves = harness.family(cfg, family).base_leaves(cfg)

    def build(key_data):
        key = jax.random.wrap_key_data(key_data)
        tree = {}
        for path, kind, per, shape, fan in leaves:
            name = "/".join(path)
            if per is None:
                v = draw(key, name, kind, shape, dtype, fan)
            else:
                v = draw_indexed(key, name, kind, (cfg["n_layers"],), shape,
                                 dtype, fan)
            _set(tree, path, v)
        return tree

    return jax.jit(build)(jax.random.key_data(root_key(seed)))


# ---------------------------------------------------------------------------
# Adapters
# ---------------------------------------------------------------------------

def adapter_leaves(cfg: dict, method: dict, family=None):
    """(path under ``layers``, kind, per-layer shape, fan_in) of one
    adapter of ``method`` ({"method", "rank", "targets", "n_prefix"}), on
    the widths of the family's ``target_dims``."""
    dims = harness.family(cfg, family).target_dims(cfg)
    out = []
    m = method["method"]
    if m == "lora":
        r = method["rank"]
        for t in method["targets"]:
            din, dout = dims[t]
            out.append(((t, "A"), "lora_a", (din, r), din))
            out.append(((t, "B"), "lora_b", (r, dout), 1))
    elif m == "ia3":
        for t in method["targets"]:
            din, dout = dims[t]
            out.append(((t, "scale"), "ia3", (din if t == "down" else dout,),
                        1))
    elif m == "prefix":
        shape = (method["n_prefix"], cfg["n_kv_heads"], cfg["head_dim"])
        out.append((("prefix_k",), "prefix", shape, 1))
        out.append((("prefix_v",), "prefix", shape, 1))
    else:
        raise ValueError(f"unknown adapter method {m!r}")
    return out


def make_bank(cfg: dict, method: dict, seed: int, bank: str, n: int,
              dtype=jnp.float32, family=None):
    """``n`` adapters of one bank, stacked [n, layers, ...] under
    ``layers`` as the program's client banks are, from one jitted call."""
    leaves = adapter_leaves(cfg, method, family)

    def build(key_data):
        key = jax.random.wrap_key_data(key_data)
        tree = {}
        for path, kind, shape, fan in leaves:
            name = f"adapter/{bank}/" + "/".join(path)
            _set(tree, ("layers",) + path, draw_indexed(
                key, name, kind, (n, cfg["n_layers"]), shape, dtype, fan))
        return tree

    return jax.jit(build)(jax.random.key_data(root_key(seed)))
