"""Jit'd public wrapper for blocked GQA decode attention.

Dispatch rules (shared by every kernel wrapper in ``repro.kernels``):

* ``interpret=None`` auto-selects by backend: compiled Pallas on TPU,
  emulation elsewhere. The CPU/GPU emulation of the *paged* path is the
  kernels' jnp stream twin (byte-identical math, see decode_attn.py) rather
  than the Pallas grid interpreter — the interpreter's per-grid-step
  dynamic-slice round-trips dwarf the math at decode shapes, while the twin
  vectorizes across rows and reads only the pages the tables name.
* The paged path (``block_tbl`` given) takes ``block_kv`` from the page
  size, so K/V are never re-padded to a block multiple — pads never
  materialize. The dense path picks the largest divisor of T near the
  requested ``block_kv`` before it falls back to zero-padding.
* Under an ambient mesh the paged op runs per device
  (``kernels.mesh.per_device``): rows over the batch axes, KV heads over
  ``model``, whole page pools on every device.
* ``via_gather=True`` is the TEST ORACLE: it materializes the dense per-row
  view with ``gather_paged_kv`` and runs the same blocked math on it with an
  identity block table — byte-identical to the table-aware read by
  construction, and the only ``gather_paged_kv`` caller left on any decode
  path.

The masked bank-wide decode step vmaps this op over clients; a custom_vmap
rule flattens that client axis away instead of batching the kernel: client
pools concatenate into one bigger pool ([C, P, ...] -> [C*P, ...]) with the
tables offset by ``c * P`` — "a bank of clients" and "one client with more
pages" are the same computation, so the masked decode and the engine's
compacted decode (which performs exactly this flattening to gather active
rows across clients) are byte-identical by construction.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.custom_batching import custom_vmap

from repro.kernels.decode_attn.decode_attn import (
    decode_attn_pallas,
    paged_decode_attn_pallas,
    paged_decode_attn_quant_pallas,
    paged_decode_attn_stream,
    paged_decode_attn_quant_stream,
)
from repro.kernels.decode_attn.ref import decode_attn_ref, gather_paged_kv, paged_view
from repro.kernels.mesh import HEADS, ROWS, per_device

# q [B, K, G, hd] and the output: rows and KV heads split; page pools
# [P, blk, K, hd] split by head (whole pages on every device); tables
# [B, nb] and positions [B] by row
_Q = (ROWS, HEADS, None, None)
_POOL = (None, None, HEADS, None)


def backend_interpret() -> bool:
    """True iff Pallas kernels should be emulated on this backend."""
    return jax.default_backend() != "tpu"


def _flatten_client_axis(axis_size, pool_batched, q, tbl, pos, *pools):
    """custom_vmap helper: fold a leading client axis into rows + pages.

    q [C, B, ...] -> [C*B, ...]; pos likewise. Batched pools [C, P, ...]
    concatenate to [C*P, ...] with tables offset by c*P; an unbatched pool is
    already shared across the axis, so tables pass through untouched."""
    C, B = axis_size, q.shape[1]
    q = q.reshape((C * B,) + q.shape[2:])
    pos = pos.reshape(C * B)
    if pool_batched:
        P = pools[0].shape[1]
        pools = tuple(p.reshape((C * P,) + p.shape[2:]) for p in pools)
        tbl = tbl + (jnp.arange(C, dtype=tbl.dtype) * P)[:, None, None]
    tbl = tbl.reshape(C * B, tbl.shape[-1])
    return q, tbl, pos, pools


@functools.lru_cache(maxsize=None)
def _paged_op(window: int, interpret: bool):
    """custom_vmap'd table-aware paged attention for one (window, backend)."""

    @custom_vmap
    def op(q, pool_k, pool_v, tbl, pos):
        impl = (functools.partial(paged_decode_attn_stream, window=window)
                if interpret else
                functools.partial(paged_decode_attn_pallas, window=window))
        return per_device(impl, (_Q, _POOL, _POOL, (ROWS, None), (ROWS,)),
                          _Q, q, pool_k, pool_v, tbl, pos)

    @op.def_vmap
    def _rule(axis_size, in_batched, q, pool_k, pool_v, tbl, pos):
        qb, pkb, pvb, tb, pb = in_batched
        assert qb and tb and pb and (pkb == pvb), (
            "paged decode attention: q/tbl/pos must batch together and the "
            "two pools alike")
        q, tbl, pos, (pool_k, pool_v) = _flatten_client_axis(
            axis_size, pkb, q, tbl, pos, pool_k, pool_v)
        out = op(q, pool_k, pool_v, tbl, pos)
        return out.reshape((axis_size, -1) + out.shape[1:]), True

    return op


@functools.lru_cache(maxsize=None)
def _paged_quant_op(window: int, interpret: bool):
    @custom_vmap
    def op(q, pool_k, pool_ks, pool_v, pool_vs, tbl, pos):
        impl = (functools.partial(paged_decode_attn_quant_stream,
                                  window=window)
                if interpret else
                functools.partial(paged_decode_attn_quant_pallas,
                                  window=window))
        return per_device(impl, (_Q,) + (_POOL,) * 4 + ((ROWS, None), (ROWS,)),
                          _Q, q, pool_k, pool_ks, pool_v, pool_vs, tbl, pos)

    @op.def_vmap
    def _rule(axis_size, in_batched, q, pool_k, pool_ks, pool_v, pool_vs,
              tbl, pos):
        qb, pkb, ksb, pvb, vsb, tb, pb = in_batched
        assert qb and tb and pb and pkb == ksb == pvb == vsb, (
            "paged decode attention: q/tbl/pos must batch together and the "
            "four pools alike")
        q, tbl, pos, pools = _flatten_client_axis(
            axis_size, pkb, q, tbl, pos, pool_k, pool_ks, pool_v, pool_vs)
        out = op(q, *pools, tbl, pos)
        return out.reshape((axis_size, -1) + out.shape[1:]), True

    return op


def _identity_tbl(B: int, nb: int):
    """Block table of a gathered dense view: row b's pages are contiguous."""
    return jnp.arange(B * nb, dtype=jnp.int32).reshape(B, nb)


def _dense_block_kv(T: int, block_kv: int):
    """Largest divisor of T in (block_kv/2, block_kv] — avoids materializing
    zero-pads for mildly non-dividing depths; degenerate depths keep the old
    pad-to-multiple behaviour."""
    bkv = min(block_kv, T)
    if T % bkv == 0:
        return bkv, 0
    for cand in range(bkv, max(bkv // 2, 1), -1):
        if T % cand == 0:
            return cand, 0
    return bkv, (-T) % bkv


@functools.partial(jax.jit, static_argnames=("block_kv", "window",
                                             "use_kernel", "interpret",
                                             "via_gather"))
def decode_attn(q, k, v, pos, *, block_kv: int = 512, window: int = 0,
                use_kernel: bool = True, interpret: bool = None,
                block_tbl=None, k_scale=None, v_scale=None,
                via_gather: bool = False):
    """Single-token GQA decode attention. q [B,K,G,hd]; k/v [B,T,K,hd];
    pos [B] int32 last-valid index. Optional sliding window.

    ``block_tbl`` [B, n_blocks] switches to the PAGED layout: k/v are page
    pools [P, page_block, K, hd] shared across rows, and the kernel reads
    each row's live pages in place through its table row (scalar-prefetched;
    the kernel copies them from the pool — no dense view is gathered, and
    no entry past a row's last live page is read). ``k_scale``/``v_scale``
    [.., K, 1] switch to int8-quantized entries with per-head f32 scales.
    ``interpret=None`` auto-selects by backend (compiled on TPU, the
    byte-identical jnp stream twin elsewhere). ``via_gather=True`` is the
    test oracle: gather first, then run the identical blocked math."""
    if interpret is None:
        interpret = backend_interpret()
    if block_tbl is not None:
        quant = k_scale is not None
        if not use_kernel:
            return decode_attn_ref(q, k, v, pos, window=window,
                                   block_tbl=block_tbl, k_scale=k_scale,
                                   v_scale=v_scale)
        if via_gather:
            # TEST ORACLE: materialize the dense per-row view, then run the
            # same blocked math over it with an identity table. Byte-equal
            # to the in-place table read; never on a serving path.
            B, nb = block_tbl.shape
            blk = k.shape[1]
            k, v = gather_paged_kv(k, v, block_tbl)
            k = k.reshape(B * nb, blk, *k.shape[2:])
            v = v.reshape(B * nb, blk, *v.shape[2:])
            if quant:
                k_scale = paged_view(k_scale, block_tbl).reshape(
                    B * nb, blk, *k_scale.shape[2:])
                v_scale = paged_view(v_scale, block_tbl).reshape(
                    B * nb, blk, *v_scale.shape[2:])
            block_tbl = _identity_tbl(B, nb)
        if quant:
            return _paged_quant_op(window, interpret)(
                q, k, k_scale, v, v_scale, block_tbl, pos.astype(jnp.int32))
        return _paged_op(window, interpret)(q, k, v, block_tbl,
                                            pos.astype(jnp.int32))
    if not use_kernel:
        return decode_attn_ref(q, k, v, pos, window=window)
    T = k.shape[1]
    bkv, pad = _dense_block_kv(T, block_kv)
    if pad:
        zeros = lambda t: jnp.pad(t, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k, v = zeros(k), zeros(v)
    return decode_attn_pallas(q, k, v, pos, block_kv=bkv, window=window,
                              interpret=interpret)
