"""Blocked GQA decode attention — Pallas TPU kernels (online softmax).

The client-side hot op for decode_32k / long_500k: one query token attends
to a seq_len-deep KV cache. The cache never fits VMEM, so it is streamed
HBM→VMEM in ``block_kv`` chunks while a running (max, denominator, weighted
accumulator) triple lives in VMEM scratch — flash-decoding restructured for
the TPU: the KV axis is the *innermost sequential grid dimension* (Pallas
TPU grids iterate sequentially per core, so the scratch carries state), and
the G query heads of one KV group form the MXU's M dimension.

Two layouts:

* **Dense** (``_da_kernel`` / ``decode_attn_pallas``): k/v are contiguous
  [B, T, K, hd] caches; grid (B, K, T/block_kv). The per-batch valid length
  is scalar-prefetched so fully-masked chunks are skipped (long_500k with
  short live prefixes pays only for live cache).
* **Paged / table-aware** (``_paged_kernel`` / ``paged_decode_attn_pallas``):
  k/v are page *pools* [P, page_block, K, hd] shared by many sequence slots;
  each row's block table is scalar-prefetched and the kernel's ``index_map``
  reads ``tbl[b, c]`` to DMA page ``c`` of row ``b`` straight out of the
  pool — the dense view is NEVER gathered (the PR-2 wrapper materialized it
  with ``gather_paged_kv`` before the kernel ran; that gather now survives
  only as the test oracle). Grid (B, n_blocks) with the K and G head axes
  vectorized inside the block, block_kv == page_block so pads never
  materialize. Quantized pools (int8 entries + f32 per-head scales) get the
  same treatment in ``paged_decode_attn_quant_pallas``.

Every paged kernel has a jnp twin (``paged_decode_attn_stream`` /
``paged_decode_attn_quant_stream``): the *same* blocked math — one
``lax.scan`` step per page, each step gathering exactly the pages the table
names — executed without the Pallas grid interpreter. The twins are
byte-identical to the kernels (asserted in tests/test_kernels.py) and are
what non-TPU backends run: interpret mode emulates each grid step with a
dynamic-slice round-trip whose per-step overhead dwarfs the math at decode
shapes, while the stream form vectorizes across rows. On TPU the pallas
kernels run compiled.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


_NEG = -1e30


def _da_kernel(pos,                    # scalar-prefetch [B] int32
               q_ref,                  # [1, 1, G, hd]
               k_ref,                  # [1, bkv, 1, hd]
               v_ref,                  # [1, bkv, 1, hd]
               o_ref,                  # [1, 1, G, hd]
               m_ref, l_ref, acc_ref,  # scratch [G,128],[G,128],[G,hd] f32
               *, block_kv: int, n_kv: int, window: int):
    b = pl.program_id(0)
    c = pl.program_id(2)
    t0 = c * block_kv
    p = pos[b]

    @pl.when(c == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # chunk live iff it intersects [max(0, p-window+1), p]
    lo = (p - window + 1) if window else 0
    live = (t0 <= p) & (t0 + block_kv > lo)

    @pl.when(live)
    def _():
        q = q_ref[0, 0].astype(jnp.float32)                  # [G, hd]
        k = k_ref[0, :, 0].astype(jnp.float32)               # [bkv, hd]
        v = v_ref[0, :, 0].astype(jnp.float32)               # [bkv, hd]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)  # [G, bkv]
        s = s * (1.0 / math.sqrt(q.shape[-1]))
        t = t0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = t <= p
        if window:
            mask &= (p - t) < window
        s = jnp.where(mask, s, _NEG)

        m_prev = m_ref[:, :1]                                # [G,1]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        ps = jnp.exp(s - m_new)                              # [G, bkv]
        l_ref[:, :1] = l_ref[:, :1] * alpha + ps.sum(-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            ps, v, preferred_element_type=jnp.float32)
        m_ref[:, :1] = m_new

    @pl.when(c == n_kv - 1)
    def _():
        denom = jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def decode_attn_pallas(q, k, v, pos, *, block_kv: int = 512, window: int = 0,
                       interpret: bool = False):
    """q [B, K, G, hd]; k/v [B, T, K, hd]; pos [B]. T % block_kv == 0."""
    B, K, G, hd = q.shape
    T = k.shape[1]
    n_kv = T // block_kv

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, K, n_kv),
        in_specs=[
            pl.BlockSpec((1, 1, G, hd), lambda b, h, c, pos: (b, h, 0, 0)),
            pl.BlockSpec((1, block_kv, 1, hd), lambda b, h, c, pos: (b, c, h, 0)),
            pl.BlockSpec((1, block_kv, 1, hd), lambda b, h, c, pos: (b, c, h, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, hd), lambda b, h, c, pos: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, 128), jnp.float32),
            pltpu.VMEM((G, 128), jnp.float32),
            pltpu.VMEM((G, hd), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_da_kernel, block_kv=block_kv, n_kv=n_kv, window=window),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, K, G, hd), q.dtype),
        interpret=interpret,
    )(pos.astype(jnp.int32), q, k, v)


# ---------------------------------------------------------------------------
# Table-aware paged kernels
# ---------------------------------------------------------------------------
#
# Shared blocked-update helper: one page's contribution to the running
# online-softmax state. Written once so the pallas kernels and their jnp
# stream twins execute the *same ops in the same order* — the byte-identity
# contract between the two execution paths (and, through it, between the
# masked bank-wide decode and the compacted decode) rests on this sharing.

def _page_update(q, k, v, ks, vs, t0, p, m, l, acc, *, window: int):
    """q [K,G,hd] f32; k/v [blk,K,hd] f32; ks/vs [blk,K] f32 scales or None;
    m/l [K,G,1]; acc [K,G,hd]. Returns updated (m, l, acc)."""
    s = jax.lax.dot_general(q, k, (((2,), (2,)), ((0,), (1,))),
                            preferred_element_type=jnp.float32)   # [K, G, blk]
    if ks is not None:
        s = s * ks.T[:, None, :]                 # per-entry k scale [K,1,blk]
    s = s * (1.0 / math.sqrt(q.shape[-1]))
    t = t0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
    mask = t <= p
    if window:
        mask &= (p - t) < window
    s = jnp.where(mask, s, _NEG)
    m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
    alpha = jnp.exp(m - m_new)
    ps = jnp.exp(s - m_new)                                       # [K, G, blk]
    # the softmax denominator accumulates the RAW exponentials; the v scales
    # only weight the numerator (p * vs) @ v, matching the dense quant math
    l = l * alpha + ps.sum(-1, keepdims=True)
    if vs is not None:
        ps = ps * vs.T[:, None, :]               # per-entry v scale
    acc = acc * alpha + jax.lax.dot_general(
        ps, v, (((2,), (0,)), ((0,), (1,))), preferred_element_type=jnp.float32)
    return m_new, l, acc


def _paged_kernel(tbl, pos,            # scalar-prefetch [B, nb], [B] int32
                  q_ref,               # [1, K, G, hd]
                  k_ref, v_ref,        # [1, blk, K, hd] — page tbl[b, c]
                  o_ref,               # [1, K, G, hd]
                  m_ref, l_ref, acc_ref,
                  *, blk: int, nb: int, window: int):
    b = pl.program_id(0)
    c = pl.program_id(1)
    t0 = c * blk
    p = pos[b]

    @pl.when(c == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    lo = (p - window + 1) if window else 0
    live = (t0 <= p) & (t0 + blk > lo)

    @pl.when(live)
    def _():
        m, l, acc = _page_update(
            q_ref[0].astype(jnp.float32), k_ref[0].astype(jnp.float32),
            v_ref[0].astype(jnp.float32), None, None, t0, p,
            m_ref[..., :1], l_ref[..., :1], acc_ref[...], window=window)
        m_ref[..., :1] = m
        l_ref[..., :1] = l
        acc_ref[...] = acc

    @pl.when(c == nb - 1)
    def _():
        denom = jnp.maximum(l_ref[..., :1], 1e-30)
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def _paged_quant_kernel(tbl, pos, q_ref, k_ref, ks_ref, v_ref, vs_ref, o_ref,
                        m_ref, l_ref, acc_ref, *, blk: int, nb: int,
                        window: int):
    b = pl.program_id(0)
    c = pl.program_id(1)
    t0 = c * blk
    p = pos[b]

    @pl.when(c == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    lo = (p - window + 1) if window else 0
    live = (t0 <= p) & (t0 + blk > lo)

    @pl.when(live)
    def _():
        m, l, acc = _page_update(
            q_ref[0].astype(jnp.float32), k_ref[0].astype(jnp.float32),
            v_ref[0].astype(jnp.float32), ks_ref[0, :, :, 0], vs_ref[0, :, :, 0],
            t0, p, m_ref[..., :1], l_ref[..., :1], acc_ref[...], window=window)
        m_ref[..., :1] = m
        l_ref[..., :1] = l
        acc_ref[...] = acc

    @pl.when(c == nb - 1)
    def _():
        denom = jnp.maximum(l_ref[..., :1], 1e-30)
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def _paged_grid_spec(B, K, G, hd, blk, nb, quant: bool):
    qspec = pl.BlockSpec((1, K, G, hd), lambda b, c, tbl, pos: (b, 0, 0, 0))
    kv = pl.BlockSpec((1, blk, K, hd), lambda b, c, tbl, pos: (tbl[b, c], 0, 0, 0))
    sc = pl.BlockSpec((1, blk, K, 1), lambda b, c, tbl, pos: (tbl[b, c], 0, 0, 0))
    in_specs = [qspec, kv, sc, kv, sc] if quant else [qspec, kv, kv]
    return pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, nb),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, K, G, hd), lambda b, c, tbl, pos: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((K, G, 128), jnp.float32),
            pltpu.VMEM((K, G, 128), jnp.float32),
            pltpu.VMEM((K, G, hd), jnp.float32),
        ],
    )


def paged_decode_attn_pallas(q, pool_k, pool_v, tbl, pos, *, window: int = 0,
                             interpret: bool = False):
    """Table-aware paged decode attention.

    q [B, K, G, hd]; pool_k/v [P, blk, K, hd] page pools; tbl [B, nb] int32
    block table (scalar-prefetched, read by the index_map — page c of row b
    is DMA'd straight from the pool); pos [B] last-valid index.
    """
    B, K, G, hd = q.shape
    blk = pool_k.shape[1]
    nb = tbl.shape[1]
    return pl.pallas_call(
        functools.partial(_paged_kernel, blk=blk, nb=nb, window=window),
        grid_spec=_paged_grid_spec(B, K, G, hd, blk, nb, quant=False),
        out_shape=jax.ShapeDtypeStruct((B, K, G, hd), q.dtype),
        interpret=interpret,
        name="paged_decode_attn",
    )(tbl.astype(jnp.int32), pos.astype(jnp.int32), q, pool_k, pool_v)


def paged_decode_attn_quant_pallas(q, pool_k, pool_ks, pool_v, pool_vs, tbl,
                                   pos, *, window: int = 0,
                                   interpret: bool = False):
    """Quantized-pool variant: pool_k/v int8 [P, blk, K, hd] with f32
    per-head scales pool_ks/vs [P, blk, K, 1]; same table-aware layout."""
    B, K, G, hd = q.shape
    blk = pool_k.shape[1]
    nb = tbl.shape[1]
    return pl.pallas_call(
        functools.partial(_paged_quant_kernel, blk=blk, nb=nb, window=window),
        grid_spec=_paged_grid_spec(B, K, G, hd, blk, nb, quant=True),
        out_shape=jax.ShapeDtypeStruct((B, K, G, hd), q.dtype),
        interpret=interpret,
        name="paged_decode_attn_int8",
    )(tbl.astype(jnp.int32), pos.astype(jnp.int32), q, pool_k, pool_ks,
      pool_v, pool_vs)


# ---------------------------------------------------------------------------
# jnp stream twins (byte-identical math, no grid interpreter)
# ---------------------------------------------------------------------------

def _stream(q, pool_k, pool_v, pool_ks, pool_vs, tbl, pos, *, window: int):
    """One lax.scan step per page; each step gathers exactly the pages named
    by the table's column c — pages are read in place from the pool, never
    materialized as a dense per-row view. Vectorized over rows; per-row ops
    match the pallas kernels' per-block ops bit for bit."""
    B, K, G, hd = q.shape
    blk = pool_k.shape[1]
    nb = tbl.shape[1]
    qf = q.astype(jnp.float32)
    pos = pos.astype(jnp.int32)
    m0 = jnp.full((B, K, G, 1), _NEG, jnp.float32)
    l0 = jnp.zeros((B, K, G, 1), jnp.float32)
    a0 = jnp.zeros((B, K, G, hd), jnp.float32)

    def body(carry, c):
        m, l, acc = carry
        t0 = c * blk
        page = tbl[:, c]
        k = pool_k[page].astype(jnp.float32)              # [B, blk, K, hd]
        v = pool_v[page].astype(jnp.float32)
        ks = pool_ks[page][..., 0] if pool_ks is not None else None
        vs = pool_vs[page][..., 0] if pool_vs is not None else None

        def upd(q1, k1, v1, ks1, vs1, p1, m1, l1, a1):
            return _page_update(q1, k1, v1, ks1, vs1, t0, p1, m1, l1, a1,
                                window=window)

        in_axes = (0, 0, 0, None if ks is None else 0,
                   None if vs is None else 0, 0, 0, 0, 0)
        m_new, l_new, acc_new = jax.vmap(upd, in_axes=in_axes)(
            qf, k, v, ks, vs, pos, m, l, acc)
        lo = (pos - window + 1) if window else jnp.zeros_like(pos)
        live = ((t0 <= pos) & (t0 + blk > lo))[:, None, None, None]
        m = jnp.where(live, m_new, m)
        l = jnp.where(live, l_new, l)
        acc = jnp.where(live, acc_new, acc)
        return (m, l, acc), None

    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0),
                                  jnp.arange(nb, dtype=jnp.int32))
    return (acc / jnp.maximum(l, 1e-30)).astype(q.dtype)


def paged_decode_attn_stream(q, pool_k, pool_v, tbl, pos, *, window: int = 0):
    """jnp twin of ``paged_decode_attn_pallas`` (byte-identical)."""
    return _stream(q, pool_k, pool_v, None, None, tbl, pos, window=window)


def paged_decode_attn_quant_stream(q, pool_k, pool_ks, pool_v, pool_vs, tbl,
                                   pos, *, window: int = 0):
    """jnp twin of ``paged_decode_attn_quant_pallas`` (byte-identical)."""
    return _stream(q, pool_k, pool_v, pool_ks, pool_vs, tbl, pos,
                   window=window)
