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
  k/v are page *pools* [P, page_block, K, hd] shared by many sequence slots,
  left in HBM; each row's block table is scalar-prefetched. The grid is
  (B, n_cb): row b walks its table in compute blocks of ``ppb`` consecutive
  columns (``pages_per_block``: as many pages as hold 128 tokens), the K
  and G head axes vectorized inside the block. The kernel copies a live
  block's pages out of the pool into a double-buffered VMEM buffer itself
  (``pltpu.make_async_copy``), starting the next live block's copy — maybe
  the next row's first — while the current block computes. A block that
  holds no position of the row's window is neither copied nor computed,
  and within a block only the pages the dead-page rule admits are read
  (see ``_page_reads``): a table entry past the row's last live page is
  never dereferenced. The dense view is NEVER gathered (``gather_paged_kv``
  survives only as the test oracle). Quantized pools (int8 entries + f32
  per-head scales) get the same treatment in
  ``paged_decode_attn_quant_pallas``.

Every paged kernel has a jnp twin (``paged_decode_attn_stream`` /
``paged_decode_attn_quant_stream``): the *same* blocked math — one
``lax.scan`` step per compute block, each step gathering exactly the pages
the kernel reads — executed without the Pallas grid interpreter. The twins
are byte-identical to the kernels (asserted in
tests/test_paged_decode_attn.py) and are what non-TPU backends run:
interpret mode emulates each grid step and DMA at a per-step cost that
dwarfs the math at decode shapes, while the stream form vectorizes across
rows. On TPU the pallas kernels run compiled.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
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
# A row's block table is walked in COMPUTE BLOCKS of ``ppb`` consecutive
# columns (``pages_per_block``). One block is one online-softmax update over
# ``ppb * blk`` keys, written once (``_block_update``) so the pallas kernels
# and their jnp stream twins execute the *same ops in the same order* — the
# byte-identity contract between the two execution paths (and, through it,
# between the masked bank-wide decode and the compacted decode) rests on
# this sharing.
#
# The dead-page rule: a page is READ iff its column lies in the table, it
# holds a position in [max(0, p - window + 1), p], and its entry names a
# page of the pool. Every other page of a block reads as zeros — the kernel
# zero-fills its VMEM slot, the twin its gathered slice — so an entry past a
# row's last live page (the engine's out-of-range sentinel included) is
# never dereferenced, and a block that holds no live position is neither
# copied nor computed.

_BLOCK_TOKENS = 128


def pages_per_block(nb: int, blk: int) -> int:
    """Pages per compute block of a table of ``nb`` columns of ``blk``-token
    pages: as many whole pages as hold 128 tokens, at most the table."""
    return max(1, min(nb, _BLOCK_TOKENS // blk))


def pages_read(pos, nb: int, blk: int, window: int = 0) -> int:
    """Pages in the compute blocks the paged kernels visit for rows at
    positions ``pos`` (host ints; a negative position visits none)."""
    ppb = pages_per_block(nb, blk)
    bk = ppb * blk
    p = np.asarray(pos, np.int64)
    lo = np.maximum(p - window + 1, 0) if window else np.zeros_like(p)
    first = lo // bk
    last = np.minimum(p // bk, -(-nb // ppb) - 1)
    n = np.minimum(nb, (last + 1) * ppb) - first * ppb
    return int(np.where((p >= 0) & (first <= last), n, 0).sum())


def _window_lo(p, window: int):
    """First position a query at ``p`` attends to."""
    return jnp.maximum(p - window + 1, 0) if window else jnp.zeros_like(p)


def _block_update(q, k, v, ks, vs, t0, p, m, l, acc, *, window: int):
    """q [K,G,hd]; k/v [bk,K,hd] keys t0 .. t0+bk-1, in the pool's dtype;
    ks/vs [bk,K] f32 scales or None; m/l [K,G,1]; acc [K,G,hd] f32.
    Returns updated (m, l, acc); the arithmetic is f32."""
    q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    s = jax.lax.dot_general(q, k, (((2,), (2,)), ((0,), (1,))),
                            preferred_element_type=jnp.float32)   # [K, G, bk]
    scale = 1.0 / math.sqrt(q.shape[-1])
    if ks is not None:
        # per-entry k scale [K,1,bk], folded with the softmax scale first
        scale = ks.T[:, None, :] * scale
    s = s * scale
    t = t0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
    mask = t <= p
    if window:
        mask &= (p - t) < window
    s = jnp.where(mask, s, _NEG)
    m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
    alpha = jnp.exp(m - m_new)
    ps = jnp.exp(s - m_new)                                       # [K, G, bk]
    # the softmax denominator accumulates the RAW exponentials; the v scales
    # only weight the numerator (p * vs) @ v, matching the dense quant math
    l = l * alpha + ps.sum(-1, keepdims=True)
    if vs is not None:
        ps = ps * vs.T[:, None, :]               # per-entry v scale
    acc = acc * alpha + jax.lax.dot_general(
        ps, v, (((2,), (0,)), ((0,), (1,))), preferred_element_type=jnp.float32)
    return m_new, l, acc


def _page_reads(tbl, pos, b, c, *, blk, nb, ppb, window, n_pages):
    """(read, page) of each of the ppb columns of block c of row b. The
    table itself is read at no column past the row's last live page."""
    p = pos[b]
    lo = _window_lo(p, window)
    last = jnp.clip(p // blk, 0, nb - 1)
    out = []
    for j in range(ppb):
        col = c * ppb + j
        page = tbl[b, jnp.minimum(col, last)]
        t = col * blk
        out.append(((col < nb) & (t <= p) & (t + blk > lo)
                    & (page >= 0) & (page < n_pages), page))
    return out


def _fetch(reads, pools, bufs, sem, slot):
    """Start the copies of a block's read pages into VMEM slot ``slot``;
    zero-fill its other pages."""
    for j, (read, page) in enumerate(reads):
        @pl.when(read)
        def _():
            for src, dst in zip(pools, bufs):
                pltpu.make_async_copy(src.at[page], dst.at[slot, j],
                                      sem.at[slot]).start()

        @pl.when(jnp.logical_not(read))
        def _():
            for dst in bufs:
                dst[slot, j] = jnp.zeros(dst.shape[2:], dst.dtype)


def _wait(reads, pools, bufs, sem, slot):
    for j, (read, page) in enumerate(reads):
        @pl.when(read)
        def _():
            for src, dst in zip(pools, bufs):
                pltpu.make_async_copy(src.at[page], dst.at[slot, j],
                                      sem.at[slot]).wait()


def _paged_kernel(tbl, pos,            # scalar-prefetch [B, nb], [B] int32
                  q_ref,               # [1, K, G, hd]
                  *refs, blk: int, nb: int, ppb: int, window: int):
    """refs: the n page pools (HBM: k, v or k, ks, v, vs), the output
    [1, K, G, hd], m/l/acc scratch, n double-buffered VMEM block buffers
    [2, ppb, blk, K, d], one DMA semaphore per buffer slot, and SMEM
    ``st`` = (slot of the next live block, 1 iff its copy is in flight)."""
    n = (len(refs) - 6) // 2
    pools, (o_ref, m_ref, l_ref, acc_ref) = refs[:n], refs[n:n + 4]
    bufs, (sem, st) = refs[n + 4:2 * n + 4], refs[2 * n + 4:]
    b, c = pl.program_id(0), pl.program_id(1)
    B, n_cb = pl.num_programs(0), pl.num_programs(1)
    bk = ppb * blk
    t0 = c * bk
    p = pos[b]
    reads = functools.partial(_page_reads, tbl, pos, blk=blk, nb=nb, ppb=ppb,
                              window=window, n_pages=pools[0].shape[0])

    @pl.when((b == 0) & (c == 0))
    def _():
        st[0] = 0
        st[1] = 0

    @pl.when(c == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when((t0 <= p) & (t0 + bk > _window_lo(p, window)))
    def _():
        slot = st[0]

        @pl.when(st[1] == 0)
        def _():
            _fetch(reads(b, c), pools, bufs, sem, slot)

        # the next live block — this row's next, else the first live block
        # of the next row that has one — starts copying under this block's
        # compute, so no row's first copy is exposed
        def row_lo(r):
            return _window_lo(pos[jnp.minimum(r, B - 1)], window)

        def dead_row(r):
            return (r < B) & ((pos[jnp.minimum(r, B - 1)] < 0)
                              | (row_lo(r) >= n_cb * bk))

        stay = (t0 + bk <= p) & (c + 1 < n_cb)
        r = jax.lax.while_loop(dead_row, lambda r: r + 1, b + 1)
        nxt_b = jnp.where(stay, b, r)
        nxt_c = jnp.where(stay, c + 1, jax.lax.div(row_lo(r), bk))
        has_next = nxt_b < B

        @pl.when(has_next)
        def _():
            _fetch(reads(nxt_b, nxt_c), pools, bufs, sem, 1 - slot)

        st[0] = 1 - slot
        st[1] = has_next.astype(jnp.int32)
        _wait(reads(b, c), pools, bufs, sem, slot)
        blocks = [buf[slot].reshape((bk,) + buf.shape[3:]) for buf in bufs]
        if len(blocks) == 4:
            K = blocks[0].shape[1]
            k, ks, v, vs = blocks
            ks, vs = ks[:, :K], vs[:, :K]
        else:
            (k, v), ks, vs = blocks, None, None
        m, l, acc = _block_update(
            q_ref[0], k, v, ks, vs, t0, p,
            m_ref[..., :1], l_ref[..., :1], acc_ref[...], window=window)
        m_ref[..., :1] = m
        l_ref[..., :1] = l
        acc_ref[...] = acc

    @pl.when(c == n_cb - 1)
    def _():
        denom = jnp.maximum(l_ref[..., :1], 1e-30)
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def _paged_call(name, q, pools, tbl, pos, window, interpret):
    """Grid (B, n_cb): row b, compute block c. The pools stay in HBM and
    the kernel copies each live block's pages itself."""
    B, K, G, hd = q.shape
    blk = pools[0].shape[1]
    nb = tbl.shape[1]
    ppb = pages_per_block(nb, blk)
    if len(pools) == 4:
        # the int8 pools' f32 scales [P, blk, K, 1] travel lane-padded,
        # [P, blk, 128 * ceil(K / 128)], so that one page is one
        # tile-aligned copy
        lanes = lambda x: jnp.pad(x[..., 0], ((0, 0), (0, 0), (0, -K % 128)))
        pools = (pools[0], lanes(pools[1]), pools[2], lanes(pools[3]))
    row = pl.BlockSpec((1, K, G, hd), lambda b, c, tbl, pos: (b, 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, pl.cdiv(nb, ppb)),
        in_specs=[row] + [pl.BlockSpec(memory_space=pl.ANY)] * len(pools),
        out_specs=row,
        scratch_shapes=[
            pltpu.VMEM((K, G, 128), jnp.float32),
            pltpu.VMEM((K, G, 128), jnp.float32),
            pltpu.VMEM((K, G, hd), jnp.float32),
            *[pltpu.VMEM((2, ppb) + x.shape[1:], x.dtype) for x in pools],
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((2,), jnp.int32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_paged_kernel, blk=blk, nb=nb, ppb=ppb,
                          window=window),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, K, G, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=pltpu.InterpretParams() if interpret else False,
        name=name,
    )(tbl.astype(jnp.int32), pos.astype(jnp.int32), q, *pools)


def paged_decode_attn_pallas(q, pool_k, pool_v, tbl, pos, *, window: int = 0,
                             interpret: bool = False):
    """Table-aware paged decode attention.

    q [B, K, G, hd]; pool_k/v [P, blk, K, hd] page pools; tbl [B, nb] int32
    block table (scalar-prefetched; the kernel copies the pages of each
    live compute block straight from the pool); pos [B] last-valid index.
    """
    return _paged_call("paged_decode_attn", q, (pool_k, pool_v), tbl, pos,
                       window, interpret)


def paged_decode_attn_quant_pallas(q, pool_k, pool_ks, pool_v, pool_vs, tbl,
                                   pos, *, window: int = 0,
                                   interpret: bool = False):
    """Quantized-pool variant: pool_k/v int8 [P, blk, K, hd] with f32
    per-head scales pool_ks/vs [P, blk, K, 1]; same table-aware layout."""
    return _paged_call("paged_decode_attn_int8", q,
                       (pool_k, pool_ks, pool_v, pool_vs), tbl, pos, window,
                       interpret)


# ---------------------------------------------------------------------------
# jnp stream twins (byte-identical math, no grid interpreter)
# ---------------------------------------------------------------------------

def _stream(q, pools, tbl, pos, *, window: int):
    """One lax.scan step per compute block; each step gathers exactly the
    pages the kernel reads (the dead-page rule: every other page of the
    block is zeros) — pages are read in place from the pool, never
    materialized as a dense per-row view. Vectorized over rows; per-row ops
    match the pallas kernels' per-block ops bit for bit."""
    B, K, G, hd = q.shape
    P, blk = pools[0].shape[:2]
    nb = tbl.shape[1]
    ppb = pages_per_block(nb, blk)
    bk = ppb * blk
    pos = pos.astype(jnp.int32)
    lo = _window_lo(pos, window)
    m0 = jnp.full((B, K, G, 1), _NEG, jnp.float32)
    l0 = jnp.zeros((B, K, G, 1), jnp.float32)
    a0 = jnp.zeros((B, K, G, hd), jnp.float32)

    def body(carry, c):
        m, l, acc = carry
        t0 = c * bk
        cols = c * ppb + jnp.arange(ppb, dtype=jnp.int32)
        page = tbl[:, jnp.minimum(cols, nb - 1)]                 # [B, ppb]
        t = cols * blk
        read = ((cols < nb) & (t <= pos[:, None]) & (t + blk > lo[:, None])
                & (page >= 0) & (page < P))

        def gather(pool):
            x = pool[jnp.clip(page, 0, P - 1)]          # [B, ppb, blk, ...]
            x = jnp.where(read.reshape(read.shape + (1,) * (x.ndim - 2)), x,
                          jnp.zeros((), x.dtype))
            return x.reshape((B, bk) + x.shape[3:])

        blocks = [gather(x) for x in pools]
        if len(blocks) == 4:
            k, ks, v, vs = blocks
            ks, vs = ks[..., 0], vs[..., 0]
        else:
            (k, v), ks, vs = blocks, None, None

        def upd(q1, k1, v1, ks1, vs1, p1, m1, l1, a1):
            return _block_update(q1, k1, v1, ks1, vs1, t0, p1, m1, l1, a1,
                                 window=window)

        in_axes = (0, 0, 0, None if ks is None else 0,
                   None if vs is None else 0, 0, 0, 0, 0)
        m_new, l_new, acc_new = jax.vmap(upd, in_axes=in_axes)(
            q, k, v, ks, vs, pos, m, l, acc)
        live = ((t0 <= pos) & (t0 + bk > lo))[:, None, None, None]
        m = jnp.where(live, m_new, m)
        l = jnp.where(live, l_new, l)
        acc = jnp.where(live, acc_new, acc)
        return (m, l, acc), None

    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0),
                                  jnp.arange(pl.cdiv(nb, ppb), dtype=jnp.int32))
    return (acc / jnp.maximum(l, 1e-30)).astype(q.dtype)


def paged_decode_attn_stream(q, pool_k, pool_v, tbl, pos, *, window: int = 0):
    """jnp twin of ``paged_decode_attn_pallas`` (byte-identical)."""
    return _stream(q, (pool_k, pool_v), tbl, pos, window=window)


def paged_decode_attn_quant_stream(q, pool_k, pool_ks, pool_v, pool_vs, tbl,
                                   pos, *, window: int = 0):
    """jnp twin of ``paged_decode_attn_quant_pallas`` (byte-identical)."""
    return _stream(q, (pool_k, pool_ks, pool_v, pool_vs), tbl, pos,
                   window=window)
