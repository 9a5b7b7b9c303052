"""Shared neural building blocks (pure JAX, functional).

Every frozen-base matmul in every architecture goes through a ``LinearFns``
hook. This is the JAX analogue of the paper's VirtLayer splice (§3.2): the
default hook executes the matmul inline ("fused baseline"); the Symbiosis core
substitutes a hook that applies the memory-optimized frozen linear (§3.6),
per-client PEFT adapters, and the privacy noise protocol (§3.8) — without any
change to model code (paper design goal 3: model transparency).
"""
from __future__ import annotations

import math
from contextlib import contextmanager
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.custom_batching import custom_vmap

# Paged decode attention goes through the table-aware kernel wrapper: pages
# are read in place from the pool via the scalar-prefetched block table —
# the dense per-row view is never gathered on a decode path (the gather
# survives only as the kernels' test oracle, see decode_attn(via_gather=...))
from repro.kernels.decode_attn import decode_attn


class LinearFns(NamedTuple):
    """Hook for base-model linear layers.

    dense(x, w, b, path):   x [..., din] @ w [din, dout] (+ b) -> [..., dout]
    expert(x, w, path):     x [E, C, din] @ w [E, din, dout]   -> [E, C, dout]
    """
    dense: Callable
    expert: Callable


def _default_dense(x, w, b, path):
    y = jnp.einsum("...i,io->...o", x, w)
    if b is not None:
        y = y + b
    return y


def _default_expert(x, w, path):
    return jnp.einsum("eci,eio->eco", x, w)


DEFAULT_LIN = LinearFns(dense=_default_dense, expert=_default_expert)


# ---------------------------------------------------------------------------
# Initializers
# ---------------------------------------------------------------------------

def dense_init(key, din, dout, dtype):
    scale = 1.0 / math.sqrt(din)
    return (jax.random.uniform(key, (din, dout), jnp.float32, -scale, scale)).astype(dtype)


def embed_init(key, vocab, d, dtype):
    return (jax.random.normal(key, (vocab, d), jnp.float32) * 0.02).astype(dtype)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def rmsnorm_init(d, dtype):
    return {"scale": jnp.ones((d,), dtype)}


def rmsnorm(params, x, eps: float = 1e-6):
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * params["scale"].astype(jnp.float32)).astype(dt)


def head_rmsnorm(scale, x, eps: float = 1e-6):
    """qk-norm: normalize the last (head) dim. scale [hd]."""
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * scale.astype(jnp.float32)).astype(dt)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_frequencies(hd: int, theta: float):
    return theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)


def apply_rope(x, positions, theta: float):
    """x [..., S, H, hd]; positions [..., S] (int)."""
    hd = x.shape[-1]
    freqs = rope_frequencies(hd, theta)                       # [hd/2]
    angles = positions[..., None].astype(jnp.float32) * freqs  # [..., S, hd/2]
    cos = jnp.cos(angles)[..., None, :]                        # [..., S, 1, hd/2]
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention (GQA, chunked-causal for long sequences, decode with cache)
# ---------------------------------------------------------------------------

def attn_init(key, cfg, dtype, causal=True):
    hd = cfg.hd
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], cfg.d_model, cfg.hp * hd, dtype),
        "wk": dense_init(ks[1], cfg.d_model, cfg.n_kv_heads * hd, dtype),
        "wv": dense_init(ks[2], cfg.d_model, cfg.n_kv_heads * hd, dtype),
        "wo": dense_init(ks[3], cfg.hp * hd, cfg.d_model, dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), dtype)
        p["k_norm"] = jnp.ones((hd,), dtype)
    return p


def _pick_chunk(S: int, B: int, H: int, T: int, chunk_q: int,
                budget_bytes: float = 256e6) -> int:
    """Query-chunk size: a divisor of S bounding the fp32 score buffer
    B*H*c*T*4 <= budget (the flash-attention memory property, statically)."""
    c = chunk_q
    while c > 16 and (S % c or B * H * c * T * 4 > budget_bytes):
        c //= 2
    while S % c and c > 1:   # S with odd factors: fall to a true divisor
        c -= 1
    return max(c, 1)


def mha_forward(params, cfg, x, positions, lin: LinearFns, *, causal: bool = True,
                kv_x: Optional[jnp.ndarray] = None, kv_positions=None,
                ext_kv=None, path_prefix: str = "", chunk_q: int = 1024):
    """Full attention over a sequence (training / prefill / encoder / cross-attn).

    x [B,S,d]. If kv_x is given this is cross-attention (non-causal over kv_x).

    ``ext_kv`` — optional ``(k, v, positions)`` of ALREADY-PROJECTED (post
    qk-norm, post-RoPE) external K/V lanes [B,E,K,hd]/[B,E] prepended to
    this call's own K/V: the suffix-prefill path attends over cached
    shared-prefix pages without recomputing them (docs/prefix_cache.md).
    Lanes whose position fails the causal mask (the engine marks unused
    lanes with a huge position) contribute exact zeros to the softmax, so
    a suffix prefill over valid ext lanes is bitwise the corresponding
    rows of a full prefill.

    Layout notes (GSPMD-friendliness, DESIGN.md §5): heads are kept *flat*
    [B,S,H,hd] and KV heads are replicated to H via ``jnp.repeat`` (classic
    kv-replication tensor parallelism) — the grouped [K,G] form cannot be
    sharded when K < the model-axis size, the flat form shards whenever
    H % model == 0. Long sequences are processed in query chunks to bound
    the score buffer (the pure-JAX analogue of flash attention's memory
    behaviour); the chunk adapts so the fp32 scores stay within budget.
    """
    B, S, _ = x.shape
    hd, K, H = cfg.hd, cfg.n_kv_heads, cfg.hp
    G = H // K
    src = kv_x if kv_x is not None else x
    T = src.shape[1]
    if kv_positions is None:
        kv_positions = positions if kv_x is None else jnp.arange(T)[None, :].repeat(B, 0)

    q = lin.dense(x, params["wq"], params.get("bq"), path_prefix + "q")
    k = lin.dense(src, params["wk"], params.get("bk"), path_prefix + "k")
    v = lin.dense(src, params["wv"], params.get("bv"), path_prefix + "v")
    q = q.reshape(B, S, H, hd)
    k = k.reshape(B, T, K, hd)
    v = v.reshape(B, T, K, hd)
    if cfg.qk_norm:
        q = head_rmsnorm(params["q_norm"], q)
        k = head_rmsnorm(params["k_norm"], k)
    if kv_x is None and cfg.rope_theta > 0:  # self-attention uses RoPE (except whisper-style)
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, kv_positions, cfg.rope_theta)
    if ext_kv is not None:   # shared-prefix lanes ride in front, pre-replication
        ek, ev, epos = ext_kv
        k = jnp.concatenate([ek.astype(k.dtype), k], axis=1)
        v = jnp.concatenate([ev.astype(v.dtype), v], axis=1)
        kv_positions = jnp.concatenate([epos, kv_positions], axis=1)
        T = k.shape[1]
    if G > 1:   # kv-replication: [B,T,K,hd] -> [B,T,H,hd]
        k = jnp.repeat(k, G, axis=2)
        v = jnp.repeat(v, G, axis=2)

    scale = 1.0 / math.sqrt(hd)
    window = cfg.sliding_window

    def attend_chunk(q_chunk, qpos_chunk):
        # q_chunk [B,c,H,hd] -> [B,c,H,hd]
        s = jnp.einsum("bshd,bthd->bhst", q_chunk, k).astype(jnp.float32) * scale
        if causal and kv_x is None:
            m = qpos_chunk[:, None, :, None] >= kv_positions[:, None, None, :]
            if window:
                m &= (qpos_chunk[:, None, :, None] - kv_positions[:, None, None, :]) < window
            s = jnp.where(m, s, -1e30)
        p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
        return jnp.einsum("bhst,bthd->bshd", p, v)

    def attend_chunk_flash(q_chunk, qpos_chunk, block_kv: int):
        """Online-softmax over KV blocks: the [c, T] score matrix never
        materializes — only [c, block_kv] tiles and running (max, denom,
        acc) carries live at once (the in-JAX analogue of our Pallas
        decode/flash kernels; §Perf iteration 1)."""
        c = q_chunk.shape[1]
        nkv = T // block_kv
        kb = k.reshape(B, nkv, block_kv, H, hd).transpose(1, 0, 2, 3, 4)
        vb = v.reshape(B, nkv, block_kv, H, hd).transpose(1, 0, 2, 3, 4)
        pb = kv_positions.reshape(B, nkv, block_kv).transpose(1, 0, 2)
        m0 = jnp.full((B, H, c, 1), -1e30, jnp.float32)
        l0 = jnp.zeros((B, H, c, 1), jnp.float32)
        a0 = jnp.zeros((B, H, c, hd), jnp.float32)

        def body(carry, blk):
            m, l, acc = carry
            kc, vc, pc = blk
            s = jnp.einsum("bshd,bthd->bhst", q_chunk, kc).astype(jnp.float32) * scale
            if causal and kv_x is None:
                msk = qpos_chunk[:, None, :, None] >= pc[:, None, None, :]
                if window:
                    msk &= (qpos_chunk[:, None, :, None]
                            - pc[:, None, None, :]) < window
                s = jnp.where(msk, s, -1e30)
            m_new = jnp.maximum(m, s.max(-1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new)
            l = l * alpha + p.sum(-1, keepdims=True)
            acc = acc * alpha + jnp.einsum("bhst,bthd->bhsd", p,
                                           vc.astype(jnp.float32))
            return (m_new, l, acc), None

        (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), (kb, vb, pb))
        out = acc / jnp.maximum(l, 1e-30)
        return out.transpose(0, 2, 1, 3).astype(v.dtype)      # [B,c,H,hd]

    # §Perf iterations 2/3: online-softmax (flash) only pays off once T is
    # large enough that score-matrix traffic dominates its loop-carry
    # traffic (empirically T > 8k); below that, plain chunked attention
    # with a 1 GB score budget (64 MB/device under 16-way head sharding)
    # minimizes K/V re-reads.
    block_kv = 1024 if T % 1024 == 0 else (512 if T % 512 == 0 else 0)
    use_flash = block_kv > 0 and T > 8192 and kv_x is None
    if use_flash:
        chunk = _pick_chunk(S, B, H, block_kv, max(chunk_q, 2048))
    else:
        chunk = _pick_chunk(S, B, H, T, chunk_q, budget_bytes=1e9)
    att = ((lambda qc, pc: attend_chunk_flash(qc, pc, block_kv))
           if use_flash else attend_chunk)
    if S <= chunk:
        out = att(q, positions)
    else:
        n = S // chunk
        qc = q.reshape(B, n, chunk, H, hd).transpose(1, 0, 2, 3, 4)
        pc = positions.reshape(B, n, chunk).transpose(1, 0, 2)
        out = jax.lax.map(lambda args: att(*args), (qc, pc))
        out = out.transpose(1, 0, 2, 3, 4).reshape(B, S, H, hd)

    out = out.reshape(B, S, H * hd)
    return lin.dense(out, params["wo"], params.get("bo"), path_prefix + "o")


def quantize_head(x):
    """Per-head symmetric int8 quantization. x [..., hd] ->
    (q int8 [..., hd], scale f32 [..., 1])."""
    scale = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1, keepdims=True) / 127.0
    scale = jnp.maximum(scale, 1e-8)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127).astype(jnp.int8)
    return q, scale


# ---------------------------------------------------------------------------
# Decode attention internals (shared by the dense, quantized and paged paths)
# ---------------------------------------------------------------------------

def _decode_qkv(params, cfg, x, pos, lin: LinearFns, path_prefix: str):
    """Single-token q/k/v projections + qk-norm + RoPE. x [B,1,d]; pos [B].
    Returns q [B,1,H,hd], k/v [B,1,K,hd]."""
    B = x.shape[0]
    hd, K, H = cfg.hd, cfg.n_kv_heads, cfg.hp
    q = lin.dense(x, params["wq"], params.get("bq"), path_prefix + "q").reshape(B, 1, H, hd)
    k = lin.dense(x, params["wk"], params.get("bk"), path_prefix + "k").reshape(B, 1, K, hd)
    v = lin.dense(x, params["wv"], params.get("bv"), path_prefix + "v").reshape(B, 1, K, hd)
    if cfg.qk_norm:
        q = head_rmsnorm(params["q_norm"], q)
        k = head_rmsnorm(params["k_norm"], k)
    if cfg.rope_theta > 0:
        q = apply_rope(q, pos[:, None], cfg.rope_theta)
        k = apply_rope(k, pos[:, None], cfg.rope_theta)
    return q, k, v


def _decode_valid(cfg, pos, T: int, ring: bool):
    """[B,T] validity of cache lanes for a query at position pos."""
    t_ar = jnp.arange(T)[None, :]
    if ring:
        # slot s holds absolute position p: p % T == s, p <= pos, p > pos - T
        cycle = (pos[:, None] - t_ar) // T
        abs_pos = cycle * T + t_ar
        valid = (abs_pos >= 0) & (abs_pos <= pos[:, None])
        if cfg.sliding_window:
            valid &= (pos[:, None] - abs_pos) < cfg.sliding_window
    else:
        valid = (t_ar <= pos[:, None])
        if cfg.sliding_window:
            valid &= (pos[:, None] - t_ar) < cfg.sliding_window
    return valid


def _decode_attend(params, cfg, q, cache_k, cache_v, valid, lin: LinearFns,
                   path_prefix: str):
    """Attention of one query token against a dense [B,T,K,hd] cache view.

    Grouped GQA einsum (NOT kv-replicated): with the cache sharded on T,
    scores stay T-local and only the softmax max/sum and the T-contraction
    psum cross chips (flash-decode style). Repeating KV to H here would
    make GSPMD reshard the whole repeated cache (all-to-all) every layer."""
    B = q.shape[0]
    hd, K, H = cfg.hd, cfg.n_kv_heads, cfg.hp
    G = H // K
    qg = q.reshape(B, 1, K, G, hd)
    scale = 1.0 / math.sqrt(hd)
    s = jnp.einsum("bskgh,btkh->bkgst", qg, cache_k).astype(jnp.float32) * scale
    s = jnp.where(valid[:, None, None, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1).astype(cache_v.dtype)
    out = jnp.einsum("bkgst,btkh->bskgh", p, cache_v).reshape(B, 1, H * hd)
    return lin.dense(out, params["wo"], params.get("bo"), path_prefix + "o")


def _decode_attend_quant(params, cfg, q, cache_k, cache_ks, cache_v, cache_vs,
                         valid, lin: LinearFns, path_prefix: str, out_dtype):
    """Attention of one query token against an int8 [B,T,K,hd] cache view
    with per-entry f32 scales [B,T,K,1]."""
    B = q.shape[0]
    hd, K, H = cfg.hd, cfg.n_kv_heads, cfg.hp
    G = H // K
    qg = q.reshape(B, 1, K, G, hd)
    scale = 1.0 / math.sqrt(hd)
    # int8 scores with per-entry rescale: q·(kq*ks) == (q·kq)*ks
    s = jnp.einsum("bskgh,btkh->bkgst", qg.astype(jnp.float32),
                   cache_k.astype(jnp.float32))
    s = s * cache_ks[..., 0].transpose(0, 2, 1)[:, :, None, None, :] * scale
    s = jnp.where(valid[:, None, None, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    pv = p * cache_vs[..., 0].transpose(0, 2, 1)[:, :, None, None, :]
    out = jnp.einsum("bkgst,btkh->bskgh", pv,
                     cache_v.astype(jnp.float32)).astype(out_dtype)
    out = out.reshape(B, 1, H * hd)
    return lin.dense(out, params["wo"], params.get("bo"), path_prefix + "o")


# ---------------------------------------------------------------------------
# Paged KV primitives (vLLM-style block pool + per-slot block tables)
# ---------------------------------------------------------------------------
#
# A paged cache stores K/V in a *pool* of fixed-size pages shared by all
# sequence slots of one client: pool [P, block, ...]. Each slot maps its
# logical token positions through a block table row tbl[b]: position t lives
# at pool[tbl[b, t // block], t % block]. A slot therefore only occupies
# pages for tokens it has actually produced; freeing a sequence returns its
# pages to the pool. Unallocated table entries may alias live pages of other
# slots — reads through them are always masked by position validity, and all
# writes are either bounded by true lengths (prefill) or dropped for
# inactive slots (decode), so cross-slot corruption is impossible.
#
# READS go through the table-aware decode kernel (kernels/decode_attn): the
# block table is scalar-prefetched, the pools stay in HBM, and the kernel
# walks each row's table in compute blocks of several pages (grid (rows,
# blocks)), copying a live block's pages straight out of the pool while the
# previous block computes. The dead-page rule: only pages that hold a
# position in the row's window [pos - window + 1, pos] and name a page of
# the pool are read — an entry past the row's last live page is never
# dereferenced, and a row masked out of the step (``active`` False) attends
# at position -1, so it reads no page at all. Attention math is the
# kernel's blocked online softmax, byte-identical between the bank-wide
# masked decode and the engine's compacted decode (the kernel's custom_vmap
# rule folds a vmapped client axis into extra pool pages, so both are
# literally the same computation). ``_ORACLE`` reroutes the read through the
# gather-based test oracle (same blocked math on a materialized dense view)
# — tests only.

_ORACLE = False


@contextmanager
def paged_gather_oracle():
    """TEST ORACLE: route paged decode reads through gather_paged_kv + the
    identical blocked kernel math. Byte-equality of a decode under this
    context and without it is the paged kernel's correctness contract.
    The flag is read at TRACE time: use only around direct model calls,
    never while constructing engines (their memoized jitted steps would
    bake the oracle in)."""
    global _ORACLE
    _ORACLE = True
    try:
        yield
    finally:
        _ORACLE = False


def _paged_attend(params, cfg, q, pools, tbl, pos, lin: LinearFns,
                  path_prefix: str, active=None):
    """Attention of one query token read in place from paged pools.

    q [B,1,H,hd]; pools = (k, v) or (k, k_s, v, v_s) page pools; tbl
    [B, n_blocks]; pos [B]; active [B] bool (None = all; an inactive row
    reads no page and attends to nothing). Returns [B,1,d_model] after the
    o-projection."""
    if active is not None:
        pos = jnp.where(active, pos, -1)
    B = q.shape[0]
    hd, K, H = cfg.hd, cfg.n_kv_heads, cfg.hp
    G = H // K
    qg = q.reshape(B, K, G, hd)
    kw = {}
    if len(pools) == 4:
        pool_k, pool_ks, pool_v, pool_vs = pools
        kw = {"k_scale": pool_ks, "v_scale": pool_vs}
    else:
        pool_k, pool_v = pools
    out = decode_attn(qg, pool_k, pool_v, pos, window=cfg.sliding_window,
                      block_tbl=tbl, via_gather=_ORACLE, **kw)
    out = out.reshape(B, 1, H * hd)
    return lin.dense(out, params["wo"], params.get("bo"), path_prefix + "o")

def paged_token_write(pool, tbl, pos, x, active=None):
    """Write one token's row x [B, ...] at logical position pos [B] through
    the block table. Rows with active == False are dropped (their target
    page index is pushed out of bounds), which is what lets a bank-wide
    masked decode share one pool: inactive slots never touch it.

    The write is a custom_vmap op: when the masked decode vmaps a bank of
    clients over a SHARED (unbatched) global pool, the rule flattens the
    client axis into more rows and issues ONE scatter — a naive vmap of a
    scatter onto an unbatched operand would broadcast the pool per lane
    (C copies of the whole pool per layer). Clients' pages are disjoint by
    the engine allocator's page-range invariant, so the flattened scatter
    touches disjoint slots."""
    active = jnp.ones(tbl.shape[:1], bool) if active is None else active
    return _paged_token_write(pool, tbl, pos.astype(jnp.int32), x, active)


@custom_vmap
def _paged_token_write(pool, tbl, pos, x, active):
    P, blk = pool.shape[:2]
    page = jnp.take_along_axis(tbl, (pos // blk)[:, None], axis=1)[:, 0]
    page = jnp.where(active, page, P)                # P is out of bounds
    return pool.at[page, pos % blk].set(x.astype(pool.dtype), mode="drop")


@_paged_token_write.def_vmap
def _paged_token_write_vmap(axis_size, in_batched, pool, tbl, pos, x, active):
    pool_b, tbl_b, pos_b, x_b, act_b = in_batched
    assert tbl_b or pool_b, \
        "paged_token_write under vmap: lanes must differ in table or pool"
    C = axis_size
    lift = lambda a, b: a if b else jnp.broadcast_to(a, (C,) + a.shape)
    tbl = lift(tbl, tbl_b)
    pos, x, active = lift(pos, pos_b), lift(x, x_b), lift(active, act_b)
    B = tbl.shape[1]
    flat = lambda a: a.reshape((C * B,) + a.shape[2:])
    if pool_b:
        # batched per-client pools: fold clients into pages ([C,P]->[C*P])
        P = pool.shape[1]
        pool = pool.reshape((C * P,) + pool.shape[2:])
        tbl = tbl + (jnp.arange(C, dtype=tbl.dtype) * P)[:, None, None]
        out = _paged_token_write(pool, flat(tbl), flat(pos), flat(x), flat(active))
        return out.reshape((C, P) + out.shape[1:]), True
    # shared global pool: one scatter for all lanes, result stays shared
    # (clients' pages are disjoint by the allocator's page-range invariant)
    out = _paged_token_write(pool, flat(tbl), flat(pos), flat(x), flat(active))
    return out, False


def paged_prefill_write(pool, tbl, x, lengths=None, start=None):
    """Scatter prefill rows x [B, S, ...] into the pool through the block
    table, writing ONLY positions < lengths — right-pad positions never
    touch the pool (pages beyond a row's true length stay unallocated,
    unlike the dense path which writes stale pad K/V to be overwritten
    later). lengths None writes all S positions.

    ``start`` [B] int32 (optional) offsets every row's writes by that many
    LOGICAL positions: token i of x lands at cache position start+i — the
    suffix-prefill path, which skips a row's shared-prefix pages and only
    fills from its first non-cached token onward."""
    P, blk = pool.shape[:2]
    B, S = x.shape[:2]
    t = jnp.arange(S)
    if start is None:
        page = jnp.take(tbl, t // blk, axis=1)       # [B, S]
        off = jnp.broadcast_to((t % blk)[None, :], (B, S))
    else:
        logical = jnp.asarray(start, jnp.int32)[:, None] + t[None, :]
        page = jnp.take_along_axis(tbl, logical // blk, axis=1, mode="clip")
        off = logical % blk
    if lengths is not None:
        valid = t[None, :] < jnp.broadcast_to(jnp.asarray(lengths, jnp.int32),
                                              (B,))[:, None]
        page = jnp.where(valid, page, P)             # P is out of bounds
    return pool.at[page, off].set(x.astype(pool.dtype), mode="drop")


def mha_decode_quant(params, cfg, x, cache_k, cache_ks, cache_v, cache_vs,
                     pos, lin: LinearFns, *, path_prefix: str = "",
                     ring: bool = False):
    """Decode against an int8-quantized KV cache (beyond-paper §Perf
    optimization: halves the HBM bytes of the cache read, the dominant
    roofline term of decode shapes).

    cache_k/v int8 [B,T,K,hd]; cache_ks/vs f32 [B,T,K,1] per-head scales.
    Returns (out, new_k, new_ks, new_v, new_vs)."""
    T = cache_k.shape[1]
    q, k, v = _decode_qkv(params, cfg, x, pos, lin, path_prefix)
    kq, ks = quantize_head(k)
    vq, vs = quantize_head(v)
    slot = (pos % T) if ring else pos
    idx = slot[:, None, None, None]
    t_iota = jnp.arange(T)[None, :, None, None]
    write = t_iota == idx
    cache_k = jnp.where(write, kq, cache_k)
    cache_ks = jnp.where(write, ks, cache_ks)
    cache_v = jnp.where(write, vq, cache_v)
    cache_vs = jnp.where(write, vs, cache_vs)
    valid = _decode_valid(cfg, pos, T, ring)
    out = _decode_attend_quant(params, cfg, q, cache_k, cache_ks, cache_v,
                               cache_vs, valid, lin, path_prefix, x.dtype)
    return out, cache_k, cache_ks, cache_v, cache_vs


def mha_decode(params, cfg, x, cache_k, cache_v, pos, lin: LinearFns,
               *, path_prefix: str = "", ring: bool = False):
    """Single-token decode. x [B,1,d]; cache_k/v [B,T,K,hd]; pos [B] int32.

    ring=True treats the cache as a ring buffer of size T (< full context):
    slot = pos % T, validity derived from absolute positions — the
    sliding-window long-context variant (cfg.sliding_window must be <= T).

    Returns (out [B,1,d], new_k, new_v).
    """
    T = cache_k.shape[1]
    q, k, v = _decode_qkv(params, cfg, x, pos, lin, path_prefix)

    # Write this token's K/V at its slot (per batch row). The write is an
    # ELEMENTWISE select over the T axis (not a scatter): per-row vector
    # scatters defeat GSPMD partitioning of a T-sharded cache (it falls back
    # to all-to-all resharding of the whole cache every layer), while the
    # broadcast-compare select partitions locally on every axis.
    slot = (pos % T) if ring else pos
    idx = slot[:, None, None, None]
    t_iota = jnp.arange(T)[None, :, None, None]
    write = t_iota == idx
    cache_k = jnp.where(write, k.astype(cache_k.dtype), cache_k)
    cache_v = jnp.where(write, v.astype(cache_v.dtype), cache_v)

    valid = _decode_valid(cfg, pos, T, ring)
    out = _decode_attend(params, cfg, q, cache_k, cache_v, valid, lin, path_prefix)
    return out, cache_k, cache_v


def mha_decode_paged(params, cfg, x, pool_k, pool_v, tbl, pos, lin: LinearFns,
                     *, active=None, path_prefix: str = ""):
    """Single-token decode against a paged KV cache.

    pool_k/v [P, block, K, hd] page pools shared across the B slots;
    tbl [B, n_blocks] block table; pos [B]; active [B] bool (None = all).
    The new token's K/V is written through the table (dropped for inactive
    rows), then the table-aware kernel attends over the pages in place —
    no dense view is gathered. Returns (out, new_pool_k, new_pool_v)."""
    q, k, v = _decode_qkv(params, cfg, x, pos, lin, path_prefix)
    pool_k = paged_token_write(pool_k, tbl, pos, k[:, 0], active)
    pool_v = paged_token_write(pool_v, tbl, pos, v[:, 0], active)
    out = _paged_attend(params, cfg, q, (pool_k, pool_v), tbl, pos, lin,
                        path_prefix, active)
    return out, pool_k, pool_v


def mha_decode_quant_paged(params, cfg, x, pool_k, pool_ks, pool_v, pool_vs,
                           tbl, pos, lin: LinearFns, *, active=None,
                           path_prefix: str = ""):
    """Paged + int8-quantized decode: pools hold int8 entries [P,block,K,hd]
    and f32 per-head scales [P,block,K,1]. Same contract as
    ``mha_decode_paged``; the kernel dequantizes per page while streaming.
    Returns (out, k, ks, v, vs) pools."""
    q, k, v = _decode_qkv(params, cfg, x, pos, lin, path_prefix)
    kq, ks = quantize_head(k)
    vq, vs = quantize_head(v)
    pool_k = paged_token_write(pool_k, tbl, pos, kq[:, 0], active)
    pool_ks = paged_token_write(pool_ks, tbl, pos, ks[:, 0], active)
    pool_v = paged_token_write(pool_v, tbl, pos, vq[:, 0], active)
    pool_vs = paged_token_write(pool_vs, tbl, pos, vs[:, 0], active)
    out = _paged_attend(params, cfg, q, (pool_k, pool_ks, pool_v, pool_vs),
                        tbl, pos, lin, path_prefix, active)
    return out, pool_k, pool_ks, pool_v, pool_vs


def cross_decode(params, cfg, x, enc_k, enc_v, lin: LinearFns, *, path_prefix: str = "xattn_"):
    """Cross-attention decode against a fixed encoder cache. x [B,1,d]."""
    B = x.shape[0]
    hd, K, H = cfg.hd, cfg.n_kv_heads, cfg.hp
    G = H // K
    q = lin.dense(x, params["wq"], params.get("bq"), path_prefix + "q").reshape(B, 1, K, G, hd)
    s = jnp.einsum("bskgh,btkh->bkgst", q, enc_k).astype(jnp.float32) / math.sqrt(hd)
    p = jax.nn.softmax(s, axis=-1).astype(enc_v.dtype)
    out = jnp.einsum("bkgst,btkh->bskgh", p, enc_v).reshape(B, 1, H * hd)
    return lin.dense(out, params["wo"], params.get("bo"), path_prefix + "o")


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------

def mlp_init(key, cfg, dtype, d_ff=None, gelu=False, bias=False):
    d_ff = d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    if gelu:
        p = {"fc1": dense_init(ks[0], cfg.d_model, d_ff, dtype),
             "fc2": dense_init(ks[1], d_ff, cfg.d_model, dtype)}
        if bias:
            p["b1"] = jnp.zeros((d_ff,), dtype)
            p["b2"] = jnp.zeros((cfg.d_model,), dtype)
        return p
    return {"gate": dense_init(ks[0], cfg.d_model, d_ff, dtype),
            "up": dense_init(ks[1], cfg.d_model, d_ff, dtype),
            "down": dense_init(ks[2], d_ff, cfg.d_model, dtype)}


def mlp_forward(params, x, lin: LinearFns, *, path_prefix: str = ""):
    if "fc1" in params:  # GELU MLP (whisper-style)
        h = lin.dense(x, params["fc1"], params.get("b1"), path_prefix + "fc1")
        h = jax.nn.gelu(h)
        return lin.dense(h, params["fc2"], params.get("b2"), path_prefix + "fc2")
    g = lin.dense(x, params["gate"], None, path_prefix + "gate")
    u = lin.dense(x, params["up"], None, path_prefix + "up")
    return lin.dense(jax.nn.silu(g) * u, params["down"], None, path_prefix + "down")
