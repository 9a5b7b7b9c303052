"""Pure-jnp oracle for blocked GQA decode attention (dense or paged cache)."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def paged_view(pool, tbl):
    """Gather a dense per-slot cache view from a page pool — the canonical
    block-table gather. Since the table-aware kernel landed, nothing on the
    serving decode path materializes this view any more; it survives as the
    TEST ORACLE (``decode_attn(via_gather=True)``) and for host-side
    debugging.

    pool [P, block, ...]; tbl [B, n_blocks] int32 page ids.
    Returns [B, n_blocks * block, ...]. An entry that names no page of the
    pool (the engine's out-of-range sentinel) reads as zeros, as in the
    kernels; lanes of other unallocated entries hold unrelated data —
    callers must mask by position validity, exactly as with an
    uninitialized dense cache."""
    blk = pool.shape[1]
    B, n_blocks = tbl.shape
    v = pool.at[tbl].get(mode="fill", fill_value=0)
    return v.reshape(B, n_blocks * blk, *pool.shape[2:])


def gather_paged_kv(k, v, block_tbl):
    """Materialize dense per-row K and V views from paged pools (test oracle
    for the table-aware kernel — see ``decode_attn(via_gather=True)``)."""
    return paged_view(k, block_tbl), paged_view(v, block_tbl)


def decode_attn_ref(q, k, v, pos, *, window: int = 0, block_tbl=None,
                    k_scale=None, v_scale=None):
    """Single-token GQA attention against a KV cache (full, un-blocked
    softmax — the numerical oracle, not byte-comparable to the kernels).

    q [B, K, G, hd]; k/v [B, T, K, hd]; pos [B] int32 (last valid index).
    Optional sliding window. With ``block_tbl`` [B, n_blocks], k/v (and the
    optional scales) are instead page pools [P, block, K, hd] and each row's
    cache is addressed through its block-table row (paged KV layout; see
    serving/kvcache.py). ``k_scale``/``v_scale`` [.., K, 1] switch to the
    int8-quantized cache semantics (entries are dequantized per head).
    Returns out [B, K, G, hd].
    """
    if block_tbl is not None:
        k, v = gather_paged_kv(k, v, block_tbl)
        if k_scale is not None:
            k_scale = paged_view(k_scale, block_tbl)
            v_scale = paged_view(v_scale, block_tbl)
    hd = q.shape[-1]
    T = k.shape[1]
    s = jnp.einsum("bkgh,btkh->bkgt", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / math.sqrt(hd)
    if k_scale is not None:
        s = s * k_scale[..., 0].transpose(0, 2, 1)[:, :, None, :]
    t = jnp.arange(T)[None, :]
    valid = t <= pos[:, None]
    if window:
        valid &= (pos[:, None] - t) < window
    s = jnp.where(valid[:, None, None, :], s, -1e30)
    # a row with no valid key (pos < 0) attends to nothing: zeros, as the
    # kernels give
    p = jnp.where(valid.any(-1)[:, None, None, None],
                  jax.nn.softmax(s, axis=-1), 0.0)
    if v_scale is not None:
        p = p * v_scale[..., 0].transpose(0, 2, 1)[:, :, None, :]
    out = jnp.einsum("bkgt,btkh->bkgh", p, v.astype(jnp.float32))
    return out.astype(q.dtype)
