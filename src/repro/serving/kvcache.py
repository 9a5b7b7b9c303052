"""KV-cache management for multi-client serving.

Per the Symbiosis split, KV caches are *client-side* runtime state — they
never live with the base executor (paper §1: "the base executor is
stateless"). This module provides:

* ``CacheSpec`` / ``cache_bytes`` — sizing logic used by the engine's
  admission control and by the heterogeneous-placement cost model (§3.4):
  whether a client's cache fits on-device or must be host-offloaded.
  ``serving.engine`` admits a request only if its full context
  (prompt + max_new_tokens) fits the slot depth, and the optional
  ``PlacementRouter`` charges ``cache_bytes`` against fleet HBM for the
  request's lifetime (released when its slots free). The model is
  layout-aware: ``quant=True`` prices int8 entries + per-head f32 scales,
  and ``page_block > 0`` prices the PAGED layout — bytes are charged per
  allocated ``page_block``-token page (the request's context rounded up to
  whole pages) instead of per dense ``max_seq``-deep slot row, which is
  what lets many short requests share the HBM one dense row used to pin.
* sliding-window ring-buffer cache ops (the beyond-paper long-context
  variant for dense archs).
* host-offload accounting: on real TPU hardware the cache is placed with
  ``jax.device_put(..., TransferToMemoryKind("pinned_host"))``; in this CPU
  container we model placement analytically (bytes + PCIe transfer terms),
  which is what the Fig 19 reproduction uses.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import ModelConfig, RWKV, HYBRID, ENCDEC
from repro.common.hardware import Chip


@dataclasses.dataclass(frozen=True)
class CacheSpec:
    """Shape/bytes description of one client's decode state."""
    kind: str                    # "kv" | "rwkv" | "hybrid" | "encdec"
    bytes_per_token: int         # marginal HBM per generated/context token
    fixed_bytes: int             # state independent of seq len (SSM state etc.)

    def total_bytes(self, seq_len: int, batch: int) -> int:
        return self.fixed_bytes * batch + self.bytes_per_token * seq_len * batch


def _dt_bytes(cfg: ModelConfig) -> int:
    return jnp.dtype(cfg.dtype).itemsize


def make_cache_spec(cfg: ModelConfig, *, quant: bool = False) -> CacheSpec:
    """Derive the decode-state spec from a model config.

    ``quant=True`` prices the int8 KV layout: 1-byte entries plus one f32
    scale per head per token for K and V each (pure-KV families only; the
    recurrent/hybrid fixed state is never quantized)."""
    it = _dt_bytes(cfg)
    kv_row = cfg.n_kv_heads * cfg.hd * it * 2          # K+V per layer per token
    if quant:
        kv_row = cfg.n_kv_heads * (cfg.hd * 1 + 4) * 2  # int8 entries + f32 scale
    if cfg.arch == RWKV:
        H = cfg.d_model // cfg.hd
        fixed = cfg.n_layers * (H * cfg.hd * cfg.hd * 4      # wkv state f32
                                + 2 * cfg.d_model * it)      # shift tails
        return CacheSpec("rwkv", 0, fixed)
    if cfg.arch == HYBRID:
        n_attn = cfg.n_layers // cfg.attn_every
        n_mamba = cfg.n_layers - n_attn
        ed = cfg.mamba_expand * cfg.d_model
        fixed = n_mamba * (ed * cfg.d_state * 4 + (cfg.d_conv - 1) * ed * 4)
        return CacheSpec("hybrid", n_attn * kv_row, fixed)
    if cfg.arch == ENCDEC:
        fixed = cfg.n_layers * cfg.n_frontend_tokens * kv_row  # cross-attn cache
        return CacheSpec("encdec", cfg.n_layers * kv_row, fixed)
    per_tok = cfg.n_layers * kv_row
    return CacheSpec("kv", per_tok, 0)


# ---------------------------------------------------------------------------
# Sliding-window ring-buffer cache (beyond-paper dense long-context variant)
# ---------------------------------------------------------------------------

def ring_cache_init(cfg: ModelConfig, batch: int, window: int, dtype=None):
    dtype = dtype or jnp.dtype(cfg.dtype)
    L = cfg.n_layers
    return {
        "k": jnp.zeros((L, batch, window, cfg.n_kv_heads, cfg.hd), dtype),
        "v": jnp.zeros((L, batch, window, cfg.n_kv_heads, cfg.hd), dtype),
        "pos": jnp.zeros((batch,), jnp.int32),
    }


def ring_write(cache_k, cache_v, k, v, pos, window: int):
    """Write one token's K/V at slot pos % window. k/v [B,1,K,hd]; pos [B]."""
    slot = pos % window
    idx = slot[:, None, None, None]
    t_iota = jnp.arange(window)[None, :, None, None]
    write = t_iota == idx
    return jnp.where(write, k, cache_k), jnp.where(write, v, cache_v)


def ring_valid_mask(pos, window: int):
    """[B, window] mask of live slots + their absolute positions.

    Slot s holds absolute position p where p % window == s and p <= pos and
    p > pos - window. Returns (mask [B,W] bool, abs_pos [B,W] int32)."""
    s = jnp.arange(window)[None, :]
    cycle = (pos[:, None] - s) // window
    abs_pos = cycle * window + s
    mask = (abs_pos >= 0) & (abs_pos <= pos[:, None])
    return mask, abs_pos


# ---------------------------------------------------------------------------
# Host-offload placement model (paper §3.4 / Fig 19 reproduction)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class PlacementCost:
    """Per-decode-token latency terms for one client placement (seconds)."""
    compute: float
    transfer: float

    @property
    def total(self):
        return self.compute + self.transfer


def decode_token_cost(cfg: ModelConfig, seq_len: int, *, placement: str,
                      chip: Chip) -> PlacementCost:
    """Analytic per-token decode cost for the §3.4 placements.

    placement:
      'gpu'          — cache + attention on accelerator (fails if cache > HBM)
      'gpu_offload'  — cache on host, attention on accelerator: the *whole
                       window's* K/V crosses PCIe every token (the paper's
                       second baseline; cost grows linearly with context)
      'hetero'       — Symbiosis: cache AND attention on host; only the
                       activations cross PCIe (constant per token), attention
                       runs at host FLOP/s

    Base-layer (linear) compute is identical across placements — it stays on
    the accelerator in all three — so it is excluded (it cancels in the
    comparison; Fig 19 plots inter-token latency dominated by attention).
    """
    spec = make_cache_spec(cfg)
    cache_bytes_total = spec.bytes_per_token * seq_len + spec.fixed_bytes
    # attention flops per token: 2 ops (QK^T, PV) * 2 MAC = 4 * L * H * hd * S
    attn_flops = 4.0 * cfg.n_layers * cfg.n_heads * cfg.hd * seq_len
    act_bytes = cfg.n_layers * cfg.d_model * _dt_bytes(cfg) * 2  # to/from per layer

    if placement == "gpu":
        if cache_bytes_total > chip.hbm_bytes * 0.65:
            return PlacementCost(compute=float("inf"), transfer=0.0)  # OOM
        # HBM-bound: read the whole cache per token.
        return PlacementCost(compute=cache_bytes_total / chip.hbm_bandwidth,
                             transfer=0.0)
    if placement == "gpu_offload":
        return PlacementCost(compute=cache_bytes_total / chip.hbm_bandwidth,
                             transfer=cache_bytes_total / chip.pcie_bandwidth)
    if placement == "hetero":
        # host attention is bound by max(CPU flops, DRAM cache read)
        compute = max(attn_flops / chip.host_flops,
                      cache_bytes_total / chip.host_mem_bandwidth)
        return PlacementCost(compute=compute,
                             transfer=act_bytes / chip.pcie_bandwidth)
    raise ValueError(placement)


def cache_bytes(cfg: ModelConfig, seq_len: int, batch: int = 1, *,
                quant: bool = False, page_block: int = 0) -> int:
    """HBM bytes of one client's decode state for ``seq_len`` context.

    ``page_block > 0`` charges the paged layout: the context is rounded up
    to whole pages (what the engine's allocator actually pins), instead of
    the caller pre-rounding to a dense ``max_seq`` row."""
    if page_block:
        seq_len = -(-seq_len // page_block) * page_block
    return make_cache_spec(cfg, quant=quant).total_bytes(seq_len, batch)
