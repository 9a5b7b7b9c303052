"""Jit'd public wrapper for the SGMV kernel (padding + dispatch + fallback)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.mesh import ROWS, per_device
from repro.kernels.sgmv.ref import sgmv_ref
from repro.kernels.sgmv.sgmv import sgmv_pallas, sgmv_stream


# bytes of x per kernel token block: with double buffering and the kernel's
# f32 copy, 4x this stays well inside v5e's 16 MiB default scoped VMEM
_MAX_BLOCK_BYTES = 512 * 1024


def _pad_to(x, axis, multiple):
    n = x.shape[axis]
    pad = (-n) % multiple
    if pad == 0:
        return x, n
    width = [(0, 0)] * x.ndim
    width[axis] = (0, pad)
    return jnp.pad(x, width), n


@functools.partial(jax.jit, static_argnames=("block_t", "block_d", "scale",
                                             "use_kernel", "interpret"))
def sgmv(x, A, B, block_adapter, *, block_t: int = 128, block_d: int = 512,
         scale: float = 1.0, use_kernel: bool = True, interpret: bool = None):
    """Multi-adapter LoRA delta over a packed token buffer.

    x [T, din]; A [n, din, r]; B [n, r, dout]; block_adapter [T // block_t]
    (id per token block; negative = dead block). Arbitrary shapes — padding
    to tile multiples is handled here. ``interpret=None`` auto-selects by
    backend: the compiled Pallas kernel on TPU, its byte-identical jnp
    stream twin (``sgmv_stream``) elsewhere — the twin skips the grid
    interpreter whose per-block overhead dwarfs the rank-r math, and is
    also byte-identical to a per-client vmapped LoRA application (the
    compacted-decode exactness contract). ``block_t=1`` degenerates to one
    adapter per row — how the engine's compacted decode tick applies
    per-row client adapters; production TPU callers should sort rows by
    client into MXU-sized blocks instead. Under an ambient mesh the body
    runs per device, rows split over the batch axes
    (``kernels.mesh.per_device``)."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if not use_kernel:
        return sgmv_ref(x, A, B, block_adapter, block_t=block_t, scale=scale)
    local = functools.partial(_sgmv, block_t=block_t, block_d=block_d,
                              scale=scale, interpret=interpret)
    # rows split across devices only when each device's rows are whole
    # blocks of the ids it holds
    aligned = x.shape[0] == block_adapter.shape[0] * block_t
    rows = ROWS if aligned else None
    return per_device(local, ((rows, None), (None,) * 3, (None,) * 3,
                              (rows,)), (rows, None), x, A, B, block_adapter)


def _sgmv(x, A, B, block_adapter, *, block_t, block_d, scale, interpret):
    T0, dout0 = x.shape[0], B.shape[-1]
    x, _ = _pad_to(x, 0, block_t)
    nb = x.shape[0] // block_t
    ids = jnp.full((nb,), -1, jnp.int32).at[:block_adapter.shape[0]].set(block_adapter)
    if interpret:
        return sgmv_stream(x, A, B, ids, block_t=block_t, scale=scale)[:T0]
    x, ids, bt = _kernel_blocks(x, ids, block_t)
    # pad rank to the fp32 sublane tile and dout to the lane tile
    A, _ = _pad_to(A, 2, 8)
    B, _ = _pad_to(B, 1, 8)
    bd = min(block_d, max(128, dout0))
    B, _ = _pad_to(B, 2, bd)
    y = sgmv_pallas(x, A, B, ids, block_t=bt, block_d=bd, scale=scale,
                    interpret=False)
    if bt > block_t:                   # rows were padded up to the 8-row tile
        y = y.reshape(nb, bt, -1)[:, :block_t].reshape(nb * block_t, -1)
    return y[:T0, :dout0]


def _kernel_blocks(x, ids, block_t):
    """Re-block a [nb * block_t, din] buffer for the compiled kernel, whose
    token block must be a multiple of the 8-row sublane tile and small
    enough that x's block, its f32 copy and the streamed adapter fit VMEM.
    The adapter block a caller names (one per row: ``block_t`` = the row's
    token count) is decoupled from the kernel's token block ``bt``:

    * ``block_t`` below a tile multiple: each block is zero-padded up to
      the next multiple of 8 rows (decode rows, short prefill suffixes);
    * larger ``block_t``: split into ``block_t // bt`` kernel blocks that
      repeat the block's adapter id, ``bt`` a multiple of 8 at most
      ``_MAX_BLOCK_BYTES`` of x per block.

    Returns (x, ids, bt); the caller un-pads rows when ``bt > block_t``."""
    nb, din = x.shape[0] // block_t, x.shape[1]
    if block_t % 8:
        bt = -(-block_t // 8) * 8
        x = jnp.pad(x.reshape(nb, block_t, din),
                    ((0, 0), (0, bt - block_t), (0, 0))).reshape(nb * bt, din)
        return x, ids, bt
    bt = block_t
    while bt % 16 == 0 and bt * din * x.dtype.itemsize > _MAX_BLOCK_BYTES:
        bt //= 2
    return x, jnp.repeat(ids, block_t // bt), bt
