"""SGMV Pallas TPU kernel: multi-adapter LoRA gather-matmul.

The Punica/S-LoRA op, re-tiled for the TPU memory hierarchy (DESIGN.md §2):
instead of a warp-level gather of adapter weights, the *grid* walks token
blocks and the adapter weights for each block are streamed HBM→VMEM by the
BlockSpec index_map, which reads the block's adapter id from a scalar-
prefetched table (``PrefetchScalarGridSpec``). MXU alignment: token blocks
of 128, dout tiles of 128+; the LoRA rank axis is zero-padded to the fp32
sublane tile (8) by ``ops.sgmv`` so the [bt, r] @ [r, bd] matmul keeps the
MXU fed.

Block i computes  y[i] = (x[i] @ A[id[i]]) @ B[id[i]] * scale  with fp32
accumulation; dead blocks (id < 0) emit zeros via ``pl.when``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _sgmv_kernel(block_adapter,        # scalar-prefetch [nb] int32
                 x_ref,                # [bt, din]
                 a_ref,                # [1, din, r]
                 b_ref,                # [1, r, bd]
                 y_ref,                # [bt, bd]
                 *, scale: float):
    i = pl.program_id(0)
    live = block_adapter[i] >= 0

    @pl.when(live)
    def _():
        x = x_ref[...].astype(jnp.float32)
        a = a_ref[0].astype(jnp.float32)
        b = b_ref[0].astype(jnp.float32)
        h = jnp.dot(x, a, preferred_element_type=jnp.float32)
        y_ref[...] = (jnp.dot(h, b, preferred_element_type=jnp.float32)
                      * scale).astype(y_ref.dtype)

    @pl.when(jnp.logical_not(live))
    def _():
        y_ref[...] = jnp.zeros_like(y_ref)


def sgmv_stream(x, A, B, block_adapter, *, block_t: int, scale: float = 1.0):
    """jnp twin of the SGMV kernel: one lax.scan step per token block, each
    step gathering its block's adapter and running the same two
    ``jnp.dot``s the kernel body runs — byte-identical to the Pallas kernel
    in interpret mode AND to a per-client vmapped LoRA application (the
    shared-weight matmul both lower to), which is what lets the serving
    engine's compacted decode apply per-row adapters through this op while
    staying byte-identical to the masked bank-wide path. Non-TPU backends
    run this twin (the grid interpreter's per-block overhead dwarfs the
    rank-r math); TPU runs the compiled kernel."""
    T, din = x.shape
    nb = T // block_t
    n_adapters = A.shape[0]
    dout = B.shape[-1]
    xb = x.reshape(nb, block_t, din)

    def body(_, inp):
        xi, idx = inp
        safe = jnp.clip(idx, 0, n_adapters - 1)
        a = A[safe].astype(jnp.float32)
        b = B[safe].astype(jnp.float32)
        h = jnp.dot(xi.astype(jnp.float32), a, preferred_element_type=jnp.float32)
        y = jnp.dot(h, b, preferred_element_type=jnp.float32) * scale
        return None, jnp.where(idx >= 0, y, 0.0).astype(x.dtype)

    # no carry -> block steps are independent; unrolling lets XLA overlap
    # the tiny rank-r dots instead of paying loop machinery per block
    _, yb = jax.lax.scan(body, None, (xb, block_adapter),
                         unroll=min(nb, 8))
    return yb.reshape(T, dout)


def sgmv_pallas(x, A, B, block_adapter, *, block_t: int = 128,
                block_d: int = 512, scale: float = 1.0,
                interpret: bool = False):
    """See ref.sgmv_ref for semantics. Shapes must be pre-padded:
    T % block_t == 0, dout % block_d == 0. The kernel body reads the raw
    prefetched ids (their sign gates dead blocks through ``pl.when``); the
    index_maps clamp them, so a dead block still names a valid adapter to
    stream."""
    T, din = x.shape
    n_adapters, _, r = A.shape
    dout = B.shape[-1]
    nb = T // block_t
    nd = dout // block_d

    def clamp(ids, i):
        return jnp.clip(ids[i], 0, n_adapters - 1)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nb, nd),
        in_specs=[
            pl.BlockSpec((block_t, din), lambda i, j, ids: (i, 0)),
            pl.BlockSpec((1, din, r), lambda i, j, ids: (clamp(ids, i), 0, 0)),
            pl.BlockSpec((1, r, block_d), lambda i, j, ids: (clamp(ids, i), 0, j)),
        ],
        out_specs=pl.BlockSpec((block_t, block_d), lambda i, j, ids: (i, j)),
    )
    return pl.pallas_call(
        functools.partial(_sgmv_kernel, scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((T, dout), x.dtype),
        interpret=interpret,
        name="sgmv",
    )(block_adapter, x, A, B)
