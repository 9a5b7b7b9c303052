"""The hot-path Pallas kernels compile for a TPU v5e at qwen3-4b widths.

Each case lowers and compiles a kernel for a described (not attached)
``v5e:2x2`` topology, so the TPU compiler's own checks run here without a
chip: block shapes against the (8, 128) tiling, scoped VMEM, and the custom
call that puts the kernel in the program. Nothing executes; results are
checked by the CPU tests against the kernels' jnp twins and on the chip by
``chip_smoke.py``.

Widths (configs/qwen3_4b.py): 8 KV heads, 4 query heads per KV head,
head_dim 128, d_model 2560, 32 query heads (o: 4096 in), d_ff 9728. Paged KV
with 16-token pages over a 2048-token context. SGMV covers every shape the
serving engine hands it: decode rows (``block_t=1``) at the row buckets and
compacted-prefill suffix buckets (``block_t=S``) up to 2048 tokens.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (AxisType, Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.kernels import decode_attn, sgmv

K, G, HD, PAGE, MAX_SEQ = 8, 4, 128, 16, 2048
# (din, dout) of the LoRA targets: q (d_model -> heads*hd), o (heads*hd ->
# d_model), down (d_ff -> d_model)
LORA_DIMS = {2560: 4096, 4096: 2560, 9728: 2560}
RANK, N_ADAPTERS = 8, 6


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache off around these
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _paged_attn_fn(quant):
    if quant:
        return lambda q, k, v, t, p, ks, vs: decode_attn(
            q, k, v, p, block_tbl=t, k_scale=ks, v_scale=vs, interpret=False)
    return lambda q, k, v, t, p: decode_attn(q, k, v, p, block_tbl=t,
                                             interpret=False)


def _paged_attn_shapes(rows, quant):
    nb = MAX_SEQ // PAGE
    pages = rows * nb
    kv = jnp.int8 if quant else jnp.bfloat16
    shapes = [((rows, K, G, HD), jnp.bfloat16), ((pages, PAGE, K, HD), kv),
              ((pages, PAGE, K, HD), kv), ((rows, nb), jnp.int32),
              ((rows,), jnp.int32)]
    if quant:
        shapes += [((pages, PAGE, K, 1), jnp.float32)] * 2
    return shapes


def _kernel_calls(hlo: str, name: str) -> int:
    return sum(1 for line in hlo.splitlines()
               if "tpu_custom_call" in line and f"/{name}/" in line)


@pytest.mark.parametrize("rows", [4, 32, 64])
@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8"])
def test_paged_decode_attn_compiles(one_chip, rows, quant):
    hlo = _compile(_paged_attn_fn(quant), one_chip,
                   *_paged_attn_shapes(rows, quant))
    name = "paged_decode_attn_int8" if quant else "paged_decode_attn"
    assert _kernel_calls(hlo, name) == 1


@pytest.mark.parametrize("mesh_shape", [(4, 1), (1, 4)],
                         ids=["rows_over_data", "heads_over_model"])
def test_kernels_compile_inside_a_sharded_program(topo, mesh_shape):
    """XLA cannot partition a Mosaic kernel: under a 2x2 mesh the kernels
    run per device (kernels/mesh.py), rows over ``data``, heads over
    ``model``."""
    mesh = Mesh(np.array(topo.devices).reshape(mesh_shape), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    page_split = NamedSharding(mesh, P("data"))       # pools split by page
    rows = 8

    def put(shapes, sharding):
        return [jax.ShapeDtypeStruct(s, d, sharding=sharding)
                for s, d in shapes]

    attn = put(_paged_attn_shapes(rows, False), page_split)
    din, dout = 4096, LORA_DIMS[4096]
    lora = put([((rows, din), jnp.bfloat16),
                ((N_ADAPTERS, din, RANK), jnp.bfloat16),
                ((N_ADAPTERS, RANK, dout), jnp.bfloat16),
                ((rows,), jnp.int32)], NamedSharding(mesh, P()))
    fn = lambda x, a, b, i: sgmv(x, a, b, i, block_t=1, interpret=False)
    with jax.set_mesh(mesh):
        hlo_attn = jax.jit(_paged_attn_fn(False)).lower(*attn).compile()
        hlo_lora = jax.jit(fn).lower(*lora).compile()
    assert _kernel_calls(hlo_attn.as_text(), "paged_decode_attn") == 1
    assert _kernel_calls(hlo_lora.as_text(), "sgmv") == 1


def _sgmv_case(sharding, rows, block_t, din):
    dout = LORA_DIMS[din]
    fn = lambda x, a, b, ids: sgmv(x, a, b, ids, block_t=block_t, scale=2.0,
                                   interpret=False)
    shapes = [((rows * block_t, din), jnp.bfloat16),
              ((N_ADAPTERS, din, RANK), jnp.bfloat16),
              ((N_ADAPTERS, RANK, dout), jnp.bfloat16), ((rows,), jnp.int32)]
    hlo = _compile(fn, sharding, *shapes)
    assert _kernel_calls(hlo, "sgmv") == 1
    out = jax.eval_shape(fn, *(jax.ShapeDtypeStruct(s, d) for s, d in shapes))
    assert out.shape == (rows * block_t, dout)


@pytest.mark.parametrize("din", sorted(LORA_DIMS))
@pytest.mark.parametrize("rows", [4, 16, 64])
def test_sgmv_decode_rows_compile(one_chip, rows, din):
    """Compacted decode: one token per row, each row its own adapter."""
    _sgmv_case(one_chip, rows, 1, din)


@pytest.mark.parametrize("din", sorted(LORA_DIMS))
@pytest.mark.parametrize("bucket", [1, 4, 8, 512, 2048])
def test_sgmv_prefill_buckets_compile(one_chip, bucket, din):
    """Compacted prefill: one S-token block per row (S = suffix bucket)."""
    _sgmv_case(one_chip, 4, bucket, din)
