"""Sharded-vs-unsharded engine identity (ISSUE 7).

The host mesh (``make_host_mesh()``: (data=1, model=1)) carries the
production axis names on a single device, so the sharded engines must tick
**byte-identically** to the unsharded ones under the full auto sharding
plan — any drift means a constraint changed the program, not just the
layout. The ``tier2_sharded`` cases re-run identity on a real 2x2 host
mesh (CI sets ``XLA_FLAGS=--xla_force_host_platform_device_count=4``)
with ``replicate_base=True``: batch/client-axis sharding with replicated
weights keeps bitwise identity, while tensor-parallel contraction
sharding is allowed last-bit drift (that regime is covered by the
collective audit, not an identity test).

The autouse trace guard doubles as the recompile check: a mesh engine
whose placements flap between committed/uncommitted would recompile on
the hot path and fail the fixture. ``test_mesh_does_not_widen_trace_domain``
pins the declared bucket sets themselves.
"""
import jax
import numpy as np
import pytest

from repro.config import (AdapterConfig, FinetuneConfig, ServeConfig,
                          DENSE, MOE)
from repro.core import symbiosis
from repro.core.engine_spec import BankSpec, EngineSpec
from repro.launch.mesh import make_mesh, make_host_mesh
from repro.serving.engine import Request, ServingEngine
from repro.training.engine import FinetuneEngine
from repro.training.job import FinetuneJob, make_job_stream
from conftest import tiny

METHODS = {
    "lora": AdapterConfig(method="lora", rank=4, alpha=8.0, targets=("q", "v")),
    "ia3": AdapterConfig(method="ia3", targets=("k", "v", "down")),
    "prefix": AdapterConfig(method="prefix", targets=("q", "v"), n_prefix=4),
}


def _serve_stream(cfg, acfg, base, bank, mesh, *, replicate_base=False,
                  keep=None):
    """Drive a 2-client workload through a fresh engine; return the
    generated token arrays keyed by client. ``keep`` (a list) holds the
    engine alive: the trace guard identifies engines by ``id()``, so
    letting one die before the next is built can alias their compile
    records and mis-report a fresh compile as a hot-path recompile."""
    scfg = ServeConfig(n_clients=2, max_seq=32, page_block=8)
    spec = EngineSpec(cfg=cfg, banks=(BankSpec("tenants", acfg, capacity=2),),
                      serve=scfg, mesh=mesh, replicate_base=replicate_base,
                      max_batch_per_client=2)
    eng = ServingEngine(spec, base, [bank])
    if keep is not None:
        keep.append(eng)
    rng = np.random.default_rng(7)
    for c in range(2):
        eng.submit(Request(client_id=c,
                           prompt=rng.integers(0, cfg.vocab, (1, 6))
                           .astype(np.int32),
                           max_new_tokens=4))
    return {r.client_id: np.asarray(r.generated) for r in eng.run()}


def _train_result(cfg, acfg, base, mesh, *, replicate_base=False, n_jobs=1,
                  keep=None):
    """Run n_jobs identical-shape jobs to completion; return their results
    (adapter/opt/losses) ordered by seed. ``keep`` as in _serve_stream."""
    spec = EngineSpec(cfg=cfg, banks=(BankSpec("jobs", acfg, capacity=2),),
                      finetune=FinetuneConfig(max_jobs=4), mesh=mesh,
                      replicate_base=replicate_base)
    eng = FinetuneEngine(spec, base)
    if keep is not None:
        keep.append(eng)
    jobs = [FinetuneJob(acfg=acfg, data=make_job_stream(cfg, 2, 8, seed=3 + i),
                        batch_size=2, seq_len=8, steps=3, seed=3 + i,
                        lr=1e-2, warmup_steps=1, max_grad_norm=1.0,
                        name=f"j{i}")
            for i in range(n_jobs)]
    for j in jobs:
        eng.submit(j)
    eng.run()
    return [j.result for j in jobs]


def _assert_results_equal(got, want, label):
    for a, b in zip(jax.tree.leaves((want.adapter, want.opt)),
                    jax.tree.leaves((got.adapter, got.opt))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=f"{label}: train state diverged")
    np.testing.assert_allclose(got.losses, want.losses, rtol=1e-6,
                               err_msg=f"{label}: losses diverged")


@pytest.mark.parametrize("arch", [DENSE, MOE])
@pytest.mark.parametrize("method", sorted(METHODS))
def test_serving_identity_host_mesh(arch, method):
    """Serving ticks on the (1,1) host mesh are byte-identical to the
    unsharded engine, across PEFT methods and dense/MoE bases."""
    cfg = tiny(arch)
    acfg = METHODS[method]
    base, bank, _ = symbiosis.init_system(cfg, acfg, 2, jax.random.PRNGKey(0))
    keep = []
    ref = _serve_stream(cfg, acfg, base, bank, None, keep=keep)
    got = _serve_stream(cfg, acfg, base, bank, make_host_mesh(), keep=keep)
    assert ref.keys() == got.keys()
    for c in ref:
        np.testing.assert_array_equal(
            got[c], ref[c], err_msg=f"{arch}/{method}: client {c} diverged")


@pytest.mark.parametrize("arch", [DENSE, MOE])
@pytest.mark.parametrize("method", sorted(METHODS))
def test_train_identity_host_mesh(arch, method):
    """Train steps on the (1,1) host mesh leave adapter + optimizer state
    bitwise equal to the unsharded engine."""
    cfg = tiny(arch)
    acfg = METHODS[method]
    base = symbiosis.init_system(cfg, acfg, 1, jax.random.PRNGKey(0))[0]
    keep = []
    (ref,) = _train_result(cfg, acfg, base, None, keep=keep)
    (got,) = _train_result(cfg, acfg, base, make_host_mesh(), keep=keep)
    _assert_results_equal(got, ref, f"{arch}/{method}")


def test_mesh_does_not_widen_trace_domain():
    """Entering a mesh must not add jit bucket keys: the declared trace
    domain is a function of configs only, and the guard (autouse fixture)
    separately proves no compile lands outside it under the mesh."""
    cfg = tiny(DENSE)
    acfg = METHODS["lora"]
    scfg = ServeConfig(n_clients=2, max_seq=32, page_block=8)
    base, bank, _ = symbiosis.init_system(cfg, acfg, 2, jax.random.PRNGKey(0))

    def spec(mesh):
        return EngineSpec(cfg=cfg, banks=(BankSpec("b", acfg, capacity=2),),
                          serve=scfg, finetune=FinetuneConfig(max_jobs=4),
                          mesh=mesh, max_batch_per_client=2)

    plain = ServingEngine(spec(None), base, [bank])
    meshed = ServingEngine(spec(make_host_mesh()), base, [bank])
    assert plain.trace_domain().families() == meshed.trace_domain().families()

    ft_plain = FinetuneEngine(spec(None), base)
    ft_meshed = FinetuneEngine(spec(make_host_mesh()), base)
    assert (ft_plain.trace_domain().families()
            == ft_meshed.trace_domain().families())


# ---------------------------------------------------------------------------
# tier2_sharded: real 2x2 device mesh (CI forces 4 host devices)
# ---------------------------------------------------------------------------
_needs_four = pytest.mark.skipif(
    jax.device_count() < 4,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=4")


@pytest.mark.tier2_sharded
@_needs_four
@pytest.mark.parametrize("method", ["lora", "ia3"])
def test_serving_identity_2x2(method):
    """2x2 mesh, replicated base: client-axis sharding of the page pool
    and banks must not change a single generated token."""
    cfg = tiny(DENSE)
    acfg = METHODS[method]
    base, bank, _ = symbiosis.init_system(cfg, acfg, 2, jax.random.PRNGKey(0))
    mesh = make_mesh((2, 2), ("data", "model"))
    keep = []
    ref = _serve_stream(cfg, acfg, base, bank, None, keep=keep)
    got = _serve_stream(cfg, acfg, base, bank, mesh, replicate_base=True,
                        keep=keep)
    for c in ref:
        np.testing.assert_array_equal(
            got[c], ref[c], err_msg=f"2x2/{method}: client {c} diverged")


@pytest.mark.tier2_sharded
@_needs_four
def test_train_identity_2x2():
    """2x2 mesh, replicated base, two concurrent jobs so the compacted
    row axis actually splits over data=2: bitwise train state."""
    cfg = tiny(DENSE)
    acfg = METHODS["lora"]
    base = symbiosis.init_system(cfg, acfg, 1, jax.random.PRNGKey(0))[0]
    mesh = make_mesh((2, 2), ("data", "model"))
    keep = []
    ref = _train_result(cfg, acfg, base, None, n_jobs=2, keep=keep)
    got = _train_result(cfg, acfg, base, mesh, replicate_base=True, n_jobs=2,
                        keep=keep)
    for r, g in zip(ref, got):
        _assert_results_equal(g, r, "2x2/lora")
