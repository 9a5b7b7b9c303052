"""The plain reference against the program at a tiny size on the CPU: the
same seeded weights give the same logits (every adapter method) and the
same loss, gradients and AdamW steps."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchtiny import BANKS, TINY_MODEL

from bench import reference, weights


def _program(method):
    from repro.config import AdapterConfig, ModelConfig
    from repro.core.virtlayer import make_client_ctx
    from repro.models import get_model
    cfg = ModelConfig(**TINY_MODEL)
    acfg = AdapterConfig(method=method["method"], rank=method["rank"] or 8,
                         alpha=method["alpha"] or 16.0,
                         targets=tuple(method["targets"]),
                         n_prefix=method["n_prefix"] or 16)
    return cfg, get_model(cfg), make_client_ctx(cfg, acfg)


@pytest.mark.parametrize("bank", BANKS, ids=[b["name"] for b in BANKS])
def test_serving_logits_match_program(bank):
    seed, T = 7, 24
    cfg, model, ctx = _program(bank)
    base = weights.make_base(TINY_MODEL, seed)
    adapter = jax.tree.map(lambda x: x[1], weights.make_bank(
        TINY_MODEL, bank, seed, bank["name"], bank["clients"]))
    toks = np.random.default_rng(0).integers(0, TINY_MODEL["vocab"], T)
    got = model.forward(base, {"tokens": jnp.asarray(toks)[None]}, ctx,
                        adapter, remat=False)[0][0]
    ref = reference.ServeReference(TINY_MODEL, seed, BANKS, bucket_min=8)
    want = ref.logits(toks, T, bank["name"], 1)
    assert float(jnp.max(jnp.abs(got - want))) < 1e-4
    bare = model.forward(base, {"tokens": jnp.asarray(toks)[None]},
                         remat=False)[0][0]
    # the adapter moves the logits far more than the agreement allows
    assert float(jnp.max(jnp.abs(bare - want))) > 1e-2


def test_lower_precision_reference_departs():
    seed, T = 7, 24
    toks = np.random.default_rng(1).integers(0, TINY_MODEL["vocab"], T)
    f32 = reference.ServeReference(TINY_MODEL, seed, BANKS, bucket_min=8)
    low = reference.ServeReference(TINY_MODEL, seed, BANKS, lowp="bf16",
                                   bucket_min=8)
    a = f32.logits(toks, T, "lora", 0)
    b = low.logits(toks, T, "lora", 0)
    assert 1e-4 < float(jnp.max(jnp.abs(a - b))) < 1.0


def test_served_gaps():
    lg = np.array([[0.0, 2.0, 1.0], [3.0, 0.5, 3.0]])
    np.testing.assert_allclose(reference.served_gaps(lg, np.array([2, 0])),
                               [1.0, 0.0])


@pytest.mark.parametrize("job", [
    {"name": "lora", "method": "lora", "rank": 4, "alpha": 8.0,
     "targets": ["q", "v", "down"], "n_prefix": 0},
    {"name": "ia3", "method": "ia3", "rank": 0, "alpha": 0.0,
     "targets": ["k", "v", "down"], "n_prefix": 0},
    {"name": "prefix", "method": "prefix", "rank": 0, "alpha": 0.0,
     "targets": ["q", "v"], "n_prefix": 4}], ids=lambda j: j["name"])
def test_training_matches_program(job):
    from repro.core.symbiosis import make_row_grad_fn
    from repro.optim import adamw_init, adamw_update
    from repro.optim.schedules import warmup_cosine
    seed = 5
    cfg, _, _ = _program(job)
    from repro.config import AdapterConfig
    acfg = AdapterConfig(method=job["method"], rank=job["rank"] or 8,
                         alpha=job["alpha"] or 16.0,
                         targets=tuple(job["targets"]),
                         n_prefix=job["n_prefix"] or 16)
    base = weights.make_base(TINY_MODEL, seed)
    a0 = jax.tree.map(lambda x: x[0], weights.make_bank(
        TINY_MODEL, job, seed, job["name"], 1))
    opt_cfg = {"lr": 1e-2, "warmup": 0, "total": 100, "wd": 0.0,
               "max_grad_norm": 1.0}
    rng = np.random.default_rng(3)
    batches = []
    for _ in range(2):
        t = rng.integers(0, TINY_MODEL["vocab"], (2, 17), dtype=np.int32)
        batches.append({"tokens": t[:, :-1], "labels": t[:, 1:]})
    grad = make_row_grad_fn(cfg, acfg, remat=True)
    p, opt, losses, first = a0, adamw_init(a0), [], None
    for s, b in enumerate(batches):
        loss, g = grad(p, base, {k: jnp.asarray(v) for k, v in b.items()})
        lr = warmup_cosine(s, opt_cfg["lr"], 0, opt_cfg["total"])
        p, opt, _ = adamw_update(p, g, opt, lr, max_grad_norm=1.0)
        losses.append(float(loss))
        first = opt.m if first is None else first
    ref = reference.TrainReference(TINY_MODEL, seed)
    r0 = ref.adapter_init(job, job["name"], 0)
    # drawn again from the seed, equal to float32 rounding (another fusion)
    for x, y in zip(jax.tree.leaves(r0), jax.tree.leaves(a0)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), rtol=1e-6)
    rl, rg, rp = ref.run(job, r0, batches, opt_cfg, 2)
    np.testing.assert_allclose(losses, rl, rtol=1e-5)
    for m, g in zip(jax.tree.leaves(first), jax.tree.leaves(rg)):
        np.testing.assert_allclose(np.asarray(m) / 0.1, np.asarray(g),
                                   rtol=2e-3, atol=1e-6)
    for x, y in zip(jax.tree.leaves(p), jax.tree.leaves(rp)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), rtol=1e-4,
                                   atol=1e-6)
