"""Run the analysis passes over the standard hot-path targets.

``run_static`` covers the per-target jaxpr/HLO passes (donation, poolcopy,
MoE remat structure, frozen-base taint); ``run_isolation`` the runtime
differential probes; ``run_buckets`` drives a small real engine workload —
serving with staggered admissions plus dynamic bank admission, and a
multi-job fine-tuning churn — under the trace-count guard. The CLI
(``python -m repro.analysis``) additionally compiles targets under a
multi-device mesh for the collective audit (``run_collectives``).
"""
from __future__ import annotations

import numpy as np

from repro.analysis import aliasing, collectives, jaxpr_passes, taint, tracecount
from repro.analysis.report import PassResult
from repro.analysis.targets import StepTarget, all_targets, tiny_config
from repro.config import MOE, DENSE, AdapterConfig, ServeConfig, FinetuneConfig


def run_static(targets=None) -> list:
    results = []
    for t in targets if targets is not None else all_targets():
        hlo = aliasing.compile_text(t.fn, t.args, t.donate_argnums)
        results.append(aliasing.check_donation(
            hlo, t.donated, target=t.name, frozen_leaves=t.frozen))
        jx = None
        if t.protected_leaves:
            jx = t.jaxpr()
            results.append(jaxpr_passes.check_pool_copies(
                jx, t.protected_sigs, target=t.name))
        if t.arch == MOE and t.kind == "train":
            jx = jx if jx is not None else t.jaxpr()
            results.append(jaxpr_passes.check_moe_checkpointed(
                jx, target=t.name))
        if t.kind == "train":
            results.append(taint.check_frozen_base(
                t.fn, t.args, update_argnums=t.donate_argnums,
                target=t.name))
    return results


def run_isolation(targets=None) -> list:
    """Differential client/row isolation probes on the compact steps."""
    from repro.core import symbiosis
    import jax

    results = []
    for t in targets if targets is not None else all_targets():
        iso = t.isolation
        if not iso:
            continue
        if t.kind == "serving":
            scfg = iso["scfg"]
            cfg = tiny_config(t.arch)
            cache_kw = symbiosis.serve_cache_kwargs(cfg, scfg)
            page_axes = symbiosis.cache_page_axes(cfg, scfg.max_seq, **cache_kw)
            client_axes = jax.tree.map(
                lambda pax: 0 if pax is None else None, page_axes,
                is_leaf=lambda x: x is None)
            base, bank, caches = t.args[0], t.args[1], t.args[2]
            extra = tuple(jax.numpy.asarray(e) for e in iso["extra"])
            n_blocks = -(-scfg.max_seq // scfg.page_block)
            fn = t.fn
            if iso.get("probe"):
                # health-probed steps return (logits, finite, caches); the
                # isolation checker's contract is (out, new_caches)
                fn = (lambda f: lambda *a: (lambda o: (o[0], o[-1]))(f(*a)))(
                    t.fn)
            results.append(taint.check_client_isolation(
                fn, base, bank, caches, extra,
                clients=np.asarray(iso["extra"][1]), victim=iso["victim"],
                pool_pages=2 * n_blocks,  # max_b * n_blocks per client
                page_axes=page_axes, slot_axes=client_axes,
                target=t.name))
        else:
            results.append(taint.check_row_isolation(
                t.fn, t.args, perturb_row=iso["perturb_row"],
                victim_slot=iso["victim_slot"],
                perturb_argnums=iso["perturb_argnums"], target=t.name))
    return results


def run_buckets() -> PassResult:
    """Real engine workloads under the trace-count guard: serving ticks
    with staggered admission and a live ``admit_bank`` growth, then a
    fine-tuning churn — every compile must land in the declared domains."""
    import jax
    from repro.core import symbiosis
    from repro.core.engine_spec import BankSpec, EngineSpec
    from repro.serving.engine import Request, ServingEngine
    from repro.training.engine import FinetuneEngine
    from repro.training.job import FinetuneJob, make_job_stream

    cfg = tiny_config(DENSE)
    lora = AdapterConfig(method="lora", rank=4, alpha=8.0, targets=("q", "v"))
    with tracecount.guard("engine-workload") as g:
        scfg = ServeConfig(n_clients=2, max_seq=32, page_block=8)
        base, bank, _ = symbiosis.init_system(cfg, lora, 2,
                                              jax.random.PRNGKey(0))
        spec = EngineSpec(cfg=cfg,
                          banks=(BankSpec("tenants", lora, capacity=2),),
                          serve=scfg, max_batch_per_client=2)
        eng = ServingEngine(spec, base, [bank])
        rng = np.random.default_rng(0)
        for c in range(2):
            eng.submit(Request(client_id=c,
                               prompt=rng.integers(0, cfg.vocab, (1, 6))
                               .astype(np.int32),
                               max_new_tokens=3))
        eng.run()
        # live bank growth: new client ids, grown buckets, a new epoch
        extra = symbiosis.init_system(cfg, lora, 1, jax.random.PRNGKey(9))[1]
        adm = eng.admit_bank(lora, extra)
        eng.submit(Request(client_id=adm.client_ids[0],
                           prompt=rng.integers(0, cfg.vocab, (1, 6))
                           .astype(np.int32), max_new_tokens=3))
        eng.run()
        eng.retire_bank(adm)

        ft = FinetuneEngine(
            EngineSpec(cfg=cfg, finetune=FinetuneConfig(max_jobs=4)), base)
        for i in range(2):
            ft.submit(FinetuneJob(acfg=lora,
                                  data=make_job_stream(cfg, 2, 8, seed=i),
                                  batch_size=2, seq_len=8, steps=2))
        ft.run()
    return g.result()


def run_collectives(targets=None, *, mesh=None) -> list:
    """Compile each target under a mesh (or single-device) and audit the
    partitioned HLO for base-sized collectives. With a real multi-device
    mesh the base is sharded via ``launch.shardings.base_param_specs``;
    single-device compiles must trivially contain no collectives at all."""
    import jax

    results = []
    for t in targets if targets is not None else all_targets():
        if mesh is None:
            hlo = aliasing.compile_text(t.fn, t.args, t.donate_argnums)
        else:
            from repro.launch.shardings import base_param_specs

            base = t.args[t.base_argnum]
            specs = base_param_specs(
                tiny_config(t.arch), mesh,
                jax.eval_shape(lambda b: b, base))
            sharded_base = jax.device_put(
                base, jax.tree.map(
                    lambda s: jax.sharding.NamedSharding(mesh, s), specs))
            args = (sharded_base,) + tuple(t.args[1:])
            with jax.set_mesh(mesh):
                hlo = (jax.jit(t.fn, donate_argnums=t.donate_argnums)
                       .lower(*args).compile().as_text())
        results.append(collectives.audit_collectives(
            hlo, t.args[t.base_argnum], target=t.name,
            # per-layer frozen-weight gathers are the FSDP executor mode;
            # reduce-type collectives at base shape stay hard errors
            allow_kinds=("all-gather", "all-gather-start") if mesh else ()))
    return results


def run_all(*, with_isolation: bool = True, mesh=None) -> list:
    targets = all_targets()
    results = run_static(targets)
    results.append(run_buckets())
    results.extend(run_collectives(targets, mesh=mesh))
    if with_isolation:
        results.extend(run_isolation(targets))
    return results
