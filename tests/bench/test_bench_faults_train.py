"""The comparison that decides ``correct`` in a fine-tuning cell fails what
it must: whole tiny runs on the CPU with the train step broken underneath
come out incorrect, and the control (the reference one precision lower)
reads past the limits that sound runs stay inside."""
import argparse
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchtiny import REPO, run_cell, tiny_root  # noqa: F401  a fixture

from bench import harness
from bench.traffic import finetune_jobs as fj


def _unchanged(real):
    """A train step that reports its loss but returns the state it got."""
    def step(base, bank, opt, *rest):
        keep = jax.tree.map(jnp.copy, (bank, opt))
        _, _, metrics = real(base, bank, opt, *rest)
        return keep[0], keep[1], metrics
    return step


def _half_batch(real):
    """A train step that leaves out half of each job's rows."""
    def step(base, bank, opt, batch, *rest):
        half = jax.tree.map(lambda x: x[:, : x.shape[1] // 2], batch)
        return real(base, bank, opt, half, *rest)
    return step


def _bank_row_dropped(real):
    """A train step that reads only the first job's rows of a bank of
    several jobs: the second job's row is left out and the first's taken in
    its place (the half of a two-job bank's batch)."""
    def step(base, bank, opt, batch, *rest):
        one = jax.tree.map(lambda x: x.at[1].set(x[0]) if x.shape[0] > 1
                           else x, batch)
        return real(base, bank, opt, one, *rest)
    return step


@pytest.mark.parametrize("fault,check", [(_unchanged, "change_gap"),
                                         (_half_batch, "loss_gap"),
                                         (_bank_row_dropped, "loss_gap")],
                         ids=["state_unchanged", "half_batch",
                              "bank_row_dropped"])
def test_broken_train_step_is_incorrect(tiny_root, capsys, monkeypatch,
                                        fault, check):
    from repro.training import engine as tengine
    real = tengine._jit_compact_train

    @functools.lru_cache(maxsize=None)
    def broken(*a):
        return jax.jit(fault(real(*a)), donate_argnums=(1, 2))

    monkeypatch.setattr(tengine, "_jit_compact_train", broken)
    res = run_cell(tiny_root, "tiny.ft", seed=6, capsys=capsys)
    assert res["correct"] is False
    c = res["checks"][check]
    assert c["value"] > c["limit"]


def _run(root, workload, seed, seconds=1.0):
    reg = harness.Registry([root, REPO])
    ns = argparse.Namespace(seed=seed, seconds=seconds, trace=0)
    return harness.Run(ns, reg.cell(workload), reg, time.perf_counter(),
                       jax.devices()[:1], REPO)


def test_finetune_control_reads_past_the_limits(tiny_root):
    run = _run(tiny_root, "tiny.ft", 9)
    ref = fj.follow(run)
    ctl = fj.worst(fj.as_seen(fj.follow(run, lowp="bf16")), ref)
    lim = run.traffic["check"]
    assert any(ctl[k] > lim[k] for k in ("loss", "grad", "change"))
    svc, jobs, adapters, _ = fj.build(run, None)
    sound = fj.worst(fj.first_steps(run, svc, jobs, adapters), ref)
    assert all(sound[k] <= lim[k] for k in ("loss", "grad", "change"))
    half = fj.worst(fj.as_seen(fj.follow(run, rows=1)), ref)
    assert half["loss"] > lim["loss"]
    dropped = fj.worst(fj.as_seen(fj.follow(
        run, data_of=fj.bank_row_dropped(run.traffic))), ref)
    assert dropped["loss"] > lim["loss"]
    assert np.isfinite(list(ctl.values())).all()
