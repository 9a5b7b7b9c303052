"""The dense model family, ``bench/arch/dense.py``, gives what the
benchmark's code gave before the family moved there, as recorded in
``fixtures/arch_dense_before.json``: the same base and adapter leaves of
both configurations (paths, kinds, shapes, without drawing them), the same
bits of the tiny trees, the same per-layer readings on the recorded v5e
trace, and the same reference programs at full size."""
import hashlib
import json
import os
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchtiny import BANKS, FINETUNE, REPO, TINY_MODEL

from bench import reference, trace, weights
from bench.harness import Registry

BEFORE = json.load(open(os.path.join(os.path.dirname(__file__), "fixtures",
                                     "arch_dense_before.json")))
CELLS = [("qwen3-4b", "decode-saturated"), ("granite-3-8b", "finetune-mixed")]
SEED = 2 ** 31 + 5


def _load(*parts):
    return json.load(open(os.path.join(REPO, "bench", *parts)))


def _method(job):
    return {"name": job["name"], "method": job["method"],
            "rank": job.get("rank", 0), "alpha": job.get("alpha", 16.0),
            "targets": job.get("targets", []),
            "n_prefix": job.get("n_prefix", 0)}


def _adapters(mix):
    """(method, bank name, adapters in the bank) of a mix."""
    if "banks" in mix:
        return [(b, b["name"], b["clients"]) for b in mix["banks"]]
    return [(_method(j), j["name"], 1) for j in mix["jobs"]]


def _plain(x):
    return json.loads(json.dumps(x))


def _shapes(tree):
    return sorted(["/".join(str(getattr(k, "key", k)) for k in path),
                   list(x.shape), str(x.dtype)]
                  for path, x in jax.tree_util.tree_leaves_with_path(tree))


def _digest(tree):
    h = hashlib.sha256()
    for path, x in sorted(jax.tree_util.tree_leaves_with_path(tree),
                          key=lambda kv: jax.tree_util.keystr(kv[0])):
        a = np.asarray(x)
        h.update(jax.tree_util.keystr(path).encode())
        h.update(str(a.dtype).encode() + str(a.shape).encode())
        h.update(a.tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("cell", CELLS, ids=[c for c, _ in CELLS])
def test_leaves_unchanged(cell):
    name, mix_name = cell
    md = _load("configs", name + ".json")["model"]
    mix = _load("traffic", mix_name + ".json")
    fam = Registry([REPO]).arch(md["arch"])
    want, shapes = BEFORE["leaves"][name], BEFORE["shapes"][name]
    assert _plain(fam.base_leaves(md)) == want["base"]
    assert _shapes(jax.eval_shape(
        lambda: weights.make_base(md, 1, fam))) == shapes["base"]
    for m, bank, n in _adapters(mix):
        assert _plain(weights.adapter_leaves(md, m, fam)) == \
            want["adapters"][bank]
        assert _shapes(jax.eval_shape(lambda: weights.make_bank(
            md, m, 1, bank, n, family=fam))) == shapes["adapters"][bank]


def test_tiny_trees_unchanged():
    got = {"base": _digest(weights.make_base(TINY_MODEL, SEED))}
    for b in BANKS:
        got["bank." + b["name"]] = _digest(weights.make_bank(
            TINY_MODEL, b, SEED, b["name"], b["clients"]))
    for j in FINETUNE["jobs"]:
        got["job." + j["name"]] = _digest(weights.make_bank(
            TINY_MODEL, _method(j), SEED, j["name"], 1))
    assert got == BEFORE["tiny"]


def _rec(client, S, n, admit):
    return SimpleNamespace(client=client, S=S, n=n, admit_tick=admit,
                           req=SimpleNamespace(status="ok"))


def test_readers_unchanged_on_the_recorded_trace():
    ev = json.load(open(os.path.join(os.path.dirname(__file__), "fixtures",
                                     "trace_v5e.json")))
    red = trace.reduce(ev["events"])
    peaks = _load("peaks.json")["chips"]["TPU v5 lite"]
    recs = [_rec(i % 12, 32 + (37 * i) % 481, 64 + (53 * i) % 449, i // 2)
            for i in range(48)]
    serve = SimpleNamespace(
        cfg=_load("configs", "qwen3-4b.json")["model"],
        mix=_load("traffic", "decode-saturated.json"), recs=recs,
        peaks=peaks, trace=red,
        snaps={"tr0": (10.0, 10, {}, {}), "tr1": (14.0, 12, {}, {})})
    train = SimpleNamespace(
        cfg=_load("configs", "granite-3-8b.json")["model"],
        mix=_load("traffic", "finetune-mixed.json"), peaks=peaks, trace=red,
        snaps={"tr0": (0.0, 3), "tr1": (6.2, 5)})
    reg = Registry([REPO])
    got = {name: reg.reader(name).read(view) for name, view in (
        ("paged_decode_attn_roofline", serve), ("sgmv_roofline", serve),
        ("mfu.serve", serve), ("mfu.train", train))}
    assert got == BEFORE["readers"]


def _text(lowered):
    t = lowered.as_text()
    return {"sha256_16": hashlib.sha256(t.encode()).hexdigest()[:16],
            "chars": len(t)}


def test_reference_programs_unchanged(monkeypatch):
    """Every program of the reference and of its control, lowered at the
    cells' own sizes from the shapes of the weights (none drawn)."""
    make_base = weights.make_base
    monkeypatch.setattr(weights, "make_base", lambda *a, **k: jax.eval_shape(
        lambda: make_base(*a, **k)))
    S = jax.ShapeDtypeStruct
    got = {}
    for lowp in ("", "fp8"):
        md = _load("configs", "qwen3-4b.json")["model"]
        mix = _load("traffic", "decode-saturated.json")
        ref = reference.ServeReference(md, 7, mix["banks"], lowp=lowp)
        for b in mix["banks"]:
            got[f"serve.{b['name']}.{lowp}"] = _text(
                ref._layer_fn(b["name"]).lower(
                    ref.base["layers"], S((2,), jnp.uint32), np.int32(3),
                    np.int32(1), S((512, md["d_model"]), jnp.float32)))
        got[f"head.{lowp}"] = _text(ref._head.lower(
            S((400, md["d_model"]), jnp.float32), ref.base))
        md = _load("configs", "granite-3-8b.json")["model"]
        tr = reference.TrainReference(md, 7, lowp=lowp)
        for j in _load("traffic", "finetune-mixed.json")["jobs"]:
            meth = _method(j)
            a0 = jax.eval_shape(lambda: tr.adapter_init(meth, j["name"], 0))
            got[f"train.{j['name']}.{lowp}"] = _text(
                tr._value_and_grad(meth).lower(
                    a0, tr.base, S((1, 2048), jnp.int32),
                    S((1, 2048), jnp.int32)))
    assert got == BEFORE["programs"]
