"""The plain reference: the configuration's model family (its
``layer``, from ``bench/arch/<arch>.py``) with LoRA, IA3 and prefix
adapters, the final norm and head, in float32 under
``jax.default_matmul_precision("highest")``; and what every family shares:
the norm, RoPE, the matmul with its lower-precision control, the serving
scorer and the fine-tuning loss, gradients and AdamW.

It follows the configuration as the program runs it, written from the
published equations and from the adapter semantics the family module
states, and imports nothing of the program. Weights come from the seed
through ``bench.weights`` (the same generator that made the program's, run
again), in their served dtype; each layer is cast to float32 as it runs,
so a 36-layer model never sits in float32 on the chip.

``lowp="fp8"`` rounds every matmul's inputs to float8 (e4m3, one scale per
tensor) before a float32 product: the control that a lower precision than
the configuration's bfloat16 must fail (``lowp="bf16"`` does the same for a
float32 configuration).
"""
from __future__ import annotations

import contextlib
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench import harness
from bench import weights as W

HIGHEST = jax.lax.Precision.HIGHEST
EPS = 1e-6


def _to_fp8(a):
    s = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / 448.0
    return (a / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


@jax.custom_vjp
def _fp8(a):
    return _to_fp8(a)


# gradients are rounded the same way, with a scale of their own, as fp8
# training rounds them; a plain cast would flush small cotangents to zero
_fp8.defvjp(lambda a: (_to_fp8(a), None), lambda _, g: (_to_fp8(g),))


def _bf16(a):
    return a.astype(jnp.bfloat16).astype(jnp.float32)


LOWER = {"fp8": _fp8, "bf16": _bf16}
# the control for a configuration: the nearest precision below its own
CONTROL = {"bfloat16": "fp8", "float16": "fp8", "float32": "bf16"}


def make_mm(lowp: str = ""):
    """``mm(spec, a, b)``: an einsum in float32 at the highest precision,
    with its inputs first rounded to ``lowp`` when set (``fp8``: the control
    for a bfloat16 configuration; ``bf16``: for a float32 one)."""
    if lowp and lowp not in LOWER:
        raise ValueError(f"unknown lower precision {lowp!r}")
    rnd = LOWER.get(lowp)

    def mm(spec, a, b):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        if rnd is not None:
            a, b = rnd(a), rnd(b)
        return jnp.einsum(spec, a, b, precision=HIGHEST)

    return mm


def rms(x, scale):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + EPS)
    return x * scale


def rope(x, pos, theta):
    """Split-half rotary embedding. x [T, heads, hd], pos [T]."""
    hd = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


@contextlib.contextmanager
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


# ---------------------------------------------------------------------------
# Serving: logits of served tokens
# ---------------------------------------------------------------------------

class ServeReference:
    """Scores served tokens: ``gaps(tokens, n_scored, client)`` runs the
    whole sequence through the reference and returns, for each of the last
    ``n_scored`` next-token positions, ``max(logits) - logits[served]``.
    One compiled layer function per (bucket length, method), reused for
    every layer: the layer's weights are drawn from the seed inside it."""

    def __init__(self, cfg: dict, seed: int, banks: list, *, lowp: str = "",
                 bucket_min: int = 256, base=None, family=None):
        self.cfg, self.seed, self.banks = cfg, seed, banks
        self.family = harness.family(cfg, family)
        self.key = jax.random.key_data(W.root_key(seed))
        self.mm = make_mm(lowp)
        self.bucket_min = bucket_min
        self._layer = {}
        if base is None:
            base = W.make_base(cfg, seed, self.family)
        self.base = base
        self._head = jax.jit(self._head_fn)

    def _layer_fn(self, bank):
        if bank not in self._layer:
            cfg, mm, fam = self.cfg, self.mm, self.family
            b = next(b for b in self.banks if b["name"] == bank)

            def fn(layers, key, li, index, x):
                k = jax.random.wrap_key_data(key)
                w = fam.layer_weights(layers, li)
                ad = _adapter_layer_k(cfg, fam, b, k, index, li)
                with _highest():
                    return fam.layer(cfg, w, ad, b, x, mm)

            self._layer[bank] = jax.jit(fn)
        return self._layer[bank]

    def _head_fn(self, x, base):
        with _highest():
            return head(base, x, self.mm, "t")

    def logits(self, tokens, n_scored: int, bank: str, index: int):
        """Reference logits [n_scored, V] at the last ``n_scored``
        positions of ``tokens`` (1-D int array)."""
        T = len(tokens)
        L = self.bucket_min
        while L < T:
            L *= 2
        toks = np.zeros((L,), np.int32)
        toks[:T] = tokens
        x = self.base["embed"][jnp.asarray(toks)].astype(jnp.float32)
        fn = self._layer_fn(bank)
        for li in range(self.cfg["n_layers"]):
            x = fn(self.base["layers"], self.key, np.int32(li),
                   np.int32(index), x)
        return self._head(x[T - n_scored:T], self.base)


def served_gaps(ref_logits, served):
    """Per position, how far the served token's reference logit lies below
    the reference's best: ``max(l) - l[served]`` (0 where it is the best)."""
    ref_logits = jnp.asarray(ref_logits, jnp.float32)
    best = jnp.max(ref_logits, axis=-1)
    got = jnp.take_along_axis(ref_logits, jnp.asarray(served)[:, None],
                              axis=-1)[:, 0]
    return np.asarray(best - got)


def head(base, x, mm, lead: str):
    """Final norm and output head (tied to the embedding or not)."""
    h = rms(x, base["final_norm"]["scale"].astype(jnp.float32))
    w = base.get("lm_head")
    if w is None:
        return mm(f"{lead}d,vd->{lead}v", h, base["embed"])
    return mm(f"{lead}d,dv->{lead}v", h, w)


def _adapter_layer_k(cfg, family, method, key, index, li):
    out = {}
    for path, kind, shape, fan in W.adapter_leaves(cfg, method, family):
        name = f"adapter/{method['name']}/" + "/".join(path)
        k = jax.random.fold_in(jax.random.fold_in(
            jax.random.fold_in(key, W._name_id(name)), index), li)
        W._set(out, path, W._draw(k, kind, shape, jnp.float32, fan))
    return out


# ---------------------------------------------------------------------------
# Fine-tuning: loss, gradients and AdamW
# ---------------------------------------------------------------------------

def lr_at(step, lr, warmup, total, min_ratio=0.1):
    """Warmup, then cosine from ``lr`` down to ``min_ratio * lr``."""
    if step < warmup:
        return lr * step / max(1.0, warmup)
    frac = min(max((step - warmup) / max(1.0, total - warmup), 0.0), 1.0)
    return lr * (min_ratio + (1 - min_ratio) * 0.5 * (1 + math.cos(math.pi
                                                                   * frac)))


class TrainReference:
    """Loss and adapter gradients of one job's rows, layer by layer under a
    rematerialised scan (each layer's weights drawn from the seed inside the
    scan body), plus the AdamW update the job's optimizer is set to."""

    def __init__(self, cfg: dict, seed: int, *, lowp: str = "",
                 family=None):
        self.cfg, self.seed = cfg, seed
        self.family = harness.family(cfg, family)
        self.key = jax.random.key_data(W.root_key(seed))
        self.mm = make_mm(lowp)
        self.base = W.make_base(cfg, seed, self.family)
        self._vg = {}

    def _value_and_grad(self, method):
        name = method["name"]
        if name not in self._vg:
            cfg, mm, fam = self.cfg, self.mm, self.family

            def loss_fn(ad_stack, base, tokens, labels):
                def body(x, inp):
                    w, ad = inp
                    w = fam.layer_weights(w)
                    return jax.vmap(lambda xx: fam.layer(cfg, w, ad, method,
                                                         xx, mm))(x), None

                with _highest():
                    x = base["embed"][tokens].astype(jnp.float32)
                    x, _ = jax.lax.scan(jax.checkpoint(body), x,
                                        (base["layers"], ad_stack))
                    logits = head(base, x, mm, "bt")
                    logp = jax.nn.log_softmax(logits, axis=-1)
                    nll = -jnp.take_along_axis(logp, labels[..., None],
                                               axis=-1)[..., 0]
                    return jnp.mean(nll)

            self._vg[name] = jax.jit(jax.value_and_grad(loss_fn))
        return self._vg[name]

    def adapter_init(self, method, bank: str, index: int):
        """The job's starting adapter, stacked over layers, from the seed."""
        k = W.root_key(self.seed)
        layers = [_adapter_layer_k(self.cfg, self.family,
                                   dict(method, name=bank), k, index, li)
                  for li in range(self.cfg["n_layers"])]
        return jax.tree.map(lambda *xs: jnp.stack(xs), *layers)

    def run(self, method: dict, adapter, batches, opt: dict, steps: int):
        """``steps`` AdamW steps from ``adapter`` on ``batches[i]``
        ({"tokens", "labels"} [B, S]). Returns (losses, first clipped
        gradient, final adapter)."""
        vg = self._value_and_grad(method)
        m = jax.tree.map(jnp.zeros_like, adapter)
        v = jax.tree.map(jnp.zeros_like, adapter)
        p = adapter
        losses, first = [], None
        b1, b2, eps = 0.9, 0.999, 1e-8
        for s in range(steps):
            loss, g = vg(p, self.base,
                         jnp.asarray(batches[s]["tokens"]),
                         jnp.asarray(batches[s]["labels"]))
            losses.append(float(loss))
            gn = math.sqrt(sum(float(jnp.sum(x * x))
                               for x in jax.tree.leaves(g)))
            clip = min(1.0, opt["max_grad_norm"] / (gn + 1e-9))
            g = jax.tree.map(lambda x: x * clip, g)
            if first is None:
                first = g
            t = s + 1
            lr = lr_at(s, opt["lr"], opt["warmup"], opt["total"])
            m = jax.tree.map(lambda a, x: b1 * a + (1 - b1) * x, m, g)
            v = jax.tree.map(lambda a, x: b2 * a + (1 - b2) * x * x, v, g)
            p = jax.tree.map(
                lambda pp, mm_, vv: pp - lr * ((mm_ / (1 - b1 ** t))
                                               / (jnp.sqrt(vv / (1 - b2 ** t))
                                                  + eps)
                                               + opt["wd"] * pp), p, m, v)
        return losses, first, p
