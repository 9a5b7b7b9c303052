"""Jaxpr traversal passes: pool-copy detector and MoE-remat structure.

Generalizes the PR-5 one-off "no scan stacks a pool-shaped ys" assertion
into a default-deny rule over the whole (recursive) jaxpr: **no equation
may produce a pool-sized output** unless it belongs to the small set of
in-place / pass-through forms —

* ``scatter`` / ``scatter-add`` / ``dynamic_update_slice`` — the in-place
  write family XLA lowers to an aliased update;
* ``reshape`` — the layer-axis fold of the global pool is a bitcast;
* carry outputs of ``scan`` / ``while`` — state threaded through a loop
  (XLA aliases loop carries), while a pool-sized scan **ys** output means
  the loop stacked per-iteration pool copies (PR 5's bug class);
* call-like containers (``pjit``, ``remat2``, ``custom_*``, ``cond``) —
  not flagged themselves, but their body jaxprs are walked recursively.

Anything else at pool size — ``concatenate``, ``gather``, ``transpose``,
``broadcast_in_dim``, ``select_n``, ``convert_element_type``, ``copy``,
arithmetic — materializes a fresh pool-sized buffer on the hot path and is
reported. Protected leaves are identified by exact (dtype, dims) signature
— byte counts alone collide with unrelated tensors (a gathered [R, ...]
adapter row can share nbytes with a smaller full-bank leaf) — and the
signature set grows through bitcast ops: a ``reshape`` whose *input* is
pool-sized protects its output's shape too, so the layer-axis fold of the
global pool stays covered. Callers derive the protected set structurally
(``core.symbiosis.cache_page_axes`` / ``cache_slot_axes``), never by shape
heuristics.

The same walker hosts the MoE structural contract: every ``top_k`` routing
equation in a train step must sit under a ``remat2`` (``jax.checkpoint``)
sub-jaxpr, i.e. the route→dispatch→combine body is rematerialized rather
than saving expert-sized residuals (PR 5's bitwise-reproducibility fix).
"""
from __future__ import annotations

from typing import Any, Iterator

import jax.extend.core as jex_core
import numpy as np

from repro.analysis.report import PassResult

_IN_PLACE = {
    "scatter", "scatter-add", "scatter_add", "scatter-mul", "scatter-min",
    "scatter-max", "dynamic_update_slice", "dynamic-update-slice",
}
_BITCAST = {"reshape", "squeeze", "expand_dims"}
_LOOPS = {"scan", "while"}
_REMAT = {"remat2", "remat", "checkpoint"}


def _sub_jaxprs(eqn) -> Iterator[Any]:
    for v in eqn.params.values():
        vs = v if isinstance(v, (tuple, list)) else (v,)
        for u in vs:
            if isinstance(u, jex_core.ClosedJaxpr):
                yield u.jaxpr
            elif isinstance(u, jex_core.Jaxpr):
                yield u


def _is_call_like(eqn) -> bool:
    return any(True for _ in _sub_jaxprs(eqn))


def leaf_size_sigs(leaves) -> set[tuple[str, tuple[int, ...]]]:
    """Exact (dtype name, dims) signatures of the protected leaves."""
    return {(np.dtype(leaf.dtype).name, tuple(int(d) for d in leaf.shape))
            for leaf in leaves}


def _var_sig(var) -> tuple[str, tuple[int, ...]] | None:
    aval = var.aval
    if not hasattr(aval, "shape") or not hasattr(aval, "dtype"):
        return None
    return np.dtype(aval.dtype).name, tuple(int(d) for d in aval.shape)


def check_pool_copies(jaxpr, protected_sigs, *, target: str,
                      pass_name: str = "poolcopy") -> PassResult:
    """Walk ``jaxpr`` (a Jaxpr or ClosedJaxpr); flag pool-sized materializations."""
    res = PassResult(pass_name, target)
    res.checked["protected_sigs"] = len(protected_sigs)
    sigs = set(protected_sigs)           # grows through bitcast aliases
    n_eqns = 0
    n_inplace = 0

    def protected(var) -> bool:
        sig = _var_sig(var)
        return sig is not None and sig in sigs

    def walk(jx, depth: int) -> None:
        nonlocal n_eqns, n_inplace
        for eqn in jx.eqns:
            n_eqns += 1
            prim = eqn.primitive.name
            if prim in _BITCAST and any(
                    protected(v) for v in eqn.invars
                    if not isinstance(v, jex_core.Literal)):
                # the pool under a new layout (layer fold etc.) — protect it
                for v in eqn.outvars:
                    sig = _var_sig(v)
                    if sig is not None:
                        sigs.add(sig)
            if prim == "scan":
                # a ys output stacks per-iteration values: pool-shaped slices
                # mean the loop copied the pool every step (PR 5's bug class)
                num_carry = eqn.params.get("num_carry", 0)
                for i, v in enumerate(eqn.outvars[num_carry:], num_carry):
                    sig = _var_sig(v)
                    if (sig is not None and len(sig[1]) >= 1
                            and (sig[0], sig[1][1:]) in sigs):
                        res.add(
                            "scan stacks a pool-sized ys output "
                            f"{v.aval.str_short()} (output {i}, "
                            f"{num_carry} carries) — per-iteration pool "
                            "copies on the hot path",
                            primitive=prim, outvar=v.aval.str_short(),
                        )
            hot = [i for i, v in enumerate(eqn.outvars) if protected(v)]
            if hot:
                if prim in _IN_PLACE:
                    n_inplace += 1
                elif prim in _BITCAST:
                    pass
                elif prim == "scan":
                    num_carry = eqn.params.get("num_carry", 0)
                    for i in hot:
                        if i >= num_carry:
                            v = eqn.outvars[i]
                            res.add(
                                "scan stacks a pool-sized ys output "
                                f"{v.aval.str_short()} (output {i}, "
                                f"{num_carry} carries) — per-iteration pool "
                                "copies on the hot path",
                                primitive=prim, outvar=v.aval.str_short(),
                            )
                elif prim == "while" or _is_call_like(eqn):
                    pass  # pass-through / aliased carry; body walked below
                else:
                    for i in hot:
                        v = eqn.outvars[i]
                        res.add(
                            f"op '{prim}' materializes a pool-sized "
                            f"intermediate {v.aval.str_short()} outside the "
                            "in-place scatter/dynamic-update-slice family",
                            primitive=prim, outvar=v.aval.str_short(),
                        )
            for sub in _sub_jaxprs(eqn):
                walk(sub, depth + 1)

    walk(jaxpr.jaxpr if isinstance(jaxpr, jex_core.ClosedJaxpr) else jaxpr, 0)
    res.checked["eqns_walked"] = n_eqns
    res.checked["inplace_writes"] = n_inplace
    return res


def check_moe_checkpointed(jaxpr, *, target: str,
                           pass_name: str = "poolcopy.moe_remat") -> PassResult:
    """Every ``top_k`` routing eqn must live under a ``remat2`` sub-jaxpr."""
    res = PassResult(pass_name, target)
    n_topk = 0
    n_remat = 0

    def walk(jx, in_remat: bool) -> None:
        nonlocal n_topk, n_remat
        for eqn in jx.eqns:
            prim = eqn.primitive.name
            if prim in _REMAT:
                n_remat += 1
            if prim == "top_k":
                n_topk += 1
                if not in_remat:
                    res.add(
                        "MoE routing (top_k) outside any jax.checkpoint/remat2 "
                        "region — the route→dispatch→combine body saves "
                        "expert-sized residuals instead of rematerializing",
                        primitive=prim,
                    )
            for sub in _sub_jaxprs(eqn):
                walk(sub, in_remat or prim in _REMAT)

    walk(jaxpr.jaxpr if isinstance(jaxpr, jex_core.ClosedJaxpr) else jaxpr, False)
    res.checked["top_k_eqns"] = n_topk
    res.checked["remat_regions"] = n_remat
    return res
