"""Frozen-base taint and client-isolation passes.

Two complementary proofs of Symbiosis's isolation contract:

* ``check_frozen_base`` — **syntactic** forward taint over the jaxpr: mark
  the invars bound to frozen-base leaves as tainted, close over equations
  (any tainted operand taints every result), and flag any jaxpr *output*
  that is (a) base-tainted, (b) exactly base-leaf-shaped, and (c) not the
  untouched base invar itself. A train step that returns an updated base
  tensor — the "accidentally trainable base" failure — trips all three.

* ``check_client_isolation`` / ``check_row_isolation`` — **differential**
  probes at runtime: corrupt one client's adapter slice (or one train row's
  inputs) and re-run the very same step from identical state; every other
  client's logits, cache pages, and slot rows (or every other row's updated
  params / optimizer state) must be bit-identical. The Pallas/custom_vmap
  kernels on the hot path don't admit a clean symbolic cross-client proof,
  but bit-equality under perturbation is exactly the observable contract.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.extend.core as jex_core
import numpy as np

from repro.analysis.aliasing import leaf_sig
from repro.analysis.report import PassResult


def check_frozen_base(fn: Callable, args: tuple, *, base_argnum: int = 0,
                      update_argnums: tuple = (), target: str,
                      pass_name: str = "taint") -> PassResult:
    """No output of ``fn`` may be a freshly-produced base-shaped tensor.

    ``update_argnums`` name the state the step legitimately rewrites
    (adapter bank, optimizer): base signatures that coincide with an
    update-leaf signature are excluded, otherwise an adapter update whose
    leaf happens to share a shape with some base leaf (e.g. a LoRA
    [layers, d_model, rank] A against the MoE gate's
    [layers, d_model, n_experts]) would be a false positive.
    """
    res = PassResult(pass_name, target)
    closed = jax.make_jaxpr(fn)(*args)
    jaxpr = closed.jaxpr

    flat_sizes = [len(jax.tree_util.tree_leaves(a)) for a in args]
    start = sum(flat_sizes[:base_argnum])
    stop = start + flat_sizes[base_argnum]
    base_invars = jaxpr.invars[start:stop]
    base_sigs = {leaf_sig(v.aval) for v in base_invars}
    for i in update_argnums:
        base_sigs -= {leaf_sig(leaf)
                      for leaf in jax.tree_util.tree_leaves(args[i])}
    res.checked["base_leaves"] = len(base_invars)

    tainted = set(map(id, base_invars))
    for eqn in jaxpr.eqns:
        if any(id(v) in tainted for v in eqn.invars
               if not isinstance(v, jex_core.Literal)):
            tainted.update(id(v) for v in eqn.outvars)

    base_ids = set(map(id, base_invars))
    for i, v in enumerate(jaxpr.outvars):
        if isinstance(v, jex_core.Literal) or id(v) in base_ids:
            continue
        if not hasattr(v.aval, "shape"):
            continue
        if leaf_sig(v.aval) in base_sigs and id(v) in tainted:
            res.add(
                f"output {i} is a freshly-computed base-weight-shaped tensor "
                f"{v.aval.str_short()} derived from the frozen base — the "
                "step produces an updated base",
                output_index=i, aval=v.aval.str_short(),
            )
    return res


def _bit_equal(a, b) -> bool:
    a, b = np.asarray(a), np.asarray(b)
    return a.shape == b.shape and bool(np.all(a.view(np.uint8) == b.view(np.uint8)))


def check_client_isolation(fn: Callable, base, bank, caches, extra_args: tuple,
                           *, clients: np.ndarray, victim: int, pool_pages: int,
                           page_axes, slot_axes, out_row_axis: int = 0,
                           target: str, pass_name: str = "taint.isolation",
                           ) -> PassResult:
    """Corrupt ``victim``'s adapter slice; other clients must be unaffected.

    ``fn(base, bank, caches, *extra_args) -> (out, new_caches)`` with
    ``out`` carrying a leading row axis mapped to clients by ``clients``.
    ``page_axes`` / ``slot_axes`` are pytrees (matching ``caches``) giving
    the global-pool page axis / client slot axis per leaf (None = not that
    kind of leaf), as produced by ``core.symbiosis.cache_page_axes`` and
    ``cache_slot_axes``.
    """
    res = PassResult(pass_name, target)
    out0, caches0 = fn(base, bank, caches, *extra_args)

    bad_bank = jax.tree.map(
        lambda p: p.at[victim].set(jax.numpy.full_like(p[victim], 1e9))
        if hasattr(p, "ndim") and p.ndim >= 1 and p.shape[0] > victim else p,
        bank,
    )
    out1, caches1 = fn(base, bad_bank, caches, *extra_args)

    other_rows = np.nonzero(np.asarray(clients) != victim)[0]
    res.checked["other_rows"] = len(other_rows)
    for r in other_rows:
        a = np.take(np.asarray(out0), r, axis=out_row_axis)
        b = np.take(np.asarray(out1), r, axis=out_row_axis)
        if not _bit_equal(a, b):
            res.add(
                f"corrupting client {victim}'s adapter changed the output of "
                f"row {r} (client {int(np.asarray(clients)[r])}) — adapter "
                "state leaks across clients",
                row=int(r), victim=victim,
            )

    flat0 = jax.tree_util.tree_flatten_with_path(caches0)[0]
    flat1 = jax.tree.leaves(caches1)
    flat_pa = jax.tree.leaves(page_axes, is_leaf=lambda x: x is None)
    flat_sa = jax.tree.leaves(slot_axes, is_leaf=lambda x: x is None)
    n_checked = 0
    for (path, l0), l1, pa, sa in zip(flat0, flat1, flat_pa, flat_sa):
        a0, a1 = np.asarray(l0), np.asarray(l1)
        if pa is not None:
            # Global pool: client c owns pages [c*P, (c+1)*P) along axis pa.
            keep = [i for i in range(a0.shape[pa])
                    if not (victim * pool_pages <= i < (victim + 1) * pool_pages)]
            a0, a1 = np.take(a0, keep, axis=pa), np.take(a1, keep, axis=pa)
        elif sa is not None:
            keep = [i for i in range(a0.shape[sa]) if i != victim]
            a0, a1 = np.take(a0, keep, axis=sa), np.take(a1, keep, axis=sa)
        else:
            continue
        n_checked += 1
        if not _bit_equal(a0, a1):
            res.add(
                f"corrupting client {victim}'s adapter changed cache leaf "
                f"{jax.tree_util.keystr(path)} outside client {victim}'s "
                "pages/slots — cache writes leak across clients",
                leaf=jax.tree_util.keystr(path), victim=victim,
            )
    res.checked["cache_leaves_checked"] = n_checked
    return res


def check_row_isolation(step: Callable, args: tuple, *, perturb_row: int,
                        victim_slot: int, perturb_argnums: tuple,
                        row_state_outs: tuple = (0, 1),
                        target: str, pass_name: str = "taint.isolation",
                        ) -> PassResult:
    """Perturb one train row's inputs; other rows' state must be unaffected.

    ``step(*args)`` returns a tuple whose entries named by ``row_state_outs``
    are pytrees with a leading bank-slot axis (new adapter params, new opt
    state). ``perturb_argnums`` name the args whose ``[perturb_row]`` slice
    gets corrupted (batch tokens, per-row hyperparams); ``victim_slot`` is
    the bank slot that row scatters into — every OTHER slot must come out
    bit-identical.
    """
    res = PassResult(pass_name, target)
    out0 = step(*args)

    def corrupt(x):
        if hasattr(x, "ndim") and x.ndim >= 1 and x.shape[0] > perturb_row:
            fill = 3 if np.issubdtype(np.asarray(x).dtype, np.integer) else 1e6
            return x.at[perturb_row].set(jax.numpy.full_like(x[perturb_row], fill))
        return x

    args1 = tuple(jax.tree.map(corrupt, a) if i in perturb_argnums else a
                  for i, a in enumerate(args))
    out1 = step(*args1)

    n_checked = 0
    for oi in row_state_outs:
        flat0 = jax.tree_util.tree_flatten_with_path(out0[oi])[0]
        flat1 = jax.tree.leaves(out1[oi])
        for (path, l0), l1 in zip(flat0, flat1):
            a0, a1 = np.asarray(l0), np.asarray(l1)
            if a0.ndim < 1 or a0.shape[0] <= victim_slot:
                continue
            keep = [i for i in range(a0.shape[0]) if i != victim_slot]
            n_checked += 1
            if not _bit_equal(np.take(a0, keep, 0), np.take(a1, keep, 0)):
                res.add(
                    f"perturbing train row {perturb_row}'s inputs changed "
                    f"output {oi} leaf {jax.tree_util.keystr(path)} outside "
                    f"bank slot {victim_slot} — per-row fine-tuning state "
                    "leaks across jobs",
                    output_index=oi, leaf=jax.tree_util.keystr(path),
                )
    res.checked["row_leaves_checked"] = n_checked
    return res
