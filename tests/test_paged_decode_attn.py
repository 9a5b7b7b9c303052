"""Table-aware paged decode attention: byte-identity contracts (ISSUE 3).

Three layers of exactness, all BIT-exact (np.testing.assert_array_equal):

1. pallas kernel (interpret) == jnp stream twin — the same blocked math
   with and without the Pallas grid machinery;
2. in-place table reads == the gather reference (``via_gather=True``:
   gather_paged_kv materializes the dense view, then the identical blocked
   math runs over it with an identity table);
3. a client-vmapped call == the flat call on concatenated pools (the
   custom_vmap rule that makes masked and compacted decode the same
   computation).

Plus tolerance checks against the un-blocked full-softmax oracle
(decode_attn_ref), and the analogous contracts for the SGMV kernel. No
hypothesis dependency — these run everywhere tier-1 runs.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.decode_attn.decode_attn import (
    paged_decode_attn_pallas, paged_decode_attn_quant_pallas,
    paged_decode_attn_stream, paged_decode_attn_quant_stream)
from repro.kernels.decode_attn.ops import decode_attn, _dense_block_kv
from repro.kernels.decode_attn.ref import decode_attn_ref
from repro.kernels.sgmv.ops import sgmv
from repro.kernels.sgmv.sgmv import sgmv_pallas, sgmv_stream


def _paged_case(B, K, G, hd, P, blk, nb, seed=0, quant=False, pos=None):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = jax.random.normal(ks[0], (B, K, G, hd))
    if quant:
        pk = jax.random.randint(ks[1], (P, blk, K, hd), -127, 128).astype(jnp.int8)
        pv = jax.random.randint(ks[2], (P, blk, K, hd), -127, 128).astype(jnp.int8)
        kss = jax.random.uniform(ks[3], (P, blk, K, 1), minval=0.005, maxval=0.03)
        vss = jax.random.uniform(ks[4], (P, blk, K, 1), minval=0.005, maxval=0.03)
    else:
        pk = jax.random.normal(ks[1], (P, blk, K, hd))
        pv = jax.random.normal(ks[2], (P, blk, K, hd))
        kss = vss = None
    # scattered page assignment: rows' pages are arbitrary pool entries
    tbl = jax.random.permutation(ks[5], P)[:B * nb].reshape(B, nb).astype(jnp.int32)
    if pos is None:
        pos = jax.random.randint(jax.random.PRNGKey(seed + 7), (B,), 0,
                                 nb * blk)
    return q, pk, pv, kss, vss, tbl, jnp.asarray(pos, jnp.int32)


# (B, K, G, hd, P, blk, nb, window[, pos]): standard / non-dividing page
# count / single-page rows / sliding window; then, at fixed positions, rows
# whose context ends mid-block, exactly at a compute block's end, at the
# next block's first key, at pos 0, and a row with no live block (pos -1);
# and a table of 12 columns that its 8-page blocks do not divide, under a
# window whose first live block is not block 0
CASES = [(3, 2, 2, 32, 16, 8, 4, 0),
         (2, 1, 4, 64, 11, 16, 3, 0),
         (1, 2, 2, 32, 4, 8, 1, 0),
         (3, 2, 2, 32, 16, 8, 4, 12),
         (5, 2, 2, 32, 160, 8, 32, 0, (70, 127, 128, 0, -1)),
         (3, 2, 2, 32, 40, 16, 12, 40, (185, 130, 100))]


def _case(case, **kw):
    B, K, G, hd, P, blk, nb, w, *pos = case
    return w, _paged_case(B, K, G, hd, P, blk, nb,
                          pos=pos[0] if pos else None, **kw)


class TestPagedKernelContracts:
    @pytest.mark.parametrize("case", CASES)
    def test_pallas_interpret_equals_stream(self, case):
        w, (q, pk, pv, _, _, tbl, pos) = _case(case)
        a = jax.jit(functools.partial(paged_decode_attn_pallas, window=w,
                                      interpret=True))(q, pk, pv, tbl, pos)
        b = jax.jit(functools.partial(paged_decode_attn_stream, window=w))(
            q, pk, pv, tbl, pos)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    @pytest.mark.parametrize("case", CASES)
    def test_quant_pallas_interpret_equals_stream(self, case):
        w, (q, pk, pv, kss, vss, tbl, pos) = _case(case, quant=True)
        a = jax.jit(functools.partial(paged_decode_attn_quant_pallas, window=w,
                                      interpret=True))(q, pk, kss, pv, vss, tbl, pos)
        b = jax.jit(functools.partial(paged_decode_attn_quant_stream, window=w))(
            q, pk, kss, pv, vss, tbl, pos)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    @pytest.mark.parametrize("case", CASES)
    @pytest.mark.parametrize("quant", [False, True])
    def test_table_read_equals_gather_reference(self, case, quant):
        """In-place page reads == gather-then-same-math (the oracle that
        replaced the PR-2 in-step gather)."""
        w, (q, pk, pv, kss, vss, tbl, pos) = _case(case, quant=quant)
        kw = {"k_scale": kss, "v_scale": vss} if quant else {}
        direct = decode_attn(q, pk, pv, pos, block_tbl=tbl, window=w, **kw)
        oracle = decode_attn(q, pk, pv, pos, block_tbl=tbl, window=w,
                             via_gather=True, **kw)
        np.testing.assert_array_equal(np.asarray(direct), np.asarray(oracle))

    @pytest.mark.parametrize("case", CASES)
    @pytest.mark.parametrize("quant", [False, True])
    def test_matches_full_softmax_oracle(self, case, quant):
        w, (q, pk, pv, kss, vss, tbl, pos) = _case(case, quant=quant)
        kw = {"k_scale": kss, "v_scale": vss} if quant else {}
        y = decode_attn(q, pk, pv, pos, block_tbl=tbl, window=w, **kw)
        yr = decode_attn_ref(q, pk, pv, pos, window=w, block_tbl=tbl, **kw)
        np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                                   rtol=2e-4, atol=2e-4)

    @pytest.mark.parametrize("window", [0, 24])
    @pytest.mark.parametrize("quant", [False, True])
    def test_dead_pages_are_never_read(self, quant, window):
        """Entries past each row's last live page hold the engine's
        out-of-range sentinel, and every pool page no entry names is NaN.
        The kernel (whose interpreter raises on an out-of-bounds read), its
        twin and the gather reference agree bit for bit, and are finite."""
        B, K, G, hd, P, blk, nb = 4, 2, 2, 32, 96, 8, 20
        q, pk, pv, kss, vss, tbl, pos = _paged_case(
            B, K, G, hd, P, blk, nb, quant=quant, pos=(0, 37, 127, 150))
        live = np.arange(nb)[None] <= np.asarray(pos)[:, None] // blk
        tbl = jnp.where(live, tbl, 1 << 30)
        dead = ~np.isin(np.arange(P), np.asarray(tbl))
        poison = lambda x: x.at[dead].set(jnp.nan)
        if quant:
            kss, vss = poison(kss), poison(vss)
            args = (q, pk, kss, pv, vss, tbl, pos)
            kernel = paged_decode_attn_quant_pallas
            twin = paged_decode_attn_quant_stream
            kw = {"k_scale": kss, "v_scale": vss}
        else:
            pk, pv = poison(pk), poison(pv)
            args = (q, pk, pv, tbl, pos)
            kernel, twin, kw = paged_decode_attn_pallas, paged_decode_attn_stream, {}
        a = jax.jit(functools.partial(kernel, window=window,
                                      interpret=True))(*args)
        b = jax.jit(functools.partial(twin, window=window))(*args)
        oracle = decode_attn(q, pk, pv, pos, block_tbl=tbl, window=window,
                             via_gather=True, **kw)
        assert np.isfinite(np.asarray(a)).all()
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        np.testing.assert_array_equal(np.asarray(a), np.asarray(oracle))
        ref = decode_attn_ref(q, pk, pv, pos, window=window, block_tbl=tbl,
                              **kw)
        np.testing.assert_allclose(np.asarray(a), np.asarray(ref),
                                   rtol=2e-4, atol=2e-4)

    def test_vmapped_clients_equal_flat_pool_concat(self):
        """The custom_vmap rule: a bank of clients IS one client with more
        pages — the masked-vs-compacted byte-identity foundation."""
        C, B, K, G, hd, P, blk, nb = 3, 2, 2, 2, 32, 8, 8, 4
        qs, pks, pvs, tbls, poss = [], [], [], [], []
        for c in range(C):
            q, pk, pv, _, _, tbl, pos = _paged_case(B, K, G, hd, P, blk, nb,
                                                    seed=c)
            qs.append(q), pks.append(pk), pvs.append(pv)
            tbls.append(tbl), poss.append(pos)
        qs, pks, pvs, tbls, poss = map(jnp.stack, (qs, pks, pvs, tbls, poss))
        vm = jax.jit(jax.vmap(
            lambda q, k, v, t, p: decode_attn(q, k, v, p, block_tbl=t)))(
            qs, pks, pvs, tbls, poss)
        flat = jax.jit(lambda q, k, v, t, p: decode_attn(q, k, v, p, block_tbl=t))(
            qs.reshape(C * B, K, G, hd), pks.reshape(C * P, blk, K, hd),
            pvs.reshape(C * P, blk, K, hd),
            (tbls + jnp.arange(C)[:, None, None] * P).reshape(C * B, nb),
            poss.reshape(C * B))
        np.testing.assert_array_equal(np.asarray(vm.reshape(C * B, K, G, hd)),
                                      np.asarray(flat))


class TestDenseBlockPick:
    def test_divisor_avoids_pads(self):
        """T=300 with block 128: pick 100 (largest divisor in (64, 128]) —
        pads never materialize for mildly non-dividing depths."""
        assert _dense_block_kv(300, 128) == (100, 0)
        assert _dense_block_kv(512, 128) == (128, 0)
        assert _dense_block_kv(48, 512) == (48, 0)
        bkv, pad = _dense_block_kv(127, 64)   # prime-ish: falls back to pads
        assert pad == (-127) % bkv and pad > 0

    def test_nondividing_depth_matches_ref(self):
        B, K, G, hd, T = 2, 2, 2, 32, 300
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q = jax.random.normal(ks[0], (B, K, G, hd))
        k = jax.random.normal(ks[1], (B, T, K, hd))
        v = jax.random.normal(ks[2], (B, T, K, hd))
        pos = jnp.array([100, 299], jnp.int32)
        y = decode_attn(q, k, v, pos, block_kv=128)
        yr = decode_attn_ref(q, k, v, pos)
        np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                                   rtol=2e-4, atol=2e-4)


class TestSgmvContracts:
    def test_pallas_interpret_equals_stream(self):
        T, din, r, dout, n = 256, 64, 8, 128, 3
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        x = jax.random.normal(ks[0], (T, din))
        A = jax.random.normal(ks[1], (n, din, r)) * 0.3
        B = jax.random.normal(ks[2], (n, r, dout)) * 0.3
        ids = jnp.array([0, -1], jnp.int32)
        a = jax.jit(lambda *t: sgmv_pallas(*t, block_t=128, block_d=128,
                                                scale=0.5, interpret=True))(
            x, A, B, ids)
        b = jax.jit(lambda *t: sgmv_stream(*t, block_t=128, scale=0.5))(
            x, A, B, ids)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_per_row_equals_vmapped_lora(self):
        """block_t=1 SGMV == the per-client vmapped LoRA delta, bit for bit
        — the compacted decode's adapter exactness contract."""
        C, n, din, r, dout, scale = 3, 5, 64, 4, 96, 2.0
        ks = jax.random.split(jax.random.PRNGKey(1), 3)
        A = jax.random.normal(ks[0], (C, din, r)) * 0.3
        B = jax.random.normal(ks[1], (C, r, dout)) * 0.3
        xb = jax.random.normal(ks[2], (C, 2, 1, din))
        rc = jnp.array([0, 2, 1, 2, 0], jnp.int32)
        sid = jnp.array([0, 1, 0, 0, 1], jnp.int32)
        masked = jax.jit(jax.vmap(lambda x1, A1, B1: scale * jnp.einsum(
            "...r,ro->...o", jnp.einsum("...i,ir->...r", x1, A1), B1)))(xb, A, B)
        want = np.asarray(masked)[np.asarray(rc), np.asarray(sid)].reshape(n, dout)
        got = jax.jit(lambda x_, A_, B_, i_: sgmv(x_, A_, B_, i_, block_t=1,
                                                  scale=scale))(
            xb[rc, sid].reshape(n, din), A, B, rc)
        np.testing.assert_array_equal(np.asarray(got), want)
