"""Pallas kernels vs pure-jnp oracles (interpret mode on CPU).

Per kernel: shape/dtype sweeps + randomized property checks against ref.py.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st

from repro.kernels import ref
from repro.kernels.farview_summarize import farview_summarize_pallas
from repro.kernels.paged_attention import paged_decode_attention_pallas
from repro.kernels.prefill_attention import (chunked_prefill_attention_pallas,
                                             prefill_attention_pallas)


def _mk_paged(key, B, H, KV, hd, P, BT, NB, dtype, max_t=None):
    ks = jax.random.split(key, 5)
    q = jax.random.normal(ks[0], (B, H, hd), dtype)
    pk = jax.random.normal(ks[1], (P, BT, KV, hd), dtype)
    pv = jax.random.normal(ks[2], (P, BT, KV, hd), dtype)
    # random DISTINCT physical blocks per slot (avoid scratch block 0)
    tbl = np.stack([np.random.default_rng(i).permutation(np.arange(1, P))[:NB]
                    for i in range(B)]).astype(np.int32)
    max_t = max_t or NB * BT
    seq = np.random.default_rng(9).integers(1, max_t, size=B).astype(np.int32)
    wb = np.zeros(B, np.int32)
    act = np.ones(B, np.int32)
    return q, pk, pv, jnp.asarray(tbl), jnp.asarray(wb), jnp.asarray(seq), jnp.asarray(act)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,H,KV,hd,BT,NB", [
    (2, 4, 2, 32, 8, 4),
    (3, 8, 8, 64, 16, 3),     # MHA
    (1, 16, 2, 128, 8, 5),    # wide GQA ratio
])
def test_paged_decode_matches_ref(B, H, KV, hd, BT, NB, dtype):
    P = NB * B + 4
    args = _mk_paged(jax.random.PRNGKey(0), B, H, KV, hd, P, BT, NB, dtype)
    q, pk, pv, tbl, wb, seq, act = args
    W = NB * BT
    out_p, _ = paged_decode_attention_pallas(q, pk, pv, tbl, wb, seq, act,
                                             near_window=W)
    out_r, _ = ref.paged_decode_attention_ref(q, pk, pv, tbl, wb, seq, act,
                                              near_window=W)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(out_p, np.float32),
                               np.asarray(out_r, np.float32),
                               rtol=tol, atol=tol)


def test_paged_decode_inactive_slots_zero():
    B, H, KV, hd, BT, NB = 2, 4, 2, 32, 8, 4
    P = 16
    q, pk, pv, tbl, wb, seq, act = _mk_paged(
        jax.random.PRNGKey(1), B, H, KV, hd, P, BT, NB, jnp.float32)
    act = jnp.asarray([1, 0], jnp.int32)
    out, _ = paged_decode_attention_pallas(q, pk, pv, tbl, wb, seq, act,
                                           near_window=NB * BT)
    assert bool((out[1] == 0).all())
    assert not bool((out[0] == 0).all())


def test_paged_decode_sliding_window_mask():
    """Only the last W positions contribute (sliding semantics)."""
    B, H, KV, hd, BT, NB = 1, 2, 2, 16, 4, 4
    P = 8
    key = jax.random.PRNGKey(2)
    q, pk, pv, tbl, wb, seq, act = _mk_paged(key, B, H, KV, hd, P, BT, NB,
                                             jnp.float32)
    seq = jnp.asarray([15], jnp.int32)
    W = 6
    out_r, _ = ref.paged_decode_attention_ref(q, pk, pv, tbl, wb, seq, act,
                                              near_window=W)
    # corrupt all pool positions OUTSIDE the window; result must not change
    pos = np.arange(NB * BT)
    outside = pos[(pos <= 15 - W) | (pos > 15)]
    pk2, pv2 = np.asarray(pk).copy(), np.asarray(pv).copy()
    tbl_np = np.asarray(tbl)
    for p_ in outside:
        blk, off = divmod(int(p_), BT)
        pk2[tbl_np[0, blk], off] = 999.0
        pv2[tbl_np[0, blk], off] = 999.0
    out2, _ = ref.paged_decode_attention_ref(
        q, jnp.asarray(pk2), jnp.asarray(pv2), tbl, wb, seq, act, near_window=W)
    np.testing.assert_allclose(np.asarray(out_r), np.asarray(out2), rtol=1e-6)
    out_p, _ = paged_decode_attention_pallas(
        q, jnp.asarray(pk2), jnp.asarray(pv2), tbl, wb, seq, act, near_window=W)
    np.testing.assert_allclose(np.asarray(out_p), np.asarray(out_r), rtol=1e-5,
                               atol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,S,H,KV,hd,qb,kb", [
    (2, 256, 4, 2, 32, 64, 64),
    (1, 512, 8, 8, 64, 128, 128),
    (2, 128, 4, 1, 32, 64, 32),
])
def test_prefill_flash_matches_dense(B, S, H, KV, hd, qb, kb, dtype):
    from repro.models.common import attention_dense
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (B, S, H, hd), dtype)
    k = jax.random.normal(ks[1], (B, S, KV, hd), dtype)
    v = jax.random.normal(ks[2], (B, S, KV, hd), dtype)
    out_p = prefill_attention_pallas(q, k, v, causal=True, q_blk=qb, k_blk=kb)
    out_r = attention_dense(q, k, v, causal=True)
    tol = 3e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(out_p, np.float32),
                               np.asarray(out_r, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("C,H,KV,hd,BT,NB,start,n_valid", [
    (8, 4, 2, 32, 4, 5, 10, 6),      # partial chunk, GQA
    (16, 8, 8, 64, 8, 3, 16, 16),    # full chunk, MHA, block-aligned start
    (4, 4, 2, 32, 4, 0, 0, 3),       # first chunk: no pool context
])
def test_chunked_prefill_matches_ref(C, H, KV, hd, BT, NB, start, n_valid, dtype):
    NBt = max(NB, 1)
    P = NBt * 2 + 4
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    q = jax.random.normal(ks[0], (C, H, hd), dtype)
    pk = jax.random.normal(ks[1], (P, BT, KV, hd), dtype)
    pv = jax.random.normal(ks[2], (P, BT, KV, hd), dtype)
    ck = jax.random.normal(ks[3], (C, KV, hd), dtype)
    cv = jax.random.normal(ks[4], (C, KV, hd), dtype)
    tbl = jnp.asarray((np.arange(NBt) % (P - 1) + 1).astype(np.int32))
    W = max(NBt * BT, C + 1)
    args = (q, pk, pv, ck, cv, tbl, jnp.int32(0), jnp.int32(start),
            jnp.int32(n_valid))
    out_p = chunked_prefill_attention_pallas(*args, near_window=W)
    out_r = ref.chunked_prefill_attention_ref(*args, near_window=W)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(out_p, np.float32),
                               np.asarray(out_r, np.float32),
                               rtol=tol, atol=tol)
    # padded query rows contribute nothing downstream
    assert bool((np.asarray(out_p, np.float32)[n_valid:] == 0).all())


@pytest.mark.parametrize("kv_dtype", ["f32", "int8"])
def test_chunked_prefill_batched_matches_ref(kv_dtype):
    """The served executor's batched launch (one chunk per slot, each at its
    own start, fill and block table) equals the per-slot oracle."""
    B, C, H, KV, hd, BT, NB = 3, 8, 4, 2, 32, 4, 6
    P = NB * B + 2
    q, pk, pv, tbl, _, _, _ = _mk_paged(jax.random.PRNGKey(5), B, H, KV, hd,
                                        P, BT, NB, jnp.float32)
    ks = jax.random.split(jax.random.PRNGKey(6), 5)
    qc = jax.random.normal(ks[0], (B, C, H, hd), jnp.float32)
    ck = jax.random.normal(ks[1], (B, C, KV, hd), jnp.float32)
    cv = jax.random.normal(ks[2], (B, C, KV, hd), jnp.float32)
    kw = {}
    if kv_dtype == "int8":
        pk, pv = (pk * 40).astype(jnp.int8), (pv * 40).astype(jnp.int8)
        kw = dict(k_scale=jax.random.uniform(ks[3], (P, KV), minval=0.01,
                                             maxval=0.03),
                  v_scale=jax.random.uniform(ks[4], (P, KV), minval=0.01,
                                             maxval=0.03))
    wb = jnp.zeros(B, jnp.int32)
    start = jnp.asarray([0, 5, 13], jnp.int32)
    n_valid = jnp.asarray([8, 3, 6], jnp.int32)
    W = NB * BT
    out_p = chunked_prefill_attention_pallas(
        qc, pk, pv, ck, cv, tbl, wb, start, n_valid, near_window=W, **kw)
    out_r = jax.vmap(lambda q, k, v, t, w, s, n:
                     ref.chunked_prefill_attention_ref(
                         q, pk, pv, k, v, t, w, s, n, near_window=W, **kw))(
        qc, ck, cv, tbl, wb, start, n_valid)
    np.testing.assert_allclose(np.asarray(out_p), np.asarray(out_r),
                               rtol=1e-5, atol=1e-5)


def test_chunked_prefill_equals_token_at_a_time():
    """Feeding a chunk through the chunked kernel == feeding its tokens one
    at a time through the decode kernel with incremental pool writes."""
    C, H, KV, hd, BT = 6, 4, 2, 16, 4
    P, W = 12, 20
    ks = jax.random.split(jax.random.PRNGKey(4), 5)
    q = jax.random.normal(ks[0], (C, H, hd), jnp.float32)
    pk = jax.random.normal(ks[1], (P, BT, KV, hd), jnp.float32)
    pv = jax.random.normal(ks[2], (P, BT, KV, hd), jnp.float32)
    ck = jax.random.normal(ks[3], (C, KV, hd), jnp.float32)
    cv = jax.random.normal(ks[4], (C, KV, hd), jnp.float32)
    start = 10                         # context tokens 0..9 in blocks 1..3
    chunk_tbl = jnp.asarray(np.array([1, 2, 3, 0, 0], np.int32))
    out_c = ref.chunked_prefill_attention_ref(
        q, pk, pv, ck, cv, chunk_tbl, jnp.int32(0), jnp.int32(start),
        jnp.int32(C), near_window=W)
    # oracle: incremental decode with chunk token j written at block 3/4/...
    wpos = [(3, 2), (3, 3), (4, 0), (4, 1), (4, 2), (4, 3)]
    dec_tbl = jnp.asarray(np.array([[1, 2, 3, 4, 5, 0]], np.int32))
    pki, pvi = pk, pv
    for i in range(C):
        o, _ = ref.paged_decode_attention_ref(
            q[i][None], pki, pvi, dec_tbl, jnp.zeros(1, jnp.int32),
            jnp.asarray([start + i], jnp.int32), jnp.ones(1, jnp.int32),
            near_window=W, cur_k=ck[i][None], cur_v=cv[i][None])
        np.testing.assert_allclose(np.asarray(o[0]), np.asarray(out_c[i]),
                                   rtol=1e-5, atol=1e-5)
        b, off = wpos[i]
        pki = pki.at[b, off].set(ck[i])
        pvi = pvi.at[b, off].set(cv[i])


def test_prefill_flash_window():
    from repro.models.common import attention_dense
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    B, S, H, hd = 1, 256, 2, 32
    q = jax.random.normal(ks[0], (B, S, H, hd), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, H, hd), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, H, hd), jnp.float32)
    out_p = prefill_attention_pallas(q, k, v, causal=True, window=64,
                                     q_blk=64, k_blk=64)
    out_r = attention_dense(q, k, v, causal=True, window=64)
    np.testing.assert_allclose(np.asarray(out_p), np.asarray(out_r),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("payload", [(4, 16), (64,), (2, 8, 4)])
def test_farview_summarize_matches_ref(payload):
    P, BT, B, CB = 12, 8, 3, 2
    key = jax.random.PRNGKey(5)
    pool = jax.random.normal(key, (P, BT) + payload, jnp.float32)
    tbl = jnp.asarray([[1, 2], [3, 4], [5, 6]], jnp.int32)
    n_tok = jnp.asarray([16, 12, 16], jnp.int32)
    gate = jnp.asarray([1, 1, 0], jnp.int32)
    out_p = farview_summarize_pallas(pool, tbl, n_tok, gate)
    out_r = ref.farview_summarize_ref(pool, tbl, n_tok, gate)
    np.testing.assert_allclose(np.asarray(out_p), np.asarray(out_r),
                               rtol=1e-5, atol=1e-6)
    assert bool((out_p[2] == 0).all())


def test_mla_absorbed_equals_naive():
    """Absorbed-matmul MLA decode == naive per-head materialization."""
    B, H, dn, dr, dv, R_lat = 2, 4, 16, 8, 16, 32
    P, BT, NB = 12, 8, 3
    ks = jax.random.split(jax.random.PRNGKey(6), 6)
    q_nope = jax.random.normal(ks[0], (B, H, dn), jnp.float32)
    q_rope = jax.random.normal(ks[1], (B, H, dr), jnp.float32)
    pool = jax.random.normal(ks[2], (P, BT, R_lat + dr), jnp.float32)
    w_k_b = jax.random.normal(ks[3], (H, R_lat, dn), jnp.float32) * 0.1
    w_v_b = jax.random.normal(ks[4], (H, R_lat, dv), jnp.float32) * 0.1
    tbl = jnp.asarray([[1, 2, 3], [4, 5, 6]], jnp.int32)
    wb = jnp.zeros(B, jnp.int32)
    seq = jnp.asarray([10, 20], jnp.int32)
    act = jnp.ones(B, jnp.int32)
    out_a, _ = ref.mla_decode_attention_ref(
        q_nope, q_rope, pool, w_k_b, w_v_b, tbl, wb, seq, act,
        near_window=NB * BT, kv_lora_rank=R_lat)
    out_n = ref.mla_decode_attention_naive(
        q_nope, q_rope, pool, w_k_b, w_v_b, tbl, wb, seq, act,
        near_window=NB * BT, kv_lora_rank=R_lat)
    np.testing.assert_allclose(np.asarray(out_a), np.asarray(out_n),
                               rtol=1e-4, atol=1e-5)


def test_paged_decode_farview_consistency():
    """Far summaries with zero far_valid == pure near-window result."""
    B, H, KV, hd, BT, NB, CAP, MAXC = 2, 4, 2, 32, 8, 4, 4, 8
    P = 16
    q, pk, pv, tbl, wb, seq, act = _mk_paged(
        jax.random.PRNGKey(7), B, H, KV, hd, P, BT, NB, jnp.float32)
    fk = jax.random.normal(jax.random.PRNGKey(8), (B, MAXC, KV, hd))
    fv_ = jax.random.normal(jax.random.PRNGKey(9), (B, MAXC, KV, hd))
    ft = jnp.zeros((B, CAP), jnp.int32)
    fval = jnp.zeros((B, CAP), jnp.int32)
    W = NB * BT
    out0, fu0 = ref.paged_decode_attention_ref(q, pk, pv, tbl, wb, seq, act,
                                               near_window=W)
    out1, fu1 = ref.paged_decode_attention_ref(
        q, pk, pv, tbl, wb, seq, act, near_window=W,
        far_k=fk, far_v=fv_, far_table=ft, far_valid=fval)
    np.testing.assert_allclose(np.asarray(out0), np.asarray(out1),
                               rtol=1e-5, atol=1e-6)
    assert float(fu1.sum()) == 0.0


@settings(max_examples=15, deadline=None)
@given(st.integers(1, 3), st.integers(1, 4), st.integers(4, 32),
       st.integers(2, 5), st.data())
def test_paged_decode_property(B, KV, hd_pow, NB, data):
    """Property: pallas == ref across random geometry."""
    hd = (hd_pow // 4 + 1) * 16
    n_rep = data.draw(st.sampled_from([1, 2, 4]))
    H = KV * n_rep
    BT = data.draw(st.sampled_from([4, 8]))
    P = NB * B + 2
    q, pk, pv, tbl, wb, seq, act = _mk_paged(
        jax.random.PRNGKey(data.draw(st.integers(0, 100))),
        B, H, KV, hd, P, BT, NB, jnp.float32)
    W = data.draw(st.integers(2, NB * BT))
    out_p, _ = paged_decode_attention_pallas(q, pk, pv, tbl, wb, seq, act,
                                             near_window=W)
    out_r, _ = ref.paged_decode_attention_ref(q, pk, pv, tbl, wb, seq, act,
                                              near_window=W)
    np.testing.assert_allclose(np.asarray(out_p), np.asarray(out_r),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# served decode contract: the current token rides outside the pool
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("depth", [0, 1])
@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
def test_paged_decode_cur_token_matches_ref(kv_dtype, depth):
    """The kernel with cur_k/cur_v (pool valid for pos < t, the current
    token folded in at finalize) equals the oracle given the same inputs —
    including a retired slot (zeros) and a slot at t=0 whose only key is
    the current token."""
    B, H, KV, hd, BT, NB = 4, 8, 2, 32, 8, 4
    P = NB * B + 4
    q, pk, pv, tbl, wb, seq, act = _mk_paged(
        jax.random.PRNGKey(11), B, H, KV, hd, P, BT, NB, jnp.float32)
    ks = jax.random.split(jax.random.PRNGKey(12), 4)
    ck = jax.random.normal(ks[0], (B, KV, hd), jnp.float32)
    cv = jax.random.normal(ks[1], (B, KV, hd), jnp.float32)
    seq = seq.at[1].set(0)
    act = act.at[2].set(0)
    kw = {}
    if kv_dtype == "int8":
        pk = (pk * 40).astype(jnp.int8)
        pv = (pv * 40).astype(jnp.int8)
        kw = dict(k_scale=jax.random.uniform(ks[2], (P, KV), minval=0.01,
                                             maxval=0.03),
                  v_scale=jax.random.uniform(ks[3], (P, KV), minval=0.01,
                                             maxval=0.03))
    else:
        q, pk, pv, ck, cv = (x.astype(jnp.bfloat16)
                             for x in (q, pk, pv, ck, cv))
    W = NB * BT
    out_p, _ = paged_decode_attention_pallas(
        q, pk, pv, tbl, wb, seq, act, near_window=W, cur_k=ck, cur_v=cv,
        prefetch_depth=depth, **kw)
    out_r, _ = ref.paged_decode_attention_ref(
        q, pk, pv, tbl, wb, seq, act, near_window=W, cur_k=ck, cur_v=cv,
        **kw)
    tol = 2e-2 if kv_dtype == "bf16" else 1e-5
    np.testing.assert_allclose(np.asarray(out_p, np.float32),
                               np.asarray(out_r, np.float32),
                               rtol=tol, atol=tol)
    assert bool((np.asarray(out_p, np.float32)[2] == 0).all())
    # t=0 with nothing in the pool: the output is the current token's value
    cur_only = np.repeat(np.asarray(cv[1], np.float32), H // KV, axis=0)
    np.testing.assert_allclose(np.asarray(out_p[1], np.float32), cur_only,
                               rtol=tol, atol=tol)
