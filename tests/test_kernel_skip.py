"""Work-skipping kernels: active-extent predication (DESIGN.md §12).

Covers the extent math (jnp / numpy twins + brute-force mask check), the
Pallas decode kernel's skip-on-vs-always-run bitwise identity across
pipeline depths / dtypes / dma modes, the chunked-prefill twin, the
interpret-resolution helper, and the engine-level token identity +
audit-counter accounting.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced
from repro.core.descriptor import active_block_extents
from repro.core.engine import EngineConfig, KVRMEngine
from repro.core.scheduler import Request
from repro.kernels import ref
from repro.kernels.paged_attention import paged_decode_attention_pallas
from repro.kernels.prefill_attention import chunked_prefill_attention_pallas
from repro.kernels.runtime import resolve_interpret
from repro.models import registry


# ---------------------------------------------------------------------------
# extent math: jnp twin == numpy twin == brute-forced mask support
# ---------------------------------------------------------------------------

def _brute_extent(wb, t, act, W, nb, bt):
    """Smallest [lo, hi) covering every unmasked decode position."""
    blocks = []
    for i in range(nb):
        pos = wb + i * bt + np.arange(bt)
        if act > 0 and np.any((pos <= t) & (pos > t - W) & (pos >= 0)):
            blocks.append(i)
    if not blocks:
        return 0, 0
    return min(blocks), max(blocks) + 1


@pytest.mark.parametrize("W,nb,bt", [(32, 5, 8), (24, 4, 8), (64, 5, 16)])
def test_extent_twins_and_brute_force(W, nb, bt):
    rng = np.random.default_rng(0)
    B = 64
    t = rng.integers(0, nb * bt + 8, size=B)
    wb = np.maximum(0, (t + 1 - W) // bt * bt)       # engine construction
    act = rng.integers(0, 2, size=B)
    lo_n, hi_n = active_block_extents(wb, t, act, near_window=W, nb=nb, bt=bt)
    lo_j, hi_j = ref.active_block_extent(
        jnp.asarray(wb), jnp.asarray(t), jnp.asarray(act),
        near_window=W, nb=nb, bt=bt)
    np.testing.assert_array_equal(lo_n, np.asarray(lo_j))
    np.testing.assert_array_equal(hi_n, np.asarray(hi_j))
    for b in range(B):
        blo, bhi = _brute_extent(wb[b], t[b], act[b], W, nb, bt)
        # exact under the engine's window-base construction: never narrower
        # (lossless) and never wider than the brute-forced support
        assert (lo_n[b], hi_n[b]) == (blo, bhi), \
            (b, wb[b], t[b], act[b], (lo_n[b], hi_n[b]), (blo, bhi))
    assert np.all(hi_n[act == 0] == lo_n[act == 0])


def test_chunk_extent_brute_force():
    W, nb, bt = 32, 5, 8
    for start in range(0, nb * bt):
        for wb in (0, 8, 16):
            if start < wb:
                continue
            lo, hi = ref.chunk_block_extent(jnp.asarray(wb), jnp.asarray(start),
                                            near_window=W, nb=nb, bt=bt)
            lo, hi = int(lo), int(hi)
            touched = []
            for i in range(nb):
                pos = wb + i * bt + np.arange(bt)
                # any chunk row attends pool positions in
                # [max(0, start - W + 1), start - 1]
                if np.any((pos >= max(0, start - W + 1)) & (pos < start)):
                    touched.append(i)
            blo, bhi = (min(touched), max(touched) + 1) if touched else (0, 0)
            assert (lo, hi) == (blo, bhi), (start, wb, (lo, hi), (blo, bhi))


# ---------------------------------------------------------------------------
# decode kernel: skip on == always-run, bitwise, across variants
# ---------------------------------------------------------------------------

def _skewed_case(seed, B, H, KV, hd, BT, NB, dtype=jnp.bfloat16):
    P = NB * B + 4
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (B, H, hd), dtype)
    pk = jax.random.normal(ks[1], (P, BT, KV, hd), dtype)
    pv = jax.random.normal(ks[2], (P, BT, KV, hd), dtype)
    tbl = np.stack([np.random.default_rng(i).permutation(np.arange(1, P))[:NB]
                    for i in range(B)]).astype(np.int32)
    # skewed lengths: one deep slot, short tails, and a retired slot
    # (extent == 0) when B allows
    rng = np.random.default_rng(seed + 9)
    seq = rng.integers(1, BT + 2, size=B).astype(np.int32)
    seq[0] = NB * BT - 1
    act = np.ones(B, np.int32)
    if B > 2:
        act[-1] = 0
    return (q, pk, pv, jnp.asarray(tbl), jnp.zeros(B, jnp.int32),
            jnp.asarray(seq), jnp.asarray(act))


@pytest.mark.parametrize("B,H,KV,hd,BT,NB", [
    (4, 4, 2, 32, 8, 4),
    (3, 8, 8, 64, 16, 3),     # MHA
    (2, 16, 2, 64, 8, 5),     # wide GQA ratio
])
def test_decode_skip_parity_and_identity(B, H, KV, hd, BT, NB):
    q, pk, pv, tbl, wb, seq, act = _skewed_case(0, B, H, KV, hd, BT, NB)
    W = NB * BT
    out_ref, _ = ref.paged_decode_attention_ref(q, pk, pv, tbl, wb, seq, act,
                                                near_window=W)
    out_ref_skip, _ = ref.paged_decode_attention_ref(
        q, pk, pv, tbl, wb, seq, act, near_window=W, skip_extent=True)
    # the extent mask only removes already-masked work: bitwise no-op
    assert jnp.array_equal(out_ref, out_ref_skip)
    outs = {}
    for depth in (0, 1):
        for skip in (True, False):
            outs[(depth, skip)], _ = paged_decode_attention_pallas(
                q, pk, pv, tbl, wb, seq, act, near_window=W,
                skip_extent=skip, prefetch_depth=depth)
    base = outs[(0, False)]
    for key, out in outs.items():
        assert jnp.array_equal(out, base), f"variant {key} not bitwise"
    np.testing.assert_allclose(np.asarray(base, np.float32),
                               np.asarray(out_ref, np.float32),
                               rtol=2e-2, atol=2e-2)


def test_decode_skip_retired_slot_zero():
    q, pk, pv, tbl, wb, seq, act = _skewed_case(1, 4, 4, 2, 32, 8, 4)
    out, _ = paged_decode_attention_pallas(q, pk, pv, tbl, wb, seq, act,
                                           near_window=32, skip_extent=True)
    assert bool((out[-1] == 0).all())          # retired slot: extent == 0
    assert not bool((out[0] == 0).all())


def test_decode_skip_dma_fallback_bitwise():
    """Double-buffered kernel: async-copy staging vs the interpret direct
    -read fallback must agree bitwise (same dequant + update order)."""
    q, pk, pv, tbl, wb, seq, act = _skewed_case(2, 3, 8, 2, 32, 8, 4)
    W = 32
    kw = dict(near_window=W, skip_extent=True, prefetch_depth=1)
    out_dma, _ = paged_decode_attention_pallas(q, pk, pv, tbl, wb, seq, act,
                                               dma=True, **kw)
    out_direct, _ = paged_decode_attention_pallas(q, pk, pv, tbl, wb, seq, act,
                                                  dma=False, **kw)
    assert jnp.array_equal(out_dma, out_direct)


def test_decode_skip_quant_bitwise():
    """int8 pools + SMEM scales: predication/double-buffering must not
    perturb the dequantizing path."""
    P, BT, KV, hd, B, H, NB = 20, 8, 2, 32, 3, 4, 4
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    q = jax.random.normal(ks[0], (B, H, hd), jnp.float32)
    kq = (jax.random.normal(ks[1], (P, BT, KV, hd)) * 60).astype(jnp.int8)
    vq = (jax.random.normal(ks[2], (P, BT, KV, hd)) * 60).astype(jnp.int8)
    ksc = jax.random.uniform(ks[3], (P, KV), minval=0.005, maxval=0.02)
    vsc = jax.random.uniform(ks[4], (P, KV), minval=0.005, maxval=0.02)
    tbl = jnp.asarray(np.stack([np.random.default_rng(i).permutation(
        np.arange(1, P))[:NB] for i in range(B)]).astype(np.int32))
    wb = jnp.zeros(B, jnp.int32)
    seq = jnp.asarray([NB * BT - 1, 3, 9], jnp.int32)
    act = jnp.ones(B, jnp.int32)
    W = NB * BT
    outs = [paged_decode_attention_pallas(
        q, kq, vq, tbl, wb, seq, act, near_window=W, k_scale=ksc,
        v_scale=vsc, skip_extent=skip, prefetch_depth=depth)[0]
        for depth in (0, 1) for skip in (True, False)]
    for out in outs[1:]:
        assert jnp.array_equal(out, outs[0])
    out_r, _ = ref.paged_decode_attention_ref(
        q, kq, vq, tbl, wb, seq, act, near_window=W,
        k_scale=ksc, v_scale=vsc)
    np.testing.assert_allclose(np.asarray(outs[0]), np.asarray(out_r),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# chunked prefill twin
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("start,n_valid", [(0, 8), (24, 6), (33, 8)])
def test_chunk_skip_parity_and_identity(start, n_valid):
    C, H, KV, hd, BT, NB = 8, 4, 2, 32, 8, 5
    P = NB + 4
    W = 32
    ks = jax.random.split(jax.random.PRNGKey(4), 5)
    q = jax.random.normal(ks[0], (C, H, hd), jnp.float32)
    pk = jax.random.normal(ks[1], (P, BT, KV, hd), jnp.float32)
    pv = jax.random.normal(ks[2], (P, BT, KV, hd), jnp.float32)
    ck = jax.random.normal(ks[3], (C, KV, hd), jnp.float32)
    cv = jax.random.normal(ks[4], (C, KV, hd), jnp.float32)
    tbl = jnp.asarray(np.random.default_rng(0).permutation(
        np.arange(1, P))[:NB].astype(np.int32))
    wb = jnp.asarray(max(0, (start + 1 - W) // BT * BT), jnp.int32)
    args = (q, pk, pv, ck, cv, tbl, wb, jnp.asarray(start, jnp.int32),
            jnp.asarray(n_valid, jnp.int32))
    out_on = chunked_prefill_attention_pallas(*args, near_window=W,
                                              skip_extent=True)
    out_off = chunked_prefill_attention_pallas(*args, near_window=W,
                                               skip_extent=False)
    assert jnp.array_equal(out_on, out_off)
    out_ref = ref.chunked_prefill_attention_ref(*args, near_window=W)
    out_ref_skip = ref.chunked_prefill_attention_ref(*args, near_window=W,
                                                     skip_extent=True)
    assert jnp.array_equal(out_ref, out_ref_skip)
    np.testing.assert_allclose(np.asarray(out_on), np.asarray(out_ref),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# interpret resolution
# ---------------------------------------------------------------------------

def test_resolve_interpret():
    """Interpret mode exactly when the backend is the CPU; an explicit
    argument wins."""
    assert resolve_interpret(True) is True
    assert resolve_interpret(False) is False
    assert resolve_interpret(None) == (jax.default_backend() == "cpu")


# ---------------------------------------------------------------------------
# engine: token identity + audit counters
# ---------------------------------------------------------------------------

def _run_engine(cfg, params, *, skip, depth, n=5):
    rng = np.random.default_rng(2)
    eng = KVRMEngine(cfg, params, EngineConfig(
        mode="paged_merge", batch=4, max_seq=64, block_tokens=8,
        pipeline_depth=depth, kernel_skip_extent=skip))
    for i in range(n):
        # bimodal skew: one long generation, short tails
        eng.submit(Request(rid=i, prompt=rng.integers(0, 100, size=4)
                           .astype(np.int32), gen_len=40 if i == 0 else 8))
    eng.run(max_steps=400)
    assert len(eng.sched.finished) == n
    return eng


def test_engine_skip_extent_token_identity():
    cfg = get_reduced("qwen2.5-32b")
    params = registry.init_params(jax.random.PRNGKey(7), cfg)
    runs = {}
    for depth in (0, 1):
        for skip in (True, False):
            eng = _run_engine(cfg, params, skip=skip, depth=depth)
            runs[(depth, skip)] = {r.rid: list(r.generated)
                                   for r in eng.sched.finished}
            a = eng.audit()
            assert a["kernel_skip_extent"] is skip
            assert a["kernel_blocks_total"] > 0
            if skip:
                # skewed lengths on a fixed grid MUST skip padded blocks,
                # and never more than the descriptor-side padded count
                assert 0 < a["kernel_blocks_skipped"] \
                    < a["kernel_blocks_total"]
            else:
                assert a["kernel_blocks_skipped"] == 0
    base = runs[(0, False)]
    for key, toks in runs.items():
        assert toks == base, f"tokens diverged for depth/skip {key}"
