"""Pallas TPU kernels: blocked causal (flash-style) prefill attention, plus
the chunked paged-prefill kernel used by the engine's prompt-ingestion
executor (DESIGN.md §3).

Dense kernel: grid (B, H, nQ, nK) with online softmax in VMEM scratch; causal
blocks above the diagonal are skipped via masking (TPU grids are static — the
mask makes the skipped block a no-op; Mosaic elides the copy when the index
map is revisited). q/k blocks are MXU-aligned (multiples of 128 recommended).

Chunked kernel: grid (R, NB + 1), one C-token chunk per row (the engine
packs the slots with chunk work into the first rows). Steps
0..NB-1 walk the row's committed near-window block table (scalar prefetch,
one ~tau-byte HBM->VMEM copy of a full-KV block per step — the same
merged-transport contract as the decode kernel) and contract every kv head
at once; the final step folds the chunk's own K/V causally.
Pool steps outside the chunk's active block extent (DESIGN.md §12 — blocks
with no position in ``[max(0, start-W+1), start-1]``, i.e. the causal upper
triangle plus the window trailing edge) are predicated off with ``@pl.when``
and their copies elided via a clamped index map: fixed grid, variable work,
bitwise-identical output.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.paged_attention import head_scales
from repro.kernels.runtime import resolve_interpret

NEG_INF = -1e30


def _prefill_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
                    *, q_blk: int, k_blk: int, hd: int, causal: bool,
                    window: int, scale: float):
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0].astype(jnp.float32)          # (q_blk, hd)
    k = k_ref[0, 0].astype(jnp.float32)          # (k_blk, hd)
    v = v_ref[0, 0].astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    qpos = iq * q_blk + jax.lax.broadcasted_iota(jnp.int32, (q_blk, k_blk), 0)
    kpos = ik * k_blk + jax.lax.broadcasted_iota(jnp.int32, (q_blk, k_blk), 1)
    valid = jnp.ones((q_blk, k_blk), jnp.bool_)
    if causal:
        valid &= kpos <= qpos
    if window > 0:
        valid &= kpos > qpos - window
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + p.sum(axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(ik == nk - 1)
    def _fin():
        o_ref[0, 0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-20)
                       ).astype(o_ref.dtype)


def _chunk_kernel(*refs, bt: int, kv: int, n_rep: int, near_window: int,
                  scale: float, quant: bool):
    if quant:
        # quantized tier (DESIGN.md §10): per-block per-head dequant scales
        # as extra flat scalar-prefetch operands (SMEM); pool-block loads
        # grow a fused dequantize epilogue (the chunk's own K/V stays full
        # width)
        (block_tbl_ref, meta_ref, ks_ref, vs_ref,
         q_ref, k_ref, v_ref, ck_ref, cv_ref, o_ref,
         acc_ref, m_ref, l_ref) = refs
    else:
        (block_tbl_ref, meta_ref, q_ref, k_ref, v_ref, ck_ref, cv_ref,
         o_ref, acc_ref, m_ref, l_ref) = refs
    b = pl.program_id(0)
    i = pl.program_id(1)
    nb = pl.num_programs(1) - 1                   # pool steps; last = chunk
    chunk = ck_ref.shape[1]
    rows = q_ref.shape[2]                         # C * n_rep query rows

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    wb = meta_ref[b, 0]
    start = meta_ref[b, 1]
    n_valid = meta_ref[b, 2]
    ext_lo = meta_ref[b, 3]
    ext_hi = meta_ref[b, 4]
    q = q_ref[0].astype(jnp.float32)              # (KV, C*n_rep, hd)
    # query row r is chunk token r // n_rep (rows are token-major)
    row_tok = jax.lax.broadcasted_iota(jnp.int32, (1, rows, 1), 1) // n_rep
    qpos = start + row_tok

    def _fold(kb, vb, valid):
        s = jax.lax.dot_general(q, kb, (((2,), (2,)), ((0,), (1,))),
                                preferred_element_type=jnp.float32) * scale
        s = jnp.where(valid, s, NEG_INF)          # (KV, rows, n)
        m_prev = m_ref[...]                       # (KV, rows)
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        p = jnp.where(valid, jnp.exp(s - m_new[..., None]), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=-1)
        m_ref[...] = m_new
        pv = jax.lax.dot_general(p, vb, (((2,), (0,)), ((0,), (1,))),
                                 preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * corr[..., None] + pv

    # pool steps (i < nb) run only inside the chunk's active block extent
    # (DESIGN.md §12); out-of-extent pool blocks are fully masked anyway, so
    # predication is a bitwise no-op that skips both dots and (with the
    # clamped index map) the HBM->VMEM copy. ext_hi <= nb always.
    @pl.when((i >= ext_lo) & (i < ext_hi))
    def _pool_block():
        kb = k_ref[0].astype(jnp.float32)         # (BT, KV, hd)
        vb = v_ref[0].astype(jnp.float32)
        if quant:
            blk = block_tbl_ref[b, i]
            kb = kb * head_scales(ks_ref, blk, kv)
            vb = vb * head_scales(vs_ref, blk, kv)
        pos = wb + i * bt + jax.lax.broadcasted_iota(
            jnp.int32, (1, 1, bt), 2)             # (1,1,BT)
        valid = (pos < start) & (pos > qpos - near_window) & (pos >= 0)
        _fold(kb, vb, valid)

    @pl.when(i == nb)
    def _chunk_causal():
        kc = ck_ref[0].astype(jnp.float32)        # (C, KV, hd)
        vc = cv_ref[0].astype(jnp.float32)
        j = jax.lax.broadcasted_iota(jnp.int32, (1, 1, chunk), 2)
        valid = (start + j <= qpos) & (start + j > qpos - near_window) \
            & (j < n_valid)
        _fold(kc, vc, valid)
        # finalize (last grid step along axis 1)
        denom = jnp.maximum(l_ref[...], 1e-20)[..., None]
        o_ref[0] = jnp.where(row_tok < n_valid, acc_ref[...] / denom, 0.0
                             ).astype(o_ref.dtype)


def chunked_prefill_attention_pallas(q, pool_k, pool_v, cur_k, cur_v,
                                     block_table, window_base, start_pos,
                                     n_valid, *, near_window,
                                     k_scale=None, v_scale=None,
                                     skip_extent=True, interpret=None):
    """C-token prompt chunks over the paged near window, one per slot.

    Batched: q (B,C,H,hd); cur_k/v (B,C,KV,hd); block_table (B,NB);
    window_base/start_pos/n_valid (B,). A single slot's arguments (q
    (C,H,hd), block_table (NB,), scalar positions) are accepted too.
    pool_k/v: (P,BT,KV,hd). k_scale/v_scale: optional (P,KV) f32 per-block
    dequant scales for narrow pools (flat scalar-prefetch/SMEM; DESIGN.md
    §10). skip_extent=False pins the extent to [0, NB) — the always-run
    masked baseline. interpret=None resolves from the backend
    (kernels/runtime.py). Returns q's shape with rows >= n_valid zeroed.
    Validated against kernels/ref.py chunked_prefill_attention_ref."""
    single = q.ndim == 3
    if single:
        q, cur_k, cur_v, block_table = (x[None] for x in (
            q, cur_k, cur_v, block_table))
        window_base, start_pos, n_valid = (
            jnp.reshape(x, (1,)) for x in (window_base, start_pos, n_valid))
    out = _chunked_prefill_attention_impl(
        q, pool_k, pool_v, cur_k, cur_v, block_table, window_base, start_pos,
        n_valid, near_window=near_window, k_scale=k_scale, v_scale=v_scale,
        skip_extent=bool(skip_extent), interpret=resolve_interpret(interpret))
    return out[0] if single else out


@functools.partial(jax.jit, static_argnames=("near_window", "skip_extent",
                                             "interpret"))
def _chunked_prefill_attention_impl(q, pool_k, pool_v, cur_k, cur_v,
                                    block_table, window_base, start_pos,
                                    n_valid, *, near_window,
                                    k_scale=None, v_scale=None,
                                    skip_extent=True, interpret=True):
    from repro.kernels.ref import chunk_block_extent

    B, C, H, hd = q.shape
    P, BT, KV, _ = pool_k.shape
    NB = block_table.shape[1]
    n_rep = H // KV
    R = C * n_rep
    scale = 1.0 / math.sqrt(hd)
    quant = k_scale is not None

    ext_lo, ext_hi = chunk_block_extent(
        window_base, start_pos, near_window=near_window, nb=NB, bt=BT)
    if not skip_extent:
        ext_lo = jnp.zeros_like(ext_lo)
        ext_hi = jnp.full_like(ext_hi, NB)
    meta = jnp.stack([window_base, start_pos, n_valid, ext_lo, ext_hi],
                     axis=1).astype(jnp.int32)                  # (B, 5)
    # (B, KV, C*n_rep, hd): each kv head's query rows, token-major, so the
    # kernel contracts all heads at once against a full-KV pool block
    qg = q.reshape(B, C, KV, n_rep, hd).transpose(0, 2, 1, 3, 4
                                                  ).reshape(B, KV, R, hd)

    # NB window-block steps, then one step for the chunk itself
    grid = (B, NB + 1)
    kernel = functools.partial(_chunk_kernel, bt=BT, kv=KV, n_rep=n_rep,
                               near_window=near_window, scale=scale,
                               quant=quant)

    def _ix(f):
        # index maps take one trailing arg per scalar-prefetch operand
        return lambda b, i, tbl, meta, *scales: f(b, i, tbl, meta)

    def _blk_ix(b, i, tbl, meta):
        # clamp out-of-extent steps (incl. the final chunk step) onto the
        # extent boundary so the revisited index elides the block copy
        j = jnp.clip(i, meta[b, 3], jnp.maximum(meta[b, 4] - 1, meta[b, 3]))
        return (tbl[b, jnp.minimum(j, NB - 1)], 0, 0, 0)

    slot4 = _ix(lambda b, i, tbl, meta: (b, 0, 0, 0))
    gs = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4 if quant else 2,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, KV, R, hd), slot4),
            pl.BlockSpec((1, BT, KV, hd), _ix(_blk_ix)),
            pl.BlockSpec((1, BT, KV, hd), _ix(_blk_ix)),
            pl.BlockSpec((1, C, KV, hd), slot4),
            pl.BlockSpec((1, C, KV, hd), slot4),
        ],
        out_specs=pl.BlockSpec((1, KV, R, hd), slot4),
        scratch_shapes=[
            pltpu.VMEM((KV, R, hd), jnp.float32),
            pltpu.VMEM((KV, R), jnp.float32),
            pltpu.VMEM((KV, R), jnp.float32),
        ],
    )
    sp_args = (block_table.astype(jnp.int32), meta)
    if quant:
        sp_args += (k_scale.astype(jnp.float32).reshape(-1),
                    v_scale.astype(jnp.float32).reshape(-1))
    out = pl.pallas_call(
        kernel, grid_spec=gs,
        out_shape=jax.ShapeDtypeStruct((B, KV, R, hd), q.dtype),
        interpret=interpret,
        name="chunked_prefill_attention",
    )(*sp_args, qg, pool_k, pool_v, cur_k, cur_v)
    return out.reshape(B, KV, C, n_rep, hd).transpose(0, 2, 1, 3, 4
                                                       ).reshape(B, C, H, hd)


def prefill_attention_pallas(q, k, v, *, causal=True, window=None,
                             q_blk=128, k_blk=128, interpret=None):
    """q: (B,S,H,hd); k,v: (B,S,KV,hd) -> (B,S,H,hd). GQA via kv replication
    at the BlockSpec level (no materialized repeat). interpret=None resolves
    from the backend (kernels/runtime.py)."""
    return _prefill_attention_impl(q, k, v, causal=causal, window=window,
                                   q_blk=q_blk, k_blk=k_blk,
                                   interpret=resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("causal", "window", "q_blk",
                                             "k_blk", "interpret"))
def _prefill_attention_impl(q, k, v, *, causal=True, window=None,
                            q_blk=128, k_blk=128, interpret=True):
    B, S, H, hd = q.shape
    KV = k.shape[2]
    n_rep = H // KV
    scale = 1.0 / math.sqrt(hd)
    q_blk = min(q_blk, S)
    k_blk = min(k_blk, S)
    assert S % q_blk == 0 and S % k_blk == 0
    grid = (B, H, S // q_blk, S // k_blk)

    qt = q.transpose(0, 2, 1, 3)                 # (B,H,S,hd)
    kt = k.transpose(0, 2, 1, 3)                 # (B,KV,S,hd)
    vt = v.transpose(0, 2, 1, 3)

    kernel = functools.partial(_prefill_kernel, q_blk=q_blk, k_blk=k_blk,
                               hd=hd, causal=causal, window=window or 0,
                               scale=scale)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, q_blk, hd), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, k_blk, hd),
                         lambda b, h, iq, ik: (b, h // n_rep, ik, 0)),
            pl.BlockSpec((1, 1, k_blk, hd),
                         lambda b, h, iq, ik: (b, h // n_rep, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, q_blk, hd),
                               lambda b, h, iq, ik: (b, h, iq, 0)),
        scratch_shapes=[
            pltpu.VMEM((q_blk, hd), jnp.float32),
            pltpu.VMEM((q_blk, 1), jnp.float32),
            pltpu.VMEM((q_blk, 1), jnp.float32),
        ],
        out_shape=jax.ShapeDtypeStruct((B, H, S, hd), q.dtype),
        interpret=interpret,
    )(qt, kt, vt)
    return out.transpose(0, 2, 1, 3)
