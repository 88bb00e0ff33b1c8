"""Pallas TPU kernel: paged near-window decode attention (KV-RM core).

One fixed-shape kernel instantiation per engine config (the paper's
shape-stable sliding decoder). The committed block table is a scalar-prefetch
operand: the grid walks the near window block-by-block and the BlockSpec
index_map dereferences the page mapping, so each grid step issues ONE
block-sized HBM->VMEM copy (~tau bytes — the merged transport quantum).
Because the pager places a session's blocks contiguously (tail-adjacent
RESERVE), consecutive grid steps touch physically-adjacent HBM regions and
Mosaic coalesces them into long DMA trains — descriptor merging realized as
a copy schedule (DESIGN.md §2).

Work skipping (DESIGN.md §12): the grid is still fixed at (B, NB) — one
compile per engine config — but the meta scalar-prefetch operand now carries
a per-slot *active block extent* [ext_lo, ext_hi): the first/last window
block with any position inside ``(t - near_window, t]`` (empty for retired
slots). Every grid step outside the extent is predicated off with
``@pl.when`` — zero dot products — and the K/V BlockSpec index map clamps
into the extent so the revisited index elides the HBM->VMEM copy too.
Fixed grid, variable work; skipping only ever removes fully-masked blocks,
so outputs are bitwise identical to the always-run kernel.

Device-side overlap (``prefetch_depth=1``): a double-buffered variant keeps
the pools in ANY memory space and stages block ``i+1``'s K/V into VMEM with
manual async copies while block ``i`` computes. ``dma=False`` swaps the
copies for direct ANY-space reads (same two-buffer rotation, no
semaphores) as a test hook.

Current token (``cur_k``/``cur_v``): the served decode step keeps the pool
read-only inside the layer scan and hands the kernel this step's K/V
directly. Pool positions are then valid for ``pos < t`` and the current
token joins the online softmax once, at finalize.

Layout notes (TPU):
  * last dim = head_dim (>= 128-lane friendly for standard models);
  * KV block = (BT, KV*hd) rows — BT >= 8 sublanes;
  * softmax state kept in VMEM scratch as (H, 128) replicated lanes.

Tensor-parallel decode (DESIGN.md §4): the kernel is shard-oblivious over a
kv-head slice — grid, BlockSpecs, and the GQA grouping ``n_rep = H // KV``
are all derived from the LOCAL operand shapes, so each `model` shard
instantiates the identical executable over its KV/tp kv heads (per-shard
softmax state (KV/tp, n_rep); no cross-shard state). Launch it per shard
through shard_map (kernels/ops.py) with q sharded on H, pools and scales on
KV, table/meta replicated; the layer's single psum happens downstream at
the output projection.

Validated in interpret mode against kernels/ref.py on CPU.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.runtime import resolve_interpret

NEG_INF = -1e30


def _online_block_update(acc_ref, m_ref, l_ref, qg, kb, vb, valid, scale):
    """One flash-style online-softmax block step (shared by both decode
    kernel variants so skip/prefetch A/Bs stay bitwise comparable)."""
    s = jax.lax.dot_general(qg, kb, (((2,), (2,)), ((0,), (1,))),
                            preferred_element_type=jnp.float32)  # (KV, n_rep, BT)
    s = s * scale
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_ref[...]                          # (KV, n_rep)
    m_new = jnp.maximum(m_prev, s.max(axis=-1))
    p = jnp.exp(s - m_new[..., None])
    p = jnp.where(valid, p, 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + p.sum(axis=-1)
    pv = jax.lax.dot_general(p, vb, (((2,), (0,)), ((0,), (1,))),
                             preferred_element_type=jnp.float32)  # (KV, n_rep, hd)
    acc_ref[...] = acc_ref[...] * corr[..., None] + pv
    m_ref[...] = m_new


def head_scales(scale_ref, blk, kv: int):
    """(1, KV, 1) f32 dequant scales of physical block ``blk``, read from a
    flat (P*KV,) SMEM scale table with one scalar load per kv head (Mosaic
    loads only scalars from SMEM) and placed into a vector by iota-select."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, kv, 1), 1)
    out = jnp.zeros((1, kv, 1), jnp.float32)
    for h in range(kv):
        out = jnp.where(lane == h, scale_ref[blk * kv + h], out)
    return out


def _block_step(acc_ref, m_ref, l_ref, q_ref, kb, vb, scales, *, blk, wb, t,
                i, active, bt, kv, n_rep, hd, near_window, scale, cur):
    """Dequantize (narrow pools) and fold one window block. With the current
    token riding outside the pool (``cur``) pool positions are valid for
    ``pos < t``; otherwise the pool already holds position t."""
    q = q_ref[0].astype(jnp.float32)             # (H, hd)
    kb = kb.astype(jnp.float32)                  # (BT, KV, hd)
    vb = vb.astype(jnp.float32)
    if scales is not None:
        ks_ref, vs_ref = scales
        kb = kb * head_scales(ks_ref, blk, kv)
        vb = vb * head_scales(vs_ref, blk, kv)
    qg = q.reshape(kv, n_rep, hd)                # group q heads per kv head
    pos = wb + i * bt + jax.lax.broadcasted_iota(jnp.int32, (1, 1, bt), 2)
    upper = (pos < t) if cur else (pos <= t)
    valid = upper & (pos > t - near_window) & (pos >= 0) & (active > 0)
    _online_block_update(acc_ref, m_ref, l_ref, qg, kb, vb, valid, scale)


def _finalize(acc_ref, m_ref, l_ref, q_ref, ck_ref, cv_ref, o_ref, *, active,
              kv, n_rep, hd, scale):
    """Fold the current token (if given) into the online softmax once, then
    normalize. An inactive slot scores -inf for it and outputs zeros."""
    if ck_ref is not None:
        qg = q_ref[0].astype(jnp.float32).reshape(kv, n_rep, hd)
        ck = ck_ref[0].astype(jnp.float32)      # (KV, 1, hd)
        cv = cv_ref[0].astype(jnp.float32)
        s_cur = (qg * ck).sum(axis=-1) * scale   # (KV, n_rep)
        s_cur = jnp.where(active > 0, s_cur, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s_cur)
        corr = jnp.exp(m_prev - m_new)
        p_cur = jnp.where(active > 0, jnp.exp(s_cur - m_new), 0.0)
        l_ref[...] = l_ref[...] * corr + p_cur
        acc_ref[...] = acc_ref[...] * corr[..., None] + p_cur[..., None] * cv
        m_ref[...] = m_new
    denom = jnp.maximum(l_ref[...], 1e-20)[..., None]
    out = (acc_ref[...] / denom).reshape(kv * n_rep, hd)
    o_ref[0] = jnp.where(active > 0, out, 0.0).astype(o_ref.dtype)


def _split_refs(refs, quant: bool, cur: bool, n_scratch: int):
    """(table, meta, scales|None, q, k, v, ck|None, cv|None, o, scratch)."""
    tbl, meta, *rest = refs
    scales = None
    if quant:
        scales, rest = (rest[0], rest[1]), rest[2:]
    q, k, v, *rest = rest
    ck = cv = None
    if cur:
        ck, cv, *rest = rest
    o, *scratch = rest
    assert len(scratch) >= n_scratch
    return tbl, meta, scales, q, k, v, ck, cv, o, scratch


def _decode_kernel(*refs, bt: int, kv: int, n_rep: int, hd: int,
                   near_window: int, scale: float, quant: bool, cur: bool):
    # quantized tier (DESIGN.md §10): per-block per-head dequant scales
    # arrive as extra flat scalar-prefetch operands (SMEM) and the
    # HBM->VMEM block copy grows a fused dequantize-on-load epilogue
    (block_tbl_ref, meta_ref, scales, q_ref, k_ref, v_ref, ck_ref, cv_ref,
     o_ref, (acc_ref, m_ref, l_ref)) = _split_refs(refs, quant, cur, 3)
    b = pl.program_id(0)
    i = pl.program_id(1)
    nb = pl.num_programs(1)

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    wb = meta_ref[b, 0]
    t = meta_ref[b, 1]
    active = meta_ref[b, 2]
    ext_lo = meta_ref[b, 3]
    ext_hi = meta_ref[b, 4]

    # active-extent predication (DESIGN.md §12): out-of-extent blocks are
    # fully masked anyway — the online update they'd run is an exact no-op
    # (m_new == m_prev, corr == 1, p == 0) — so the whole step is skipped.
    @pl.when((i >= ext_lo) & (i < ext_hi))
    def _compute():
        _block_step(acc_ref, m_ref, l_ref, q_ref, k_ref[0], v_ref[0], scales,
                    blk=block_tbl_ref[b, i], wb=wb, t=t, i=i, active=active,
                    bt=bt, kv=kv, n_rep=n_rep, hd=hd,
                    near_window=near_window, scale=scale, cur=cur)

    @pl.when(i == nb - 1)
    def _fin():
        _finalize(acc_ref, m_ref, l_ref, q_ref, ck_ref, cv_ref, o_ref,
                  active=active, kv=kv, n_rep=n_rep, hd=hd, scale=scale)


def _decode_kernel_db(*refs, bt: int, kv: int, n_rep: int, hd: int,
                      near_window: int, scale: float, quant: bool, cur: bool,
                      dma: bool):
    """Double-buffered decode variant (prefetch_depth=1): pools live in ANY
    memory space; block i+1's K/V is staged into one of two VMEM buffers
    (async copy when `dma`, direct read otherwise) while block i computes."""
    (block_tbl_ref, meta_ref, scales, q_ref, kh_ref, vh_ref, ck_ref, cv_ref,
     o_ref, scratch) = _split_refs(refs, quant, cur, 5)
    kbuf, vbuf, acc_ref, m_ref, l_ref, *sems = scratch
    b = pl.program_id(0)
    i = pl.program_id(1)
    nb = pl.num_programs(1)

    wb = meta_ref[b, 0]
    t = meta_ref[b, 1]
    active = meta_ref[b, 2]
    ext_lo = meta_ref[b, 3]
    ext_hi = meta_ref[b, 4]

    def _start_fetch(ib):
        slot = ib % 2
        blk = block_tbl_ref[b, ib]
        if dma:
            ksem, vsem = sems
            pltpu.make_async_copy(kh_ref.at[blk], kbuf.at[slot],
                                  ksem.at[slot]).start()
            pltpu.make_async_copy(vh_ref.at[blk], vbuf.at[slot],
                                  vsem.at[slot]).start()
        else:
            # test hook: same two-buffer rotation, synchronous read
            kbuf[slot] = kh_ref[blk]
            vbuf[slot] = vh_ref[blk]

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

        @pl.when(ext_hi > ext_lo)
        def _prime():
            _start_fetch(ext_lo)

    @pl.when((i >= ext_lo) & (i < ext_hi))
    def _compute():
        slot = i % 2
        blk = block_tbl_ref[b, i]
        if dma:
            ksem, vsem = sems
            pltpu.make_async_copy(kh_ref.at[blk], kbuf.at[slot],
                                  ksem.at[slot]).wait()
            pltpu.make_async_copy(vh_ref.at[blk], vbuf.at[slot],
                                  vsem.at[slot]).wait()

        # overlap: issue block i+1's fetch before touching block i's data
        @pl.when(i + 1 < ext_hi)
        def _ahead():
            _start_fetch(i + 1)

        _block_step(acc_ref, m_ref, l_ref, q_ref, kbuf[slot], vbuf[slot],
                    scales, blk=blk, wb=wb, t=t, i=i, active=active, bt=bt,
                    kv=kv, n_rep=n_rep, hd=hd, near_window=near_window,
                    scale=scale, cur=cur)

    @pl.when(i == nb - 1)
    def _fin():
        _finalize(acc_ref, m_ref, l_ref, q_ref, ck_ref, cv_ref, o_ref,
                  active=active, kv=kv, n_rep=n_rep, hd=hd, scale=scale)


@functools.partial(jax.jit, static_argnames=(
    "near_window", "skip_extent", "prefetch_depth", "dma", "interpret"))
def _paged_decode_attention_impl(q, pool_k, pool_v, block_table, window_base,
                                 seq_lens, slot_active, cur_k=None, cur_v=None,
                                 *, near_window, k_scale=None, v_scale=None,
                                 skip_extent=True, prefetch_depth=0,
                                 dma=True, interpret=True):
    from repro.kernels.ref import active_block_extent

    B, H, hd = q.shape
    P, BT, KV, _ = pool_k.shape
    NB = block_table.shape[1]
    assert H % KV == 0, (H, KV)          # holds globally AND per TP shard
    n_rep = H // KV
    scale = 1.0 / math.sqrt(hd)
    quant = k_scale is not None
    cur = cur_k is not None

    ext_lo, ext_hi = active_block_extent(
        window_base, seq_lens, slot_active,
        near_window=near_window, nb=NB, bt=BT)
    if not skip_extent:
        # always-run baseline: full extents make the predication trivially
        # true — the exact masked kernel, same executable, for bitwise A/Bs
        ext_lo = jnp.zeros_like(ext_lo)
        ext_hi = jnp.full_like(ext_hi, NB)
    meta = jnp.stack([window_base, seq_lens, slot_active.astype(jnp.int32),
                      ext_lo, ext_hi], axis=1).astype(jnp.int32)   # (B, 5)

    grid = (B, NB)
    nsp = 4 if quant else 2

    def _ix(f):
        # index maps take one trailing arg per scalar-prefetch operand
        return lambda b, i, tbl, meta, *scales: f(b, i, tbl, meta)

    def _blk_ix(b, i, tbl, meta):
        # clamp out-of-extent steps onto the extent boundary: the index map
        # revisits a block it already mapped, so Mosaic elides the copy for
        # every predicated-off grid step (the bandwidth half of the skip)
        j = jnp.clip(i, meta[b, 3], jnp.maximum(meta[b, 4] - 1, meta[b, 3]))
        return (tbl[b, j], 0, 0, 0)

    sp_args = (block_table.astype(jnp.int32), meta)
    if quant:
        # flat (P*KV,) tables: SMEM holds them unpadded, read per scalar
        sp_args += (k_scale.astype(jnp.float32).reshape(-1),
                    v_scale.astype(jnp.float32).reshape(-1))
    row_spec = pl.BlockSpec((1, H, hd), _ix(lambda b, i, tbl, meta: (b, 0, 0)))
    cur_specs, cur_args = [], ()
    if cur:
        # (B, KV, 1, hd): the block's last two dims are the array's own
        cur_specs = [pl.BlockSpec((1, KV, 1, hd),
                                  _ix(lambda b, i, tbl, meta: (b, 0, 0, 0)))] * 2
        cur_args = (cur_k.reshape(B, KV, 1, hd), cur_v.reshape(B, KV, 1, hd))
    softmax_scratch = [
        pltpu.VMEM((KV, n_rep, hd), jnp.float32),
        pltpu.VMEM((KV, n_rep), jnp.float32),
        pltpu.VMEM((KV, n_rep), jnp.float32),
    ]

    if prefetch_depth > 0:
        # double-buffered manual staging: pools bypass the BlockSpec pipeline
        kernel = functools.partial(
            _decode_kernel_db, bt=BT, kv=KV, n_rep=n_rep, hd=hd,
            near_window=near_window, scale=scale, quant=quant, cur=cur,
            dma=dma)
        scratch = [pltpu.VMEM((2, BT, KV, hd), pool_k.dtype),
                   pltpu.VMEM((2, BT, KV, hd), pool_v.dtype)] + softmax_scratch
        if dma:
            scratch += [pltpu.SemaphoreType.DMA((2,)),
                        pltpu.SemaphoreType.DMA((2,))]
        pool_specs = [pl.BlockSpec(memory_space=pl.ANY)] * 2
    else:
        kernel = functools.partial(
            _decode_kernel, bt=BT, kv=KV, n_rep=n_rep, hd=hd,
            near_window=near_window, scale=scale, quant=quant, cur=cur)
        scratch = softmax_scratch
        pool_specs = [pl.BlockSpec((1, BT, KV, hd), _ix(_blk_ix))] * 2
    gs = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=nsp,
        grid=grid,
        in_specs=[row_spec, *pool_specs, *cur_specs],
        out_specs=row_spec,
        scratch_shapes=scratch,
    )
    return pl.pallas_call(
        kernel, grid_spec=gs,
        out_shape=jax.ShapeDtypeStruct((B, H, hd), q.dtype),
        interpret=interpret,
        name="paged_decode_attention",
    )(*sp_args, q, pool_k, pool_v, *cur_args)


def paged_decode_attention_pallas(q, pool_k, pool_v, block_table, window_base,
                                  seq_lens, slot_active, *, near_window,
                                  cur_k=None, cur_v=None, k_scale=None,
                                  v_scale=None, skip_extent=True,
                                  prefetch_depth=0, dma=True, interpret=None):
    """Near-window paged decode attention over the committed block table.

    q: (B,H,hd); pool_k/pool_v: (P,BT,KV,hd); block_table: (B,NB).
    cur_k/cur_v: optional (B,KV,hd) current-token K/V riding outside the
    read-only pool (the served decode step's contract): pool positions are
    then valid for ``pos < t`` and the current token is folded into the
    online softmax once, at finalize — the semantics of
    ``ref.paged_decode_attention_ref`` with the same arguments.
    k_scale/v_scale: optional (P,KV) f32 per-block per-head dequant scales
    for narrow (int8 / float8_e4m3) pools — they ride as flat scalar-prefetch
    operands (SMEM) and each grid step's block copy dequantizes on load, so
    the descriptor contract and grid are unchanged (DESIGN.md §10).

    skip_extent=False pins every slot's extent to [0, NB) — the always-run
    masked baseline (same executable) for bitwise A/Bs. prefetch_depth=1
    selects the double-buffered manual-staging variant; dma=False swaps its
    async copies for direct reads (test hook). interpret=None resolves from
    the backend (kernels/runtime.py): CPU -> interpret, otherwise compiled.
    Returns (out (B,H,hd), far_util zeros (B,1))."""
    near_out = _paged_decode_attention_impl(
        q, pool_k, pool_v, block_table, window_base, seq_lens, slot_active,
        cur_k, cur_v, near_window=near_window, k_scale=k_scale,
        v_scale=v_scale, skip_extent=bool(skip_extent),
        prefetch_depth=int(prefetch_depth), dma=bool(dma),
        interpret=resolve_interpret(interpret))
    B = q.shape[0]
    return near_out, jnp.zeros((B, 1), jnp.float32)
