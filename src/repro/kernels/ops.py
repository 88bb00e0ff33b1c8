"""Attention entry points the models call, one implementation per backend.

Each entry point that has a Pallas kernel lowers per platform
(``jax.lax.platform_dependent``): on TPU the compiled kernel, everywhere
else the pure-jnp oracle in ``ref.py``. The choice is made where the
program is lowered, so a compile for a TPU that is only described (not
attached) takes the kernel branch too. No option selects the
implementation.

Under a tensor-parallel engine the kernels cannot be partitioned by
jit-auto; the engine traces its executors inside :func:`kernel_mesh`, and
the kernel branch then runs per ``model`` shard through ``shard_map`` (q on
H, pools and scales on KV, tables and meta replicated). The layer's one
psum stays at the output projection.

Models call these entry points and stay ignorant of paging internals.
"""
from __future__ import annotations

from contextlib import contextmanager

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels import ref

_MESH = None
_MODEL = "model"


@contextmanager
def kernel_mesh(mesh):
    """Trace the kernels' TPU branch per ``model`` shard of ``mesh`` (None:
    unsharded)."""
    global _MESH
    prev, _MESH = _MESH, mesh
    try:
        yield
    finally:
        _MESH = prev


def _per_shard(fn, in_specs, out_specs):
    """``fn`` over the kernel mesh's ``model`` shards (identity when the
    engine traces unsharded)."""
    if _MESH is None or _MESH.shape.get(_MODEL, 1) == 1:
        return fn
    return jax.shard_map(fn, mesh=_MESH, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


# ---------------------------------------------------------------------------

def pool_write(pool, new_vals, write_block, write_offset, active):
    return ref.pool_write_ref(pool, new_vals, write_block, write_offset, active)


def pool_write_stacked(pool, vals, write_block, write_offset, active):
    return ref.pool_write_stacked_ref(pool, vals, write_block, write_offset,
                                      active)


def pool_write_chunk(pool, vals, write_block, write_offset, n_valid):
    return ref.pool_write_chunk_ref(pool, vals, write_block, write_offset,
                                    n_valid)


def quant_pool_write_stacked(pool, scale, vals, write_block, write_offset,
                             active):
    """Quantize-at-commit write for the decode executor (DESIGN.md §10):
    narrow pool + per-block per-head scale pool updated together."""
    return ref.quant_pool_write_stacked_ref(pool, scale, vals, write_block,
                                            write_offset, active)


def quant_pool_write_chunk(pool, scale, vals, write_block, write_offset,
                           n_valid):
    """Quantize-at-commit write for the chunked prefill executor (§10)."""
    return ref.quant_pool_write_chunk_ref(pool, scale, vals, write_block,
                                          write_offset, n_valid)


_HEADS = P(None, _MODEL, None)            # (B, H|KV, hd)
_POOL = P(None, None, _MODEL, None)       # (P, BT, KV, hd)
_SCALE = P(None, _MODEL)                  # (P, KV)
_REPL = P()


def paged_decode_attention(q, pool_k, pool_v, block_table, window_base,
                           seq_lens, slot_active, *, near_window, cur_k,
                           cur_v, far_k=None, far_v=None, far_table=None,
                           far_valid=None, k_scale=None, v_scale=None,
                           skip_extent=False):
    """The served decode step's attention: the pool is read-only and the
    current token's K/V (B,KV,hd) rides outside it. Returns (out (B,H,hd),
    far_utility (B,CAP)). The far view (an optional policy, DESIGN.md §5)
    has no kernel and runs the oracle."""
    from repro.distributed.act_sharding import constrain_model_dim
    q = constrain_model_dim(q, -1)
    if far_k is not None and far_table is not None:
        return ref.paged_decode_attention_ref(
            q, pool_k, pool_v, block_table, window_base, seq_lens,
            slot_active, near_window=near_window, far_k=far_k, far_v=far_v,
            far_table=far_table, far_valid=far_valid, cur_k=cur_k,
            cur_v=cur_v, k_scale=k_scale, v_scale=v_scale,
            skip_extent=skip_extent)
    quant = k_scale is not None
    args = (q, pool_k, pool_v, block_table, window_base, seq_lens,
            slot_active, cur_k, cur_v)
    specs = (_HEADS, _POOL, _POOL, _REPL, _REPL, _REPL, _REPL, _HEADS,
             _HEADS)
    if quant:
        args += (k_scale, v_scale)
        specs += (_SCALE, _SCALE)

    def _kernel(*a):
        from repro.kernels import paged_attention
        ks, vs = a[9:] if quant else (None, None)
        return paged_attention.paged_decode_attention_pallas(
            *a[:7], near_window=near_window, cur_k=a[7], cur_v=a[8],
            k_scale=ks, v_scale=vs, skip_extent=skip_extent,
            interpret=False)[0]

    def _oracle(*a):
        ks, vs = a[9:] if quant else (None, None)
        return ref.paged_decode_attention_ref(
            *a[:7], near_window=near_window, cur_k=a[7], cur_v=a[8],
            k_scale=ks, v_scale=vs, skip_extent=skip_extent)[0]

    out = jax.lax.platform_dependent(
        *args, tpu=_per_shard(_kernel, specs, _HEADS), default=_oracle)
    return out, jnp.zeros((q.shape[0], 1), jnp.float32)


def chunked_prefill_attention(q, pool_k, pool_v, cur_k, cur_v, block_table,
                              window_base, start_pos, n_valid, *,
                              near_window, k_scale=None, v_scale=None,
                              skip_extent=False):
    """Batched prompt-chunk attention, one chunk per slot: paged pre-chunk
    context + in-chunk causal (the chunked prefill executor's core;
    DESIGN.md §3). q (B,C,H,hd); cur_k/v (B,C,KV,hd); block_table (B,NB);
    window_base/start_pos/n_valid (B,)."""
    quant = k_scale is not None
    args = (q, pool_k, pool_v, cur_k, cur_v, block_table, window_base,
            start_pos, n_valid)
    rows = P(None, None, _MODEL, None)    # (B, C, H|KV, hd)
    specs = (rows, _POOL, _POOL, rows, rows, _REPL, _REPL, _REPL, _REPL)
    if quant:
        args += (k_scale, v_scale)
        specs += (_SCALE, _SCALE)

    def _kernel(*a):
        from repro.kernels import prefill_attention as pfa
        ks, vs = a[9:] if quant else (None, None)
        return pfa.chunked_prefill_attention_pallas(
            *a[:9], near_window=near_window, k_scale=ks, v_scale=vs,
            skip_extent=skip_extent, interpret=False)

    def _oracle(*a):
        ks, vs = a[9:] if quant else (None, None)
        one = lambda q, k, v, tbl, wb, sp, nv: ref.chunked_prefill_attention_ref(
            q, a[1], a[2], k, v, tbl, wb, sp, nv, near_window=near_window,
            k_scale=ks, v_scale=vs, skip_extent=skip_extent)
        return jax.vmap(one)(a[0], *a[3:9])

    return jax.lax.platform_dependent(
        *args, tpu=_per_shard(_kernel, specs, rows), default=_oracle)


def mla_decode_attention(q_nope, q_rope, pool_lat, w_k_b, w_v_b, block_table,
                         window_base, seq_lens, slot_active, *, near_window,
                         kv_lora_rank, far_lat=None, far_table=None,
                         far_valid=None, cur_lat=None):
    return ref.mla_decode_attention_ref(
        q_nope, q_rope, pool_lat, w_k_b, w_v_b, block_table, window_base,
        seq_lens, slot_active, near_window=near_window,
        kv_lora_rank=kv_lora_rank, far_lat=far_lat, far_table=far_table,
        far_valid=far_valid, cur_lat=cur_lat)


def farview_summarize(pool, chunk_blocks, n_tokens, do_summarize):
    """Mean-pool one completed chunk per slot: pool (P,BT,KV,hd) ->
    (B,KV,hd)."""
    def _kernel(pool, chunk_blocks, n_tokens, do_summarize):
        from repro.kernels import farview_summarize as fvs
        return fvs.farview_summarize_pallas(pool, chunk_blocks, n_tokens,
                                            do_summarize, interpret=False)

    return jax.lax.platform_dependent(
        pool, chunk_blocks, n_tokens, do_summarize,
        tpu=_per_shard(_kernel, (_POOL, _REPL, _REPL, _REPL), _HEADS),
        default=ref.farview_summarize_ref)
