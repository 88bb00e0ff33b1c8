"""Pallas TPU kernel: far-view chunk summarization (uniform aggregation).

Mean-pools one completed sv_chunk per slot from the paged pool into a single
summary row (paper §4.4: O(1) per-block construction, no scoring kernels).
Grid (B, CB): each step copies one chunk block (scalar-prefetched id) and
accumulates into VMEM scratch; the gate predicates the whole slot.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.runtime import resolve_interpret


def _sum_kernel(chunk_tbl_ref, meta_ref, pool_ref, o_ref, acc_ref,
                *, bt: int, width: int):
    b = pl.program_id(0)
    i = pl.program_id(1)
    cb = pl.num_programs(1)

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    n_tok = meta_ref[b, 0]
    gate = meta_ref[b, 1]

    blk = pool_ref[0].astype(jnp.float32)                      # (BT, width)
    pos = i * bt + jax.lax.broadcasted_iota(jnp.int32, (bt, 1), 0)
    m = ((pos < n_tok) & (gate > 0)).astype(jnp.float32)
    acc_ref[...] += (blk * m).sum(axis=0, keepdims=True)

    @pl.when(i == cb - 1)
    def _fin():
        denom = jnp.maximum(n_tok, 1).astype(jnp.float32)
        out = acc_ref[...] / denom
        o_ref[0] = jnp.where(gate > 0, out, 0.0).astype(o_ref.dtype)


def farview_summarize_pallas(pool, chunk_blocks, n_tokens, do_summarize,
                             interpret=None):
    """pool: (P,BT,...payload); chunk_blocks: (B,CB); n_tokens/do_summarize:
    (B,). Returns (B, ...payload) mean summaries (zeros where gated off).
    interpret=None resolves from the backend (kernels/runtime.py)."""
    return _farview_summarize_impl(pool, chunk_blocks, n_tokens, do_summarize,
                                   interpret=resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("interpret",))
def _farview_summarize_impl(pool, chunk_blocks, n_tokens, do_summarize,
                            interpret=True):
    P, BT = pool.shape[:2]
    payload = pool.shape[2:]
    width = 1
    for d in payload:
        width *= d
    B, CB = chunk_blocks.shape
    pool2 = pool.reshape(P, BT, width)
    meta = jnp.stack([n_tokens, do_summarize.astype(jnp.int32)], axis=1
                     ).astype(jnp.int32)

    kernel = functools.partial(_sum_kernel, bt=BT, width=width)
    gs = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, CB),
        in_specs=[pl.BlockSpec((1, BT, width),
                               lambda b, i, tbl, meta: (tbl[b, i], 0, 0))],
        # (B, 1, width) with (1, 1, width) blocks: the block's last two dims
        # are the array's own, as the TPU tiling requires
        out_specs=pl.BlockSpec((1, 1, width),
                               lambda b, i, tbl, meta: (b, 0, 0)),
        scratch_shapes=[pltpu.VMEM((1, width), jnp.float32)],
    )
    out = pl.pallas_call(kernel, grid_spec=gs,
                         out_shape=jax.ShapeDtypeStruct((B, 1, width),
                                                        pool.dtype),
                         interpret=interpret, name="farview_summarize",
                         )(chunk_blocks.astype(jnp.int32), meta, pool2)
    return out.reshape((B,) + payload)
