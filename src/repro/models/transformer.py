"""Dense decoder-only transformer (qwen2.5 / qwen3 / yi / nemotron / internvl2
backbone). Layers are stacked and consumed with lax.scan for compact HLO.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels import ops
from repro.models import common as cm


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _layer_init(key, cfg: ModelConfig):
    ks = jax.random.split(key, 4)
    return {
        "ln1": cm.norm_init(cfg.d_model),
        "attn": cm.gqa_init(ks[0], cfg),
        "ln2": cm.norm_init(cfg.d_model),
        "mlp": cm.mlp_init(ks[1], cfg.d_model, cfg.d_ff, cfg.mlp_act),
    }


def init_params(key, cfg: ModelConfig):
    k_emb, k_layers, k_out = jax.random.split(key, 3)
    params = {
        "embed": (jax.random.normal(k_emb, (cfg.vocab_size, cfg.d_model), jnp.float32)
                  * 0.02).astype(cm.DTYPE),
        "layers": cm.stack_layers(partial(_layer_init, cfg=cfg), k_layers, cfg.n_layers),
        "ln_f": cm.norm_init(cfg.d_model),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = cm.dense_init(k_out, cfg.d_model, cfg.vocab_size)
    return params


def logits_head(params, cfg: ModelConfig, x):
    if cfg.tie_embeddings:
        return x @ params["embed"].T
    return cm.dense(params["lm_head"], x)


# ---------------------------------------------------------------------------
# full-sequence forward (train / prefill)
# ---------------------------------------------------------------------------

def forward(params, cfg: ModelConfig, tokens, *, extra_embeds=None,
            remat: bool = False):
    """tokens: (B, S) int32 -> logits (B, S, V).

    extra_embeds: optional (B, S_front, d) precomputed modality embeddings
    (vision/audio stubs) overwriting the first S_front positions.
    """
    B, S = tokens.shape
    x = params["embed"][tokens]
    if extra_embeds is not None:
        sf = extra_embeds.shape[1]
        x = jax.lax.dynamic_update_slice(x, extra_embeds.astype(x.dtype), (0, 0, 0))
    positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))

    def block(x, layer):
        x = cm.constrain_batch(x)
        h = cm.rmsnorm(layer["ln1"], x, cfg.norm_eps)
        x = x + cm.gqa_full(layer["attn"], cfg, h, positions)
        h = cm.rmsnorm(layer["ln2"], x, cfg.norm_eps)
        x = x + cm.mlp_apply(layer["mlp"], h, cfg.mlp_act)
        return x, None

    body = jax.checkpoint(block) if remat else block
    x, _ = jax.lax.scan(body, x, params["layers"])
    x = cm.rmsnorm(params["ln_f"], x, cfg.norm_eps)
    return logits_head(params, cfg, x)


# ---------------------------------------------------------------------------
# chunked prefill step (prompt ingestion; DESIGN.md §3)
# ---------------------------------------------------------------------------

def prefill_chunk(params, cfg: ModelConfig, pools, descr):
    """Ingest one C-token prompt chunk PER ROW under the KV-RM contract.

    descr: PrefillChunkDescriptor of R rows x C tokens over NB window
    blocks. A row is whichever slot the engine packed into it: each row
    carries its own positions, window table and write targets, and rows
    with n_valid=0 are masked. The engine compiles this at a few row widths
    and dispatches the smallest that holds the step's slots with chunk work
    (ONE dispatch per engine step), so the cost follows the rows passed.
    Writes each chunk's K/V into the paged pool and returns the updated
    pools. No logits: the final prompt token always goes through the
    decode step, so sampled-token semantics are unchanged.
    """
    sv = cfg.serving
    B, C = descr.tokens.shape
    x = params["embed"][descr.tokens]                 # (B, C, d)
    positions = descr.start_pos[:, None] + jnp.arange(C)[None]  # (B, C)
    quant = "k_scale" in pools                        # narrow KV tier (§10)

    # Same read-only pool discipline as decode_step: each layer's chunk K/V
    # attends explicitly and is emitted as a delta, scattered once post-scan.
    def block(x, layer_xs):
        if quant:
            layer, pk, pv, psk, psv = layer_xs
        else:
            layer, pk, pv = layer_xs
            psk = psv = None
        h = cm.rmsnorm(layer["ln1"], x, cfg.norm_eps)
        q, k, v = cm.gqa_qkv(layer["attn"], cfg, h, positions)
        o = ops.chunked_prefill_attention(
            q, pk, pv, k, v, descr.block_table, descr.window_base,
            descr.start_pos, descr.n_valid, near_window=sv.near_window,
            k_scale=psk, v_scale=psv, skip_extent=sv.skip_extent)  # (B,C,H,hd)
        x = x + cm.dense(layer["attn"]["wo"], o.reshape(B, C, -1))
        h = cm.rmsnorm(layer["ln2"], x, cfg.norm_eps)
        x = x + cm.mlp_apply(layer["mlp"], h, cfg.mlp_act)
        return x, (k, v)

    xs = ((params["layers"], pools["k"], pools["v"], pools["k_scale"],
           pools["v_scale"]) if quant
          else (params["layers"], pools["k"], pools["v"]))
    _, ys = jax.lax.scan(block, x, xs)
    new_pools = dict(pools)
    if quant:
        new_pools["k"], new_pools["k_scale"] = ops.quant_pool_write_chunk(
            pools["k"], pools["k_scale"], ys[0], descr.write_block,
            descr.write_offset, descr.n_valid)
        new_pools["v"], new_pools["v_scale"] = ops.quant_pool_write_chunk(
            pools["v"], pools["v_scale"], ys[1], descr.write_block,
            descr.write_offset, descr.n_valid)
        return new_pools
    new_pools["k"] = ops.pool_write_chunk(pools["k"], ys[0], descr.write_block,
                                          descr.write_offset, descr.n_valid)
    new_pools["v"] = ops.pool_write_chunk(pools["v"], ys[1], descr.write_block,
                                          descr.write_offset, descr.n_valid)
    return new_pools


# ---------------------------------------------------------------------------
# paged decode step (KV-RM path)
# ---------------------------------------------------------------------------

def decode_step(params, cfg: ModelConfig, tokens, pools, descr):
    """One fixed-shape decode step under the KV-RM contract.

    tokens: (B,) int32 current tokens. pools: dict with
      k, v: (L, P, BT, KV, hd); optionally far_k, far_v: (L, B, MAXC, KV, hd).
    descr: FrameDescriptor. Returns (logits (B,V), pools, far_util (B,CAP)).
    """
    B = tokens.shape[0]
    sv = cfg.serving
    x = params["embed"][tokens]                      # (B, d)
    pos = descr.seq_lens.astype(jnp.float32)[:, None]  # rope position = t

    farview = "far_k" in pools
    quant = "k_scale" in pools                       # narrow KV tier (§10)
    assert not (farview and quant), \
        "far view and the quantized KV tier are exclusive (DESIGN.md §10)"

    # The KV pools are READ-ONLY inside the layer scan; each layer's new K/V
    # attends explicitly (cur_k/cur_v) and is emitted as a per-layer delta,
    # scattered into the pool ONCE after the scan. Carrying the pool through
    # scan ys makes XLA copy (and on some backends convert) the full stacked
    # pool every layer (§Perf iteration 8: 850ms -> ~30ms memory term).
    def block(carry, layer_xs):
        x, fu = carry
        psk = psv = None
        if farview:
            layer, pk, pv, fk, fv = layer_xs
        elif quant:
            layer, pk, pv, psk, psv = layer_xs
            fk = fv = None
        else:
            layer, pk, pv = layer_xs
            fk = fv = None
        h = cm.rmsnorm(layer["ln1"], x, cfg.norm_eps)
        hq = h[:, None, :]                            # (B,1,d)
        q, k, v = cm.gqa_qkv(layer["attn"], cfg, hq, descr.seq_lens[:, None])
        q, k, v = q[:, 0], k[:, 0], v[:, 0]           # (B,H,hd)/(B,KV,hd)

        if farview:
            # summarize the just-completed chunk (predicated, fixed shape)
            sk = ops.farview_summarize(pk, descr.far_chunk_blocks,
                                       descr.far_chunk_tokens, descr.far_do_summarize)
            svv = ops.farview_summarize(pv, descr.far_chunk_blocks,
                                        descr.far_chunk_tokens, descr.far_do_summarize)
            bidx = jnp.arange(B)
            gate = (descr.far_do_summarize > 0)[:, None, None]
            fk = fk.at[bidx, descr.far_write_idx].set(
                jnp.where(gate, sk, fk[bidx, descr.far_write_idx]))
            fv = fv.at[bidx, descr.far_write_idx].set(
                jnp.where(gate, svv, fv[bidx, descr.far_write_idx]))

        o, futil = ops.paged_decode_attention(
            q, pk, pv, descr.block_table, descr.window_base, descr.seq_lens,
            descr.slot_active, near_window=sv.near_window,
            far_k=fk, far_v=fv,
            far_table=descr.far_table if farview else None,
            far_valid=descr.far_valid if farview else None,
            cur_k=k, cur_v=v, k_scale=psk, v_scale=psv,
            skip_extent=sv.skip_extent)
        x = x + cm.dense(layer["attn"]["wo"], o.reshape(B, -1))
        h = cm.rmsnorm(layer["ln2"], x, cfg.norm_eps)
        x = x + cm.mlp_apply(layer["mlp"], h, cfg.mlp_act)
        ys = (k, v, fk, fv) if farview else (k, v)
        return (x, fu + futil), ys

    fu0 = jnp.zeros((B, descr.far_table.shape[1]), jnp.float32)
    if farview:
        xs = (params["layers"], pools["k"], pools["v"], pools["far_k"],
              pools["far_v"])
    elif quant:
        xs = (params["layers"], pools["k"], pools["v"], pools["k_scale"],
              pools["v_scale"])
    else:
        xs = (params["layers"], pools["k"], pools["v"])
    (x, fu), ys = jax.lax.scan(block, (x, fu0), xs)
    if quant:
        # quantize-at-commit (§10): data + scale pools updated together
        new_k, new_ks = ops.quant_pool_write_stacked(
            pools["k"], pools["k_scale"], ys[0], descr.write_block,
            descr.write_offset, descr.slot_active)
        new_v, new_vs = ops.quant_pool_write_stacked(
            pools["v"], pools["v_scale"], ys[1], descr.write_block,
            descr.write_offset, descr.slot_active)
        new_pools = {"k": new_k, "v": new_v,
                     "k_scale": new_ks, "v_scale": new_vs}
    else:
        new_pools = {
            "k": ops.pool_write_stacked(pools["k"], ys[0], descr.write_block,
                                        descr.write_offset, descr.slot_active),
            "v": ops.pool_write_stacked(pools["v"], ys[1], descr.write_block,
                                        descr.write_offset, descr.slot_active),
        }
    if farview:
        new_pools["far_k"], new_pools["far_v"] = ys[2], ys[3]
    x = cm.rmsnorm(params["ln_f"], x, cfg.norm_eps)
    logits = logits_head(params, cfg, x)
    return logits, new_pools, fu / cfg.n_layers
