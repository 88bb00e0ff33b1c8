"""Shared model building blocks (functional JAX, no framework deps).

Conventions:
  * params are nested dicts of jnp arrays; layer stacks carry a leading L dim
    and are consumed with ``jax.lax.scan`` so HLO stays compact for 60-80 layer
    models (essential for dry-run compile times).
  * compute dtype bf16, fp32 for softmax/norm accumulation.
  * attention over long sequences uses blocked (flash-style) online softmax so
    compile-time memory analysis reflects O(S * block) temps, not O(S^2).
"""
from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.distributed.act_sharding import constrain_batch

DTYPE = jnp.bfloat16
# attention softmax/score accumulation dtype — f32 default; the bf16 variant
# halves score-tensor HBM traffic (EXPERIMENTS.md §Perf)
SCORE_DTYPE = jnp.float32


def set_score_dtype(dt):
    global SCORE_DTYPE
    SCORE_DTYPE = dt


# ---------------------------------------------------------------------------
# param init
# ---------------------------------------------------------------------------

def dense_init(key, d_in: int, d_out: int, bias: bool = False, scale: float | None = None):
    scale = scale if scale is not None else 1.0 / math.sqrt(d_in)
    p = {"w": (jax.random.normal(key, (d_in, d_out), jnp.float32) * scale).astype(DTYPE)}
    if bias:
        p["b"] = jnp.zeros((d_out,), DTYPE)
    return p


def dense(p, x):
    # f32 accumulation with one rounding at the end. Under tensor-parallel
    # decode (DESIGN.md §4) a contraction-sharded projection (wo, mlp down,
    # lm_head) becomes per-shard partial dots + one psum; keeping the partials
    # and the all-reduce in f32 makes the sharded result match the
    # single-device result bitwise on the CPU at every tested degree — a
    # bf16-output dot would round each partial before a bf16 all-reduce and
    # drift ~1e-2, flipping greedy argmax at bf16 logit ties. The TPU
    # compiler narrows these all-reduces to bf16 anyway (chip_smoke.py,
    # TP_TOL), so on the chip TP matches 1x1 only to bf16 rounding.
    y = jax.lax.dot_general(x, p["w"], (((x.ndim - 1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32).astype(x.dtype)
    if "b" in p:
        y = y + p["b"]
    return y


def norm_init(dim: int):
    return {"scale": jnp.ones((dim,), DTYPE)}


def rmsnorm(p, x, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * p["scale"].astype(jnp.float32)).astype(x.dtype)


def stack_layers(init_fn, key, n_layers: int):
    """vmap a per-layer init over split keys -> params with leading L dim."""
    keys = jax.random.split(key, n_layers)
    return jax.vmap(init_fn)(keys)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float):
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


# rope rotation pairing:
#   'half'        — (i, i+hd/2) pairs (llama convention; faithful default)
#   'interleaved' — (2i, 2i+1) pairs. Numerically a fixed permutation of the
#     'half' layout (weights permute accordingly when loading checkpoints);
#     crucially the pairs stay WITHIN a model-axis shard when head_dim is
#     sharded for TP decode, so rope doesn't force a resharding of K/Q and
#     the partial-score psum stays viable (EXPERIMENTS.md §Perf iter. 3).
ROPE_PAIRING = "half"


def set_rope_pairing(mode: str):
    global ROPE_PAIRING
    assert mode in ("half", "interleaved")
    ROPE_PAIRING = mode


def apply_rope(x, positions, theta: float):
    """x: (..., S, H, hd); positions: broadcastable to (..., S)."""
    hd = x.shape[-1]
    inv = rope_freqs(hd, theta)                       # (hd/2,)
    ang = positions[..., None].astype(jnp.float32) * inv   # (..., S, hd/2)
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]  # (..., S, 1, hd/2)
    xf = x.astype(jnp.float32)
    if ROPE_PAIRING == "interleaved":
        x1, x2 = xf[..., 0::2], xf[..., 1::2]
        r1 = x1 * cos - x2 * sin
        r2 = x1 * sin + x2 * cos
        out = jnp.stack([r1, r2], axis=-1).reshape(x.shape)
    else:
        x1, x2 = jnp.split(xf, 2, axis=-1)
        out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# attention (blocked flash-style for long sequences)
# ---------------------------------------------------------------------------

def repeat_kv(x, n_rep: int):
    """(B, S, KV, hd) -> (B, S, KV*n_rep, hd)."""
    if n_rep == 1:
        return x
    b, s, kv, hd = x.shape
    return jnp.broadcast_to(x[:, :, :, None, :], (b, s, kv, n_rep, hd)).reshape(b, s, kv * n_rep, hd)


def attention_dense(q, k, v, *, causal: bool, window: Optional[int] = None,
                    q_offset: int = 0):
    """Unblocked reference attention. q:(B,Sq,H,hd) k/v:(B,Sk,KV,hd)."""
    b, sq, h, hd = q.shape
    kvh = k.shape[2]
    k = repeat_kv(k, h // kvh)
    v = repeat_kv(v, h // kvh)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) / math.sqrt(hd)
    qpos = jnp.arange(sq) + q_offset
    kpos = jnp.arange(k.shape[1])
    mask = jnp.ones((sq, k.shape[1]), bool)
    if causal:
        mask &= kpos[None, :] <= qpos[:, None]
    if window is not None:
        mask &= kpos[None, :] > qpos[:, None] - window
    scores = jnp.where(mask[None, None], scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    vd = v.shape[-1]
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v).reshape(b, sq, h, vd)


def attention_blocked(q, k, v, *, causal: bool = True, window: Optional[int] = None,
                      q_block: int = 512, kv_block: int = 512):
    """Flash-style blocked attention with online softmax.

    q: (B, Sq, H, hd); k, v: (B, Sk, KV, hd). Memory O(Sq * kv_block) instead of
    O(Sq * Sk); compiled cost still counts the full causal einsum FLOPs.
    """
    b, sq, h, hd = q.shape
    sk, kvh = k.shape[1], k.shape[2]
    vd = v.shape[-1]
    n_rep = h // kvh
    if sq % q_block or sk % kv_block:
        return attention_dense(q, k, v, causal=causal, window=window)
    nq, nk = sq // q_block, sk // kv_block
    scale = 1.0 / math.sqrt(hd)

    qr = q.reshape(b, nq, q_block, h, hd).transpose(1, 0, 3, 2, 4)       # (nq,B,H,qb,hd)
    kr = k.reshape(b, nk, kv_block, kvh, hd).transpose(1, 0, 3, 2, 4)    # (nk,B,KV,kb,hd)
    vr = v.reshape(b, nk, kv_block, kvh, vd).transpose(1, 0, 3, 2, 4)

    def q_step(_, qi_and_idx):
        qi, iq = qi_and_idx
        qpos = iq * q_block + jnp.arange(q_block)

        def kv_step(carry, ki_vi_idx):
            acc, m, l = carry
            ki, vi, ik = ki_vi_idx
            kpos = ik * kv_block + jnp.arange(kv_block)
            # broadcast kv heads to q heads: group query heads per kv head
            qg = qi.reshape(b, kvh, n_rep, q_block, hd)
            s = jnp.einsum("bknqd,bkcd->bknqc", qg, ki).astype(SCORE_DTYPE) * scale
            mask = jnp.ones((q_block, kv_block), bool)
            if causal:
                mask &= kpos[None, :] <= qpos[:, None]
            if window is not None:
                mask &= kpos[None, :] > qpos[:, None] - window
            s = jnp.where(mask[None, None, None], s, -jnp.inf)
            m_new = jnp.maximum(m, s.max(axis=-1))
            # guard fully-masked rows
            m_safe = jnp.where(jnp.isinf(m_new), 0.0, m_new)
            p = jnp.exp((s - m_safe[..., None]).astype(SCORE_DTYPE))
            p = jnp.where(jnp.isinf(m_new)[..., None], 0.0, p)
            corr = jnp.exp(jnp.where(jnp.isinf(m), 0.0, m) - m_safe)
            corr = jnp.where(jnp.isinf(m), 0.0, corr)
            l = l * corr + p.sum(axis=-1)
            pv = jnp.einsum("bknqc,bkcd->bknqd", p.astype(vi.dtype), vi).astype(jnp.float32)
            acc = acc * corr[..., None] + pv
            return (acc, m_new, l), None

        acc0 = jnp.zeros((b, kvh, n_rep, q_block, vd), jnp.float32)
        m0 = jnp.full((b, kvh, n_rep, q_block), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((b, kvh, n_rep, q_block), jnp.float32)
        (acc, m, l), _ = jax.lax.scan(kv_step, (acc0, m0, l0),
                                      (kr, vr, jnp.arange(nk)))
        out = acc / jnp.maximum(l[..., None], 1e-20)
        return None, out.astype(q.dtype)

    _, outs = jax.lax.scan(q_step, None, (qr, jnp.arange(nq)))
    # outs: (nq, B, KV, n_rep, qb, vd) -> (B, Sq, H, vd)
    outs = outs.transpose(1, 0, 4, 2, 3, 5).reshape(b, sq, h, vd)
    return outs


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------

def mlp_init(key, d_model: int, d_ff: int, act: str):
    ks = jax.random.split(key, 3)
    if act == "swiglu":
        return {"gate": dense_init(ks[0], d_model, d_ff),
                "up": dense_init(ks[1], d_model, d_ff),
                "down": dense_init(ks[2], d_ff, d_model)}
    return {"up": dense_init(ks[0], d_model, d_ff),
            "down": dense_init(ks[1], d_ff, d_model)}


def mlp_apply(p, x, act: str):
    if act == "swiglu":
        return dense(p["down"], jax.nn.silu(dense(p["gate"], x)) * dense(p["up"], x))
    h = dense(p["up"], x)
    if act == "sq_relu":
        h = jnp.square(jax.nn.relu(h))
    else:
        h = jax.nn.gelu(h)
    return dense(p["down"], h)


# ---------------------------------------------------------------------------
# GQA attention layer (params + apply for full-sequence mode)
# ---------------------------------------------------------------------------

def gqa_init(key, cfg):
    ks = jax.random.split(key, 6)
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    p = {
        "wq": dense_init(ks[0], d, h * hd, bias=cfg.qkv_bias),
        "wk": dense_init(ks[1], d, kv * hd, bias=cfg.qkv_bias),
        "wv": dense_init(ks[2], d, kv * hd, bias=cfg.qkv_bias),
        "wo": dense_init(ks[3], h * hd, d),
    }
    if cfg.qk_norm:
        p["q_norm"] = norm_init(hd)
        p["k_norm"] = norm_init(hd)
    return p


def gqa_qkv(p, cfg, x, positions):
    """Project + rope. x: (B, S, d) -> q:(B,S,H,hd), k/v:(B,S,KV,hd)."""
    b, s, _ = x.shape
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = dense(p["wq"], x).reshape(b, s, h, hd)
    k = dense(p["wk"], x).reshape(b, s, kv, hd)
    v = dense(p["wv"], x).reshape(b, s, kv, hd)
    if cfg.qk_norm:
        q = rmsnorm(p["q_norm"], q, cfg.norm_eps)
        k = rmsnorm(p["k_norm"], k, cfg.norm_eps)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def gqa_full(p, cfg, x, positions, *, causal=True, window=None):
    """Full-sequence GQA attention (train / prefill)."""
    b, s, _ = x.shape
    q, k, v = gqa_qkv(p, cfg, x, positions)
    q, k, v = (constrain_batch(t) for t in (q, k, v))
    if s > 1024:
        o = attention_blocked(q, k, v, causal=causal, window=window)
    else:
        o = attention_dense(q, k, v, causal=causal, window=window)
    return dense(p["wo"], o.reshape(b, s, cfg.n_heads * cfg.head_dim))
