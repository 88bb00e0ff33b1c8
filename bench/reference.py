"""Plain float32 reference of the dense GQA decoder, kept with the benchmark.

Written from the published architecture and nothing of the program:
token embedding, RMSNorm, RoPE (theta, rotate-half pairing), grouped-query
attention with the optional q/k/v bias, SwiGLU MLP and the untied lm head.
It regenerates the weights from the seed itself (``weights.py``), one
layer at a time, so that it fits beside nothing: it runs after the
program's state is freed.

Several requests are packed into one fixed-length row (segment ids keep
them apart), so one compiled program serves every run of a cell. For each
packed position it returns the reference's best logit and its logit of the
next packed token; with ``control=True`` also the reference logit of the
token a float8 (e4m3, per-row / per-column scaled) computation puts first,
which is the lower-precision control of the correctness check.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights

HI = jax.lax.Precision.HIGHEST
Q_BLOCK = 512
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


def _q8(x, axis):
    """Round to float8 e4m3 with one scale per slice along ``axis``; the
    dequantized float32 values."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    s = jnp.where(amax > 0, amax / F8_MAX, 1.0)
    return (x / s).astype(F8).astype(jnp.float32) * s


def _mm(x, w, control):
    if control:
        x, w = _q8(x, -1), _q8(w, 0)
    return jnp.dot(x, w, precision=HI)


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, pos, theta):
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos[:, None, None].astype(jnp.float32) * inv
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], -1)


@partial(jax.jit, static_argnums=(5, 6))
def _layer(x, w, pos, seg, valid, mc_items, control):
    mc = dict(mc_items)
    n = x.shape[0]
    h_, kv, hd = (mc["num_attention_heads"], mc["num_key_value_heads"],
                  mc["head_dim"])
    eps = mc["rms_norm_eps"]
    h = _rmsnorm(x, w["ln1/scale"], eps)

    def proj(name):
        y = _mm(h, w[f"attn/{name}/w"], control)
        if f"attn/{name}/b" in w:
            y = y + w[f"attn/{name}/b"]
        return y

    q = _rope(proj("wq").reshape(n, h_, hd), pos, mc["rope_theta"])
    k = _rope(proj("wk").reshape(n, kv, hd), pos, mc["rope_theta"])
    v = proj("wv").reshape(n, kv, hd)
    rep = h_ // kv
    idx = jnp.arange(n)

    def attend(qi):
        # one block of queries against every packed key of its own segment
        rows = qi * Q_BLOCK + jnp.arange(Q_BLOCK)
        qb = jax.lax.dynamic_slice_in_dim(q, qi * Q_BLOCK, Q_BLOCK)
        qb = qb.reshape(Q_BLOCK, kv, rep, hd)
        s = jnp.einsum("qgrd,kgd->grqk", qb, k, precision=HI) / np.sqrt(hd)
        ok = ((seg[rows][:, None] == seg[None, :])
              & (idx[None, :] <= rows[:, None]) & valid[None, :])
        s = jnp.where(ok[None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, -1)
        p = jnp.where(ok[None, None], p, 0.0)
        o = jnp.einsum("grqk,kgd->qgrd", p, v, precision=HI)
        return o.reshape(Q_BLOCK, h_ * hd)

    o = jax.lax.map(attend, jnp.arange(n // Q_BLOCK)).reshape(n, h_ * hd)
    x = x + _mm(o, w["attn/wo/w"], control)
    h = _rmsnorm(x, w["ln2/scale"], eps)
    g = _mm(h, w["mlp/gate/w"], control)
    u = _mm(h, w["mlp/up/w"], control)
    return x + _mm(jax.nn.silu(g) * u, w["mlp/down/w"], control)


@jax.jit
def _embed(emb, tokens, valid):
    return jnp.where(valid[:, None], emb[tokens], 0.0)


@partial(jax.jit, static_argnums=(5,))
def _head(x_ref, x_ctl, ln_f, w_out, target, eps):
    """Per position: best reference logit, reference logit of ``target``
    and, given the control's hidden states ``x_ctl``, the reference logit
    of the control's first choice (blocked over rows)."""
    n = x_ref.shape[0]

    def one(i):
        sl = lambda a: jax.lax.dynamic_slice_in_dim(a, i * Q_BLOCK, Q_BLOCK)
        lr = jnp.dot(_rmsnorm(sl(x_ref), ln_f, eps), w_out, precision=HI)
        best = lr.max(-1)
        at_t = jnp.take_along_axis(lr, sl(target)[:, None], -1)[:, 0]
        if x_ctl is None:
            return best, at_t, None
        lc = _mm(_rmsnorm(sl(x_ctl), ln_f, eps), w_out, True)
        pick = jnp.argmax(lc, -1)
        return best, at_t, jnp.take_along_axis(lr, pick[:, None], -1)[:, 0]

    best, at_t, at_c = jax.lax.map(one, jnp.arange(n // Q_BLOCK))
    return (best.reshape(n), at_t.reshape(n),
            None if at_c is None else at_c.reshape(n))


def pack(seqs, length: int):
    """Pack token sequences into one row of ``length``. Returns tokens,
    positions, segment ids, validity and each sequence's offset."""
    tokens = np.zeros(length, np.int32)
    pos = np.zeros(length, np.int32)
    seg = np.full(length, -1, np.int32)
    offs, at = [], 0
    for i, s in enumerate(seqs):
        n = len(s)
        if at + n > length:
            raise ValueError(f"pack overflow: {at} + {n} > {length}")
        tokens[at:at + n] = s
        pos[at:at + n] = np.arange(n)
        seg[at:at + n] = i
        offs.append(at)
        at += n
    return tokens, pos, seg, seg >= 0, offs


def logit_gaps(seed: int, mc: dict, seqs, length: int, *,
               control: bool = False):
    """Run the reference over ``seqs`` packed into ``length`` tokens.

    Returns per-packed-position float64 arrays ``best`` and ``at_next``
    (reference logit of the next packed token) and, with ``control``,
    ``at_control``: the reference logit of the float8 computation's first
    choice. ``length`` must be a multiple of ``Q_BLOCK``.
    """
    if length % Q_BLOCK:
        raise ValueError(f"length {length} not a multiple of {Q_BLOCK}")
    mc_items = tuple(sorted(mc.items()))
    tokens, pos, seg, valid, offs = pack(seqs, length)
    target = np.zeros(length, np.int32)
    target[:-1] = tokens[1:]
    jt, jp, js, jv = (jnp.asarray(a) for a in (tokens, pos, seg, valid))
    emb = weights.top_f32(seed, mc, "embed")
    x = _embed(emb, jt, jv)
    del emb
    xc = x if control else None
    for layer in range(mc["num_hidden_layers"]):
        w = weights.layer_f32(seed, mc, layer)
        x = _layer(x, w, jp, js, jv, mc_items, False)
        if control:
            xc = _layer(xc, w, jp, js, jv, mc_items, True)
        del w
    ln_f = weights.top_f32(seed, mc, "ln_f/scale")
    w_out = (weights.top_f32(seed, mc, "embed").T if mc["tie_word_embeddings"]
             else weights.top_f32(seed, mc, "lm_head/w"))
    eps = mc["rms_norm_eps"]
    best, at_t, at_c = _head(x, xc, ln_f, w_out, jnp.asarray(target), eps)
    out = {} if at_c is None else {"at_control": np.asarray(at_c, np.float64)}
    out["best"] = np.asarray(best, np.float64)
    out["at_next"] = np.asarray(at_t, np.float64)
    out["offsets"] = offs
    return out
