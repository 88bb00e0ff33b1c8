"""Seeded random weights, made by the benchmark and handed to the program.

Every leaf is drawn from its own key, ``fold_in(fold_in(seed_key,
crc32(path)), layer)``, so the whole tree can be made on the device in one
jitted call for the program, and one layer at a time for the reference,
with the same values: a normal draw in float32, scaled, then rounded to the
served type (bfloat16). The reference upcasts those rounded values.

The tree layout is the program's parameter interface (``serving.build(...,
params=...)``): ``embed``, stacked ``layers`` (``ln1``, ``attn`` with
``wq``/``wk``/``wv``/``wo`` and their optional biases, ``ln2``, ``mlp`` with
``gate``/``up``/``down``), ``ln_f`` and ``lm_head``.
"""
from __future__ import annotations

import zlib
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

SERVED_DTYPE = jnp.bfloat16
EMBED_STD = 0.02
BIAS_STD = 0.1
NORM_JITTER = 0.1


def seed_key_data(seed: int) -> np.ndarray:
    """Two uint32 words of threefry key data from any non-negative seed
    (larger than 32 bits too)."""
    return np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)


def layer_leaves(mc: dict):
    """(path, shape, kind) of one decoder layer's leaves."""
    d, h, kv, hd, ff = (mc["hidden_size"], mc["num_attention_heads"],
                        mc["num_key_value_heads"], mc["head_dim"],
                        mc["intermediate_size"])
    out = [("ln1/scale", (d,), "norm"), ("ln2/scale", (d,), "norm")]
    for name, width in (("wq", h * hd), ("wk", kv * hd), ("wv", kv * hd)):
        out.append((f"attn/{name}/w", (d, width), "dense"))
        if mc["attention_bias"]:
            out.append((f"attn/{name}/b", (width,), "bias"))
    out.append(("attn/wo/w", (h * hd, d), "dense"))
    out += [("mlp/gate/w", (d, ff), "dense"), ("mlp/up/w", (d, ff), "dense"),
            ("mlp/down/w", (ff, d), "dense")]
    return out


def top_leaves(mc: dict):
    d, v = mc["hidden_size"], mc["vocab_size"]
    out = [("embed", (v, d), "embed"), ("ln_f/scale", (d,), "norm")]
    if not mc["tie_word_embeddings"]:
        out.append(("lm_head/w", (d, v), "dense"))
    return out


def _draw(key, path: str, shape, kind: str, layer=None):
    k = jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)
    if layer is not None:
        k = jax.random.fold_in(k, layer)
    x = jax.random.normal(k, shape, jnp.float32)
    if kind == "dense":
        x = x * (1.0 / np.sqrt(shape[0]))
    elif kind == "bias":
        x = x * BIAS_STD
    elif kind == "norm":
        x = 1.0 + NORM_JITTER * x
    elif kind == "embed":
        x = x * EMBED_STD
    return x.astype(SERVED_DTYPE)


def _nest(flat: dict) -> dict:
    tree: dict = {}
    for path, val in flat.items():
        node = tree
        parts = path.split("/")
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = val
    return tree


def _key(key_data):
    return jax.random.wrap_key_data(key_data, impl="threefry2x32")


@partial(jax.jit, static_argnums=1)
def _make_params(key_data, mc_items):
    mc = dict(mc_items)
    key = _key(key_data)
    layers = jnp.arange(mc["num_hidden_layers"])
    flat = {}
    for path, shape, kind in layer_leaves(mc):
        flat["layers/" + path] = jax.vmap(
            lambda l, p=path, s=shape, k=kind: _draw(key, p, s, k, l))(layers)
    for path, shape, kind in top_leaves(mc):
        flat[path] = _draw(key, path, shape, kind)
    return _nest(flat)


def make_params(seed: int, mc: dict):
    """The served parameter tree, made on the default device in one call."""
    return _make_params(jnp.asarray(seed_key_data(seed)),
                        tuple(sorted(mc.items())))


@partial(jax.jit, static_argnums=(1, 2))
def _layer_f32(key_data, mc_items, layer):
    mc = dict(mc_items)
    key = _key(key_data)
    return {p: _draw(key, p, s, k, layer).astype(jnp.float32)
            for p, s, k in layer_leaves(mc)}


@partial(jax.jit, static_argnums=(1, 2))
def _top_f32(key_data, mc_items, path):
    mc = dict(mc_items)
    (shape, kind), = [(s, k) for p, s, k in top_leaves(mc) if p == path]
    return _draw(_key(key_data), path, shape, kind).astype(jnp.float32)


def layer_f32(seed: int, mc: dict, layer: int) -> dict:
    """One layer's leaves as float32 (the served bf16 values, upcast),
    keyed by path."""
    return _layer_f32(jnp.asarray(seed_key_data(seed)),
                      tuple(sorted(mc.items())), int(layer))


def top_f32(seed: int, mc: dict, path: str):
    """``embed``, ``ln_f/scale`` or ``lm_head/w`` as float32."""
    return _top_f32(jnp.asarray(seed_key_data(seed)),
                    tuple(sorted(mc.items())), path)
