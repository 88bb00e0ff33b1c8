"""A stand-in architecture module for the tests, registered as
``bench.archs.stub`` by the test that uses it: the dense GQA decoder under
weight draws of its own (every leaf's path key carries ``PREFIX``), with a
reference of its own that draws the same weights again, its own kernel
names, and counts that record their calls. It shows that a configuration
brings its architecture as a new file and nothing else."""
from __future__ import annotations

import collections
from functools import partial, reduce

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference, weights
from bench.archs import dense_gqa

PREFIX = "stub/"
# the prefix the reference draws with: a test sets another one for a
# reference that disagrees with the weights
REF_PREFIX = PREFIX
CALLS: collections.Counter = collections.Counter()

HF_KEYS = dense_gqa.HF_KEYS
KERNELS = {"decode": "stub_decode_attention",
           "prefill": "stub_prefill_attention"}
program_config = dense_gqa.program_config


@partial(jax.jit, static_argnums=(1, 2))
def _make(key_data, mc_items, prefix):
    mc = dict(mc_items)
    key = weights._key(key_data)
    layers = jnp.arange(mc["num_hidden_layers"])
    flat = {}
    for path, shape, kind in weights.layer_leaves(mc):
        flat["layers/" + path] = jax.vmap(
            lambda l, p=prefix + path, s=shape, k=kind:
            weights._draw(key, p, s, k, l))(layers)
    for path, shape, kind in weights.top_leaves(mc):
        flat[path] = weights._draw(key, prefix + path, shape, kind)
    return weights._nest(flat)


def _tree(seed, mc, prefix):
    return _make(jnp.asarray(weights.seed_key_data(seed)),
                 tuple(sorted(mc.items())), prefix)


def make_params(seed, mc, shardings=None):
    CALLS["make_params"] += 1
    if shardings is None:
        return _tree(seed, mc, PREFIX)
    return jax.jit(lambda: _tree(seed, mc, PREFIX),
                   out_shardings=shardings)()


def logit_gaps(seed, mc, seqs, length, *, control=False):
    """``reference.logit_gaps`` over this module's own draws."""
    CALLS["logit_gaps"] += 1
    tree = _tree(seed, mc, REF_PREFIX)
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    get = lambda path: reduce(dict.__getitem__, path.split("/"), tree)
    items = tuple(sorted(mc.items()))
    tokens, pos, seg, valid, offs = reference.pack(seqs, length)
    target = np.zeros(length, np.int32)
    target[:-1] = tokens[1:]
    jt, jp, js, jv = (jnp.asarray(a) for a in (tokens, pos, seg, valid))
    x = reference._embed(f32(tree["embed"]), jt, jv)
    xc = x if control else None
    for layer in range(mc["num_hidden_layers"]):
        w = {p: f32(get("layers/" + p)[layer])
             for p, _, _ in weights.layer_leaves(mc)}
        x = reference._layer(x, w, jp, js, jv, items, False)
        if control:
            xc = reference._layer(xc, w, jp, js, jv, items, True)
    w_out = (f32(tree["embed"]).T if mc["tie_word_embeddings"]
             else f32(get("lm_head/w")))
    best, at_t, at_c = reference._head(x, xc, f32(get("ln_f/scale")), w_out,
                                       jnp.asarray(target),
                                       mc["rms_norm_eps"])
    out = {} if at_c is None else {"at_control": np.asarray(at_c, np.float64)}
    out["best"] = np.asarray(best, np.float64)
    out["at_next"] = np.asarray(at_t, np.float64)
    out["offsets"] = offs
    return out


def _counted(name, fn):
    def count(*a, **k):
        CALLS[name] += 1
        return fn(*a, **k)
    return count


decode_token_flops = _counted("decode_token_flops",
                              dense_gqa.decode_token_flops)
prefill_chunk_flops = _counted("prefill_chunk_flops",
                               dense_gqa.prefill_chunk_flops)
decode_attn_cost = _counted("decode_attn_cost", dense_gqa.decode_attn_cost)
prefill_attn_cost = _counted("prefill_attn_cost", dense_gqa.prefill_attn_cost)
