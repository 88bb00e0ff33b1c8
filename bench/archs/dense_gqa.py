"""Dense grouped-query-attention decoder (Qwen2, Llama, Yi): the default
architecture module. It binds the seeded weights (``weights.py``), the
float32 reference (``reference.py``) and the operation and byte counts
(``flops.py``) to the program's ``ModelConfig``."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench import flops, reference, tracereduce, weights

# published config key -> (ModelConfig field, value map)
HF_KEYS = {
    "num_hidden_layers": ("n_layers", int),
    "hidden_size": ("d_model", int),
    "num_attention_heads": ("n_heads", int),
    "num_key_value_heads": ("n_kv_heads", int),
    "head_dim": ("head_dim", int),
    "intermediate_size": ("d_ff", int),
    "vocab_size": ("vocab_size", int),
    "attention_bias": ("qkv_bias", bool),
    "rope_theta": ("rope_theta", float),
    "rms_norm_eps": ("norm_eps", float),
    "tie_word_embeddings": ("tie_embeddings", bool),
    "hidden_act": ("mlp_act", {"silu": "swiglu"}.__getitem__),
}
KERNELS = dict(zip(("decode", "prefill"), tracereduce.KERNELS))


def program_config(cfile: dict):
    """The program's ModelConfig for a config file: the architecture's
    published config with every key of the file applied."""
    from repro.configs import get_config
    kw = {fld: conv(cfile["model"][k]) for k, (fld, conv) in HF_KEYS.items()}
    return get_config(cfile["arch"]).replace(**kw)


def make_params(seed: int, mc: dict, shardings=None):
    """The served parameter tree (``weights.make_params``). With
    ``shardings`` the one jitted call places each leaf in its sharding as
    it draws it, so no device holds the whole tree; the values are the
    same."""
    if shardings is None:
        return weights.make_params(seed, mc)
    items = tuple(sorted(mc.items()))
    make = jax.jit(lambda kd: weights._make_params(kd, items),
                   out_shardings=shardings)
    return make(jnp.asarray(weights.seed_key_data(seed)))


logit_gaps = reference.logit_gaps
decode_token_flops = flops.decode_token_flops
prefill_chunk_flops = flops.prefill_chunk_flops
decode_attn_cost = flops.decode_attn_cost
prefill_attn_cost = flops.prefill_attn_cost
