"""Operations and bytes the algorithm needs, computed from shapes.

Counted from the model's configuration (HF keys) and the live token
positions, never from the compiled program's cost analysis or its padded
shapes. A multiply-add is two operations. KV is bfloat16 (2 bytes).
"""
from __future__ import annotations

from typing import Iterable, Tuple

KV_BYTES = 2


def layer_matmul_params(mc: dict) -> int:
    """Weights one token multiplies through in one decoder layer."""
    d, h, kv, hd, ff = (mc["hidden_size"], mc["num_attention_heads"],
                        mc["num_key_value_heads"], mc["head_dim"],
                        mc["intermediate_size"])
    return d * (h + 2 * kv) * hd + h * hd * d + 3 * d * ff


def attn_flops_per_layer(mc: dict, n_ctx: int) -> int:
    """One query attending over ``n_ctx`` keys (itself included): scores
    and the weighted sum of values, every query head."""
    return 4 * mc["num_attention_heads"] * mc["head_dim"] * n_ctx


def kv_bytes_per_token_layer(mc: dict) -> int:
    return 2 * mc["num_key_value_heads"] * mc["head_dim"] * KV_BYTES


def decode_token_flops(mc: dict, ctx: int) -> int:
    """Model operations to produce one token at position ``ctx`` (it
    attends over ``ctx + 1`` keys), lm head included."""
    L = mc["num_hidden_layers"]
    return (2 * L * layer_matmul_params(mc) + L * attn_flops_per_layer(mc, ctx + 1)
            + 2 * mc["hidden_size"] * mc["vocab_size"])


def prefill_chunk_flops(mc: dict, start: int, n: int) -> int:
    """Model operations to ingest ``n`` prompt tokens at positions
    ``start .. start+n-1`` (no logits: the chunk executor computes none)."""
    L = mc["num_hidden_layers"]
    keys = n * start + n * (n + 1) // 2          # sum of (start + i + 1)
    return 2 * L * layer_matmul_params(mc) * n + L * attn_flops_per_layer(mc, keys)


def decode_attn_cost(mc: dict, ctxs: Iterable[int]) -> Tuple[int, int]:
    """(flops, bytes) of the paged decode kernel for one call, all layers:
    each active slot at position t reads its t cached tokens' K and V and
    attends over t + 1 keys; q in, out written, the current K/V in."""
    L, h, hd = (mc["num_hidden_layers"], mc["num_attention_heads"],
                mc["head_dim"])
    kvb = kv_bytes_per_token_layer(mc)
    fl = by = 0
    for t in ctxs:
        fl += L * attn_flops_per_layer(mc, t + 1)
        by += L * (t * kvb + kvb + 2 * h * hd * KV_BYTES)
    return fl, by


def prefill_attn_cost(mc: dict, rows: Iterable[Tuple[int, int]]) -> Tuple[int, int]:
    """(flops, bytes) of the chunked prefill kernel for one call, all
    layers: per (start, n) row, causal attention of n queries over the
    start cached tokens and the chunk; reads the cached K/V once, the
    chunk's q and K/V, writes the output."""
    L, h, hd = (mc["num_hidden_layers"], mc["num_attention_heads"],
                mc["head_dim"])
    kvb = kv_bytes_per_token_layer(mc)
    fl = by = 0
    for start, n in rows:
        keys = n * start + n * (n + 1) // 2
        fl += L * attn_flops_per_layer(mc, keys)
        by += L * (start * kvb + n * kvb + 2 * n * h * hd * KV_BYTES)
    return fl, by
