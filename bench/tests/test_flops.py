"""Operation and byte counts against hand counts."""
from bench import flops

# a tiny dense GQA layer: d 8, 4 q heads / 2 kv heads of 2, d_ff 16
MC = {"num_hidden_layers": 2, "hidden_size": 8, "num_attention_heads": 4,
      "num_key_value_heads": 2, "head_dim": 2, "intermediate_size": 16,
      "vocab_size": 10}


def test_layer_params():
    # wq 8x8, wk 8x4, wv 8x4, wo 8x8, gate/up 8x16, down 16x8
    assert flops.layer_matmul_params(MC) == 64 + 32 + 32 + 64 + 3 * 128


def test_decode_token_flops():
    # 2 layers x (2 x 576 matmul + 4 x 4 heads x 2 hd x 6 keys) + 2 x 8 x 10
    assert flops.decode_token_flops(MC, 5) == 2 * (2 * 576 + 4 * 4 * 2 * 6) + 160


def test_prefill_chunk_flops():
    # 3 tokens at positions 4, 5, 6 attend over 5 + 6 + 7 = 18 keys
    want = 2 * (2 * 576 * 3 + 4 * 4 * 2 * 18)
    assert flops.prefill_chunk_flops(MC, 4, 3) == want


def test_decode_attn_cost():
    # one slot at t=5: 2 layers x 4*4*2*6 flops; bytes per layer: 5 cached
    # tokens x (2 x 2 kv x 2 hd x 2 B) + the current K/V + q in and out
    fl, by = flops.decode_attn_cost(MC, [5])
    assert fl == 2 * 4 * 4 * 2 * 6
    assert by == 2 * (5 * 16 + 16 + 2 * 4 * 2 * 2)
    fl2, by2 = flops.decode_attn_cost(MC, [5, 5])
    assert (fl2, by2) == (2 * fl, 2 * by)


def test_prefill_attn_cost():
    fl, by = flops.prefill_attn_cost(MC, [(4, 3)])
    assert fl == 2 * 4 * 4 * 2 * 18
    assert by == 2 * (4 * 16 + 3 * 16 + 2 * 3 * 4 * 2 * 2)
