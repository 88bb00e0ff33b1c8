"""A whole run on the CPU at a tiny size, past the look for a chip: a
sound run is correct, the float8 control fails the cell's limit, and each
fault planted in the timed path makes ``correct`` come out false."""
import json
import time
from pathlib import Path

import pytest

from bench import harness

ROOT = Path(__file__).resolve().parents[2]
CELL = "qwen2.5-32b-l4.chat-bursty"
SEED = 2**32 + 17


def tiny_cell():
    real = harness.load_cell(CELL)
    cfile = json.loads((ROOT / "bench/configs/qwen2.5-32b-l4.json").read_text())
    cfile["model"].update(num_hidden_layers=2, hidden_size=64,
                          num_attention_heads=4, num_key_value_heads=2,
                          head_dim=16, intermediate_size=128, vocab_size=256)
    cfile["engine"] = {"batch": 4, "max_seq": 512, "block_tokens": 8,
                       "prefill_chunk": 16, "kv_dtype": "bf16"}
    mix = dict(real.mix, rate_per_s=8.0, windows_per_run=4, tenants=3,
               prompt={"median": 40, "sigma": 1.0, "min": 8, "max": 200},
               output={"median": 24, "sigma": 1.0, "min": 2, "max": 64},
               warmup_s=1.0, tail_s=1.0, drain_s=20.0)
    return harness.Cell(name=CELL, chips=1, cfile=cfile, mix=mix,
                        limits=real.limits, end_to_end=real.end_to_end,
                        per_layer=real.per_layer)


def run(tmp_path, hook=None, control=False):
    return harness.run_cell(tiny_cell(), SEED, 3.0, False,
                            t_start=time.perf_counter(), work_dir=tmp_path,
                            control=control, build_hook=hook)


@pytest.fixture(scope="module")
def sound(tmp_path_factory):
    return run(tmp_path_factory.mktemp("sound"), control=True)


def test_sound_run_is_correct(sound):
    result, diag, _ = sound
    assert result["correct"] is True
    assert diag["compared_tokens"] > 50
    assert list(result)[-1] == "checks"
    assert set(result["metrics"]) == {"setup_s", "out_tok_s", "ttft_p90_ms"}


def test_control_fails_the_limit(sound):
    _, diag, _ = sound
    limit = harness.load_cell(CELL).limits["max_logit_gap"]["limit"]
    assert diag["control_max_logit_gap"] > limit
    assert diag["control_correct"] is False


def _alter_tokens(gw):
    """Every token the decode executor produces is replaced by the next id."""
    eng = gw.engines[0]
    fn, vocab = eng._step_fn, eng.cfg.vocab_size

    def wrapped(*a):
        nxt, pools, fu, lg = fn(*a)
        return (nxt + 1) % vocab, pools, fu, lg
    eng._step_fn = wrapped


def _drop_prefill_state(gw):
    """The prefill-chunk executor returns the KV pools unchanged."""
    gw.engines[0]._chunk_fn = lambda params, pools, cflat: pools


def _drop_decode_state(gw):
    """The decode executor returns the KV pools it was given (its own
    token's K/V never lands in the cache)."""
    import jax
    import jax.numpy as jnp
    eng = gw.engines[0]
    fn = eng._step_fn

    def wrapped(params, flat, prev, pools):
        nxt, _, fu, lg = fn(params, flat, prev, jax.tree.map(jnp.copy, pools))
        return nxt, pools, fu, lg
    eng._step_fn = wrapped


def _drop_half_batch(gw):
    """The decode executor computes only the first half of its slots; the
    rest keep the token they had (nothing produced for them)."""
    eng = gw.engines[0]
    fn, half = eng._step_fn, eng.e.batch // 2

    def wrapped(params, flat, prev, pools):
        nxt, pools, fu, lg = fn(params, flat, prev, pools)
        return nxt.at[half:].set(prev[half:]), pools, fu, lg
    eng._step_fn = wrapped


@pytest.mark.parametrize(
    "fault", [_alter_tokens, _drop_prefill_state, _drop_decode_state,
              _drop_half_batch],
    ids=["token_altered", "prefill_state_unchanged",
         "decode_state_unchanged", "half_batch_left_out"])
def test_fault_makes_the_run_incorrect(tmp_path, fault):
    result, diag, _ = run(tmp_path, hook=fault)
    assert result["correct"] is False, diag
