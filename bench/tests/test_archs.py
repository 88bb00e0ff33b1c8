"""Architecture modules: both configuration files run the dense GQA module,
whose config, weights and counts are those the benchmark had before it
found architectures by name; and a multi-chip cell's weights are made
straight into the program's own parameter shardings."""
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import archs, flops, harness, weights
from bench.archs import dense_gqa

ROOT = Path(__file__).resolve().parents[2]
CELLS = ["qwen2.5-32b-l4.chat-bursty", "yi-34b-l4.reason-closed"]
SEED = 2**32 + 41
TINY = {"num_hidden_layers": 2, "hidden_size": 64, "num_attention_heads": 8,
        "num_key_value_heads": 4, "head_dim": 16, "intermediate_size": 128,
        "vocab_size": 256, "attention_bias": True, "rope_theta": 1e4,
        "rms_norm_eps": 1e-5, "tie_word_embeddings": False,
        "hidden_act": "silu", "max_position_embeddings": 4096}


@pytest.mark.parametrize("name", CELLS)
def test_config_files_run_the_dense_module(name):
    from repro.configs import get_config
    cell = harness.load_cell(name)
    assert "bench_arch" not in cell.cfile
    assert cell.arch is dense_gqa
    assert harness.Run(cell=cell, peaks={}).arch is dense_gqa
    m = cell.mc
    want = get_config(cell.cfile["arch"]).replace(
        n_layers=m["num_hidden_layers"], d_model=m["hidden_size"],
        n_heads=m["num_attention_heads"], n_kv_heads=m["num_key_value_heads"],
        head_dim=m["head_dim"], d_ff=m["intermediate_size"],
        vocab_size=m["vocab_size"], qkv_bias=m["attention_bias"],
        rope_theta=m["rope_theta"], norm_eps=m["rms_norm_eps"],
        tie_embeddings=m["tie_word_embeddings"], mlp_act="swiglu")
    assert harness.model_config(cell.cfile) == want


def test_a_module_is_found_by_name_only():
    assert archs.load("dense_gqa") is dense_gqa
    assert archs.for_config({"bench_arch": "dense_gqa"}) is dense_gqa
    for bad in ("../weights", "dense-gqa", ""):
        with pytest.raises(ValueError):
            archs.load(bad)
    with pytest.raises(ModuleNotFoundError):
        archs.for_config({"bench_arch": "no_such_arch"})


def _recorded_run(name):
    """A traced window [0, 10) with decode contexts and chunk rows written
    out, and kernel times in the trace."""
    peaks = json.loads((ROOT / "bench/peaks.json").read_text())
    run = harness.Run(cell=harness.load_cell(name),
                      peaks=peaks["devices"]["TPU v5 lite"], window=(0.0, 10.0))
    run.decode_calls = [(1.0, [700, 1024, 4000]), (2.0, [701, 1025]),
                        (11.0, [9, 9])]
    run.chunk_calls = [(1.5, [(0, 64), (192, 64)]), (3.0, [(2048, 17)]),
                       (-1.0, [(0, 64)])]
    run.trace = {"kernels": {"paged_decode_attention": 0.0123,
                             "chunked_prefill_attention": 0.0456}}
    return run


@pytest.mark.parametrize("name", CELLS)
def test_readers_read_the_dense_counts(name):
    run = _recorded_run(name)
    mc, pf, pb = run.mc, run.peaks["bf16_flops_per_s"], \
        run.peaks["hbm_bytes_per_s"]
    dec = [c for t, c in run.decode_calls if 0 <= t < 10]
    rows = [r for t, r in run.chunk_calls if 0 <= t < 10]
    total = sum(sum(flops.decode_token_flops(mc, c) for c in cs) for cs in dec)
    total += sum(sum(flops.prefill_chunk_flops(mc, s, n) for s, n in rs)
                 for rs in rows)
    assert harness.load_reader("step.mfu")(run) == \
        100.0 * total / (10.0 * 1 * pf)
    for metric, kernel, costs in (
            ("kernel.decode_attn_roofline", "paged_decode_attention",
             [flops.decode_attn_cost(mc, cs) for cs in dec]),
            ("kernel.prefill_attn_roofline", "chunked_prefill_attention",
             [flops.prefill_attn_cost(mc, rs) for rs in rows])):
        fl, by = sum(c[0] for c in costs), sum(c[1] for c in costs)
        want = 100.0 * max(fl / pf, by / pb) / run.trace["kernels"][kernel]
        assert harness.load_reader(metric)(run) == want, metric


def test_dense_make_params_is_the_weights_tree():
    got = dense_gqa.make_params(SEED, TINY)
    want = weights.make_params(SEED, TINY)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert a.dtype == b.dtype == jnp.bfloat16
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def test_an_engine_that_places_the_weights_again_is_refused():
    params = weights.make_params(SEED, TINY)
    harness.check_placed(params, SimpleNamespace(params=params))
    copied = dict(params, embed=jnp.copy(params["embed"]))
    with pytest.raises(RuntimeError, match="embed"):
        harness.check_placed(params, SimpleNamespace(params=copied))


SHARDED = textwrap.dedent("""
    import json, sys
    import jax, numpy as np
    from bench import harness
    from bench.tests.test_archs import TINY, SEED
    from repro import serving
    from repro.distributed import sharding as shd
    from repro.launch.mesh import lane_meshes_for_spec

    cell = harness.load_cell("qwen2.5-32b-l4.chat-bursty")
    cell.cfile = dict(cell.cfile, model=dict(TINY), engine={
        "batch": 4, "max_seq": 256, "block_tokens": 8, "prefill_chunk": 16,
        "kv_dtype": "bf16", "mesh_spec": "1x4"})
    params = harness.make_weights(cell, SEED)
    mesh = lane_meshes_for_spec("1x4")[0]
    cfg = harness.model_config(cell.cfile)
    specs = shd.sanitize_specs(mesh, params, shd.param_specs(cfg, params))
    whole = cell.arch.make_params(SEED, cell.mc)
    out = {"leaves": 0, "split": []}
    flat = jax.tree_util.tree_leaves_with_path(params)
    for (path, a), spec, b in zip(flat, jax.tree.leaves(
            specs, is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec)),
            jax.tree.leaves(whole)):
        name = jax.tree_util.keystr(path)
        assert a.sharding.mesh == mesh and a.sharding.spec == spec, name
        assert np.array_equal(np.asarray(a, np.float32),
                              np.asarray(b, np.float32)), name
        if max(s.data.size for s in a.addressable_shards) < a.size:
            out["split"].append(name)
        out["leaves"] += 1
    gw = serving.build(cfg, gateway=True, params=params, **cell.cfile["engine"])
    harness.check_placed(params, gw.engines[0])
    print(json.dumps(out))
""")


def test_a_multichip_cell_makes_its_weights_in_the_program_shardings():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([str(ROOT / "src"), str(ROOT)]))
    p = subprocess.run([sys.executable, "-c", SHARDED], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-4000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["leaves"] == len(jax.tree.leaves(
        jax.eval_shape(lambda: weights.make_params(SEED, TINY))))
    # kv-head and d_ff shards, the vocabulary and the lm head: none whole
    for name in ("['layers']['attn']['wk']['w']", "['layers']['attn']['wv']['w']",
                 "['layers']['mlp']['gate']['w']", "['layers']['mlp']['down']['w']",
                 "['embed']", "['lm_head']['w']"):
        assert name in out["split"], name
