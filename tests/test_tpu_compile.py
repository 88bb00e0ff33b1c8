"""Compile the served path for a described TPU v5e (no chip attached).

The TPU compiler is installed with jax; it compiles for a topology that is
only described, and refuses what the chip would refuse (block shapes the
tiling cannot take, vector loads from SMEM, programs that do not fit).
Interpret mode on the CPU cannot show any of that. Shapes are
qwen2.5-32b's published widths: H=40 query / KV=8 kv heads of 128.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and pytest-xdist workers
all import every test module.
"""
import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

from repro.configs import get_config
from repro.core.descriptor import (chunk_flat_rows, chunk_flat_size,
                                   chunk_row_widths, empty_descriptor,
                                   unflatten_chunk_descriptor)
from repro.kernels.farview_summarize import farview_summarize_pallas
from repro.kernels.paged_attention import paged_decode_attention_pallas
from repro.kernels.prefill_attention import chunked_prefill_attention_pallas
from repro.models import registry

B, H, KV, HD, NB, C = 8, 40, 8, 128, 40, 64


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip: keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:                    # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sh):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sh)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _decode_args(sh, bt, pool_dtype):
    P = B * NB + 1
    i32 = jnp.int32
    return (_sds((B, H, HD), jnp.bfloat16, sh),
            _sds((P, bt, KV, HD), pool_dtype, sh),
            _sds((P, bt, KV, HD), pool_dtype, sh),
            _sds((B, NB), i32, sh), _sds((B,), i32, sh),
            _sds((B,), i32, sh), _sds((B,), i32, sh),
            _sds((B, KV, HD), jnp.bfloat16, sh),
            _sds((B, KV, HD), jnp.bfloat16, sh),
            _sds((P, KV), jnp.float32, sh), _sds((P, KV), jnp.float32, sh))


@pytest.mark.parametrize("bt,depth,kv_dtype", [
    (16, 0, jnp.bfloat16), (16, 1, jnp.bfloat16), (8, 0, jnp.bfloat16),
    (16, 0, jnp.int8), (16, 1, jnp.int8),
])
def test_paged_decode_kernel_compiles(one_chip, bt, depth, kv_dtype):
    quant = kv_dtype != jnp.bfloat16

    def fn(q, pk, pv, tbl, wb, seq, act, ck, cv, ks, vs):
        return paged_decode_attention_pallas(
            q, pk, pv, tbl, wb, seq, act, near_window=NB * bt, cur_k=ck,
            cur_v=cv, k_scale=ks if quant else None,
            v_scale=vs if quant else None, prefetch_depth=depth,
            interpret=False)[0]

    _compile(fn, *_decode_args(one_chip, bt, kv_dtype))


@pytest.mark.parametrize("kv_dtype", [jnp.bfloat16, jnp.int8])
def test_chunked_prefill_kernel_compiles(one_chip, kv_dtype):
    quant = kv_dtype != jnp.bfloat16
    bt = 16
    P = B * NB + 1
    sh, i32 = one_chip, jnp.int32

    def fn(q, pk, pv, ck, cv, tbl, wb, sp, nv, ks, vs):
        return chunked_prefill_attention_pallas(
            q, pk, pv, ck, cv, tbl, wb, sp, nv, near_window=NB * bt,
            k_scale=ks if quant else None, v_scale=vs if quant else None,
            interpret=False)

    _compile(fn, _sds((B, C, H, HD), jnp.bfloat16, sh),
             _sds((P, bt, KV, HD), kv_dtype, sh),
             _sds((P, bt, KV, HD), kv_dtype, sh),
             _sds((B, C, KV, HD), jnp.bfloat16, sh),
             _sds((B, C, KV, HD), jnp.bfloat16, sh),
             _sds((B, NB), i32, sh), _sds((B,), i32, sh),
             _sds((B,), i32, sh), _sds((B,), i32, sh),
             _sds((P, KV), jnp.float32, sh), _sds((P, KV), jnp.float32, sh))


def test_farview_summarize_kernel_compiles(one_chip):
    bt, cb = 16, 4
    P, sh = B * NB + 1, one_chip

    def fn(pool, tbl, n, gate):
        return farview_summarize_pallas(pool, tbl, n, gate, interpret=False)

    _compile(fn, _sds((P, bt, KV, HD), jnp.bfloat16, sh),
             _sds((B, cb), jnp.int32, sh), _sds((B,), jnp.int32, sh),
             _sds((B,), jnp.int32, sh))


def _served_shapes(cfg, sh, *, batch, max_seq, bt, kv_dtype="bf16"):
    """Param/pool/descriptor shapes of the engine's executors (its sizing
    rules: NB = ceil(W/bt)+1 window blocks, a worst-case pool + scratch)."""
    nb = -(-max_seq // bt) + 1
    blocks = batch * nb + 1
    params = _param_shapes(cfg)
    pools = registry.decode_pool_shapes(cfg, batch=batch, num_blocks=blocks,
                                        block_tokens=bt, kv_dtype=kv_dtype)
    place = lambda t: jax.tree.map(lambda s: _sds(s.shape, s.dtype, sh), t)
    return nb, place(params), place(pools)


def _param_shapes(cfg):
    return jax.eval_shape(lambda k: registry.init_params(k, cfg),
                          jax.random.PRNGKey(0))


def _decode_fn(cfg, max_seq):
    cfg_dec = cfg.replace(serving=dataclasses.replace(cfg.serving,
                                                near_window=max_seq))

    def step(params, tokens, pools, descr):
        logits, pools, _ = registry.decode_step(params, cfg_dec, tokens,
                                                pools, descr)
        return jnp.argmax(logits, -1).astype(jnp.int32), pools
    return step


def test_served_decode_step_compiles(one_chip):
    """The 4-layer published-width qwen2.5-32b decode step with the Pallas
    kernel inside, for one v5e: it fits, and the kernel is in its HLO."""
    cfg = get_config("qwen2.5-32b").replace(n_layers=4)
    batch, max_seq, bt = 8, 2048, 16
    nb, params, pools = _served_shapes(cfg, one_chip, batch=batch,
                                       max_seq=max_seq, bt=bt)
    descr = jax.tree.map(lambda a: _sds(a.shape, a.dtype, one_chip),
                         empty_descriptor(batch, nb, 1, nb + 1))
    tokens = _sds((batch,), jnp.int32, one_chip)
    compiled = jax.jit(_decode_fn(cfg, max_seq), donate_argnums=(2,)).lower(
        params, tokens, pools, descr).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes > 7e9          # bf16 weights + KV
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes) < 15e9


def test_served_prefill_chunk_compiles(one_chip):
    cfg = get_config("qwen2.5-32b").replace(n_layers=4)
    batch, max_seq, bt, chunk = 8, 2048, 16, 128
    nb, params, pools = _served_shapes(cfg, one_chip, batch=batch,
                                       max_seq=max_seq, bt=bt)
    cfg_dec = cfg.replace(serving=dataclasses.replace(cfg.serving,
                                                near_window=max_seq))

    def step(params, pools, cflat):
        cdescr = unflatten_chunk_descriptor(cflat, batch, chunk, nb)
        return registry.prefill_chunk(params, cfg_dec, pools, cdescr)

    cflat = _sds((chunk_flat_size(batch, chunk, nb),), jnp.int32, one_chip)
    compiled = jax.jit(step, donate_argnums=(1,)).lower(
        params, pools, cflat).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("rows", chunk_row_widths(48))
@pytest.mark.parametrize("arch,chunk", [("qwen2.5-32b", 128), ("yi-34b", 64)])
def test_served_chunk_executor_compiles_at_every_row_width(one_chip, arch,
                                                           chunk, rows):
    """The benchmark cells' chunk executor (4 published-width layers, 48
    slots x 8192 positions, 16-token blocks) at each row width the engine
    compiles: the row width is the flat operand's length, as in the
    engine; the kernel is in it and the program fits one v5e."""
    cfg = get_config(arch).replace(n_layers=4)
    batch, max_seq, bt = 48, 8192, 16
    nb, params, pools = _served_shapes(cfg, one_chip, batch=batch,
                                       max_seq=max_seq, bt=bt)
    cfg_dec = cfg.replace(serving=dataclasses.replace(cfg.serving,
                                                near_window=max_seq))

    def _chunk_step(params, pools, cflat):
        n = chunk_flat_rows(cflat.shape[0], chunk, nb)
        cdescr = unflatten_chunk_descriptor(cflat, n, chunk, nb)
        return registry.prefill_chunk(params, cfg_dec, pools, cdescr)

    cflat = _sds((chunk_flat_size(rows, chunk, nb),), jnp.int32, one_chip)
    compiled = jax.jit(_chunk_step, donate_argnums=(1,)).lower(
        params, pools, cflat).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes) < 15.5e9


def test_tp4_decode_step_compiles(topo):
    """Tensor-parallel decode over the described 2x2 host: the kernel runs
    per `model` shard through shard_map (10 q / 2 kv heads per chip)."""
    from jax.sharding import Mesh

    from repro.distributed import sharding as shd
    from repro.kernels import ops
    mesh = Mesh(np.array(topo.devices).reshape(4), ("model",))
    cfg = get_config("qwen2.5-32b").replace(n_layers=4)
    batch, max_seq, bt = 8, 2048, 16
    nb = -(-max_seq // bt) + 1
    p_shapes = _param_shapes(cfg)
    pool_shapes = registry.decode_pool_shapes(
        cfg, batch=batch, num_blocks=batch * nb + 1, block_tokens=bt)
    pspecs = shd.sanitize_specs(mesh, p_shapes, shd.param_specs(cfg, p_shapes))
    kvspecs = shd.sanitize_specs(
        mesh, pool_shapes, registry.decode_pool_partition_specs(
            cfg, pool_shapes))
    put = lambda shapes, specs: jax.tree.map(
        lambda s, sp: _sds(s.shape, s.dtype, NamedSharding(mesh, sp)),
        shapes, specs)
    repl = NamedSharding(mesh, PartitionSpec())
    descr = jax.tree.map(lambda a: _sds(a.shape, a.dtype, repl),
                         empty_descriptor(batch, nb, 1, nb + 1))
    step = _decode_fn(cfg, max_seq)

    def sharded(params, tokens, pools, descr):
        with ops.kernel_mesh(mesh):
            return step(params, tokens, pools, descr)

    compiled = jax.jit(sharded).lower(
        put(p_shapes, pspecs), _sds((batch,), jnp.int32, repl),
        put(pool_shapes, kvspecs), descr).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "all-reduce" in text
