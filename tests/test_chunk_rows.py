"""Packed chunk rows (DESIGN.md §3): the chunked prefill executor runs only
as wide as the slots that have prompt work this step.

Each slot with chunk work takes the next free row; the call runs at the
smallest compiled row width that holds them (``chunk_row_widths``), every
width compiled when the engine is built. A row carries its own
positions, window table and write targets, so a packed narrow call writes
exactly what the same rows write at their slot positions in a full-width
call, and token streams do not move.
"""
import hashlib
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced
from repro.core.descriptor import (chunk_flat_rows, chunk_flat_size,
                                   chunk_row_width, chunk_row_widths,
                                   flat_chunk_views, pack_chunk_rows)
from repro.core.engine import EngineConfig, KVRMEngine
from repro.core.scheduler import Request
from repro.models import registry

C, BT = 8, 8


@pytest.fixture(scope="module")
def dense_setup():
    cfg = get_reduced("qwen2.5-32b")
    params = registry.init_params(jax.random.PRNGKey(7), cfg)
    return cfg, params


def _engine(cfg, params, batch, **kw):
    e = dict(mode="paged_merge", batch=batch, max_seq=64, block_tokens=BT,
             prefill_chunk=C)
    e.update(kw)
    return KVRMEngine(cfg, params, EngineConfig(**e))


# ---------------------------------------------------------------------------
# row widths
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("batch,widths", [
    (1, (1,)), (2, (2,)), (4, (4,)), (5, (4, 5)), (8, (4, 8)),
    (16, (4, 16)), (17, (4, 16, 17)), (48, (4, 16, 48)),
    (64, (4, 16, 64)), (65, (4, 16, 64, 65)),
])
def test_row_widths_are_powers_of_four_below_the_batch_then_the_batch(
        batch, widths):
    assert chunk_row_widths(batch) == widths


@pytest.mark.parametrize("rows,width", [
    (0, 4), (1, 4), (4, 4), (5, 16), (16, 16), (17, 48), (48, 48)])
def test_the_smallest_width_that_holds_the_rows(rows, width):
    assert chunk_row_width(chunk_row_widths(48), rows) == width


def test_more_rows_than_the_batch_is_refused():
    with pytest.raises(ValueError):
        chunk_row_width(chunk_row_widths(48), 49)


def test_a_packed_flat_is_the_narrow_layout_of_the_first_rows():
    B, NB = 6, 5
    flat = np.arange(chunk_flat_size(B, C, NB), dtype=np.int32)
    cd = flat_chunk_views(flat, B, C, NB)
    packed = pack_chunk_rows(cd, 4)
    assert packed.shape == (chunk_flat_size(4, C, NB),)
    assert chunk_flat_rows(packed.size, C, NB) == 4
    narrow = flat_chunk_views(packed, 4, C, NB)
    for wide, got in zip(cd, narrow):
        np.testing.assert_array_equal(got, wide[:4])
    # the widest width is the whole flat, as the executor took it before
    np.testing.assert_array_equal(pack_chunk_rows(cd, B), flat)
    with pytest.raises(ValueError):
        chunk_flat_rows(packed.size + 1, C, NB)


# ---------------------------------------------------------------------------
# the engine dispatches the smallest width, live rows packed first
# ---------------------------------------------------------------------------

WIDE = 20                                   # widths 4, 16, 20


@pytest.fixture(scope="module")
def wide_engine(dense_setup):
    cfg, params = dense_setup
    eng = _engine(cfg, params, WIDE, max_seq=32)
    calls = []
    fn = eng._chunk_fn

    def record(params, pools, cflat):
        cd = eng._cdescr
        calls.append((chunk_flat_rows(cflat.shape[0], C, eng.NB),
                      cd.n_valid.copy()))
        return fn(params, pools, cflat)

    eng._chunk_fn = record
    return eng, calls


@pytest.mark.parametrize("live", [0, 1, 4, 5, 16, 17, WIDE])
def test_the_call_runs_at_the_smallest_width_that_holds_the_live_slots(
        dense_setup, wide_engine, live):
    cfg, _ = dense_setup
    eng, calls = wide_engine
    assert eng._chunk_widths == (4, 16, WIDE)
    calls.clear()
    rng = np.random.default_rng(live)
    # a prompt of C + 1 tokens is one chunk call, then its last token
    # through the decode step; two-token prompts never chunk
    plen = C + 1 if live else 2
    for i in range(max(live, 1)):
        eng.submit(Request(rid=1000 * live + i, prompt=rng.integers(
            0, cfg.vocab_size, size=plen).astype(np.int32), gen_len=1))
    eng.run(max_steps=50)
    eng.flush()
    if live == 0:
        assert calls == []
        return
    assert len(calls) == 1
    width, n_valid = calls[0]
    assert width == chunk_row_width(eng._chunk_widths, live)
    # the host view keeps B rows, the live ones first
    assert n_valid.shape == (WIDE,)
    assert (n_valid[:live] == C).all() and (n_valid[live:] == 0).all()
    # every width was compiled when the engine was built, none since
    assert eng.audit()["prefill_compilations"] <= len(eng._chunk_widths)


def test_no_width_compiles_after_the_first_chunk_dispatch(dense_setup):
    """Building the engine compiles every width; no step compiles one."""
    cfg, params = dense_setup
    compiles = []

    def listen(ev, dur, fun_name="", **kw):
        if ev == "/jax/core/compile/backend_compile_duration":
            compiles.append(fun_name)

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        eng = _engine(cfg, params, 8)
        assert compiles.count("jit(_chunk_step)") == len(eng._chunk_widths) == 2
        compiles.clear()
        rng = np.random.default_rng(0)
        eng.submit(Request(rid=0, prompt=rng.integers(
            0, cfg.vocab_size, size=C + 1).astype(np.int32), gen_len=2))
        eng.run(max_steps=50)                   # width 4
        eng.flush()
        assert "jit(_chunk_step)" not in compiles
        compiles.clear()
        for i in range(1, 7):                   # six at once: width 8
            eng.submit(Request(rid=i, prompt=rng.integers(
                0, cfg.vocab_size, size=C + 1).astype(np.int32), gen_len=2))
        eng.run(max_steps=50)
        eng.flush()
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    assert compiles == []
    assert eng._chunk_jit._cache_size() == 2


# ---------------------------------------------------------------------------
# a packed narrow call writes what the full-width call writes
# ---------------------------------------------------------------------------

B = 8
LIVE = {1: (5, C), 4: (13, 6), 6: (0, C)}  # slot -> (start_pos, n_valid)


def _random_pools(pools, key):
    out = {}
    for i, (name, a) in enumerate(sorted(pools.items())):
        k = jax.random.fold_in(key, i)
        if a.dtype == jnp.int8:
            out[name] = jax.random.randint(k, a.shape, -127, 128, jnp.int32
                                           ).astype(jnp.int8)
        elif name.endswith("_scale"):
            out[name] = jax.random.uniform(k, a.shape, a.dtype, 0.005, 0.02)
        else:
            out[name] = jax.random.normal(k, a.shape, jnp.float32
                                          ).astype(a.dtype)
    return out


def _fill_row(cd, r, slot, start, n, nb, rng, vocab):
    """Row ``r`` holds ``slot``'s chunk: its own blocks, window base 0."""
    blocks = 1 + slot * nb + np.arange(nb)
    pos = start + np.arange(n)
    cd.tokens[r] = 0
    cd.tokens[r, :n] = rng.integers(0, vocab, n)
    cd.start_pos[r] = start
    cd.n_valid[r] = n
    cd.block_table[r] = blocks
    cd.window_base[r] = 0
    cd.write_block[r] = 0
    cd.write_block[r, :n] = blocks[pos // BT]
    cd.write_offset[r] = 0
    cd.write_offset[r, :n] = pos % BT


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
def test_packed_rows_write_the_same_pools_as_the_full_width_call(
        dense_setup, kv_dtype):
    cfg, params = dense_setup
    eng = _engine(cfg, params, B, kv_dtype=kv_dtype)
    nb = eng.NB
    assert eng._chunk_widths == (4, B)
    pools = _random_pools(eng.pools, jax.random.PRNGKey(3))
    vocab = cfg.vocab_size

    wide = flat_chunk_views(np.zeros(chunk_flat_size(B, C, nb), np.int32),
                            B, C, nb)
    # dead rows carry stale work: n_valid = 0 must mask all of it
    for slot in range(B):
        _fill_row(wide, slot, slot, 3, C, nb, np.random.default_rng(slot),
                  vocab)
        wide.n_valid[slot] = 0
    packed = flat_chunk_views(np.zeros(chunk_flat_size(B, C, nb), np.int32),
                              B, C, nb)
    for r, (slot, (start, n)) in enumerate(sorted(LIVE.items())):
        _fill_row(wide, slot, slot, start, n, nb,
                  np.random.default_rng(100 + slot), vocab)
        _fill_row(packed, r, slot, start, n, nb,
                  np.random.default_rng(100 + slot), vocab)
    # a chunk that starts mid-block is among them
    assert any(s % BT for s, _ in LIVE.values())

    copy = lambda: jax.tree.map(jnp.copy, pools)
    out_wide = eng._chunk_jit(params, copy(),
                              jnp.asarray(pack_chunk_rows(wide, B)))
    width = chunk_row_width(eng._chunk_widths, len(LIVE))
    assert width == 4
    out_packed = eng._chunk_jit(params, copy(),
                                jnp.asarray(pack_chunk_rows(packed, width)))
    for name in pools:
        np.testing.assert_array_equal(np.asarray(out_packed[name]),
                                      np.asarray(out_wide[name]), name)
    # and the call wrote something: each live chunk's first token
    changed = np.asarray(out_wide["k"]) != np.asarray(pools["k"])
    for slot, (start, n) in LIVE.items():
        blk = 1 + slot * nb + start // BT
        assert changed[:, blk, start % BT].any(), slot


# ---------------------------------------------------------------------------
# token streams do not move
# ---------------------------------------------------------------------------

LENS = [(5, 6), (40, 4), (3, 8), (33, 5), (60, 7), (21, 3), (4, 5),
        (18, 5), (8, 5), (50, 3), (26, 4), (12, 6), (44, 2), (9, 9),
        (30, 3), (17, 4), (56, 2), (11, 3), (25, 5), (36, 3), (7, 4),
        (48, 2)]
# captured on the executor before rows were packed (every row a slot, the
# full batch width every call): batch 20, chunk 8, this trace, depths 0
# and 1 alike; sha256 over the sorted rid -> tokens JSON
GOLDEN_DIGEST = \
    "898625ca7881ae9721f7ccfbc2668a480841a1760937bde3ba83493511b7f278"


def _mixed(vocab):
    rng = np.random.default_rng(1)
    return [Request(rid=i, prompt=rng.integers(0, vocab, size=p)
                    .astype(np.int32), gen_len=g)
            for i, (p, g) in enumerate(LENS)]


def _streams(cfg, params, depth, widths=None):
    eng = _engine(cfg, params, WIDE, max_seq=128, pipeline_depth=depth)
    if widths is not None:
        eng._chunk_widths = widths
    for r in _mixed(cfg.vocab_size):
        eng.submit(r)
    eng.run(max_steps=500)
    toks = {r.rid: list(map(int, r.generated)) for r in eng.sched.finished}
    return eng, toks


@pytest.mark.parametrize("depth", [0, 1])
def test_token_streams_equal_the_full_width_executors(dense_setup, depth):
    cfg, params = dense_setup
    eng, toks = _streams(cfg, params, depth)
    _, full = _streams(cfg, params, depth, widths=(WIDE,))
    assert len(toks) == len(LENS)
    assert toks == full
    digest = hashlib.sha256(
        json.dumps(toks, sort_keys=True).encode()).hexdigest()
    assert digest == GOLDEN_DIGEST, digest
    a = eng.audit()
    assert a["single_commit_per_step"] and a["prefill_chunks_run"] > 0
    assert 1 < a["prefill_compilations"] <= len(eng._chunk_widths)
    eng.pager.check_invariants()
    assert eng.pager.reserved_blocks() == 0


def test_a_tensor_parallel_engine_packs_rows_the_same(dense_setup):
    """Every width compiles with the mesh's shardings, and the sharded
    engine's streams equal the single-device ones."""
    if len(jax.devices()) < 2:
        pytest.skip("needs a forced multi-device CPU topology")
    from repro.launch.mesh import make_engine_mesh
    cfg, params = dense_setup
    _, toks = _streams(cfg, params, 1)
    eng = _engine(cfg, params, WIDE, max_seq=128, pipeline_depth=1,
                  mesh=make_engine_mesh(1, 2))
    for r in _mixed(cfg.vocab_size):
        eng.submit(r)
    eng.run(max_steps=500)
    assert {r.rid: list(map(int, r.generated))
            for r in eng.sched.finished} == toks
    assert 1 < eng.audit()["prefill_compilations"] <= len(eng._chunk_widths)


def test_quantized_kv_streams_equal_the_full_width_executors(dense_setup):
    cfg, params = dense_setup
    runs = []
    for widths in (None, (WIDE,)):
        eng = _engine(cfg, params, WIDE, max_seq=128, kv_dtype="int8")
        if widths is not None:
            eng._chunk_widths = widths
        for r in _mixed(cfg.vocab_size):
            eng.submit(r)
        eng.run(max_steps=500)
        runs.append({r.rid: list(map(int, r.generated))
                     for r in eng.sched.finished})
    assert len(runs[0]) == len(LENS) and runs[0] == runs[1]
