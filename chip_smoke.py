#!/usr/bin/env python3
"""Smoke run of the served path on a TPU. A smoke, not a benchmark: its
times are printed for orientation only.

    python3 chip_smoke.py             # one chip
    python3 chip_smoke.py --chips 4   # tensor-parallel 1x4 vs 1x1, only that

The model is qwen2.5-32b at its published widths (d_model 5120, 40 query /
8 kv heads of 128, d_ff 27648, vocab 152064) with random weights from a
seed, cut to 4 layers (the only cut; the model is dense, so every layer
kind is present). It is built through ``serving.build`` like any user's
server.

One chip, in order:
  1. kernels: the compiled Pallas kernels (paged decode bf16 and int8 with
     the current token, batched chunked prefill bf16 and int8, far-view
     summarize) against ``kernels/ref.py`` on seeded inputs;
  2. serve: 16 seeded requests (prompts 128-1024 tokens, 32-64 new tokens)
     through ``Gateway`` -> ``KVRMEngine``; every one must finish with all
     its tokens, the decode executor must have compiled once and the
     chunk executor once per row width it ran at, and the decode
     executor's HLO must hold the kernel (``tpu_custom_call``);
  3. logits: one request's decode logits from an engine with
     ``debug_logits=True`` against ``transformer.forward`` in float32 at
     ``highest`` matmul precision on the same weights and tokens.

``--chips 4``: the same model at ``--mesh 1x4`` (10 query / 2 kv heads per
chip) against the 1x1 engine in this process: decode logits within
``TP_TOL`` of 1x1 and ``LOGIT_TOL`` of the float32 forward, and params and
KV pools spread over four devices.

Any failed phase exits non-zero. Without a TPU, or outside a checkout of
the repository, it exits non-zero before printing a result. The last line
of a passing run is ``{"ok": true, "device": {...}}``.
"""
import argparse
import asyncio
import json
import os
import sys
import time
import traceback
from collections import Counter

ROOT = os.path.dirname(os.path.abspath(__file__))

# Engine-vs-float32 logits, relative L2 error per decode step. The engine
# keeps bf16 activations and KV (unit roundoff 2^-9) and rounds at about
# ten points per layer; over 4 layers that leaves about 0.013 against the
# f32 forward (measured on the CPU at d_model 1280, 4 layers). Storing KV
# below the configured bf16 (int8 with per-block scales) raised it to
# about 0.024 there, fp8 to about 0.07. 0.02 admits the configured
# precision and refuses an attention that reads narrower KV.
LOGIT_TOL = 0.02
# 1x4 vs 1x1 decode logits, relative L2 error per step. The TPU compiler
# narrows the wo and mlp-down all-reduces to bf16 (the compiled 1x4 step
# holds `all-reduce bf16[B,5120]`), so each shard's partial is rounded
# before the sum, and the two programs fuse, and so round, differently.
# Each engine then carries its own bf16 error against f32 (about 0.012,
# see LOGIT_TOL); two such independent errors differ by about
# sqrt(2) * 0.012 = 0.017. The 1x4 engine is also held to LOGIT_TOL
# against the f32 forward. A sharding fault (heads on the wrong shard, a
# lost psum, scales on the wrong kv head) moves the logits by O(1).
TP_TOL = 0.02
# Compiled kernels against ref.py on the same bf16/int8 inputs: the
# outputs are bf16 (relative rounding 2^-8), accumulation f32 on both.
KERNEL_TOL = 2e-2


def _fail(msg: str) -> None:
    print(f"chip_smoke: {msg}", file=sys.stderr)
    sys.exit(1)


def _setup():
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        _fail("run from a checkout of the repository (src/repro missing)")
    sys.path.insert(0, os.path.join(ROOT, "src"))
    platforms = os.environ.get("JAX_PLATFORMS", "")
    if platforms and "tpu" in platforms and "cpu" not in platforms:
        # the float32 reference runs on the host's CPU backend
        os.environ["JAX_PLATFORMS"] = platforms + ",cpu"
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        _fail(f"needs a TPU; jax found {dev.platform!r}")
    from repro.launch.compile_cache import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}")
    return jax


def _qwen4():
    from repro.configs import get_config
    return get_config("qwen2.5-32b").replace(n_layers=4)


def _rel_l2(a, b):
    import numpy as np
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def _close(name, got, want, tol):
    import numpy as np
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    err = float(np.abs(got - want).max())
    print(f"  {name}: max|err| {err:.3e} (tol {tol} abs+rel)")
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol, err_msg=name)


# ---------------------------------------------------------------------------
# phase 1: kernels against ref
# ---------------------------------------------------------------------------

def phase_kernels(cfg, *, batch=8, bt=16, nb=40, chunk=128, seed=0):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ref
    from repro.kernels.farview_summarize import farview_summarize_pallas
    from repro.kernels.paged_attention import paged_decode_attention_pallas
    from repro.kernels.prefill_attention import \
        chunked_prefill_attention_pallas

    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    P = batch * nb + 1
    W = nb * bt
    rng = np.random.default_rng(seed)
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    bf = jnp.bfloat16
    pools = {
        "bf16": (jax.random.normal(ks[0], (P, bt, KV, hd), bf),
                 jax.random.normal(ks[1], (P, bt, KV, hd), bf), {}),
        "int8": (jax.random.randint(ks[0], (P, bt, KV, hd), -127, 128,
                                    jnp.int32).astype(jnp.int8),
                 jax.random.randint(ks[1], (P, bt, KV, hd), -127, 128,
                                    jnp.int32).astype(jnp.int8),
                 dict(k_scale=jax.random.uniform(ks[2], (P, KV), minval=0.004,
                                                 maxval=0.012),
                      v_scale=jax.random.uniform(ks[3], (P, KV), minval=0.004,
                                                 maxval=0.012))),
    }
    tbl = jnp.asarray(np.stack([rng.permutation(np.arange(1, P))[:nb]
                                for _ in range(batch)]).astype(np.int32))
    q = jax.random.normal(ks[4], (batch, H, hd), bf)
    ck = jax.random.normal(ks[5], (batch, KV, hd), bf)
    cv = jax.random.normal(ks[6], (batch, KV, hd), bf)
    seq = rng.integers(0, W, batch).astype(np.int32)
    seq[0] = W - 1
    act = np.ones(batch, np.int32)
    act[-1] = 0
    dargs = (q, None, None, tbl, jnp.zeros(batch, jnp.int32),
             jnp.asarray(seq), jnp.asarray(act))
    for kv_dtype, (pk, pv, sc) in pools.items():
        args = (dargs[0], pk, pv) + dargs[3:]
        with jax.default_matmul_precision("highest"):
            want = ref.paged_decode_attention_ref(
                *args, near_window=W, cur_k=ck, cur_v=cv, **sc)[0]
        for depth in (0, 1):
            got = paged_decode_attention_pallas(
                *args, near_window=W, cur_k=ck, cur_v=cv,
                prefetch_depth=depth, **sc)[0]
            _close(f"paged decode {kv_dtype} depth {depth}", got, want,
                   KERNEL_TOL)

    # chunked prefill: one chunk per slot at seeded start positions
    qc = jax.random.normal(ks[7], (batch, chunk, H, hd), bf)
    ckc = jax.random.normal(ks[5], (batch, chunk, KV, hd), bf)
    cvc = jax.random.normal(ks[6], (batch, chunk, KV, hd), bf)
    start = rng.integers(0, W - chunk, batch).astype(np.int32)
    start[0] = 0
    n_valid = rng.integers(1, chunk + 1, batch).astype(np.int32)
    n_valid[1] = chunk
    for kv_dtype, (pk, pv, sc) in pools.items():
        cargs = (qc, pk, pv, ckc, cvc, tbl, jnp.zeros(batch, jnp.int32),
                 jnp.asarray(start), jnp.asarray(n_valid))
        got = chunked_prefill_attention_pallas(*cargs, near_window=W, **sc)
        with jax.default_matmul_precision("highest"):
            want = jax.vmap(
                lambda q, k, v, t, wb, sp, nv: ref.chunked_prefill_attention_ref(
                    q, pk, pv, k, v, t, wb, sp, nv, near_window=W, **sc))(
                qc, ckc, cvc, tbl, cargs[6], cargs[7], cargs[8])
        _close(f"chunked prefill {kv_dtype}", got, want, KERNEL_TOL)

    # far-view summarize: mean of one 64-token chunk per slot
    cb = 4
    n_tok = rng.integers(1, cb * bt + 1, batch).astype(np.int32)
    gate = (np.arange(batch) % 3 != 2).astype(np.int32)
    pk = pools["bf16"][0]
    fargs = (pk, tbl[:, :cb], jnp.asarray(n_tok), jnp.asarray(gate))
    _close("far-view summarize", farview_summarize_pallas(*fargs),
           ref.farview_summarize_ref(*fargs), KERNEL_TOL)


# ---------------------------------------------------------------------------
# phase 2: serve through the gateway
# ---------------------------------------------------------------------------

def _requests(vocab, n, *, seed, prompt=(128, 1024), gen=(32, 64)):
    import numpy as np

    from repro.serving import GenerationRequest
    rng = np.random.default_rng(seed)
    out = []
    for rid in range(n):
        plen = int(rng.integers(prompt[0], prompt[1] + 1))
        out.append(GenerationRequest(
            rid=rid, prompt=tuple(int(t) for t in rng.integers(0, vocab, plen)),
            gen_len=int(rng.integers(gen[0], gen[1] + 1))))
    return out


def phase_serve(cfg, *, batch=16, max_seq=4096, bt=16, chunk=128,
                n_requests=16, seed=0, **req_kw):
    import jax

    from repro import serving
    from repro.core.descriptor import chunk_row_widths

    t0 = time.perf_counter()
    gw = serving.build(cfg, gateway=True, batch=batch, max_seq=max_seq,
                       block_tokens=bt, prefill_chunk=chunk, seed=seed)
    jax.block_until_ready(gw.engines[0].params)
    t_build = time.perf_counter() - t0
    reqs = _requests(cfg.vocab_size, n_requests, seed=seed, **req_kw)

    async def _serve():
        res = await asyncio.gather(*[gw.generate(r) for r in reqs])
        await gw.drain()
        gw.close()
        return res

    t0 = time.perf_counter()
    results = asyncio.run(_serve())
    t_serve = time.perf_counter() - t0
    by_rid = {r.rid: r for r in results}
    for greq in reqs:
        res = by_rid.get(greq.rid)
        if res is None or len(res.tokens) != greq.gen_len:
            raise AssertionError(
                f"request {greq.rid}: wanted {greq.gen_len} tokens, got "
                f"{None if res is None else len(res.tokens)}")
        if not all(0 <= t < cfg.vocab_size for t in res.tokens):
            raise AssertionError(f"request {greq.rid}: token out of vocab")

    eng = gw.engines[0]
    audit = eng.audit()
    # the chunk executor has one program per row width it was called at
    widths = len(chunk_row_widths(batch))
    if (audit["compilations"] != 1
            or not 1 <= audit["prefill_compilations"] <= widths):
        raise AssertionError(
            f"executors compiled {audit['compilations']} (decode) / "
            f"{audit['prefill_compilations']} (prefill) times, want 1 / "
            f"1 to {widths}")
    if "tpu_custom_call" not in eng.decode_executor_hlo():
        raise AssertionError("decode executor HLO holds no tpu_custom_call")
    tokens = sum(len(r.tokens) for r in results)
    prompt_tokens = sum(len(r.prompt) for r in reqs)
    steps = [m for m in eng.metrics if m.active > 0]
    step_ms = sorted(1e3 * m.wall for m in steps)
    print(f"  served {len(results)}/{len(reqs)} requests: {prompt_tokens} "
          f"prompt + {tokens} generated tokens")
    print(f"  decode executor compiled once, chunk executor at "
          f"{audit['prefill_compilations']} of {widths} row widths; decode "
          f"HLO holds tpu_custom_call")
    print(f"  smoke timings (not a benchmark): build+init {t_build:.1f} s, "
          f"serve incl. compile {t_serve:.1f} s, {tokens / t_serve:.1f} "
          f"generated tok/s incl. compile, median dispatch step "
          f"{step_ms[len(step_ms) // 2]:.2f} ms over {len(steps)} steps")
    return gw


# ---------------------------------------------------------------------------
# phase 3 (and the 4-chip check): decode logits
# ---------------------------------------------------------------------------

def _decode_logits(cfg, *, prompt_len, gen_len, seed, **build_kw):
    """One request through an engine with debug_logits: its decode-step
    logits, the prompt, and the generated tokens."""
    import numpy as np

    from repro import serving
    from repro.core.scheduler import Request
    eng = serving.build(cfg, debug_logits=True, seed=seed, **build_kw)[0]
    rng = np.random.default_rng(seed + 1)
    prompt = rng.integers(0, cfg.vocab_size, prompt_len).astype(np.int32)
    req = Request(rid=0, prompt=prompt, gen_len=gen_len)
    eng.submit(req)
    eng.run()
    logits = np.stack(req.logit_trace).astype(np.float32)
    if logits.shape != (gen_len, cfg.vocab_size):
        raise AssertionError(f"logit trace {logits.shape}")
    return eng, logits, prompt, np.asarray(req.generated, np.int32)


def phase_logits(cfg, *, prompt_len=320, gen_len=16, seed=0, batch=4,
                 max_seq=1024, bt=16, chunk=128):
    import numpy as np

    kw = dict(batch=batch, max_seq=max_seq, block_tokens=bt,
              prefill_chunk=chunk)
    errs = {}
    for kv_dtype in ("bf16", "int8"):
        eng, logits, prompt, gen = _decode_logits(
            cfg, prompt_len=prompt_len, gen_len=gen_len, seed=seed,
            kv_dtype=kv_dtype, **kw)
        if kv_dtype == "bf16":
            ref = _f32_decode_logits(cfg, seed, prompt, gen)
            ref_gen = gen
        elif not np.array_equal(gen, ref_gen):
            # the int8 engine may pick other tokens; compare on the bf16
            # engine's stream only while the two agree
            n = int(np.argmax(gen != ref_gen)) + 1
            logits, ref = logits[:n], ref[:n]
        errs[kv_dtype] = max(_rel_l2(l, r) for l, r in zip(logits, ref))
        del eng
        print(f"  {kv_dtype} KV engine vs f32 forward: max rel L2 "
              f"{errs[kv_dtype]:.4f} over {len(logits)} decode steps")
    if errs["bf16"] > LOGIT_TOL:
        raise AssertionError(f"bf16 engine logits off the f32 reference: "
                             f"{errs['bf16']:.4f} > {LOGIT_TOL}")
    # the int8 run shows how much room the tolerance leaves; it is reported,
    # not asserted, since it sits near the limit by design
    print(f"  tolerance {LOGIT_TOL}: bf16 passes; int8 KV would "
          f"{'fail' if errs['int8'] > LOGIT_TOL else 'pass'} it")


def _f32_decode_logits(cfg, seed, prompt, gen):
    """The logits ``transformer.forward`` gives at each decode step of
    ``prompt`` -> ``gen``, in float32 at highest matmul precision on the
    engine's weights. It runs on the host's CPU backend: the f32 weights
    of this model do not fit beside the engine in one chip's HBM."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import transformer
    from repro.serving.factory import cached_params
    cpu = jax.devices("cpu")[0]
    params = jax.device_put(cached_params(cfg, seed), cpu)
    tokens = np.concatenate([prompt, gen[:-1]])[None]
    fwd = jax.jit(lambda p, t: transformer.forward(
        jax.tree.map(lambda x: x.astype(jnp.float32), p), cfg, t)[0])
    with jax.default_matmul_precision("highest"):
        out = fwd(params, jax.device_put(jnp.asarray(tokens), cpu))
    return np.asarray(out, np.float32)[len(prompt) - 1:]


def phase_tp4(cfg, *, prompt_len=320, gen_len=16, seed=0, batch=8,
              max_seq=2048, bt=16, chunk=128):
    import re

    import jax

    kw = dict(prompt_len=prompt_len, gen_len=gen_len, seed=seed, batch=batch,
              max_seq=max_seq, block_tokens=bt, prefill_chunk=chunk)
    e1, l1, p1, g1 = _decode_logits(cfg, mesh_spec="1x1", **kw)
    e4, l4, _, g4 = _decode_logits(cfg, mesh_spec="1x4", **kw)
    n = len(l1) if (g1 == g4).all() else int((g1 != g4).argmax()) + 1
    err = max(_rel_l2(a, b) for a, b in zip(l4[:n], l1[:n]))
    ref = _f32_decode_logits(cfg, seed, p1, g1)
    err1, err4 = (max(_rel_l2(a, b) for a, b in zip(l[:n], ref))
                  for l in (l1, l4))
    print(f"  1x4 vs 1x1 decode logits: max rel L2 {err:.2e} over {n} steps; "
          f"tokens {'identical' if (g1 == g4).all() else 'diverge'}")
    print(f"  vs f32 forward: 1x1 {err1:.4f}, 1x4 {err4:.4f}")
    if err > TP_TOL:
        raise AssertionError(f"TP logits off 1x1: {err:.2e} > {TP_TOL}")
    if err4 > LOGIT_TOL:
        raise AssertionError(f"TP logits off the f32 reference: "
                             f"{err4:.4f} > {LOGIT_TOL}")

    a1, a4 = e1.audit(), e4.audit()
    if a4["tp_degree"] != 4 or a4["kv_shards"] != 4:
        raise AssertionError(f"tp {a4['tp_degree']} kv shards "
                             f"{a4['kv_shards']}")
    if a4["per_device_peak_reserved_kv"] != a1["peak_reserved_kv"] // 4:
        raise AssertionError(
            f"per-device KV {a4['per_device_peak_reserved_kv']} is not a "
            f"quarter of {a1['peak_reserved_kv']}")
    for name, arr in (("mlp up weight", e4.params["layers"]["mlp"]["up"]["w"]),
                      ("k pool", e4.pools["k"])):
        shards = arr.addressable_shards
        devs = {s.device for s in shards}
        if len(devs) != 4 or any(s.data.size * 4 != arr.size for s in shards):
            raise AssertionError(f"{name} not split over four devices: "
                                 f"{[(s.device, s.data.shape) for s in shards]}")
        print(f"  {name} {arr.shape}: one {shards[0].data.shape} shard on "
              f"each of {len(devs)} devices")
    hlo = e4.decode_executor_hlo()
    if "tpu_custom_call" not in hlo:
        raise AssertionError("TP decode executor HLO holds no tpu_custom_call")
    reduces = re.findall(r"= \(?(\w+)\[[\d,]*\][^ ]* all-reduce(?:-start)?\(", hlo)
    print(f"  TP decode executor HLO holds tpu_custom_call; all-reduce "
          f"element types: {dict(sorted(Counter(reduces).items()))}")
    used = [(d.memory_stats() or {}).get("bytes_in_use", 0)
            for d in jax.devices()]
    print(f"  bytes in use per device: {used}")


# ---------------------------------------------------------------------------

def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: only the tensor-parallel 1x4 vs 1x1 check")
    args = ap.parse_args(argv)
    jax = _setup()
    devs = jax.devices()
    if len(devs) < args.chips:
        _fail(f"--chips {args.chips} needs {args.chips} devices, "
              f"have {len(devs)}")
    cfg = _qwen4()
    print(f"device: {devs[0].device_kind} x{len(devs)}; jax {jax.__version__}")
    print(f"model: {cfg.arch_id} published widths (d_model {cfg.d_model}, "
          f"{cfg.n_heads}q/{cfg.n_kv_heads}kv heads of {cfg.head_dim}, d_ff "
          f"{cfg.d_ff}, vocab {cfg.vocab_size}); cut: 64 -> {cfg.n_layers} "
          f"layers; random weights from seed 0")
    phases = ([("tp4", phase_tp4)] if args.chips == 4 else
              [("kernels", phase_kernels), ("serve", phase_serve),
               ("logits", phase_logits)])
    failed = []
    for name, fn in phases:
        # a failed phase is reported and the rest still run, so one run
        # shows every fault; any failure fails the smoke at the end
        print(f"phase {name}:", flush=True)
        t0 = time.perf_counter()
        try:
            fn(cfg)
        except Exception:
            traceback.print_exc()
            failed.append(name)
            print(f"  phase {name} FAILED", flush=True)
            continue
        print(f"  phase {name} ok ({time.perf_counter() - t0:.1f} s)",
              flush=True)
    stats = devs[0].memory_stats() or {}
    if "peak_bytes_in_use" in stats:
        print(f"HBM peak in use on device 0: "
              f"{stats['peak_bytes_in_use'] / 2**30:.2f} GiB of "
              f"{stats.get('bytes_limit', 0) / 2**30:.2f} GiB")
    if failed:
        _fail(f"failed phases: {', '.join(failed)}")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))


if __name__ == "__main__":
    main()
