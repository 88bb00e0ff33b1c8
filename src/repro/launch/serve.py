"""Serving launcher: run a workload through the KV-RM engine (or the
static-arena baseline) and print throughput / tail latency / memory /
transport / invariant audits.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2.5-32b \
        --mode paged_merge --workload mixed --requests 32

SPMD serving (DESIGN.md §4): ``--mesh DxM`` runs D data-parallel engine
lanes, each lane one replicated engine whose params and KV pools shard
M-ways over its row's `model` axis. The trace is striped round-robin over
lanes; lanes are stepped round-robin so their (async) device work overlaps.

Oversubscription (DESIGN.md §8): ``--kv-oversubscribe R`` (R > 1) or
``--host-pool-blocks N`` enables the host KV tier — the device pool may
be smaller than the admitted working set; bursts are absorbed by cold
swap-out and preemption-aware scheduling below the fixed descriptor
interface. ``audit()`` splits admission stalls into compute-bound
(``admit_blocked_no_slot``) vs memory-bound
(``admit_blocked_kv_watermark``) so operators can tell which resource is
gating the queue.

    PYTHONPATH=src python -m repro.launch.serve --workload replay \
        --requests 48 --kv-oversubscribe 1.5

Shared-prefix reuse (DESIGN.md §9): ``--prefix-cache`` indexes committed
prompt blocks in a radix tree and COW-aliases matches at admission —
repeated system prompts skip their prefill entirely, bitwise-identically.
``--workload shared_prefix`` generates the matching multi-tenant trace;
``audit()`` reports ``prefix_hits`` / ``prefix_tokens_reused`` /
``cow_copies``.

    PYTHONPATH=src python -m repro.launch.serve --workload shared_prefix \
        --requests 32 --prefix-cache

Quantized KV tier (DESIGN.md §10): ``--kv-dtype fp8_e4m3 | int8`` stores
KV blocks narrow with per-block per-head scales — ~2x less reserved KV
and half the swap/COW bytes (``quant_bytes_saved``), composable with all
of the above. ``--kv-dtype bf16`` (default) is bitwise-identical to seed.

    PYTHONPATH=src python -m repro.launch.serve --workload mixed \
        --requests 32 --kv-dtype fp8_e4m3

Every flag and every ``audit()`` counter is tabulated with the invariant
it witnesses in docs/OPERATIONS.md.

Published widths: ``--layers N`` serves the arch's full
published config cut to N layers instead of its reduced CPU preset — on a
chip. Multi-device meshes on the CPU need virtual devices set before jax
starts:

    XLA_FLAGS=--xla_force_host_platform_device_count=4 \
        PYTHONPATH=src python -m repro.launch.serve --mesh 2x2
"""
from __future__ import annotations

# --xla-profile bootstrap: the tuned XLA flag profile must be set BEFORE
# jax initializes, which is before this module's own jax import when run
# as a script.
import sys

if __name__ == "__main__":
    _prof = None
    for _i, _a in enumerate(sys.argv):
        if _a == "--xla-profile" and _i + 1 < len(sys.argv):
            _prof = sys.argv[_i + 1]
        elif _a.startswith("--xla-profile="):
            _prof = _a.split("=", 1)[1]
    if _prof is not None:
        from repro.launch import xla_flags as _xf
        if _prof in _xf.PROFILES:      # unknown name -> argparse errors later
            _xf.apply_profile(_prof)

import argparse
import contextlib
import json
import threading
import time

import numpy as np

from repro.configs import get_config
from repro.configs.base import SamplingConfig
from repro.core import spans
from repro.core.engine import KVRMEngine
from repro.data import traces
from repro.launch import xla_flags
from repro.launch.compile_cache import enable_compile_cache


def build_engine(arch: str, mode: str, batch: int, max_seq: int,
                 near_window=None, seed: int = 0, mesh=None,
                 params=None, **kw) -> KVRMEngine:
    """Thin shim over the consolidated ``serving.build`` factory (§14)."""
    from repro.serving.factory import build
    return build(arch, mode=mode, batch=batch, max_seq=max_seq,
                 near_window=near_window, seed=seed, mesh=mesh,
                 params=params, **kw)[0]


def build_lanes(arch: str, mode: str, batch: int, max_seq: int,
                mesh_spec: str, **kw) -> list:
    """One replicated engine per `data` row of the requested mesh; params
    are initialized once (cached) and placed per lane. Delegates to
    ``serving.build`` (§14) — the one construction path for serve,
    benchmarks and the gateway."""
    from repro.serving.factory import build
    return build(arch, mode=mode, batch=batch, max_seq=max_seq,
                 mesh_spec=mesh_spec, seed=kw.pop("seed", 0), **kw)


def run_lanes(engines: list, reqs, *, max_steps: int = 100_000,
              now_fn=None) -> dict:
    """Stripe requests round-robin over lanes, step lanes round-robin (their
    dispatched device work overlaps), and aggregate the lane audits.

    ``aggregate_tok_s`` measures steady state: the clock starts after the
    first round of steps (which pays each lane's one-time executor compile —
    seconds on CPU, and systematically larger for sharded executors), and
    the first round's emissions are excluded from the numerator, matching
    the warmup-skipping convention of ``KVRMEngine.throughput``.
    ``wall_tok_s`` keeps the raw end-to-end figure, compile included."""
    for i, r in enumerate(reqs):
        engines[i % len(engines)].submit(r)
    t0 = time.perf_counter()
    t_warm = t0
    warm_tok = 0
    steps = 0
    while steps < max_steps:
        busy = False
        for eng in engines:
            if eng.sched.waiting or eng.sched.preempted \
                    or eng.sched.active_slots():
                eng.step(now=now_fn() if now_fn else float("inf"))
                busy = True
        if steps == 0:
            t_warm = time.perf_counter()
            warm_tok = sum(m.emitted for e in engines for m in e.metrics)
        steps += 1
        if not busy:
            break
    for eng in engines:
        eng.flush()
    end = time.perf_counter()

    tok = sum(sum(len(r.generated) for r in e.sched.finished) for e in engines)
    emitted = sum(m.emitted for e in engines for m in e.metrics)
    out = {
        "lanes": len(engines),
        "finished": sum(len(e.sched.finished) for e in engines),
        "tokens": tok,
        "aggregate_tok_s": (emitted - warm_tok) / max(end - t_warm, 1e-12),
        "wall_tok_s": tok / max(end - t0, 1e-12),
        "per_lane_tok_s": [e.throughput() for e in engines],
        "audit": engines[0].audit(),
        "latency": engines[0].latency_stats(),
    }
    if len(engines) > 1:
        out["lane_audits"] = [e.audit() for e in engines[1:]]
    return out


def run_gateway(engines: list, reqs, *, slo_class: str = "standard",
                arrival_scale: float = 0.02, tenants: int = 4,
                router=None, admission=None) -> dict:
    """Open-loop serving through the asyncio gateway (DESIGN.md §14): an
    async driver submits each request at its (scaled) trace arrival and
    consumes its token-event stream; rejected/shed submissions surface as
    typed AdmissionRejected backpressure, counted not raised."""
    import asyncio

    from repro import serving

    classes = [serving.SLO_CLASSES[slo_class]] if slo_class != "mixed" \
        else [serving.INTERACTIVE, serving.STANDARD, serving.BATCH]
    jobs = [(float(r.arrival) * arrival_scale,
             serving.GenerationRequest(
                 rid=r.rid, prompt=tuple(int(t) for t in r.prompt),
                 gen_len=r.gen_len, tenant=f"tenant{i % tenants}",
                 slo=classes[i % len(classes)],
                 stop_tokens=tuple(r.stop_tokens)))
            for i, r in enumerate(reqs)]

    gw = serving.Gateway(engines, router=router, admission=admission)
    rejects = []

    async def _one(arrival, greq):
        await asyncio.sleep(max(0.0, arrival - gw.now()))
        try:
            return await gw.generate(greq)
        except serving.AdmissionRejected as e:
            rejects.append((greq.rid, e.reason))
            return None

    async def _drive():
        res = await asyncio.gather(*[_one(a, g) for a, g in jobs])
        await gw.drain()
        gw.close()
        return res

    results = [r for r in asyncio.run(_drive()) if r is not None]
    audit = gw.audit()
    lane_audits = audit.pop("lane_audits")
    ttft = sorted(r.ttft_s for r in results) or [0.0]
    tpot = sorted(r.tpot_s for r in results) or [0.0]
    p99 = lambda xs: xs[min(len(xs) - 1, int(0.99 * len(xs)))]
    return {
        "lanes": len(engines),
        "offered": len(jobs),
        "finished": len(results),
        "rejected": len(rejects),
        "tokens": sum(len(r.tokens) for r in results),
        "ttft_p50_ms": 1e3 * ttft[len(ttft) // 2],
        "ttft_p99_ms": 1e3 * p99(ttft),
        "tpot_p50_ms": 1e3 * tpot[len(tpot) // 2],
        "tpot_p99_ms": 1e3 * p99(tpot),
        "slo": gw.slo_stats(),
        "gateway_audit": audit,
        "audit": lane_audits[0],
        **({"lane_audits": lane_audits[1:]} if len(lane_audits) > 1 else {}),
        "results": {r.rid: list(r.tokens) for r in results},
    }


class ProfileWindow:
    """A profiler capture of the serving run, written under ``trace_dir``
    (open it in TensorBoard or Perfetto): from entry until ``seconds`` have
    passed (0: until exit). The engine and gateway spans (``kvrm.*``) lie
    in it on the device trace's clock; ``summary()`` totals the ones the
    process kept in memory while it ran."""

    def __init__(self, trace_dir: str, seconds: float = 0.0):
        self.trace_dir, self.seconds = trace_dir, seconds
        self._lock = threading.Lock()
        self._timer = None
        self._on = False

    def __enter__(self):
        import jax
        spans.LOG.spans.clear()
        jax.profiler.start_trace(self.trace_dir)
        self._on = True
        if self.seconds > 0:
            self._timer = threading.Timer(self.seconds, self.stop)
            self._timer.daemon = True
            self._timer.start()
        return self

    def stop(self) -> None:
        import jax
        with self._lock:
            if self._on:
                jax.profiler.stop_trace()
                self._on = False

    def __exit__(self, *exc):
        if self._timer is not None:
            self._timer.cancel()
        self.stop()
        return False

    def summary(self) -> dict:
        """Count and mean milliseconds of each span captured (the span log
        keeps spans only while a trace is captured)."""
        per = {}
        for name, t0, t1, _ in list(spans.LOG.spans):
            per.setdefault(name, []).append(1e3 * (t1 - t0))
        return {n: {"count": len(v), "mean_ms": sum(v) / len(v)}
                for n, v in sorted(per.items())}


def build_arg_parser() -> argparse.ArgumentParser:
    """The serve CLI surface. Kept in a named builder so the operator-doc
    regression test (tests/test_docs.py) can diff every flag against
    docs/OPERATIONS.md."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2.5-32b")
    ap.add_argument("--layers", type=int, default=0,
                    help="serve the arch's published config cut to this "
                         "many layers (a chip run); 0 = the reduced preset "
                         "that CPU runs use")
    ap.add_argument("--mode", default="paged_merge",
                    choices=["arena", "paged", "paged_merge", "full"])
    ap.add_argument("--workload", default="mixed",
                    choices=["mixed", "predictable", "replay",
                             "shared_prefix", "stop_token"])
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--max-seq", type=int, default=256)
    ap.add_argument("--token-scale", type=float, default=0.25)
    ap.add_argument("--mesh", default="1x1",
                    help="DxM device mesh: D data-parallel engine lanes, "
                         "M-way tensor-parallel decode per lane (DESIGN.md §4)")
    ap.add_argument("--pool-budget", type=float, default=1.0,
                    help="device KV pool size as a fraction of worst case")
    ap.add_argument("--kv-oversubscribe", type=float, default=1.0,
                    help="KV capacity ratio vs the device pool (> 1 enables "
                         "the host tier: host = (R-1) * device blocks, "
                         "DESIGN.md §8)")
    ap.add_argument("--host-pool-blocks", type=int, default=0,
                    help="explicit host KV tier size in blocks "
                         "(overrides --kv-oversubscribe's derivation)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="automatic shared-prefix KV reuse: index committed "
                         "prompt blocks in a radix tree and COW-alias "
                         "matches at admission (DESIGN.md §9)")
    ap.add_argument("--prefix-cache-blocks", type=int, default=0,
                    help="prefix-cache pin budget in blocks "
                         "(0 = half the device pool)")
    ap.add_argument("--kv-dtype", default="bf16",
                    choices=["bf16", "fp8_e4m3", "int8"],
                    help="KV-block storage width (DESIGN.md §10): narrow "
                         "dtypes store K/V quantized with per-block "
                         "per-head scales, halving reserved/swap/COW KV "
                         "bytes under the same descriptor interface")
    ap.add_argument("--xla-profile", default=None,
                    choices=xla_flags.profile_names(),
                    help="tuned XLA flag profile (launch/xla_flags.py, "
                         "DESIGN.md §11): applied pre-jax-import by the "
                         "__main__ bootstrap; 'latency_hiding' enables the "
                         "latency-hiding scheduler, pipelined collectives, "
                         "and combine-threshold/allocator hygiene")
    ap.add_argument("--no-async-movement", action="store_true",
                    help="disable the async movement engine (DESIGN.md "
                         "§11): swap readbacks block at the pressure event "
                         "instead of deferring behind fences — the A/B "
                         "baseline for the overlap identity gate")
    ap.add_argument("--no-kernel-skip", action="store_true",
                    help="disable active-extent work skipping in the paged "
                         "decode/prefill kernels (DESIGN.md §12): every "
                         "grid step runs its block even when fully masked "
                         "— the always-run A/B baseline for the skip "
                         "identity gate (kernel_blocks_skipped audits 0)")
    ap.add_argument("--continuous-batching", dest="continuous_batching",
                    action="store_true", default=True,
                    help="step-level admission (DESIGN.md §15, default on): "
                         "a slot freed by EOS retirement, cancel or "
                         "preemption is refilled at the very next decode "
                         "step while surviving slots keep stepping; the "
                         "gateway releases arrived requests immediately")
    ap.add_argument("--no-continuous-batching", dest="continuous_batching",
                    action="store_false",
                    help="round-based A/B baseline (DESIGN.md §15): admit "
                         "only once every active slot has drained — the "
                         "head-of-line-blocking baseline for the TTFT gate "
                         "(continuous_admits/slot_idle_steps_saved audit 0)")
    # --- on-device sampling + detected-EOS retirement (DESIGN.md §13).
    # Passing ANY of these switches the engine out of the legacy greedy
    # budget-EOS path (greedy=False); with none of them the run stays
    # bitwise-identical to seed. "Greedy with stop tokens" is
    # --temperature 0 plus --stop-token (the sampler's exact argmax
    # branch, retired at readback on the detected stop).
    ap.add_argument("--temperature", type=float, default=None,
                    help="sampling temperature (0 = exact argmax branch); "
                         "any sampling flag enables sampled decode with "
                         "detected-EOS retirement (DESIGN.md §13)")
    ap.add_argument("--top-k", type=int, default=None,
                    help="keep the k highest logits before sampling "
                         "(0 = disabled; ties at the k-th value included)")
    ap.add_argument("--top-p", type=float, default=None,
                    help="nucleus filter: smallest logit-sorted set with "
                         "mass >= p (top-1 always kept)")
    ap.add_argument("--stop-token", type=int, action="append", default=None,
                    help="token id ending a request (repeatable); stamped "
                         "on every submitted request and detected on the "
                         "readback path, one step late under pipelining")
    ap.add_argument("--seed", type=int, default=None,
                    help="sampler base PRNG key (threefry), folded with "
                         "(rid, position) per slot-step so token streams "
                         "are invariant to slot/batch/depth placement")
    # --- async serving gateway (DESIGN.md §14). Default OFF: without
    # --gateway the closed-loop replay path below is bitwise-identical to
    # seed (the gateway reuses the same engines, so the identity gate in
    # bench_gateway_slo can diff the two token streams).
    ap.add_argument("--gateway", action="store_true",
                    help="serve through the asyncio gateway (DESIGN.md "
                         "§14): typed submit/stream/cancel API, SLO-aware "
                         "admission with typed backpressure, per-tenant "
                         "fairness, prefix-affinity lane routing; off = "
                         "the closed-loop replay driver (seed-exact)")
    ap.add_argument("--arrival", default="trace",
                    choices=["trace", "poisson", "bursty"],
                    help="open-loop arrival process overriding the "
                         "workload's own arrivals (data/traces.py): "
                         "memoryless 'poisson' or Pareto-window 'bursty'; "
                         "'trace' keeps the workload's arrivals")
    ap.add_argument("--slo-class", default="standard",
                    choices=["interactive", "standard", "batch", "mixed"],
                    help="SLO class stamped on gateway requests "
                         "(serving/api.py: TTFT/TPOT targets + shed "
                         "depth); 'mixed' stripes all three classes "
                         "round-robin over the trace")
    ap.add_argument("--trace-dir", default=None,
                    help="capture a profiler trace of the run here: the "
                         "engine and gateway spans (kvrm.*) on the device "
                         "trace's clock; off by default")
    ap.add_argument("--trace-seconds", type=float, default=0.0,
                    help="with --trace-dir: stop the capture after this "
                         "many seconds of serving (0 = the whole run)")
    ap.add_argument("--json", action="store_true")
    return ap


def main(argv=None):
    ap = build_arg_parser()
    args = ap.parse_args(argv)
    enable_compile_cache()

    if (args.kv_oversubscribe > 1.0 or args.host_pool_blocks > 0) \
            and args.mesh not in ("1x1", "1X1"):
        ap.error("the host KV tier is single-device for now: "
                 "use --mesh 1x1 with --kv-oversubscribe/--host-pool-blocks")
    if args.prefix_cache and args.mesh not in ("1x1", "1X1"):
        ap.error("the prefix cache is single-device for now: "
                 "use --mesh 1x1 with --prefix-cache")

    # sampled decode (§13): any sampling flag leaves the legacy greedy path
    sampling = SamplingConfig(
        temperature=1.0 if args.temperature is None else args.temperature,
        top_k=args.top_k or 0,
        top_p=1.0 if args.top_p is None else args.top_p,
        seed=args.seed or 0,
        stop_tokens=tuple(args.stop_token or ()),
        legacy=all(v is None for v in (
            args.temperature, args.top_k, args.top_p, args.stop_token,
            args.seed)) and args.workload != "stop_token",
    )
    sample_kw = {}
    if not sampling.greedy():
        sample_kw = dict(greedy=False,
                         temperature=sampling.temperature,
                         top_k=sampling.top_k, top_p=sampling.top_p,
                         sample_seed=sampling.seed)

    model = (get_config(args.arch).replace(n_layers=args.layers)
             if args.layers else args.arch)
    engines = build_lanes(model, args.mode, args.batch, args.max_seq,
                          args.mesh, pool_budget_frac=args.pool_budget,
                          **sample_kw,
                          kv_oversubscribe=args.kv_oversubscribe,
                          host_pool_blocks=args.host_pool_blocks,
                          prefix_cache=args.prefix_cache,
                          prefix_cache_blocks=args.prefix_cache_blocks,
                          kv_dtype=args.kv_dtype,
                          async_movement=not args.no_async_movement,
                          kernel_skip_extent=not args.no_kernel_skip,
                          continuous_batching=args.continuous_batching)
    tcfg = traces.TraceConfig(n_requests=args.requests,
                              vocab=engines[0].cfg.vocab_size,
                              token_scale=args.token_scale,
                              stop_tokens=sampling.stop_tokens)
    gen = {"mixed": traces.mixed_length_workload,
           "predictable": traces.predictable_workload,
           "replay": traces.azure_like_replay,
           "shared_prefix": traces.shared_prefix_workload,
           "stop_token": traces.stop_token_workload}[args.workload]
    reqs = gen(tcfg)
    if sampling.stop_tokens and args.workload != "stop_token":
        for r in reqs:
            r.stop_tokens = sampling.stop_tokens
    if args.arrival != "trace":
        # open-loop arrival override (§14): Poisson or bursty process over
        # the TraceConfig window, independent of the length mixture
        traces.assign_arrivals(reqs, args.arrival, tcfg)
    print("workload:", traces.trace_summary(reqs))

    scale = 0.02                # trace window -> wall seconds compression
    window = (ProfileWindow(args.trace_dir, args.trace_seconds)
              if args.trace_dir else contextlib.nullcontext())
    with window:
        if args.gateway:
            out = run_gateway(engines, reqs, slo_class=args.slo_class,
                              arrival_scale=scale)
            out.pop("results")  # per-rid token streams: bench-only payload
        else:
            now_fn = None
            if args.workload == "replay" or args.arrival != "trace":
                # virtual-time replay: arrivals gate admission. The trace
                # window is compressed into wall seconds up front (arrivals
                # and the engine's latency stamps then share one clock;
                # admission timing is equivalent to dividing now by the
                # scale).
                for r in reqs:
                    r.arrival *= scale
                t0 = time.perf_counter()
                now_fn = lambda: time.perf_counter() - t0
            out = run_lanes(engines, reqs, now_fn=now_fn)
            out["throughput_tok_s"] = out["aggregate_tok_s"]
    if args.trace_dir:
        out["spans"] = window.summary()
    out["xla_profile"] = xla_flags.active_profile()

    if args.json:
        print(json.dumps(out, indent=1, default=float))
    else:
        for k, v in out.items():
            if k == "lane_audits":
                continue
            print(f"{k}: {v}")
    return out


if __name__ == "__main__":
    main()
