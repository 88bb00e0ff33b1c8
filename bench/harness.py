"""One run of one cell: build the served engine, warm it, drive the cell's
traffic through the gateway for the window, measure, then check what the
window produced against the float32 reference.

Everything that belongs to one configuration, traffic mix or per-layer
metric is a file of its own, found by the name ``BENCHMARK.json`` gives it:
``configs/<config>.json``, ``traffic/<mix>.json``, ``checks/<cell>.json``
and ``metrics/<metric>.py``.
"""
from __future__ import annotations

import asyncio
import gc
import importlib.util
import json
import shutil
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional

import numpy as np

from bench import loadgen, reference, stats, tracereduce, weights

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent

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
PROBE_RID = 10 ** 9
REF_ROWS = 3        # packed rows of max_seq tokens the reference runs over


# ---------------------------------------------------------------------------
# the cell, found by name
# ---------------------------------------------------------------------------

@dataclass
class Cell:
    name: str
    chips: int
    cfile: dict                 # configs/<config>.json
    mix: dict                   # traffic/<mix>.json
    limits: dict                # checks/<cell>.json
    end_to_end: List[dict]
    per_layer: List[dict]

    @property
    def mc(self) -> dict:
        return self.cfile["model"]


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    with open(root / "BENCHMARK.json") as f:
        manifest = json.load(f)
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise KeyError(f"no cell {name!r}; cells: {sorted(cells)}")
    w = cells[name]
    (centry,) = [c for c in manifest["configs"] if c["name"] == w["config"]]
    with open(root / centry["file"]) as f:
        cfile = json.load(f)
    with open(BENCH / "checks" / f"{name}.json") as f:
        limits = json.load(f)
    return Cell(name=name, chips=int(w["chips"]), cfile=cfile,
                mix=loadgen.load_mix(w["traffic"]), limits=limits,
                end_to_end=[m for m in manifest["end_to_end"]
                            if _reports(m, name)],
                per_layer=[m for m in manifest["per_layer"]
                           if _reports(m, name)])


def max_context(cfile: dict) -> int:
    """Prompt and output together stay within the configuration's
    published positions and the engine's slot length."""
    return min(cfile["engine"]["max_seq"],
               cfile["model"]["max_position_embeddings"])


def model_config(cfile: dict):
    """The program's ModelConfig for a config file: the architecture's
    published config with every key of the file applied."""
    from repro.configs import get_config
    kw = {fld: conv(cfile["model"][k]) for k, (fld, conv) in HF_KEYS.items()}
    return get_config(cfile["arch"]).replace(**kw)


def open_chips(chips: int, who: str) -> None:
    """Exit non-zero, before any result, without a TPU or with fewer than
    ``chips`` of them; then keep JAX's compile cache where the program's
    entry points keep it (inside the checkout unless
    ``JAX_COMPILATION_CACHE_DIR`` says otherwise), every program in it."""
    import jax

    from repro.launch.compile_cache import enable_compile_cache
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"{who}: needs a TPU; jax found {devs[0].platform!r}")
    if len(devs) < chips:
        sys.exit(f"{who}: needs {chips} chips, found {len(devs)}")
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


# ---------------------------------------------------------------------------
# client-side records
# ---------------------------------------------------------------------------

@dataclass
class Rec:
    rid: int
    prompt: tuple
    gen_len: int
    tenant: str
    phase: str
    due: Optional[float] = None        # gateway clock
    sent: Optional[float] = None
    times: List[float] = field(default_factory=list)
    tokens: List[int] = field(default_factory=list)
    rejected: bool = False
    finish: str = ""


@dataclass
class Run:
    """What one run recorded, handed to the per-layer metric readers."""
    cell: Cell
    peaks: dict
    recs: Dict[int, Rec] = field(default_factory=dict)
    window: tuple = (0.0, 0.0)         # gateway clock
    t_end: float = 0.0                 # gateway clock, end of the drain
    clock0: float = 0.0                # perf_counter at gateway time 0
    steps: list = field(default_factory=list)
    decode_calls: list = field(default_factory=list)
    chunk_calls: list = field(default_factory=list)
    trace: Optional[dict] = None
    rid_base: int = 0                  # keeps request ids unique per gateway
    phases: dict = field(default_factory=dict)   # set-up seconds by phase

    @property
    def mc(self) -> dict:
        return self.cell.mc

    @property
    def chips(self) -> int:
        return self.cell.chips

    def in_window(self, t_perf: float) -> bool:
        t = t_perf - self.clock0
        return self.window[0] <= t < self.window[1]


def _greq(rec: Rec):
    from repro.serving import STANDARD, GenerationRequest
    return GenerationRequest(rid=rec.rid, prompt=rec.prompt,
                             gen_len=rec.gen_len, tenant=rec.tenant,
                             slo=STANDARD)


async def _consume(gw, rec: Rec) -> None:
    from jax.profiler import TraceAnnotation

    from repro.serving import AdmissionRejected
    rec.sent = gw.now()
    try:
        with TraceAnnotation("bench.submit"):
            stream = gw.submit(_greq(rec))
    except AdmissionRejected:
        rec.rejected = True
        return
    async for ev in stream:
        if ev.token >= 0:
            rec.times.append(ev.t)
            rec.tokens.append(ev.token)
        if ev.finished:
            rec.finish = ev.finish_reason


def _pump_alive(gw) -> None:
    """Raise what stopped the gateway's pump, if it died."""
    task = gw._task
    if task is not None and task.done() and not task.cancelled() \
            and task.exception() is not None:
        raise RuntimeError("gateway pump failed") from task.exception()


async def _sleep_until(gw, t: float) -> None:
    while gw.now() < t:
        _pump_alive(gw)
        await asyncio.sleep(min(0.05, max(0.0, t - gw.now())))


# ---------------------------------------------------------------------------
# instrumentation (traced runs only): host spans and per-step records
# ---------------------------------------------------------------------------

# what the traced run reads from the program's internals: the engine's two
# executors and the descriptors they are called with; ``hooks_seen`` checks
# after every traced run that each was found and read
ENGINE_HOOKS = ("step", "_step_fn", "_chunk_fn", "_pdescr", "_cdescr")


def instrument(gw, eng, run: Run) -> None:
    """Wrap this engine's step and its two executors, per instance: a
    host span around each, and per step the program's own counters."""
    from jax.profiler import TraceAnnotation
    missing = [h for h in ENGINE_HOOKS if not hasattr(eng, h)]
    if missing:
        raise RuntimeError(f"engine lacks what the benchmark reads: {missing}")
    step, step_fn, chunk_fn = eng.step, eng._step_fn, eng._chunk_fn

    def step_w(*a, **k):
        t = time.perf_counter()
        with TraceAnnotation("bench.step"):
            m = step(*a, **k)
        run.steps.append((t, m.active, m.emitted, m.host, m.dma_groups,
                          gw.outstanding(), eng.active_kv_bytes(),
                          eng.reserved_kv_bytes(), eng.e.batch))
        return m

    def step_fn_w(*a):
        d = eng._pdescr
        run.decode_calls.append(
            (time.perf_counter(), d.seq_lens[d.slot_active > 0].tolist()))
        with TraceAnnotation("bench.decode_dispatch"):
            return step_fn(*a)

    def chunk_fn_w(*a):
        cd = eng._cdescr
        live = cd.n_valid > 0
        run.chunk_calls.append(
            (time.perf_counter(),
             list(zip(cd.start_pos[live].tolist(), cd.n_valid[live].tolist()))))
        with TraceAnnotation("bench.chunk_dispatch"):
            return chunk_fn(*a)

    eng.step = step_w
    eng._step_fn = step_fn_w
    if chunk_fn is not None:
        eng._chunk_fn = chunk_fn_w


def hooks_seen(run: Run) -> None:
    """Raise where a traced window shows that a hook read nothing while the
    program worked: a renamed executor, descriptor or kernel would
    otherwise leave its metrics silent."""
    tr = run.trace
    if tr is None:
        raise RuntimeError("traced run: no profile or no bench.window span")
    n = lambda calls: sum(run.in_window(c[0]) for c in calls)
    steps, dec, chunk = n(run.steps), n(run.decode_calls), n(run.chunk_calls)
    ex = tr["executors"]
    faults = []
    if not steps:
        faults.append("no engine step in the window")
    if steps and not dec:
        faults.append("engine steps but no decode-executor call recorded")
    if dec and not ex["decode"]["calls"]:
        faults.append(f"no jit_{tracereduce.EXECUTORS['decode']} module "
                      "in the trace")
    if chunk and not ex["prefill"]["calls"]:
        faults.append(f"no jit_{tracereduce.EXECUTORS['prefill']} module "
                      "in the trace")
    if ex["decode"]["calls"] and not tr["kernels"]["paged_decode_attention"]:
        faults.append("decode calls but no paged_decode_attention op")
    if ex["prefill"]["calls"] and \
            not tr["kernels"]["chunked_prefill_attention"]:
        faults.append("chunk calls but no chunked_prefill_attention op")
    if tr["other_modules_s"] > 0.01 * tr["window_s"]:
        faults.append(f"{tr['other_modules_s']:.3f} s of device modules of "
                      "no known executor")
    if faults:
        raise RuntimeError("trace hooks read nothing: " + "; ".join(faults))


# ---------------------------------------------------------------------------
# driving the traffic
# ---------------------------------------------------------------------------

async def _probe(gw, cell: Cell, seed: int, rid: int) -> None:
    """One request whose prompt takes two prefill chunks: compiles (or
    loads) the decode and prefill-chunk executors before any timing."""
    chunk = cell.cfile["engine"]["prefill_chunk"]
    rng = np.random.default_rng([seed, 9])
    rec = Rec(rid=rid, gen_len=4, tenant="probe", phase="probe",
              prompt=tuple(rng.integers(0, cell.mc["vocab_size"],
                                        2 * chunk + 1).tolist()))
    task = asyncio.create_task(_consume(gw, rec))
    while not task.done():
        _pump_alive(gw)
        await asyncio.sleep(0.005)
    task.result()
    if len(rec.tokens) != rec.gen_len:
        raise RuntimeError(f"warm-up probe got {len(rec.tokens)} tokens")


class _Trace:
    def __init__(self, out_dir: Optional[Path]):
        self.dir = out_dir
        self.span = None

    def start(self):
        if self.dir is None:
            return
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(self.dir), profiler_options=opts)

    def open_window(self):
        if self.dir is not None:
            from jax.profiler import TraceAnnotation
            self.span = TraceAnnotation("bench.window")
            self.span.__enter__()

    def close_window(self):
        if self.span is not None:
            self.span.__exit__(None, None, None)
            import jax
            jax.profiler.stop_trace()


async def drive(gw, cell: Cell, seed: int, seconds: float, run: Run,
                trace: _Trace, t_start: float, close: bool = True) -> float:
    """Probe, warm-up, window and drain, then (with ``close``) stop the
    gateway. Returns the set-up seconds."""
    mix = cell.mix
    run.clock0 = time.perf_counter() - gw.now()
    await _probe(gw, cell, seed, PROBE_RID + run.rid_base)
    run.phases["probe"] = time.perf_counter() - t_start
    vocab = cell.mc["vocab_size"]
    tasks = []
    if mix["kind"] == "open":
        items = loadgen.open_loop(mix, seconds, seed, vocab,
                                  max_context(cell.cfile))
        origin = gw.now() + mix["warmup_s"]
        for it in items:
            it.rid += run.rid_base
            run.recs[it.rid] = Rec(rid=it.rid, prompt=tuple(it.prompt.tolist()),
                                   gen_len=it.gen_len, tenant=it.tenant,
                                   phase=it.phase, due=origin + it.due)

        async def client(rec):
            await _sleep_until(gw, rec.due)
            await _consume(gw, rec)

        tasks = [asyncio.create_task(client(r)) for r in run.recs.values()]
        await _sleep_until(gw, origin - 2.0)
        trace.start()
        await _sleep_until(gw, origin)
    else:
        queues = loadgen.closed_loop(mix, seed, vocab,
                                     max_context(cell.cfile))
        closed = {"window_end": float("inf")}
        for q in queues:
            for it in q:
                it.rid += run.rid_base
                run.recs[it.rid] = Rec(rid=it.rid,
                                       prompt=tuple(it.prompt.tolist()),
                                       gen_len=it.gen_len, tenant=it.tenant,
                                       phase=it.phase)

        async def client(q):
            for it in q:
                if gw.now() >= closed["window_end"]:
                    return
                await _consume(gw, run.recs[it.rid])

        tasks = [asyncio.create_task(client(q)) for q in queues]
        firsts = [run.recs[q[0].rid] for q in queues]
        # the window opens once every caller's history is prefilled
        while not all(r.times or r.rejected for r in firsts):
            _pump_alive(gw)
            await asyncio.sleep(0.005)
        trace.start()
        origin = gw.now()
    setup_s = time.perf_counter() - t_start
    run.window = (origin, origin + seconds)
    trace.open_window()
    if mix["kind"] == "closed":
        closed["window_end"] = run.window[1]
    await _sleep_until(gw, run.window[1])
    trace.close_window()
    if mix["kind"] == "open":
        deadline = run.window[1] + mix["drain_s"]
        due_in = [r for r in run.recs.values()
                  if run.window[0] <= r.due < run.window[1]]
        while gw.now() < deadline and any(
                not r.times and not r.rejected for r in due_in):
            _pump_alive(gw)
            await asyncio.sleep(0.005)
    run.t_end = gw.now()
    for t in tasks:
        t.cancel()
    await asyncio.gather(*tasks, return_exceptions=True)
    if close:
        gw.close()
        for eng in gw.engines:
            eng.flush()
    return setup_s


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------

def end_to_end(run: Run, setup_s: float) -> dict:
    t0, t1 = run.window
    recs = list(run.recs.values())
    vals = {"setup_s": setup_s, "out_tok_s": stats.out_tok_s(recs, t0, t1)}
    gaps = stats.itl_gaps(recs, t0, t1)
    if gaps:
        vals["itl_p99_ms"] = 1e3 * stats.percentile(gaps, 99)
    ttft = stats.ttft_values(recs, t0, t1, run.t_end)
    if ttft:
        vals["ttft_p90_ms"] = 1e3 * stats.percentile(ttft, 90)
    return vals


def attempted_failed(run: Run):
    t0, t1 = run.window
    if run.cell.mix["kind"] == "open":
        due = [r for r in run.recs.values() if t0 <= r.due < t1]
        failed = [r for r in due if r.rejected or not r.times
                  or r.times[0] > run.t_end]
        return len(due), len(failed)
    live = [r for r in run.recs.values() if r.sent is not None and r.sent < t1
            and (r.rejected or not r.finish or r.times[-1] >= t0)]
    return len(live), sum(r.rejected for r in live)


def load_reader(name: str):
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def per_layer(run: Run) -> dict:
    out = {}
    for m in run.cell.per_layer:
        v = load_reader(m["name"])(run)
        if v is not None:
            out[m["name"]] = v
    return out


# ---------------------------------------------------------------------------
# correctness: the window's served tokens against the reference
# ---------------------------------------------------------------------------

def choose_sample(run: Run, seed: int, row_len: int,
                  rows: int = REF_ROWS) -> List[List[Rec]]:
    """Finished requests packed first-fit into at most ``rows`` rows of
    ``row_len`` tokens: the one with the longest context, the one with the
    most served tokens, then others in an order drawn from the seed."""
    done = sorted((r for r in run.recs.values()
                   if r.finish == "budget" and len(r.tokens) == r.gen_len),
                  key=lambda r: r.rid)
    if not done:
        return []
    size = lambda r: len(r.prompt) + len(r.tokens)
    longest = max(done, key=lambda r: (size(r), -r.rid))
    most = max(done, key=lambda r: (len(r.tokens), -r.rid))
    rng = np.random.default_rng([seed, 3])
    order = [longest, most] + [done[i] for i in rng.permutation(len(done))]
    bins: List[List[Rec]] = [[] for _ in range(rows)]
    used = [0] * rows
    seen = set()
    for r in order:
        if r.rid in seen:
            continue
        seen.add(r.rid)
        for b in range(rows):
            if used[b] + size(r) <= row_len:
                bins[b].append(r)
                used[b] += size(r)
                break
    return [b for b in bins if b]


def served_gaps(run: Run, seed: int, rows: List[List[Rec]], *,
                control: bool = False) -> dict:
    """Per served token of the sample's rows: how far its reference logit
    lies below the reference's best (and, with ``control``, the same for
    the float8 computation's first choice)."""
    row_len = run.cell.cfile["engine"]["max_seq"]
    prog, ctl = [], []
    for sample in rows:
        seqs = [np.asarray(r.prompt + tuple(r.tokens), np.int32)
                for r in sample]
        ref = reference.logit_gaps(seed, run.mc, seqs, row_len,
                                   control=control)
        idx = np.concatenate([
            o + len(r.prompt) - 1 + np.arange(len(r.tokens))
            for r, o in zip(sample, ref["offsets"])])
        prog.append(ref["best"][idx] - ref["at_next"][idx])
        if control:
            ctl.append(ref["best"][idx] - ref["at_control"][idx])
    out = {"program": np.concatenate(prog)}
    out["n"] = len(out["program"])
    if control:
        out["control"] = np.concatenate(ctl)
    return out


def check(run: Run, seed: int, gaps: Optional[dict]) -> dict:
    """The numbers ``correct`` compares, each with its limit."""
    vocab = run.mc["vocab_size"]
    ended = [r for r in run.recs.values() if r.finish == "budget"]
    bad = sum(len(r.tokens) != r.gen_len
              or any(not 0 <= t < vocab for t in r.tokens) for r in ended)
    # nothing compared: no number, and the run is not correct
    gap = float(gaps["program"].max()) if gaps and gaps["n"] else None
    return {
        "max_logit_gap": {"value": gap,
                          "limit": run.cell.limits["max_logit_gap"]["limit"]},
        "bad_streams": {"value": bad, "limit": 0},
    }


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def free_device_state() -> None:
    import jax
    gc.collect()
    for a in jax.live_arrays():
        a.delete()


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             t_start: float, work_dir: Path, control: bool = False,
             build_hook=None):
    """Build, drive, measure and check one run. Returns the result line,
    diagnostics for standard error and the run's records.
    ``build_hook(gw)`` lets a test reach the built engine before any
    traffic."""
    import jax

    from repro import serving
    with open(BENCH / "peaks.json") as f:
        peaks_all = json.load(f)
    devs = jax.devices()
    kind = devs[0].device_kind
    peaks = peaks_all["devices"].get(kind) if devs[0].platform == "tpu" else None
    if devs[0].platform == "tpu" and peaks is None:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")

    cfg = model_config(cell.cfile)
    run = Run(cell=cell, peaks=peaks or {})
    run.phases["imports"] = time.perf_counter() - t_start
    params = weights.make_params(seed, cell.mc)
    jax.block_until_ready(params)
    run.phases["weights"] = time.perf_counter() - t_start
    eng_kw = dict(cell.cfile["engine"])
    gw = serving.build(cfg, gateway=True, params=params, **eng_kw)
    jax.block_until_ready(gw.engines[0].pools)
    run.phases["build"] = time.perf_counter() - t_start
    del params
    if build_hook is not None:
        build_hook(gw)
    trace_dir = work_dir / "trace" if trace else None
    if trace_dir is not None:
        shutil.rmtree(trace_dir, ignore_errors=True)
        instrument(gw, gw.engines[0], run)

    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda ev, dur, **kw: compiles.append(time.perf_counter())
        if ev == "/jax/core/compile/backend_compile_duration" else None)
    setup_s = asyncio.run(drive(gw, cell, seed, seconds, run,
                                _Trace(trace_dir), t_start))
    n_compiles = sum(run.in_window(t) for t in compiles)
    mem = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
              for d in devs[:cell.chips])
    del gw
    free_device_state()

    t_trace = time.perf_counter()
    if trace_dir is not None:
        run.trace = tracereduce.reduce_dir(trace_dir, n_chips=cell.chips)
        shutil.rmtree(trace_dir, ignore_errors=True)
        hooks_seen(run)
    t_trace = time.perf_counter() - t_trace

    t_ref = time.perf_counter()
    sample = choose_sample(run, seed, cell.cfile["engine"]["max_seq"])
    gaps = served_gaps(run, seed, sample, control=control) if sample else None
    t_ref = time.perf_counter() - t_ref
    checks = check(run, seed, gaps)
    correct = all(c["value"] is not None and c["value"] <= c["limit"]
                  for c in checks.values())
    attempted, failed = attempted_failed(run)
    e2e = end_to_end(run, setup_s)
    ttft = stats.ttft_values(list(run.recs.values()), *run.window, run.t_end)
    lat = [r.sent - r.due for r in run.recs.values()
           if r.due is not None and r.sent is not None
           and run.window[0] <= r.due < run.window[1]]
    diag = {"compiles_in_window": n_compiles,
            "sample_requests": sum(len(row) for row in sample),
            "sample_rows": len(sample),
            "compared_tokens": gaps["n"] if gaps else 0,
            "generator_late_p99_ms": (1e3 * stats.percentile(lat, 99)
                                      if lat else 0.0),
            "end_to_end_all": e2e, "setup_phases_s": run.phases,
            "ttft_p50_ms": (1e3 * stats.percentile(ttft, 50) if ttft else None),
            "due_in_window": len(ttft), "reference_s": t_ref,
            "trace_reduce_s": t_trace}
    if gaps and control:
        # the float8 computation in the program's place, through the same
        # comparison: it has to come out as not correct
        ctl = check(run, seed, {"program": gaps["control"], "n": gaps["n"]})
        diag["control_max_logit_gap"] = ctl["max_logit_gap"]["value"]
        diag["control_correct"] = all(c["value"] is not None
                                      and c["value"] <= c["limit"]
                                      for c in ctl.values())
    units = {m["name"]: m["unit"] for m in cell.end_to_end + cell.per_layer}
    vals = per_layer(run) if trace else {
        m["name"]: e2e[m["name"]] for m in cell.end_to_end
        if m["name"] in e2e}
    result = {
        "correct": correct, "attempted": attempted, "failed": failed,
        "metrics": {k: {"value": v, "unit": units[k]} for k, v in vals.items()},
        "device": {"platform": devs[0].platform, "kind": kind,
                   "count": cell.chips, "memory_peak_bytes": int(mem)},
    }
    if run.trace is not None:
        result["device"]["busy_s"] = run.trace["busy_s"]
        result["device"]["window_s"] = run.trace["window_s"]
        result["breakdown"] = {"device_ops": run.trace["device_ops"][:10],
                               "idle_gaps": run.trace["idle_gaps"][:10]}
    result["checks"] = checks
    return result, diag, run


def print_result(result: dict, diag: dict) -> None:
    print(json.dumps(diag), file=sys.stderr)
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']} limit {c['limit']}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
