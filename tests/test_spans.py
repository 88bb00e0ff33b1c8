"""Host spans and request timelines of the serving path, read back from a
profiler trace of a tiny gateway (docs/OPERATIONS.md "Spans and request
timelines").

* one ``kvrm.engine.step`` per engine step, ``step_num`` = ``steps_run``;
* every other engine span nests inside its step, except the readbacks a
  drain issues between steps, and each dispatched step is read back once;
* a step's span count does not grow with what it emits;
* each request's stamps are monotone and split ``ttft_s`` exactly, and its
  chunk and tail tokens add up to its prompt less its prefix-cache hit;
* a preempted and resumed request keeps its first admission.
"""
import asyncio

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro import serving
from repro.core import spans
from repro.serving.factory import build

CHUNK = 16


def _greq(rid, prompt, gen_len):
    return serving.GenerationRequest(
        rid=rid, prompt=tuple(int(t) for t in prompt), gen_len=gen_len,
        tenant=f"t{rid % 2}", slo=serving.STANDARD)


def _host_spans(trace_dir):
    """(name, start_ns, end_ns, stats) of every ``kvrm.*`` host event."""
    (path,) = sorted(trace_dir.rglob("*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("kvrm."):
                    out.append((ev.name, ev.start_ns, ev.end_ns,
                                dict(ev.stats)))
    return sorted(out, key=lambda x: (x[1], -x[2]))


def _serve(gw, batches, trace_dir):
    """Submit each batch of requests once the one before has finished,
    all under one profiler trace; returns the results by rid."""
    async def main():
        out = {}
        for batch in batches:
            res = await asyncio.gather(*[gw.generate(g) for g in batch])
            out.update({r.rid: r for r in res})
        await gw.drain()
        gw.close()
        return out

    with jax.profiler.trace(str(trace_dir)):
        return asyncio.run(main())


@pytest.fixture(scope="module")
def chunked(tmp_path_factory):
    """Chunked prefill with a prefix cache: short prompts (tail only),
    long ones (chunks then tail), and a repeat of a long prompt's prefix
    served after it, so its admission hits the cache."""
    gw = build("qwen2.5-32b", mode="paged_merge", batch=4, max_seq=128,
               block_tokens=8, lanes=1, prefill_chunk=CHUNK,
               prefix_cache=True, gateway=True)
    eng = gw.engines[0]
    rng = np.random.default_rng(5)
    shared = rng.integers(0, 100, size=48)
    first = [_greq(0, rng.integers(0, 100, size=5), 12),
             _greq(1, rng.integers(0, 100, size=70), 3),
             _greq(2, shared, 6),
             _greq(3, rng.integers(0, 100, size=21), 9)]
    second = [_greq(4, np.concatenate([shared, rng.integers(0, 100, 11)]), 5)]
    trace_dir = tmp_path_factory.mktemp("chunked")
    steps0 = eng.steps_run
    results = _serve(gw, [first, second], trace_dir)
    return {"gw": gw, "eng": eng, "results": results,
            "greqs": {g.rid: g for g in first + second},
            "steps": range(steps0, eng.steps_run),
            "spans": _host_spans(trace_dir)}


def _inside(child, parent):
    return parent[1] <= child[1] and child[2] <= parent[2]


def _steps(sp):
    return [s for s in sp if s[0] == spans.ENGINE_STEP]


def test_every_span_name_is_in_the_vocabulary(chunked):
    names = {s[0] for s in chunked["spans"]}
    assert names <= set(spans.SPANS)
    # this engine has no host tier: no pressure pass, no swaps
    assert names == set(spans.SPANS) - {spans.PAGER_PRESSURE,
                                        spans.SWAP_WAIT}


def test_one_step_span_per_engine_step(chunked):
    steps = _steps(chunked["spans"])
    assert [s[3]["step_num"] for s in steps] == list(chunked["steps"])
    eng = chunked["eng"]
    tail = eng.metrics[len(eng.metrics) - len(steps):]
    assert [s[3]["emitted"] for s in steps] == [m.emitted for m in tail]
    assert [s[3]["active"] for s in steps] == [m.active for m in tail]


def test_child_spans_nest_inside_their_step(chunked):
    sp = chunked["spans"]
    steps = _steps(sp)
    loose = []
    for s in sp:
        if s[0] in (spans.ENGINE_STEP, spans.GATEWAY_RELEASE):
            continue
        if not any(_inside(s, st) for st in steps):
            loose.append(s)
    # only a drain's readbacks (and their device waits) run between steps
    assert {s[0] for s in loose} <= {spans.READBACK, spans.DEVICE_WAIT}
    for s in loose:
        if s[0] == spans.DEVICE_WAIT:
            assert any(_inside(s, r) for r in loose
                       if r[0] == spans.READBACK)
    # the gateway releases between steps, never inside one
    for s in sp:
        if s[0] == spans.GATEWAY_RELEASE:
            assert not any(_inside(s, st) for st in steps)
    # each dispatched step is read back exactly once
    reads = [s for s in sp if s[0] == spans.READBACK]
    assert len(reads) == len(steps)
    assert sum(r[3]["emits"] for r in reads) == \
        sum(st[3]["emitted"] for st in steps)


def test_span_count_of_a_step_does_not_grow_with_its_emits(chunked):
    sp = chunked["spans"]
    by_shape = {}
    for st in _steps(sp):
        kids = [s[0] for s in sp if s is not st and _inside(s, st)]
        shape = (spans.CHUNK_DISPATCH in kids, spans.READBACK in kids)
        by_shape.setdefault(shape, set()).add(len(kids))
        assert len(kids) <= 15
    assert all(len(counts) == 1 for counts in by_shape.values()), by_shape
    emitted = {st[3]["emitted"] for st in _steps(sp)}
    assert len(emitted) >= 3                 # steps emitting 0, 1, ... 4


def test_timelines_split_ttft_exactly(chunked):
    gw = chunked["gw"]
    for rid, res in chunked["results"].items():
        tl = gw.timeline(rid)
        stamps = [tl[k] for k in spans.TIMELINE_STAMPS]
        assert None not in stamps
        assert stamps == sorted(stamps), (rid, tl)
        parts = [b - a for a, b in zip(stamps, stamps[1:])]
        assert sum(parts) == pytest.approx(res.ttft_s, abs=1e-9)
        assert res.queue_s == pytest.approx(parts[0])
        assert res.admit_wait_s == pytest.approx(parts[1])
        assert res.prefill_s == pytest.approx(parts[2])
        assert res.prompt_tail_steps == tl["prompt_tail_steps"]
        assert spans.LOG.timeline(rid) == tl
    assert gw.timeline(10 ** 6) is None


def _split(prompt_len, hit, chunk_min):
    """Chunk steps, chunk tokens and tail tokens of a prompt: chunks of up
    to CHUNK while at least ``chunk_min`` tokens remain before the last,
    then one token a step."""
    left, steps, toks = prompt_len - hit, 0, 0
    while left - 1 >= chunk_min:
        n = min(CHUNK, left - 1)
        left -= n
        steps += 1
        toks += n
    return steps, toks, left


def test_chunk_and_tail_tokens_make_the_prompt(chunked):
    gw, eng = chunked["gw"], chunked["eng"]
    hit = eng.audit()["prefix_tokens_reused"]
    assert hit >= 8                          # rid 4 reused rid 2's blocks
    tails = set()
    for rid, g in chunked["greqs"].items():
        tl = gw.timeline(rid)
        h = hit if rid == 4 else 0
        steps, toks, tail = _split(len(g.prompt), h, eng._chunk_min)
        assert (tl["prompt_chunk_steps"], tl["prompt_tail_steps"]) == \
            (steps, tail), rid
        assert toks + tl["prompt_tail_steps"] == len(g.prompt) - h
        tails.add(tail)
    assert len(tails) > 1


def test_preempted_request_keeps_its_first_admission(tmp_path):
    """An oversubscribed engine preempts and resumes requests; each
    preempted request's timeline keeps the admission it had before."""
    gw = build("qwen2.5-32b", mode="paged_merge", batch=4, max_seq=64,
               block_tokens=8, near_window=32, lanes=1,
               pool_budget_frac=0.1, host_pool_blocks=40, gateway=True)
    eng = gw.engines[0]
    at_preempt = {}
    preempt = eng._preempt_slot

    def record(slot):
        req = eng.sched.request_at(slot)
        if req is not None:
            at_preempt.setdefault(req.rid, req.t_admit)
        preempt(slot)

    eng._preempt_slot = record
    rng = np.random.default_rng(1)
    greqs = [_greq(i, rng.integers(0, 100, size=8), 48) for i in range(6)]
    results = _serve(gw, [greqs], tmp_path)
    assert at_preempt and eng.audit()["preemptions"] >= 1
    for rid, t_admit in at_preempt.items():
        tl = gw.timeline(rid)
        assert tl["preemptions"] >= 1
        assert tl["t_admit"] == t_admit
        stamps = [tl[k] for k in spans.TIMELINE_STAMPS]
        assert stamps == sorted(stamps)
        assert sum(b - a for a, b in zip(stamps, stamps[1:])) == \
            pytest.approx(results[rid].ttft_s, abs=1e-9)
    sp = _host_spans(tmp_path)
    steps = _steps(sp)
    for name in (spans.PAGER_PRESSURE, spans.SWAP_WAIT):
        found = [s for s in sp if s[0] == name]
        assert found, name
        assert all(any(_inside(s, st) for st in steps) for s in found)


def test_serve_captures_a_trace_with_the_spans(tmp_path):
    """``serve.py --trace-dir`` writes a profiler trace holding the spans,
    and its result totals them."""
    from repro.launch import serve
    out = serve.main(["--gateway", "--requests", "4", "--batch", "2",
                      "--max-seq", "64", "--trace-dir", str(tmp_path),
                      "--json"])
    assert out["finished"] == 4
    assert out["spans"][spans.ENGINE_STEP]["count"] >= 1
    assert set(out["spans"]) <= set(spans.SPANS)
    names = {s[0] for s in _host_spans(tmp_path)}
    assert {spans.ENGINE_STEP, spans.GATEWAY_RELEASE} <= names


def test_timeline_of_a_request_still_queued_is_partial():
    """A request the gateway still holds has only its arrival; cancelled
    there, its result splits nothing and its timeline is logged."""
    gw = build("qwen2.5-32b", mode="paged_merge", batch=2, max_seq=64,
               block_tokens=8, lanes=1, gateway=True)
    rid = 7_000_001

    async def main():
        g = serving.GenerationRequest(rid=rid, prompt=(1, 2, 3), gen_len=2,
                                      tenant="t", slo=serving.STANDARD,
                                      arrival=1e9)
        stream = gw.submit(g)
        await asyncio.sleep(0.01)
        tl = gw.timeline(rid)
        assert gw.cancel(rid)
        async for _ in stream:
            pass
        gw.close()
        return tl

    tl = asyncio.run(main())
    assert tl["arrival"] == 1e9
    assert [tl[k] for k in spans.TIMELINE_STAMPS[1:]] == [None] * 4
    res = gw.result(rid)
    assert (res.queue_s, res.admit_wait_s, res.prefill_s) == (None,) * 3
    assert spans.LOG.timeline(rid) == tl


def test_profile_window_stops_after_its_seconds(tmp_path):
    import time

    from repro.launch import serve
    with serve.ProfileWindow(str(tmp_path), 0.3) as window:
        with spans.Span(spans.GATEWAY_RELEASE):
            pass
        time.sleep(1.0)
        with spans.Span(spans.GATEWAY_RELEASE):   # after the stop
            pass
    summary = window.summary()
    assert list(summary) == [spans.GATEWAY_RELEASE]
    assert summary[spans.GATEWAY_RELEASE]["count"] == 1
    assert [s[0] for s in _host_spans(tmp_path)] == [spans.GATEWAY_RELEASE]


def test_synchronous_step_reads_back_inside_itself(tmp_path):
    """``pipeline_depth=0``: each step dispatches, waits and reads back
    inside its own span, and stamps the same timeline."""
    gw = build("qwen2.5-32b", mode="paged_merge", batch=2, max_seq=64,
               block_tokens=8, lanes=1, pipeline_depth=0, gateway=True)
    rng = np.random.default_rng(3)
    greqs = [_greq(i, rng.integers(0, 100, size=6), 4) for i in range(3)]
    results = _serve(gw, [greqs], tmp_path)
    sp = _host_spans(tmp_path)
    steps = _steps(sp)
    assert len(steps) == gw.engines[0].steps_run
    for name in (spans.DECODE_DISPATCH, spans.READBACK, spans.DEVICE_WAIT):
        found = [s for s in sp if s[0] == name]
        assert len(found) == len(steps)
        assert all(any(_inside(s, st) for st in steps) for s in found)
    for rid, res in results.items():
        stamps = [gw.timeline(rid)[k] for k in spans.TIMELINE_STAMPS]
        assert stamps == sorted(stamps)
        assert stamps[-1] - stamps[0] == pytest.approx(res.ttft_s)
        assert gw.timeline(rid)["prompt_tail_steps"] == 6
