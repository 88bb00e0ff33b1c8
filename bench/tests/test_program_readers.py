"""The per-layer metrics read from what the program records of itself
(``repro.core.spans.LOG``), on hand-built runs: the window's requests and
their timelines, and spans on ``perf_counter`` time. Against a program
that records nothing, each reads nothing instead of raising."""
import json
from pathlib import Path

import pytest

from bench import harness, timelines
from repro.core import spans

ROOT = Path(__file__).resolve().parents[2]
NEW = ("gateway.queue_wait_p90_ms", "sched.admit_wait_p90_ms",
       "engine.prefill_p90_ms", "engine.prompt_tail_steps",
       "engine.device_wait_ms_per_step", "gateway.between_steps_ms")
CLOCK0 = 1000.0          # perf_counter at gateway time 0


def _tl(arrival, release, admit, done, first, tail=3, chunk=2):
    return {"arrival": arrival, "t_release": release, "t_admit": admit,
            "t_prompt_done": done, "t_first": first,
            "prompt_chunk_steps": chunk, "prompt_tail_steps": tail,
            "preemptions": 0}


@pytest.fixture
def log(monkeypatch):
    fresh = spans.SpanLog()
    monkeypatch.setattr(spans, "LOG", fresh)
    return fresh


def _run(log):
    """A chat window [10, 20) drained at 25: four served requests, one
    with no first token by the drain's end, one rejected, one due before
    the window."""
    cell = harness.load_cell("qwen2.5-32b-l4.chat-bursty")
    run = harness.Run(cell=cell, peaks={}, window=(10.0, 20.0), t_end=25.0,
                      clock0=CLOCK0)
    served = {1: _tl(11.0, 11.1, 11.1, 13.0, 13.2, tail=1),
              2: _tl(12.0, 12.4, 12.5, 16.5, 16.7, tail=5),
              3: _tl(13.0, 13.0, 13.2, 14.0, 14.5, tail=9),
              4: _tl(14.0, 14.2, 14.2, 18.2, 18.3, tail=16),
              # admitted, prompt never done by the drain's end
              5: _tl(15.0, 15.5, 16.0, None, None, tail=0),
              # due before the window: not read
              9: _tl(5.0, 9.0, 9.0, 9.0, 9.0, tail=50)}
    for rid, tl in served.items():
        due = tl["arrival"] - 0.001
        run.recs[rid] = harness.Rec(rid=rid, prompt=(1,), gen_len=4,
                                    tenant="t", phase="window", due=due,
                                    sent=tl["arrival"])
        if tl["t_first"] is not None:
            run.recs[rid].times = [tl["t_first"]]
        log.add_timeline(rid, tl)
    run.recs[6] = harness.Rec(rid=6, prompt=(1,), gen_len=4, tenant="t",
                              phase="window", due=16.0, sent=16.0,
                              rejected=True)
    # a stale timeline under the rejected request's rid, from another run
    log.add_timeline(6, _tl(0.0, 0.0, 0.0, 0.0, 0.0))
    return run


def _read(name, run):
    return harness.load_reader(name)(run)


def test_each_new_metric_has_a_reader_and_its_cells():
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    per = {m["name"]: m for m in manifest["per_layer"]}
    chat, reason = "qwen2.5-32b-l4.chat-bursty", "yi-34b-l4.reason-closed"
    for name in NEW:
        assert (ROOT / "bench" / "metrics" / f"{name}.py").is_file()
        want = [chat] if per[name]["moves"] == "ttft_p90_ms" \
            else [chat, reason]
        assert per[name]["workloads"] == want


def test_stages_of_the_requests_due_in_the_window(log):
    run = _run(log)
    # queue waits, rid 1-6: 0.1 0.4 0 0.2 0.5, rejected 25-16 = 9
    assert _read("gateway.queue_wait_p90_ms", run) == pytest.approx(9000.0)
    # admit waits: 0 0.1 0.2 0 0.5, rejected 0
    assert _read("sched.admit_wait_p90_ms", run) == pytest.approx(500.0)
    # prefill: 1.9 4.0 0.8 4.0, rid 5 to the drain's end 25-16 = 9, 0
    assert _read("engine.prefill_p90_ms", run) == pytest.approx(9000.0)
    # the prompts done: tails 1 5 9 16
    assert _read("engine.prompt_tail_steps", run) == pytest.approx(7.75)


def test_stages_split_each_first_token_time(log):
    run = _run(log)
    rows = timelines.due_stamps(run)
    assert len(rows) == 6
    for row in rows:
        assert row == sorted(row) and row[-1] <= run.t_end
    got = {rid: row for rid, row in zip(
        [r for r in run.recs if 10 <= run.recs[r].due < 20], rows)}
    # a served request: the stages sum to its first token less submission
    assert got[2][-1] - got[2][0] == pytest.approx(16.7 - 12.0)
    # no first token: everything counts to the drain's end
    assert got[5][-2:] == [25.0, 25.0]
    assert got[6] == [16.0] + [25.0] * 4


def _spans(log, steps, waits):
    for i, (t0, t1) in enumerate(steps):
        log.spans.append((timelines.STEP, CLOCK0 + t0, CLOCK0 + t1,
                          {"step_num": i}))
    for t0, t1 in waits:
        log.spans.append((timelines.DEVICE_WAIT, CLOCK0 + t0, CLOCK0 + t1,
                          {}))


def test_device_wait_and_time_between_steps(log):
    run = _run(log)
    # one step before the window: not read
    _spans(log, [(9.0, 9.9), (10.0, 10.4), (10.5, 10.9), (11.0, 11.5)],
           [(9.5, 9.9), (10.1, 10.35), (10.6, 10.8), (11.1, 11.4)])
    run.steps = [(CLOCK0 + 10.0,)]
    assert _read("engine.device_wait_ms_per_step", run) == \
        pytest.approx(1e3 * (0.25 + 0.2 + 0.3) / 3)
    assert _read("gateway.between_steps_ms", run) == pytest.approx(100.0)


def test_steps_but_no_step_span_stops_the_run(log):
    run = _run(log)
    run.steps = [(CLOCK0 + 12.0,)]
    for name in ("engine.device_wait_ms_per_step",
                 "gateway.between_steps_ms"):
        with pytest.raises(RuntimeError):
            _read(name, run)


def test_a_program_that_records_nothing_reads_nothing(log, monkeypatch):
    run = _run(log)
    run.steps = [(CLOCK0 + 12.0,)]
    monkeypatch.setattr(timelines, "span_log", lambda: None)
    for name in NEW:
        assert _read(name, run) is None


def test_readers_look_for_the_names_the_program_emits():
    assert timelines.STEP == spans.ENGINE_STEP
    assert timelines.DEVICE_WAIT == spans.DEVICE_WAIT
    assert timelines.STAMPS == spans.TIMELINE_STAMPS
    assert {timelines.STEP, timelines.DEVICE_WAIT} <= set(spans.SPANS)
