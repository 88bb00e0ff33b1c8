"""``engine.chunk_row_fill`` read from the program's
``kvrm.engine.prefill_assemble`` spans on a hand-built run, and nothing
read from a program whose spans carry no row width."""
import json
from pathlib import Path

import pytest

from bench import harness
from repro.core import spans

ROOT = Path(__file__).resolve().parents[2]
NAME = "engine.chunk_row_fill"
CLOCK0 = 1000.0          # perf_counter at gateway time 0


@pytest.fixture
def log(monkeypatch):
    fresh = spans.SpanLog()
    monkeypatch.setattr(spans, "LOG", fresh)
    return fresh


def _run():
    cell = harness.load_cell("qwen2.5-32b-l4.chat-bursty")
    return harness.Run(cell=cell, peaks={}, window=(10.0, 20.0), t_end=25.0,
                       clock0=CLOCK0)


def _assemble(log, t, **stats):
    log.spans.append((spans.PREFILL_ASSEMBLE, CLOCK0 + t, CLOCK0 + t + 0.004,
                      stats))


def _read(run):
    return harness.load_reader(NAME)(run)


def test_rows_filled_over_row_width_of_the_calls_in_the_window(log):
    run = _run()
    _assemble(log, 9.5, rows=4, tokens=512, row_width=4)     # before
    _assemble(log, 11.0, rows=3, tokens=300, row_width=4)
    _assemble(log, 12.0, rows=0, tokens=0, row_width=0)      # no call
    _assemble(log, 13.0, rows=9, tokens=1000, row_width=16)
    _assemble(log, 14.0, rows=48, tokens=3072, row_width=48)
    _assemble(log, 21.0, rows=1, tokens=10, row_width=4)     # after
    assert _read(run) == pytest.approx(100.0 * (3 + 9 + 48) / (4 + 16 + 48))


def test_no_chunk_call_in_the_window_reads_nothing(log):
    run = _run()
    _assemble(log, 11.0, rows=0, tokens=0, row_width=0)
    assert _read(run) is None


def test_spans_without_a_row_width_read_nothing(log):
    """The program before rows were packed: rows and tokens only."""
    run = _run()
    _assemble(log, 11.0, rows=3, tokens=300)
    assert _read(run) is None


def test_a_program_that_records_nothing_reads_nothing(log, monkeypatch):
    from bench import timelines
    monkeypatch.setattr(timelines, "span_log", lambda: None)
    assert _read(_run()) is None


def test_the_reader_looks_for_the_span_the_program_emits():
    mod = harness.load_reader(NAME).__globals__
    assert mod["PREFILL_ASSEMBLE"] == spans.PREFILL_ASSEMBLE
    assert spans.PREFILL_ASSEMBLE in spans.SPANS


def test_the_metric_is_read_in_both_cells():
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    per = {m["name"]: m for m in manifest["per_layer"]}
    m = per[NAME]
    assert m["workloads"] == [w["name"] for w in manifest["workloads"]]
    assert (m["layer"], m["moves"], m["unit"]) == (
        "engine control plane", "out_tok_s", "%")
