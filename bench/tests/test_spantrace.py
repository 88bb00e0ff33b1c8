"""The trace read by the program's own spans (``bench/spantrace.py``): idle
gaps named by the innermost ``kvrm.*`` host span, on small synthetic
planes; and the benchmark's own reduction of the committed chip trace,
unchanged."""
import gzip
import json
from pathlib import Path

import pytest

from bench import spantrace, tracereduce

DATA = Path(__file__).resolve().parent / "data"


def _op(name, s, e):
    return [f"%{name} = bf16[2] fusion(%a)", s, e, None]


def _planes():
    """A 100-ns window: two engine steps (0-40, 50-95); in the first, a
    decode dispatch and a device wait; a gateway release between them."""
    host = [["bench.window", 0, 100, None],
            ["kvrm.engine.step", 0, 40, {"step_num": 0}],
            ["kvrm.engine.decode_dispatch", 2, 11, {}],
            ["kvrm.engine.readback", 20, 38, {"emits": 2}],
            ["kvrm.engine.device_wait", 20, 36, {}],
            ["kvrm.gateway.release", 41, 44, {"released": 1}],
            ["kvrm.engine.step", 50, 95, {"step_num": 1}],
            ["bench.step", 0, 95, None]]
    ops = [_op("fusion.1", 0, 8),               # idle 8-12: dispatch
           _op("fusion.2", 12, 30),
           _op("dynamic-slice_bitcast_fusion.3", 30, 34),
           # idle 34-46: midpoint 40, between steps
           _op("fusion.4", 46, 47),
           # idle 47-60: midpoint 53.5, in step 1
           _op("fusion.5", 60, 100)]
    return [{"name": "/host:CPU", "lines": [{"name": "python3",
                                             "events": host}]},
            {"name": "/device:TPU:0", "lines": [
                {"name": "XLA Ops", "events": ops},
                {"name": "XLA Modules", "events": [
                    ["jit__step_flat(1)", 0, 100, None]]}]}]


def test_idle_gaps_named_by_program_span():
    r = spantrace.reduce_program(_planes(), 1)
    gaps = dict(r["idle_gaps_program"])
    assert gaps == {"kvrm.engine.decode_dispatch": pytest.approx(4e-9),
                    spantrace.BETWEEN: pytest.approx(12e-9),
                    "kvrm.engine.step": pytest.approx(13e-9)}
    # the same idle time as the benchmark's own reduction reads
    three = [{"name": p["name"], "lines": [
        {"name": ln["name"], "events": [ev[:3] for ev in ln["events"]]}
        for ln in p["lines"]]} for p in _planes()]
    old = tracereduce.reduce(three, 1)
    assert sum(gaps.values()) == pytest.approx(
        old["window_s"] - old["busy_s"])


def test_span_counts_in_the_window():
    r = spantrace.reduce_program(_planes(), 1)
    assert r["spans"]["kvrm.engine.step"]["count"] == 2
    assert r["spans"]["kvrm.engine.step"]["max_ms"] == pytest.approx(45e-6)
    assert r["spans"]["kvrm.engine.device_wait"]["mean_ms"] == \
        pytest.approx(16e-6)
    assert r["spans_per_step"] == 3


def test_the_benchmark_reduction_of_the_chip_trace_is_unchanged():
    planes = json.loads(gzip.decompress(
        (DATA / "qwen_chat_trace.json.gz").read_bytes()))
    want = json.loads((DATA / "qwen_chat_reduced.json").read_text())
    assert tracereduce.reduce(planes, 1) == want


def test_longest_gaps_are_named_and_placed():
    r = spantrace.reduce_program(_planes(), 1)
    assert [g[1] for g in r["longest_gaps"]] == [
        "kvrm.engine.step", spantrace.BETWEEN, "kvrm.engine.decode_dispatch"]
    assert r["longest_gaps"][0][0] == pytest.approx(13e-9)
    assert r["longest_gaps"][0][2] == pytest.approx(47e-9)
