"""The trace reduction on a small trace recorded on a TPU v5e: 0.69 s of
the qwen2.5-32b-l4 chat cell (two prefill-chunk and three decode executor
runs), the device plane's op and module lines and the benchmark's host
spans, cropped from a run's trace with its window set to the crop."""
import gzip
import json
from pathlib import Path

import numpy as np
import pytest

from bench import tracereduce

DATA = Path(__file__).resolve().parent / "data" / "qwen_chat_trace.json.gz"


@pytest.fixture(scope="module")
def planes():
    return json.loads(gzip.decompress(DATA.read_bytes()))


@pytest.fixture(scope="module")
def reduced(planes):
    return tracereduce.reduce(planes, 1)


def _events(planes, line):
    (dev,) = [p for p in planes if p["name"] == "/device:TPU:0"]
    (ev,) = [ln["events"] for ln in dev["lines"] if ln["name"] == line]
    return ev


def _window(planes):
    return [(s, e) for p in planes for ln in p["lines"]
            for n, s, e in ln["events"] if n == "bench.window"][0]


def test_busy_union_against_a_timeline(planes, reduced):
    lo, hi = (int(round(t)) for t in _window(planes))
    busy = np.zeros(hi - lo, bool)               # one cell per nanosecond
    for _, s, e in _events(planes, "XLA Ops"):
        s, e = int(round(s)), int(round(e))
        busy[max(s, lo) - lo:max(min(e, hi) - lo, 0)] = True
    assert reduced["window_s"] == pytest.approx((hi - lo) * 1e-9)
    assert reduced["busy_s"] == pytest.approx(busy.sum() * 1e-9, rel=1e-9)
    # as read by hand: the device ran without a gap worth the name
    assert reduced["window_s"] == pytest.approx(0.692346541)
    assert reduced["busy_s"] == pytest.approx(0.692332801)
    idle = 1 - reduced["busy_s"] / reduced["window_s"]
    assert 0 < idle < 1e-4


def test_kernel_time_by_its_own_name(planes, reduced):
    lo, hi = _window(planes)
    for kernel, want in (("paged_decode_attention", 0.043527452),
                         ("chunked_prefill_attention", 0.273728254)):
        total = sum(min(e, hi) - max(s, lo)
                    for n, s, e in _events(planes, "XLA Ops")
                    if n.startswith(f"%{kernel}.") and e > lo and s < hi)
        assert reduced["kernels"][kernel] == pytest.approx(total * 1e-9)
        assert reduced["kernels"][kernel] == pytest.approx(want)


def test_time_per_executor_call(planes, reduced):
    lo, hi = _window(planes)
    mods = [(n, s, e) for n, s, e in _events(planes, "XLA Modules")
            if lo <= s < hi]
    dec = [e - s for n, s, e in mods if n.startswith("jit__step_flat(")]
    pre = [e - s for n, s, e in mods if n.startswith("jit__chunk_step(")]
    ex = reduced["executors"]
    assert (ex["decode"]["calls"], ex["prefill"]["calls"]) == (3, 2)
    assert ex["decode"]["seconds"] == pytest.approx(sum(dec) * 1e-9)
    assert ex["prefill"]["seconds"] == pytest.approx(sum(pre) * 1e-9)
    # about 49 ms a decode run and 295 ms a prefill-chunk run, by hand
    assert 45 < 1e3 * ex["decode"]["seconds"] / 3 < 53
    assert 290 < 1e3 * ex["prefill"]["seconds"] / 2 < 300


def test_device_ops_count_self_time(reduced):
    ops = dict(reduced["device_ops"])
    # the layer loop holds the kernels and matmuls: its own time is small
    assert ops["while"] < 0.01
    assert sum(ops.values()) == pytest.approx(reduced["busy_s"], rel=1e-3)
    assert reduced["device_ops"][0][0] == "chunked_prefill_attention"


def test_idle_gaps_named_by_host_span(reduced):
    names = [n for n, _ in reduced["idle_gaps"]]
    assert names and all(n.startswith("bench.") or n == "outside bench spans"
                         for n in names)


def test_an_op_taking_a_kernel_output_is_not_the_kernel():
    planes = [
        {"name": "/host:CPU", "lines": [{"name": "python3", "events": [
            ["bench.window", 0, 100], ["bench.step", 0, 100]]}]},
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": [
                ["%paged_decode_attention.3 = bf16[2] custom-call(%a)", 10, 30],
                ["%copy.4 = bf16[2] copy(%paged_decode_attention.3)", 40, 50]]},
            {"name": "XLA Modules", "events": [["jit__step_flat(7)", 5, 60]]}]}]
    r = tracereduce.reduce(planes, 1)
    assert r["kernels"]["paged_decode_attention"] == pytest.approx(20e-9)
    assert r["busy_s"] == pytest.approx(30e-9)
    assert r["executors"]["decode"] == {"calls": 1, "seconds": 55e-9}
    assert dict(r["idle_gaps"]) == {"bench.step": pytest.approx(70e-9)}


def test_op_name():
    assert tracereduce.op_name(
        "%paged_decode_attention.11 = bf16[48,56,128] custom-call(%x.3)") \
        == "paged_decode_attention"
    assert tracereduce.op_name("%while.13 = (s32[]) while(%t)") == "while"
    assert tracereduce.op_name("%copy-start = (f32[64]) copy-start(%a)") \
        == "copy-start"
