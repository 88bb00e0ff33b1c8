"""A configuration brings its architecture as a new file: a stand-in module
(``data/stub_arch.py``, its own weight draws, reference, counts and kernel
names), named by the config file and registered for these tests only,
drives a whole tiny run through the harness, and the traced run's checks
read its kernel names instead of the dense ones."""
import copy
import dataclasses
import gzip
import importlib.util
import json
import sys
import time
from pathlib import Path

import numpy as np
import pytest

from bench import harness, tracereduce, weights
from bench.tests.test_faults import tiny_cell

DATA = Path(__file__).resolve().parent / "data"
SEED = 2**32 + 29


@pytest.fixture
def stub(monkeypatch):
    spec = importlib.util.spec_from_file_location("bench.archs.stub",
                                                  DATA / "stub_arch.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    monkeypatch.setitem(sys.modules, "bench.archs.stub", mod)
    return mod


def _stub_cell(cell):
    return dataclasses.replace(cell, cfile=dict(cell.cfile, bench_arch="stub"))


def _run(tmp_path):
    return harness.run_cell(_stub_cell(tiny_cell()), SEED, 3.0, False,
                            t_start=time.perf_counter(), work_dir=tmp_path)


def test_a_stub_architecture_runs_correct_through_the_harness(stub,
                                                              tmp_path):
    cell = _stub_cell(tiny_cell())
    assert cell.arch is stub
    # its own draws: not the dense module's weights
    mine = stub.make_params(SEED, cell.mc)["embed"]
    dense = weights.make_params(SEED, cell.mc)["embed"]
    assert not np.array_equal(np.asarray(mine, np.float32),
                              np.asarray(dense, np.float32))
    stub.CALLS.clear()
    result, diag, run = _run(tmp_path)
    assert result["correct"] is True, result["checks"]
    assert diag["compared_tokens"] > 50
    assert stub.CALLS["make_params"] >= 1 and stub.CALLS["logit_gaps"] >= 1
    # the per-layer readers count by the stub and read its kernels
    peaks = json.loads((DATA.parent.parent / "peaks.json").read_text())
    run.peaks = peaks["devices"]["TPU v5 lite"]
    t = run.clock0 + run.window[0] + 0.5
    run.decode_calls = [(t, [40, 90])]
    run.chunk_calls = [(t, [(0, 16), (16, 9)])]
    run.trace = {"kernels": {stub.KERNELS["decode"]: 1e-3,
                             stub.KERNELS["prefill"]: 2e-3}}
    for m in ("step.mfu", "kernel.decode_attn_roofline",
              "kernel.prefill_attn_roofline"):
        assert harness.load_reader(m)(run) > 0, m
    for fn in ("decode_token_flops", "prefill_chunk_flops",
               "decode_attn_cost", "prefill_attn_cost"):
        assert stub.CALLS[fn] >= 1, fn


def test_a_stub_reference_that_disagrees_with_its_weights_fails(
        stub, tmp_path, monkeypatch):
    monkeypatch.setattr(stub, "REF_PREFIX", "")     # the dense draws
    result, diag, _ = _run(tmp_path)
    assert result["correct"] is False
    gap = result["checks"]["max_logit_gap"]
    assert gap["value"] > gap["limit"], diag


@pytest.fixture(scope="module")
def planes():
    return json.loads(gzip.decompress(
        (DATA / "qwen_chat_trace.json.gz").read_bytes()))


def test_the_trace_is_read_by_the_stub_kernel_names(stub, planes):
    from bench.archs import dense_gqa
    renamed = copy.deepcopy(planes)
    for p in renamed:
        for ln in p["lines"]:
            for ev in ln["events"]:
                for arch_name, kernel in dense_gqa.KERNELS.items():
                    ev[0] = ev[0].replace(kernel, stub.KERNELS[arch_name])
    tr = tracereduce.reduce(renamed, 1, tuple(stub.KERNELS.values()))
    assert set(tr["kernels"]) == set(stub.KERNELS.values())
    assert all(v > 0 for v in tr["kernels"].values())

    def run_of(cell):
        run = harness.Run(cell=cell, peaks={}, window=(0.0, 1.0), trace=tr)
        run.steps = [(0.5,)]
        run.decode_calls = [(0.5, [10])]
        run.chunk_calls = [(0.5, [(0, 128)])]
        return run

    dense_cell = harness.load_cell("qwen2.5-32b-l4.chat-bursty")
    harness.hooks_seen(run_of(_stub_cell(dense_cell)))
    with pytest.raises(RuntimeError, match="paged_decode_attention"):
        harness.hooks_seen(run_of(dense_cell))
