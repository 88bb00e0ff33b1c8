"""What a traced run reads from the program's internals exists under the
names the benchmark looks for, and a traced window in which a hook read
nothing while the program worked stops the run instead of leaving its
metrics silent."""
import copy
import gzip
import json
from pathlib import Path

import numpy as np
import pytest

from bench import harness, tracereduce

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data" / "qwen_chat_trace.json.gz"


@pytest.fixture(scope="module")
def gw():
    from bench.tests.test_faults import tiny_cell
    from repro import serving
    cell = tiny_cell()
    return serving.build(harness.model_config(cell.cfile), gateway=True,
                         **cell.cfile["engine"])


def test_engine_has_the_hooks_and_executor_names(gw):
    eng = gw.engines[0]
    for h in harness.ENGINE_HOOKS:
        assert hasattr(eng, h), h
    assert hasattr(gw, "_task")
    assert eng._step_fn.__name__ == tracereduce.EXECUTORS["decode"]
    assert eng._chunk_fn.__name__ == tracereduce.EXECUTORS["prefill"]
    d, cd = eng._pdescr, eng._cdescr
    for a in (d.seq_lens, d.slot_active, cd.start_pos, cd.n_valid):
        assert isinstance(a, np.ndarray) and len(a) == eng.e.batch


@pytest.mark.parametrize("kernel", tracereduce.KERNELS)
def test_kernels_carry_the_names_the_trace_is_read_by(kernel):
    src = "".join(p.read_text() for p in
                  (ROOT / "src/repro/kernels").glob("*.py"))
    assert f'name="{kernel}"' in src


def _run(trace):
    cell = harness.load_cell("qwen2.5-32b-l4.chat-bursty")
    run = harness.Run(cell=cell, peaks={}, window=(0.0, 1.0))
    run.steps = [(0.5,)]
    run.decode_calls = [(0.5, [10])]
    run.chunk_calls = [(0.5, [(0, 128)])]
    run.trace = trace
    return run


@pytest.fixture(scope="module")
def reduced():
    planes = json.loads(gzip.decompress(DATA.read_bytes()))
    return tracereduce.reduce(planes, 1)


def test_a_consistent_window_passes(reduced):
    harness.hooks_seen(_run(reduced))


@pytest.mark.parametrize("silent", ["decode_module", "prefill_module",
                                    "decode_kernel", "decode_hook",
                                    "unknown_module", "no_trace"])
def test_a_silent_hook_stops_the_run(reduced, silent):
    tr = copy.deepcopy(reduced)
    run = _run(tr)
    if silent == "decode_module":
        tr["executors"]["decode"]["calls"] = 0
    elif silent == "prefill_module":
        tr["executors"]["prefill"]["calls"] = 0
    elif silent == "decode_kernel":
        tr["kernels"]["paged_decode_attention"] = 0.0
    elif silent == "decode_hook":
        run.decode_calls = []
    elif silent == "unknown_module":
        tr["other_modules_s"] = 0.5 * tr["window_s"]
    else:
        run.trace = None
    with pytest.raises(RuntimeError):
        harness.hooks_seen(run)
