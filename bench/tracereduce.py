"""From a profiler trace to the numbers the per-layer metrics read.

The JAX profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``.
Each TPU is a plane named ``/device:TPU:<n>``; on it the line ``XLA Ops``
holds one event per device operation (a Pallas kernel appears under the
name its ``pallas_call`` gives it) and ``XLA Modules`` one event per
executable run, named after the jitted function. Host threads are lines of
the ``/host:CPU`` plane; the benchmark's own spans there are named
``bench.*``. All events are on one clock, in nanoseconds.

``reduce`` returns, for the ``bench.window`` span: its length, the union of
device-op intervals (averaged over the chips), device time by kernel, by
executor and by operation, and the idle gaps between device ops,
attributed to the innermost benchmark span the host was in.
"""
from __future__ import annotations

import bisect
import re
from pathlib import Path
from typing import Dict, List, Optional, Tuple

# the dense decoder's decode and prefill-chunk attention kernels
# (``archs/dense_gqa.py``); a cell reads those its architecture module names
KERNELS = ("paged_decode_attention", "chunked_prefill_attention")
# executor -> the jitted function whose module runs it (``jit_<fn>(<id>)``)
EXECUTORS = {"decode": "_step_flat", "prefill": "_chunk_step"}
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
_SUFFIX = re.compile(r"\.\d+$")


def load_planes(path) -> List[dict]:
    """Planes as plain data: name and lines of (name, start_ns, end_ns)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    out = []
    for plane in pd.planes:
        lines = []
        for line in plane.lines:
            lines.append({"name": line.name,
                          "events": [(ev.name, ev.start_ns, ev.end_ns)
                                     for ev in line.events]})
        out.append({"name": plane.name, "lines": lines})
    return out


def _union(iv: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(s, e, lo, hi):
    return max(s, lo), min(e, hi)


def op_name(event_name: str) -> str:
    """An XLA op event is named by its HLO text, ``%fusion.12 = bf16[...]
    fusion(...)``: the instruction's own name without ``%`` and its
    numeric suffix (``fusion``, ``while``, ``paged_decode_attention``)."""
    return _SUFFIX.sub("", event_name.split(" = ", 1)[0].lstrip("%"))


def _self_times(iv) -> Dict[str, float]:
    """Seconds per op name, each op's time less that of the ops nested in
    it (a ``while`` holds its body's ops)."""
    out: Dict[str, float] = {}
    stack: List[list] = []          # [name, start, end, child time]

    def close(item):
        out[item[0]] = out.get(item[0], 0.0) + (item[2] - item[1] - item[3]) * 1e-9

    for name, s, e in sorted(iv, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][2] <= s:
            close(stack.pop())
        if stack:
            stack[-1][3] += min(e, stack[-1][2]) - s
        stack.append([name, s, e, 0])
    while stack:
        close(stack.pop())
    return out


def reduce(planes: List[dict], n_chips: int = 1,
           kernels: Tuple[str, ...] = KERNELS) -> Optional[dict]:
    spans = sorted(((n, s, e) for p in planes
                    if not DEVICE_PLANE.match(p["name"])
                    for ln in p["lines"] for n, s, e in ln["events"]
                    if n.startswith("bench.")), key=lambda x: x[1])
    win = [(s, e) for n, s, e in spans if n == "bench.window"]
    spans = [x for x in spans if x[0] != "bench.window"]
    starts = [s for _, s, _ in spans]
    devs = [p for p in planes if DEVICE_PLANE.match(p["name"])][:n_chips]
    if not win or not devs:
        return None
    lo, hi = win[0]
    busy = 0.0
    kernel_s: Dict[str, float] = {k: 0.0 for k in kernels}
    ops: Dict[str, float] = {}
    execs = {k: {"calls": 0, "seconds": 0.0} for k in EXECUTORS}
    other = 0.0                 # modules of no known executor
    gaps: Dict[str, float] = {}
    for dev in devs:
        lines = {ln["name"]: ln["events"] for ln in dev["lines"]}
        iv = []
        for n, s, e in lines.get("XLA Ops", []):
            s, e = _clip(s, e, lo, hi)
            if e > s:
                iv.append((op_name(n), s, e))
        for name, d in _self_times(iv).items():
            ops[name] = ops.get(name, 0.0) + d
        for name, s, e in iv:
            if name in kernel_s:
                kernel_s[name] += (e - s) * 1e-9
        merged = _union([(s, e) for _, s, e in iv])
        busy += sum(e - s for s, e in merged) * 1e-9
        for n, s, e in lines.get("XLA Modules", []):
            if not lo <= s < hi:
                continue
            for k, fn in EXECUTORS.items():
                if n.startswith("jit_" + fn + "("):
                    execs[k]["calls"] += 1
                    execs[k]["seconds"] += (e - s) * 1e-9
                    break
            else:
                other += (min(e, hi) - s) * 1e-9
        edges = [lo] + [x for se in merged for x in se] + [hi]
        for gs, ge in zip(edges[::2], edges[1::2]):
            if ge > gs:
                name = _host_activity(spans, starts, (gs + ge) / 2)
                gaps[name] = gaps.get(name, 0.0) + (ge - gs) * 1e-9
    k = len(devs)
    rank = lambda d: sorted(([n, v / k] for n, v in d.items()),
                            key=lambda x: -x[1])
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": busy / k,
        "kernels": {n: v / k for n, v in kernel_s.items()},
        "executors": {n: {"calls": v["calls"] // k, "seconds": v["seconds"] / k}
                      for n, v in execs.items()},
        "other_modules_s": other / k,
        "device_ops": rank(ops),
        "idle_gaps": rank(gaps),
    }


def _host_activity(spans, starts, t) -> str:
    """The innermost benchmark span (latest start) covering time t;
    ``spans`` sorted by start, ``starts`` their starts."""
    i = bisect.bisect_right(starts, t)
    for n, s, e in reversed(spans[max(0, i - 64):i]):
        if e > t:
            return n
    return "outside bench spans"


def reduce_dir(trace_dir, n_chips: int = 1,
               kernels: Tuple[str, ...] = KERNELS) -> Optional[dict]:
    files = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not files:
        return None
    return reduce(load_planes(files[-1]), n_chips, kernels)


def roofline_share(run, kernel: str, costs) -> Optional[float]:
    """Least time for ``costs`` [(flops, bytes)] at the chip's peaks over
    the kernel's traced device time, in percent (None with nothing to
    read)."""
    secs = ((run.trace or {}).get("kernels") or {}).get(kernel, 0.0)
    pf, pb = run.peaks.get("bf16_flops_per_s"), run.peaks.get("hbm_bytes_per_s")
    if not secs or not costs or not pf or not pb:
        return None
    fl = sum(c[0] for c in costs)
    by = sum(c[1] for c in costs)
    least = max(fl / pf, by / pb)
    return 100.0 * least / (secs * run.chips) if least else None
