#!/usr/bin/env python3
"""A traced run of one cell, read by the program's own spans.

    python3 bench/spantrace.py --workload <cell> --seed <n> --seconds <s>

Runs the cell once as ``run_cell.py --trace 1`` does and prints its result
line, then one JSON line that reads the same profiler trace by what the
program marks in it:

* ``idle_gaps_program``: device idle time in the window, named by the
  innermost ``kvrm.*`` host span the host was in at the gap's midpoint, or
  ``between_engine_steps`` outside every one; ``longest_gaps``, the five
  longest gaps (seconds, name, start in the window);
* ``spans``: count, mean and longest milliseconds of each ``kvrm.*`` span
  in the window, and ``spans_per_step``, the most spans one step held;
* ``ttft_split``: per request due in the window, the client's lateness
  (sent - due) and its timeline's stages, at p50 and p90, with
  ``partition_max_ms``, how far the stages' sum lies from each served
  request's first token less its submission, both as its client saw them.

The functions below take the planes ``load`` returns: events
``(name, start_ns, end_ns, stats)``, with stats kept only for ``kvrm.*``
host spans. (On a TPU v5e an ``XLA Ops`` event carries its HLO text as its
name and only its device offset and duration as stats: no scope path.)
"""
from __future__ import annotations

import bisect
import json
import sys
import time
from pathlib import Path
from typing import Dict, List, Optional

T_START = time.perf_counter()
ROOT = Path(__file__).resolve().parent.parent
if __name__ == "__main__":
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from bench import stats, timelines, tracereduce  # noqa: E402

PREFIX = "kvrm."
BETWEEN = "between_engine_steps"


def load(path) -> List[dict]:
    """Planes as plain data; host events named ``kvrm.*`` keep their
    stats."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        host = not tracereduce.DEVICE_PLANE.match(plane.name)
        lines = [{"name": line.name,
                  "events": [(ev.name, ev.start_ns, ev.end_ns,
                              dict(ev.stats) if host and
                              ev.name.startswith(PREFIX) else None)
                             for ev in line.events]}
                 for line in plane.lines]
        out.append({"name": plane.name, "lines": lines})
    return out


def _window(planes):
    for p in planes:
        if not tracereduce.DEVICE_PLANE.match(p["name"]):
            for ln in p["lines"]:
                for n, s, e, *_ in ln["events"]:
                    if n == "bench.window":
                        return s, e
    return None


def program_spans(planes) -> List[tuple]:
    """Every host ``kvrm.*`` span, (name, start_ns, end_ns, stats), by
    start."""
    return sorted(((n, s, e, (rest or [None])[0]) for p in planes
                   if not tracereduce.DEVICE_PLANE.match(p["name"])
                   for ln in p["lines"] for n, s, e, *rest in ln["events"]
                   if n.startswith(PREFIX)), key=lambda x: (x[1], -x[2]))


def _innermost(sp, starts, t) -> str:
    i = bisect.bisect_right(starts, t)
    for n, s, e, _ in reversed(sp[max(0, i - 64):i]):
        if e > t:
            return n
    return BETWEEN


def _rank(d: Dict[str, float], k: int) -> list:
    return sorted(([n, v / k] for n, v in d.items()), key=lambda x: -x[1])


def reduce_program(planes, n_chips: int = 1) -> Optional[dict]:
    """The window's idle gaps by program span, and the program spans'
    counts and lengths."""
    win = _window(planes)
    devs = [p for p in planes
            if tracereduce.DEVICE_PLANE.match(p["name"])][:n_chips]
    if win is None or not devs:
        return None
    lo, hi = win
    sp = program_spans(planes)
    starts = [s for _, s, _, _ in sp]
    gaps: Dict[str, float] = {}
    longest: List[list] = []            # [seconds, span, start in window]
    for dev in devs:
        ops = {ln["name"]: ln["events"] for ln in dev["lines"]}.get(
            "XLA Ops", [])
        iv = [(max(s, lo), min(e, hi)) for _, s, e, _ in ops
              if min(e, hi) > max(s, lo)]
        merged = tracereduce._union(iv)
        edges = [lo] + [x for se in merged for x in se] + [hi]
        for gs, ge in zip(edges[::2], edges[1::2]):
            if ge > gs:
                name = _innermost(sp, starts, (gs + ge) / 2)
                gaps[name] = gaps.get(name, 0.0) + (ge - gs) * 1e-9
                longest.append([(ge - gs) * 1e-9, name, (gs - lo) * 1e-9])
    k = len(devs)
    in_win = [x for x in sp if lo <= x[1] < hi]
    per_name: Dict[str, list] = {}
    for n, s, e, _ in in_win:
        per_name.setdefault(n, []).append((e - s) * 1e-6)
    win_starts = [x[1] for x in in_win]
    most = 0
    for i, st in enumerate(in_win):
        if st[0] == timelines.STEP:
            j = bisect.bisect_right(win_starts, st[2])
            most = max(most, sum(1 for y in in_win[i + 1:j]
                                 if y[2] <= st[2]))
    return {
        "idle_gaps_program": _rank(gaps, k),
        "longest_gaps": sorted(longest, reverse=True)[:5],
        "spans": {n: {"count": len(v), "mean_ms": sum(v) / len(v),
                      "max_ms": max(v)}
                  for n, v in sorted(per_name.items())},
        "spans_per_step": most,
    }


def ttft_split(run) -> Optional[dict]:
    """The window's first-token times split by stage, from the program's
    timelines and the client records."""
    rows = timelines.due_stamps(run)
    if not rows:
        return None
    t0, t1 = run.window
    due = [r for r in run.recs.values()
           if r.due is not None and t0 <= r.due < t1]
    late = [max(0.0, (r.sent if r.sent is not None else run.t_end) - r.due)
            for r in due]
    names = ["queue", "admit_wait", "prefill", "first_token_lag"]
    cols = {"client_late": late}
    for i, n in enumerate(names):
        cols[n] = [row[i + 1] - row[i] for row in rows]
    out = {n: {"p50_ms": 1e3 * stats.percentile(v, 50),
               "p90_ms": 1e3 * stats.percentile(v, 90)}
           for n, v in cols.items()}
    # the stages against the client's own first token less its submission
    log = timelines.span_log()
    worst = 0.0
    for r in due:
        tl = None if r.rejected else log.timeline(r.rid)
        if not tl or tl["t_first"] is None or not r.times:
            continue
        st = [tl[k] for k in timelines.STAMPS]
        parts = sum(b - a for a, b in zip(st, st[1:]))
        worst = max(worst, abs(parts - (r.times[0] - r.sent)))
    out["partition_max_ms"] = 1e3 * worst
    return out


def main(argv=None) -> None:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    from bench import harness
    cell = harness.load_cell(args.workload)
    harness.open_chips(cell.chips, "spantrace")
    extra = {}
    reduce_dir = tracereduce.reduce_dir

    def reduce_both(trace_dir, n_chips=1, **kw):
        # read the trace a second time before the run deletes it
        files = sorted(Path(trace_dir).rglob("*.xplane.pb"))
        if files:
            t = time.perf_counter()
            extra.update(reduce_program(load(files[-1]), n_chips) or {})
            extra["reduce_s"] = time.perf_counter() - t
        return reduce_dir(trace_dir, n_chips, **kw)

    tracereduce.reduce_dir = reduce_both
    work = ROOT / "bench" / "_work"
    work.mkdir(exist_ok=True)
    result, diag, run = harness.run_cell(cell, args.seed, args.seconds, True,
                                         t_start=T_START, work_dir=work)
    harness.print_result(result, diag)
    extra["ttft_split"] = ttft_split(run)
    print(json.dumps({"program_trace": extra}), flush=True)


if __name__ == "__main__":
    main()
