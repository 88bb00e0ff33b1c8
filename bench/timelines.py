"""What the program records of itself, for the per-layer metrics of a traced
run: request timelines and the host spans it captured while the profiler
was on, both kept in memory by ``repro.core.spans.LOG``.

Timelines are on the gateway clock, like the run's window and ``t_end``;
spans on ``time.perf_counter``, which ``Run.in_window`` reads. Against a
program that keeps neither, every function here returns None, so a reader
of a metric the program cannot give leaves it out instead of raising.
"""
from __future__ import annotations

from typing import List, Optional

# the program's names, as the readers look for them; the tests check that
# the program emits them under these names
STEP = "kvrm.engine.step"
DEVICE_WAIT = "kvrm.engine.device_wait"
STAMPS = ("arrival", "t_release", "t_admit", "t_prompt_done", "t_first")


def span_log():
    """The program's span log, or None when it keeps none."""
    try:
        from repro.core import spans
    except ImportError:
        return None
    return getattr(spans, "LOG", None)


def due_stamps(run) -> Optional[List[List[float]]]:
    """For each request due in the window (the requests ``ttft_p90_ms``
    reads): its ``STAMPS``, each missing one counted to ``run.t_end`` and
    none past it, so that consecutive stamps split the time from its
    submission to its first token, or to the drain's end. A request
    admission rejected has no timeline: all of it counts from its
    submission. None when the program keeps no timelines."""
    log = span_log()
    if log is None:
        return None
    t0, t1, end = run.window[0], run.window[1], run.t_end
    out = []
    for r in run.recs.values():
        if r.due is None or not t0 <= r.due < t1:
            continue
        tl = None if r.rejected or r.sent is None else log.timeline(r.rid)
        tl = tl or {}
        start = tl.get("arrival")
        if start is None:
            start = r.sent if r.sent is not None else r.due
        stamps, last = [], min(start, end)
        for k in STAMPS:
            t = start if k == "arrival" else tl.get(k)
            last = max(last, end if t is None else min(t, end))
            stamps.append(last)
        out.append(stamps)
    return out


def stage(run, start: str, end: str) -> Optional[List[float]]:
    """Seconds from stamp ``start`` to stamp ``end`` of each request due
    in the window (``due_stamps``); None with no timelines to read."""
    rows = due_stamps(run)
    if not rows:
        return None
    i, j = STAMPS.index(start), STAMPS.index(end)
    return [row[j] - row[i] for row in rows]


def due_timelines(run) -> Optional[List[dict]]:
    """The logged timelines of the requests due in the window."""
    log = span_log()
    if log is None:
        return None
    t0, t1 = run.window
    return [log.timeline(r.rid) for r in run.recs.values()
            if r.due is not None and t0 <= r.due < t1 and not r.rejected
            and r.sent is not None and log.timeline(r.rid) is not None]


def window_spans(run, name: str) -> Optional[list]:
    """(start, end, stats) of the program's ``name`` spans starting in the
    window, in order; None when the program keeps no spans. Raises where
    the window held engine steps but the program recorded no step span:
    a renamed or silent span would otherwise leave its metrics out."""
    log = span_log()
    if log is None:
        return None
    out = sorted((t0, t1, st) for n, t0, t1, st in log.spans
                 if n == name and run.in_window(t0))
    if name == STEP and not out and \
            any(run.in_window(s[0]) for s in run.steps):
        raise RuntimeError(f"engine steps in the window but no {STEP} "
                           "span recorded")
    return out
