"""End-to-end metrics from client-side records, over all requests.

A record holds a request's due time, the gateway-clock time of each of its
streamed tokens, and whether admission rejected it. Times are seconds on
the gateway clock; the window is ``[t0, t1)``.
"""
from __future__ import annotations

import math
from typing import Iterable, List


def percentile(values: Iterable[float], q: float) -> float:
    """Nearest-rank percentile: the smallest value with at least q% of the
    sample at or below it."""
    v = sorted(values)
    if not v:
        raise ValueError("percentile of an empty sample")
    return v[max(0, math.ceil(q / 100.0 * len(v)) - 1)]


def out_tok_s(records, t0: float, t1: float) -> float:
    """Every token streamed in the window over the window's length."""
    n = sum(1 for r in records for t in r.times if t0 <= t < t1)
    return n / (t1 - t0)


def itl_gaps(records, t0: float, t1: float) -> List[float]:
    """All gaps between consecutive tokens of a request whose later token
    lands in the window."""
    return [b - a for r in records for a, b in zip(r.times, r.times[1:])
            if t0 <= b < t1]


def ttft_values(records, t0: float, t1: float, t_end: float) -> List[float]:
    """Time to first token from the due time, for every request due in the
    window. One rejected, or without a first token by ``t_end``, counts as
    ``t_end - due``: it sits in the tail, never below the served ones."""
    out = []
    for r in records:
        if r.due is None or not t0 <= r.due < t1:
            continue
        if r.rejected or not r.times or r.times[0] > t_end:
            out.append(max(t_end - r.due, 0.0))
        else:
            out.append(r.times[0] - r.due)
    return out
