"""The one traffic generator. A mix is a data file under ``traffic/``.

Every seed gets the same requests: lengths are stratified quantiles of
their lognormal, clipped; burst windows take stratified quantiles of the
Pareto weights of the repository's bursty process (``data/traces.py``
``bursty_arrivals``, copied here); tenants take Zipf shares; and the order
of all of these, the pairing of prompt with output length and the arrival
times come from one fixed schedule generator. The seed draws the token ids
(uniform over the vocabulary), as it draws the weights.

The schedule is fixed because the order alone moves the results: a decode
step without a prefill chunk beside it is several times shorter, so which
of a run's few dozen requests arrive together set the chat cell's token
rate by tens of percent. With a fixed schedule, runs on different seeds do
the same work on other tokens.

Two kinds of mix:

* ``open``: requests due at fixed times at ``rate_per_s``; a Poisson
  warm-up at the same rate before the window and a Poisson tail after it.
* ``closed``: ``clients`` callers, each sending its next request when the
  last one finishes. Each caller's first request carries a stratified
  share of its output as history in its prompt, so the window opens with
  contexts spread over their whole range.

Given ``max_context`` (the configuration's positions), an output is cut so
that prompt and output together stay within it.
"""
from __future__ import annotations

import json
import math
from dataclasses import dataclass
from pathlib import Path
from statistics import NormalDist
from typing import List, Optional

import numpy as np

TRAFFIC_DIR = Path(__file__).resolve().parent / "traffic"
SCHEDULE = 0        # seed of the fixed schedule generator


@dataclass
class Item:
    """One request as the client will send it."""
    rid: int
    prompt: np.ndarray
    gen_len: int
    tenant: str = "t0"
    due: Optional[float] = None      # seconds from the window's start
    phase: str = "window"            # warmup | window | tail | first
    client: int = -1


def load_mix(name: str, path: Optional[Path] = None) -> dict:
    path = path or TRAFFIC_DIR / f"{name}.json"
    with open(path) as f:
        mix = json.load(f)
    if mix.get("kind") not in ("open", "closed"):
        raise ValueError(f"traffic {name}: kind must be open or closed")
    return mix


def lognormal_set(n: int, spec: dict) -> np.ndarray:
    """n stratified quantiles of lognormal(median, sigma), clipped."""
    nd = NormalDist()
    z = np.array([nd.inv_cdf((i + 0.5) / n) for i in range(n)])
    x = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def pareto_window_weights(n_windows: int, burstiness: float) -> np.ndarray:
    """Stratified quantiles of ``pareto(burstiness / 2) + 0.1`` (numpy's
    Lomax form, as in the repository's ``bursty_arrivals``), normalized."""
    a = burstiness / 2
    p = (np.arange(n_windows) + 0.5) / n_windows
    w = (1 - p) ** (-1 / a) - 1 + 0.1
    return w / w.sum()


def largest_remainder(total: int, shares: np.ndarray) -> np.ndarray:
    raw = shares * total
    out = np.floor(raw).astype(np.int64)
    order = np.argsort(-(raw - out), kind="stable")
    out[order[:total - out.sum()]] += 1
    return out


def zipf_tenants(n: int, n_tenants: int, s: float, rng) -> List[str]:
    shares = 1.0 / np.arange(1, n_tenants + 1) ** s
    counts = largest_remainder(n, shares / shares.sum())
    names = [f"t{k}" for k, c in enumerate(counts) for _ in range(c)]
    return [names[i] for i in rng.permutation(n)]


def bursty_times(n: int, seconds: float, n_windows: int, burstiness: float,
                 rng) -> np.ndarray:
    """n arrival offsets in [0, seconds): fixed window weights in a seeded
    order, stratified uniform placement inside each window."""
    w = pareto_window_weights(n_windows, burstiness)[rng.permutation(n_windows)]
    counts = largest_remainder(n, w)
    width = seconds / n_windows
    out = []
    for wi, c in enumerate(counts):
        u = (np.arange(c) + rng.uniform(size=c)) / max(c, 1)
        out.append(width * (wi + u))
    return np.sort(np.concatenate(out))


def poisson_times(seconds: float, rate: float, rng) -> np.ndarray:
    n = int(round(seconds * rate))
    gaps = rng.exponential(1.0 / rate, size=n)
    t = np.cumsum(gaps) * (seconds / max(gaps.sum(), 1e-9))
    return t - t[0] if n else t


def _tokens(rng, n: int, vocab: int) -> np.ndarray:
    return rng.integers(0, vocab, size=int(n)).astype(np.int32)


def _fit(prompt_len: int, out: int, max_context: Optional[int]) -> int:
    if max_context is None:
        return int(out)
    return int(max(1, min(out, max_context - prompt_len)))


def open_loop(mix: dict, seconds: float, seed: int, vocab: int,
              max_context: Optional[int] = None) -> List[Item]:
    """Warm-up, window and tail requests of an open mix; ``due`` is seconds
    from the window's start (negative during the warm-up)."""
    sched = np.random.default_rng(SCHEDULE)
    rng = np.random.default_rng([int(seed), 1])
    rate = float(mix["rate_per_s"])
    n = max(1, int(round(rate * seconds)))
    phases = [("window", bursty_times(n, seconds, mix["windows_per_run"],
                                      mix["burstiness"], sched))]
    # warm-up before and tail after: Poisson at the same rate
    for phase, span, base in (("warmup", mix["warmup_s"], -mix["warmup_s"]),
                              ("tail", mix["tail_s"], seconds)):
        phases.append((phase, base + poisson_times(span, rate, sched)))
    items = []
    for phase, times in phases:
        m = len(times)
        pl = lognormal_set(m, mix["prompt"])[sched.permutation(m)]
        ol = lognormal_set(m, mix["output"])[sched.permutation(m)]
        tenants = zipf_tenants(m, mix["tenants"], mix["tenant_zipf"], sched)
        for j in range(m):
            items.append(Item(rid=len(items), prompt=_tokens(rng, pl[j], vocab),
                              gen_len=_fit(pl[j], ol[j], max_context),
                              tenant=tenants[j],
                              due=float(times[j]), phase=phase))
    return items


def closed_loop(mix: dict, seed: int, vocab: int,
                max_context: Optional[int] = None) -> List[List[Item]]:
    """Per client, its queue of requests; the first carries history."""
    sched = np.random.default_rng(SCHEDULE)
    rng = np.random.default_rng([int(seed), 2])
    c, k = int(mix["clients"]), int(mix["requests_per_client"])
    n = c * k
    pl = lognormal_set(n, mix["prompt"])[sched.permutation(n)]
    ol = lognormal_set(n, mix["output"])[sched.permutation(n)]
    frac = ((np.arange(c) + 0.5) / c)[sched.permutation(c)]
    queues, rid = [], 0
    for ci in range(c):
        q = []
        for j in range(k):
            i = ci * k + j
            out = _fit(pl[i], ol[i], max_context)
            hist = int(frac[ci] * out) if j == 0 else 0
            q.append(Item(rid=rid, prompt=_tokens(rng, pl[i] + hist, vocab),
                          gen_len=max(1, out - hist), tenant=f"c{ci}",
                          phase="first" if j == 0 else "window", client=ci))
            rid += 1
        queues.append(q)
    return queues
