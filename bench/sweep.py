#!/usr/bin/env python3
"""Find the knee of an open-loop cell: one engine, several fixed rates.

    python3 bench/sweep.py --workload <cell> --rates 1,2,3 --seconds 30 --seed 1

Builds the cell's engine once and drives its traffic mix at each rate in
turn (the mix's ``rate_per_s`` replaced), each with its own warm-up
(``--warmup-s``, long enough to fill the slots at the knee), window and
drain. Per rate it prints the end-to-end metrics, the gateway's backlog
(requests submitted and not finished) when the window opened and when it
closed, and the median time to first token over the window and over its
first and last thirds. The knee is the highest rate at which neither the
backlog nor the median time to first token runs away (``knee``); a cell
below it runs at 0.8 x the knee.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import json  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent


def sustained(row: dict, base_ttft_ms: float) -> bool:
    """No runaway queue: the backlog at the close at most a quarter (and 4
    requests) above the one at the open, and the window's median
    first-token time within twice ``base_ttft_ms``, the lowest rate's.
    (The window's thirds are not compared: the schedule is fixed, so they
    hold prompts of different lengths, and a prompt's first token waits on
    its own chunks.)"""
    return (row["backlog_close"] <= 1.25 * row["backlog_open"] + 4
            and row["ttft_p50_ms"] <= 2.0 * base_ttft_ms)


def knee(rows) -> float:
    """The highest rate such that it and every lower rate are sustained
    (0 when the lowest is not)."""
    rows = sorted(rows, key=lambda r: r["rate"])
    best = 0.0
    for row in rows:
        if not sustained(row, rows[0]["ttft_p50_ms"]):
            break
        best = row["rate"]
    return best


async def _sweep(gw, cell, rates, seconds, seed, harness, warmup_s):
    out = []
    samples = []

    async def sample_backlog():
        while True:
            samples.append((gw.now(), gw.outstanding()))
            await asyncio.sleep(0.25)

    def backlog_at(t):
        return ([n for ts, n in samples if ts <= t] or [0])[-1]

    sampler = asyncio.create_task(sample_backlog())
    for i, rate in enumerate(rates):
        cell.mix = dict(cell.mix, rate_per_s=rate, warmup_s=warmup_s)
        run = harness.Run(cell=cell, peaks={}, rid_base=i * 100_000)
        t0 = time.perf_counter()
        await harness.drive(gw, cell, seed, seconds, run,
                            harness._Trace(None), t0, close=False)
        w0, w1 = run.window
        due = sorted((r for r in run.recs.values() if w0 <= r.due < w1),
                     key=lambda r: r.due)
        ttft = [(r.times[0] - r.due) if r.times else float("inf")
                for r in due]
        third = max(1, len(ttft) // 3)
        late = [r for r in due if not r.times or r.times[0] >= w1]
        row = {"rate": rate, "due": len(due),
               "backlog_open": backlog_at(w0), "backlog_close": backlog_at(w1),
               "no_first_token_at_close": len(late),
               "ttft_p50_ms": 1e3 * statistics.median(ttft),
               "ttft_first_third_p50_ms": 1e3 * statistics.median(ttft[:third]),
               "ttft_last_third_p50_ms": 1e3 * statistics.median(ttft[-third:]),
               **harness.end_to_end(run, 0.0)}
        out.append(row)
        print(json.dumps(row), flush=True)
        for r in run.recs.values():   # clear the tail before the next rate
            if r.sent is not None and not r.finish and not r.rejected:
                gw.cancel(r.rid)
    sampler.cancel()
    print(json.dumps({"knee": knee(out)}), flush=True)
    return out


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--warmup-s", type=float, default=60.0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench import harness, weights
    from repro import serving
    cell = harness.load_cell(args.workload)
    harness.open_chips(cell.chips, "sweep")
    if cell.mix["kind"] != "open":
        sys.exit("sweep: only an open-loop mix has a rate")
    params = weights.make_params(args.seed, cell.mc)
    gw = serving.build(harness.model_config(cell.cfile), gateway=True,
                       params=params, **cell.cfile["engine"])
    del params
    rates = [float(r) for r in args.rates.split(",")]
    asyncio.run(_sweep(gw, cell, rates, args.seconds, args.seed, harness,
                       args.warmup_s))


if __name__ == "__main__":
    main()
