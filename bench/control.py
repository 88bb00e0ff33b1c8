#!/usr/bin/env python3
"""Readings that set a cell's correctness limit, on the chip.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds 10

For each seed, one run of the cell as ``run_cell.py`` makes it (the same
build, traffic and window, at the cell's own size), then the reference
over the window's served tokens twice: in float32, giving the program's
widest logit gap, and with float8 (e4m3) operands, giving the control's,
the gap of the token the lower precision puts first, put through the same
comparison as the program (``control_correct`` has to come out false).
Prints one JSON line per seed and a summary: ``lower`` is the widest
program gap over the seeds, ``upper`` the narrowest control gap. The
benchmark's own runs never run the control.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench import harness
    cell = harness.load_cell(args.workload)
    harness.open_chips(cell.chips, "control")
    work = ROOT / "bench" / "_work"
    work.mkdir(exist_ok=True)
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        result, diag, _ = harness.run_cell(cell, seed, args.seconds, False,
                                           t_start=t0, work_dir=work,
                                           control=True)
        row = {"seed": seed, "correct": result["correct"],
               "program_gap": result["checks"]["max_logit_gap"]["value"],
               "control_gap": diag.get("control_max_logit_gap"),
               "control_correct": diag.get("control_correct"),
               "compared_tokens": diag["compared_tokens"],
               "metrics": {k: v["value"] for k, v in result["metrics"].items()},
               "seconds": time.perf_counter() - t0}
        rows.append(row)
        print(json.dumps(row), flush=True)
    print(json.dumps({
        "workload": cell.name, "seeds": len(rows),
        "lower": max((r["program_gap"] for r in rows
                      if r["program_gap"] is not None), default=None),
        "upper": min((r["control_gap"] for r in rows
                      if r["control_gap"] is not None), default=None),
        "control_ever_correct": any(r["control_correct"] is not False
                                    for r in rows)}))


if __name__ == "__main__":
    main()
