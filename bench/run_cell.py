#!/usr/bin/env python3
"""Run one benchmark cell once, on the chips of the host it starts on.

    python3 bench/run_cell.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Builds the cell's configuration through ``serving.build`` with weights made
on the device from the seed, warms its two executors and its traffic,
drives the traffic through the gateway for ``--seconds``, then checks the
served tokens against the float32 reference. The last line of standard
output is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics, or with ``--trace 1`` its
per-layer metrics), ``device`` (with ``--trace 1`` also ``breakdown``), and
last ``checks``: each number compared with its limit, which also end
standard error. Without a TPU, or with fewer chips than the cell asks for,
it exits non-zero and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        sys.exit("run_cell: run from a checkout of the repository "
                 "(src/repro missing)")
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench import harness
    cell = harness.load_cell(args.workload)
    harness.open_chips(cell.chips, "run_cell")
    work = ROOT / "bench" / "_work"
    work.mkdir(exist_ok=True)
    result, diag, _ = harness.run_cell(cell, args.seed, args.seconds,
                                       bool(args.trace), t_start=T_START,
                                       work_dir=work)
    harness.print_result(result, diag)


if __name__ == "__main__":
    main()
