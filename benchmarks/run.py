"""Benchmark harness — one module per paper table/figure (DESIGN.md §7).
Prints ``name,us_per_call,derived`` CSV per row.

    PYTHONPATH=src python -m benchmarks.run [--only idle_floor,mixed_length]
    PYTHONPATH=src python -m benchmarks.run --json BENCH_PR2.json
    PYTHONPATH=src python -m benchmarks.run --xla-profile latency_hiding ...

``--json PATH`` aggregates every module's rows PLUS the engine audits
recorded during the run into one JSON artifact — the per-PR perf
trajectory (BENCH_PR<n>.json committed at the repo root; CI uploads the
fresh file and diffs it against the committed previous one with
benchmarks/diff_json.py; selected tokens/s rows gate via ``--gate``).
Each artifact also records the provenance a perf number needs to be
comparable: the active XLA flag profile, the jax version, and the
per-kernel achieved-vs-peak roofline rows (BENCH_SCHEMA.md).

``--xla-profile NAME`` installs a launch/xla_flags.py profile. XLA reads
XLA_FLAGS when jax initializes, so the profile is applied from a
pre-import bootstrap below — before any bench module (and through them
jax) is imported.
"""
import argparse
import json
import os
import sys
import time
import traceback

# ---- pre-import bootstrap: XLA_FLAGS must be set before jax loads.
# Only sys/os may be imported above this point; repro.launch.xla_flags
# deliberately imports no jax.
if "--xla-profile" in sys.argv:
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
    from repro.launch import xla_flags as _xf
    _xf.apply_profile(sys.argv[sys.argv.index("--xla-profile") + 1])

from benchmarks.common import collected_audits, print_rows, rows_to_json

MODULES = [
    ("idle_floor", "benchmarks.bench_idle_floor"),
    ("bandwidth_wall", "benchmarks.bench_bandwidth_wall"),
    ("mixed_length", "benchmarks.bench_mixed_length"),
    ("trace_replay", "benchmarks.bench_trace_replay"),
    ("oversubscribe", "benchmarks.bench_oversubscribe"),
    ("prefix_reuse", "benchmarks.bench_prefix_reuse"),
    ("kv_quant", "benchmarks.bench_kv_quant"),
    ("predictable", "benchmarks.bench_predictable"),
    ("transport_audit", "benchmarks.bench_transport_audit"),
    ("farview_quality", "benchmarks.bench_farview_quality"),
    ("boundary_stress", "benchmarks.bench_boundary_stress"),
    ("longcontext_budget", "benchmarks.bench_longcontext_budget"),
    ("decode_skew", "benchmarks.bench_decode_skew"),
    ("sampling_eos", "benchmarks.bench_sampling_eos"),
    ("gateway_slo", "benchmarks.bench_gateway_slo"),
    ("continuous", "benchmarks.bench_continuous"),
    ("kernels", "benchmarks.bench_kernels"),
    ("scaling", "benchmarks.bench_scaling"),
]


def main() -> None:
    from repro.launch import xla_flags

    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of bench names")
    ap.add_argument("--json", default=None,
                    help="aggregate all rows + engine audits into one JSON "
                         "artifact (perf trajectory)")
    ap.add_argument("--xla-profile", default=None,
                    choices=xla_flags.profile_names(),
                    help="launch/xla_flags.py profile to run under "
                         "(applied pre-jax-import by the bootstrap above)")
    args = ap.parse_args()
    only = set(args.only.split(",")) if args.only else None
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    failed = []
    agg = {}
    print("name,us_per_call,derived")
    for name, modname in MODULES:
        if only and name not in only:
            continue
        t0 = time.time()
        try:
            mod = __import__(modname, fromlist=["run"])
            rows = mod.run()
            print_rows(rows)
            agg[name] = rows_to_json(rows)
            print(f"# {name}: {time.time() - t0:.1f}s", file=sys.stderr)
        except Exception:
            failed.append(name)
            print(f"# {name}: FAILED", file=sys.stderr)
            traceback.print_exc()

    if args.json:
        import jax
        from repro.roofline import bench as roofline_bench
        payload = {"benches": agg, "audits": collected_audits(),
                   "failed": failed,
                   "xla_profile": xla_flags.active_profile(),
                   "jax_version": jax.__version__,
                   "roofline": roofline_bench.report()}
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=1, sort_keys=True, default=float)
        print(f"# wrote {args.json}", file=sys.stderr)

    if failed:
        print(f"# FAILED: {failed}", file=sys.stderr)
        raise SystemExit(1)


if __name__ == "__main__":
    main()
