"""SPMD scaling table (DESIGN.md §4): tokens/s and per-device KV bytes vs
mesh size. Each row serves the SAME mixed-length workload through
serve.build_lanes/run_lanes:

  * 1x1 — single-device baseline (mesh plumbing off)
  * 1x2 — one lane, 2-way tensor-parallel decode (kv-head-sharded pools)
  * 2x1 — two data-parallel engine lanes, striped trace
  * 2x2 — two lanes x TP2

Reported per row: aggregate tokens/s, per-lane tokens/s, per-device
peak reserved/active KV bytes (the 1xM rows shrink these by M), TP degree.

Every mesh runs in this process, over the devices it holds (one process
per chip); a mesh wider than that is left out of the table. CPU runs get
four virtual devices by setting XLA_FLAGS before jax starts:

    XLA_FLAGS=--xla_force_host_platform_device_count=4 \
        PYTHONPATH=src python -m benchmarks.bench_scaling --json scaling.json
"""
import argparse

MESHES = ("1x1", "1x2", "2x1", "2x2")


def run():
    """Harness entry (benchmarks/run.py): the meshes the process's devices
    can hold."""
    import jax
    from benchmarks.common import record_audit, row, smoke_scale
    from repro.data import traces
    from repro.launch.mesh import parse_mesh_spec
    from repro.launch.serve import build_lanes, run_lanes

    scale = smoke_scale()
    n_req = max(8, int(24 * scale))
    n_dev = len(jax.devices())
    rows = []
    for spec in MESHES:
        d, m = parse_mesh_spec(spec)
        if d * m > n_dev:
            continue
        engines = build_lanes("qwen2.5-32b", "paged_merge", 8, 128, spec,
                              pool_budget_frac=0.5, pipeline_depth=1,
                              prefill_chunk=16)
        reqs = traces.mixed_length_workload(traces.TraceConfig(
            n_requests=n_req, token_scale=0.3,
            vocab=engines[0].cfg.vocab_size, seed=3))
        out = run_lanes(engines, reqs)
        a = out["audit"]
        record_audit(f"scaling/{spec}", a)
        rows.append(row(
            f"scaling/{spec}", 1e6 / max(out["aggregate_tok_s"], 1e-9),
            tok_s=out["aggregate_tok_s"],
            wall_tok_s=out["wall_tok_s"],
            lane_tok_s=float(sum(out["per_lane_tok_s"])),
            lanes=out["lanes"], tp=a["tp_degree"],
            per_device_peak_reserved_kv=a["per_device_peak_reserved_kv"],
            per_device_active_kv=a["per_device_active_kv"],
            peak_reserved_kv=a["peak_reserved_kv"],
            finished=out["finished"]))
    return rows


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", default=None,
                    help="write rows as a JSON summary")
    args = ap.parse_args()

    rows = run()
    from benchmarks.common import print_rows, write_json
    print_rows(rows)
    if args.json:
        write_json(rows, args.json)
