"""Per-kernel achieved-vs-peak roofline rows for bench artifacts.

``benchmarks/run.py --json`` embeds these so every artifact carries a
model-level accounting of the kernels the serving path leans on: for
each kernel the trip-count-aware HLO walk (roofline.hlo_cost) yields
per-device FLOPs/bytes, and the roofline terms report how far the
*useful* work sits from the bound step time on the reference chip
(analysis.PEAK_FLOPS / HBM_BW) — ``roofline_fraction`` IS the
achieved-vs-peak figure under perfect overlap (see analysis docstring;
this is a compile-time dry-run metric, independent of the host the
bench happened to run on).

Kernels are compiled at small fixed shapes on the reduced config so the
rows are cheap (<~10 s total) and stable across runs: a chunked-prefill
style forward and a single-token decode-style forward, the two programs
the engine's step dispatch amortizes everything else against.
"""
from __future__ import annotations

import time
from typing import Dict, Optional

DEFAULT_ARCH = "qwen2.5-32b"

# (name, tokens-per-slot lowered, batch, kind, visible_window,
#  effective_window) — effective_window models the mean per-slot extent a
# skewed batch leaves after the extent-predicated kernels (DESIGN.md §12)
# drop fully-masked KV blocks; None means no skew (effective == padded).
KERNELS = (
    ("prefill_chunk", 128, 2, "prefill", None, None),
    ("decode_step", 1, 8, "decode", 512, None),
    # bimodal skew (1 long : 7 short slots) — same compiled program as
    # decode_step, accounted at the mean visible extent instead of padded
    ("decode_step_skewed", 1, 8, "decode", 512, 160),
)


def _cost_dict(compiled) -> dict:
    """compiled.cost_analysis() as a dict ({} where unavailable)."""
    try:
        cost = compiled.cost_analysis()
    except Exception:
        return {}
    return dict(cost or {})


def kernel_rows(arch: str = DEFAULT_ARCH) -> Dict[str, dict]:
    """Compile each reference kernel for the reduced config and summarize
    its roofline terms. Raises on breakage — callers wanting a
    best-effort artifact field use ``report``."""
    import jax
    import jax.numpy as jnp

    from repro.configs import get_reduced
    from repro.configs.base import ShapeConfig
    from repro.models import registry
    from repro.roofline import analysis

    cfg = get_reduced(arch)
    params = jax.eval_shape(lambda k: registry.init_params(k, cfg),
                            jax.random.PRNGKey(0))
    rows: Dict[str, dict] = {}
    for name, toks, batch, kind, vis, eff in KERNELS:
        # seq_len feeds useful-work accounting (the decode kernel's KV
        # window), toks is what the kernel actually lowers per slot
        shape_cfg = ShapeConfig(name, max(toks, vis or 0), batch, kind)
        tok = jax.ShapeDtypeStruct((batch, toks), jnp.int32)
        t0 = time.perf_counter()
        compiled = jax.jit(
            lambda p, t: registry.forward(p, cfg, t)).lower(
            params, tok).compile()
        compile_s = time.perf_counter() - t0
        roof = analysis.summarize(
            _cost_dict(compiled), compiled.as_text(), cfg, shape_cfg,
            arch, name, "single", 1, visible_window=vis,
            effective_window=eff)
        d = roof.to_dict()
        rows[name] = {
            "kernel": name, "arch": arch, "kind": kind,
            "tokens": toks, "batch": batch,
            "compile_s": round(compile_s, 3),
            "hlo_flops": d["hlo_flops"], "hlo_bytes": d["hlo_bytes"],
            "coll_bytes": d["coll_bytes"],
            "compute_s": d["compute_s"], "memory_s": d["memory_s"],
            "collective_s": d["collective_s"],
            "bottleneck": d["bottleneck"],
            "bound_step_s": d["bound_step_s"],
            "ideal_step_s": d["ideal_step_s"],
            "roofline_fraction": d["roofline_fraction"],
            "effective_ideal_step_s": d["effective_ideal_step_s"],
            "effective_roofline_fraction": d["effective_roofline_fraction"],
            "work_skip_fraction": d["work_skip_fraction"],
            "peak_flops": analysis.PEAK_FLOPS,
            "peak_hbm_bw": analysis.HBM_BW,
        }
    return rows


def report(arch: str = DEFAULT_ARCH) -> dict:
    """Best-effort wrapper for artifact embedding: never raises, records
    the failure instead so a roofline breakage cannot sink a bench run."""
    try:
        return {"ok": True, "arch": arch, "kernels": kernel_rows(arch)}
    except Exception as e:                              # pragma: no cover
        return {"ok": False, "arch": arch,
                "error": f"{type(e).__name__}: {e}"}
