import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture x input-shape) cell
on the production mesh, print memory/cost analysis, extract roofline terms.

    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-32b \
        --shape decode_32k --mesh single
    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both

Results are cached as JSON under experiments/dryrun/ (one file per cell);
existing files are skipped so the 40-cell x 2-mesh sweep is resumable.

The two os.environ lines above MUST stay the first statements in this module:
jax locks the device count at first initialization.
"""
import argparse
import json
import time
import traceback
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCH_IDS, SHAPES, get_config
from repro.configs.base import ModelConfig, ServingConfig, ShapeConfig
from repro.core.descriptor import FrameDescriptor
from repro.distributed import sharding as shd
from repro.launch.mesh import make_production_mesh, mesh_context
from repro.models import registry
from repro.roofline import analysis
from repro.training.optimizer import OptimizerConfig, OptState
from repro.training.train_loop import TrainConfig, make_train_step

BLOCK_TOKENS = 64          # BLOCKALIGN quantum: 64 tok x kv_width ~ tau bytes

# ---- §Perf variant knobs (set per run_cell call) --------------------------
VARIANT_OPTS = {}

VARIANTS = {
    # hillclimb iterations (EXPERIMENTS.md §Perf)
    "bf16scores":  {"score_dtype": "bfloat16"},
    "accbf16":     {"accum_dtype": "bfloat16"},
    "both16":      {"score_dtype": "bfloat16", "accum_dtype": "bfloat16"},
    "ep_off":      {"ep_off": True},
    "cf10":        {"capacity_factor": 1.0},
    "noremat":     {"no_remat": True},
    "mb4":         {"microbatches": 4},
    "ropeil":      {"rope_pairing": "interleaved"},
    "ropeil16":    {"rope_pairing": "interleaved", "score_dtype": "bfloat16"},
    "epfix":       {},   # post-fix MoE EP resharding (code default now)
    "timechunk":   {},   # post-fix xlstm chunked-time remat (code default)
    "notimechunk": {"time_chunk": 0},
    "qpsum":       {"q_model_constraint": True},
    "qpsum16":     {"q_model_constraint": True, "score_dtype": "bfloat16"},
}
OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "experiments", "dryrun")


# ---------------------------------------------------------------------------
# geometry helpers
# ---------------------------------------------------------------------------

def serving_plan(cfg: ModelConfig, shape: ShapeConfig) -> dict:
    """Decode-cell geometry: window, far-view, pool sizing, semantics tag."""
    if shape.name == "long_500k":
        if cfg.sub_quadratic:
            # native sub-quadratic (SSM/hybrid): bounded window on attention
            # sites, O(1) recurrent state; dense long-context is native.
            return dict(near_window=512, farview=False, semantics="native")
        # full-attention archs: paper's optional bounded-budget policy
        return dict(near_window=512, farview=True, cap=64, sv_chunk=128,
                    semantics="bounded-budget")
    # decode_32k: dense semantics — kernel width = full history
    return dict(near_window=shape.seq_len, farview=False, semantics="dense")


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def decode_geometry(cfg: ModelConfig, shape: ShapeConfig, groups: int) -> dict:
    plan = serving_plan(cfg, shape)
    W = plan["near_window"]
    bt = BLOCK_TOKENS
    NB = -(-W // bt) + 1
    B_loc = max(1, shape.global_batch // groups)
    g_eff = min(groups, shape.global_batch)
    if plan.get("farview"):
        blocks_per_seq = NB + plan["sv_chunk"] // bt + 2
        max_chunks = _round_up((shape.seq_len - W) // plan["sv_chunk"] + 1, 8)
    else:
        blocks_per_seq = -(-shape.seq_len // bt) + 1
        max_chunks = 0
    P_loc = B_loc * blocks_per_seq + 1
    return dict(plan=plan, W=W, bt=bt, NB=NB, B_loc=B_loc, groups=g_eff,
                P_loc=P_loc, max_chunks=max_chunks,
                cap=plan.get("cap", 1), MT=NB + 1,
                chunk_blocks=max(1, plan.get("sv_chunk", bt) // bt))


# ---------------------------------------------------------------------------
# input specs (ShapeDtypeStruct stand-ins; no allocation)
# ---------------------------------------------------------------------------

def input_specs(cfg: ModelConfig, shape: ShapeConfig, groups: int) -> dict:
    """Returns dict with 'batch' (train/prefill) or decode-cell structures."""
    s = jax.ShapeDtypeStruct
    if shape.kind in ("train", "prefill"):
        B = shape.global_batch
        S = shape.seq_len
        out = {}
        if cfg.family == "encdec":
            out["tokens"] = s((B, S // 2), jnp.int32)
            out["extra_embeds"] = s((B, S // 2, cfg.d_model), jnp.bfloat16)
        elif cfg.frontend == "vision_stub":
            out["tokens"] = s((B, S), jnp.int32)
            out["extra_embeds"] = s((B, min(256, S // 2), cfg.d_model),
                                    jnp.bfloat16)
        else:
            out["tokens"] = s((B, S), jnp.int32)
        return out

    g = decode_geometry(cfg, shape, groups)
    G, B_loc = g["groups"], g["B_loc"]
    tokens = s((G, B_loc), jnp.int32)
    pools = registry.decode_pool_shapes(
        cfg, batch=B_loc, num_blocks=g["P_loc"], block_tokens=g["bt"],
        max_chunks=g["max_chunks"],
        enc_len=4096 if cfg.family == "encdec" else 0)
    pools = jax.tree.map(lambda x: s((G,) + x.shape, x.dtype), pools)
    i32 = lambda *sh: s(sh, jnp.int32)
    descr = FrameDescriptor(
        block_table=i32(G, B_loc, g["NB"]), window_base=i32(G, B_loc),
        seq_lens=i32(G, B_loc), slot_active=i32(G, B_loc),
        write_block=i32(G, B_loc), write_offset=i32(G, B_loc),
        train_start=i32(G, B_loc, g["MT"]), train_len=i32(G, B_loc, g["MT"]),
        train_dst=i32(G, B_loc, g["MT"]),
        far_table=i32(G, B_loc, g["cap"]), far_valid=i32(G, B_loc, g["cap"]),
        far_chunk_blocks=i32(G, B_loc, g["chunk_blocks"]),
        far_chunk_tokens=i32(G, B_loc), far_do_summarize=i32(G, B_loc),
        far_write_idx=i32(G, B_loc), epoch=i32(G))
    return {"tokens": tokens, "pools": pools, "descr": descr, "geom": g}


# ---------------------------------------------------------------------------
# cell builders: (fn, example_args, in_shardings)
# ---------------------------------------------------------------------------

def build_train_cell(cfg, shape, mesh):
    groups = shd.data_shards(mesh)
    ba = shd.batch_axes(mesh)
    bspec = ba if len(ba) > 1 else ba[0]
    moe_ep = cfg.family == "moe"
    ep_axes = (bspec if moe_ep else None)

    rows_per_shard = max(1, shape.global_batch // groups)
    v = VARIANT_OPTS
    if v.get("ep_off"):
        ep_axes = None
    tcfg = TrainConfig(microbatches=v.get("microbatches", rows_per_shard),
                       remat=not v.get("no_remat", False),
                       token_groups=groups, ep_axes=ep_axes,
                       batch_axes=bspec,
                       accum_dtype=v.get("accum_dtype", "float32"),
                       compression="bf16" if "pod" in mesh.axis_names else "none")
    ocfg = OptimizerConfig(
        moment_dtype="bfloat16" if cfg.param_count() > 1e11 else "float32")
    step = make_train_step(cfg, ocfg, tcfg)

    params_sh = jax.eval_shape(lambda k: registry.init_params(k, cfg),
                               jax.random.PRNGKey(0))
    pspecs = shd.param_specs(cfg, params_sh, ep_axes=ep_axes)
    opt_sh = jax.eval_shape(
        lambda p: OptState(step=jnp.zeros((), jnp.int32), mu=p, nu=p), params_sh)
    ospecs = OptState(step=P(), mu=pspecs, nu=pspecs)
    err_sh = params_sh
    especs = pspecs

    ins = input_specs(cfg, shape, groups)
    batch_sh = {k: v for k, v in ins.items()}
    bspecs = {k: P(bspec, *([None] * (len(v.shape) - 1)))
              for k, v in batch_sh.items()}

    def fn(params, opt, err, batch):
        return step(params, opt, err, batch)

    pspecs = shd.sanitize_specs(mesh, params_sh, pspecs)
    ospecs = OptState(step=P(), mu=pspecs, nu=pspecs)
    especs = pspecs
    bspecs = shd.sanitize_specs(mesh, batch_sh, bspecs)
    args = (params_sh, opt_sh, err_sh, batch_sh)
    in_sh = (shd.to_shardings(mesh, pspecs), shd.to_shardings(mesh, ospecs),
             shd.to_shardings(mesh, especs),
             shd.to_shardings(mesh, bspecs))
    return fn, args, in_sh


def build_prefill_cell(cfg, shape, mesh):
    groups = shd.data_shards(mesh)
    ba = shd.batch_axes(mesh)
    bspec = ba if len(ba) > 1 else ba[0]
    moe_ep = cfg.family == "moe" and not VARIANT_OPTS.get("ep_off")
    ep_axes = (bspec if moe_ep else None)

    params_sh = jax.eval_shape(lambda k: registry.init_params(k, cfg),
                               jax.random.PRNGKey(0))
    pspecs = shd.param_specs(cfg, params_sh,
                             ep_axes=(bspec if cfg.family == "moe" else None))
    ins = input_specs(cfg, shape, groups)
    bspecs = {k: P(bspec, *([None] * (len(v.shape) - 1)))
              for k, v in ins.items()}

    kw = {}
    if cfg.family == "moe":
        kw = dict(token_groups=groups, ep_axes=ep_axes)

    def fn(params, batch):
        tokens = batch["tokens"]
        extra = batch.get("extra_embeds")
        if extra is not None:
            logits = registry.forward(params, cfg, tokens, extra_embeds=extra,
                                      remat=True, **kw)
        else:
            out = registry.forward(params, cfg, tokens, remat=True, **kw)
            logits = out[0] if isinstance(out, tuple) else out
        # serving prefill emits the LAST position's logits (first new token)
        return logits[:, -1, :]

    pspecs = shd.sanitize_specs(mesh, params_sh, pspecs)
    bspecs = shd.sanitize_specs(mesh, ins, bspecs)
    return fn, (params_sh, ins), (shd.to_shardings(mesh, pspecs),
                                  shd.to_shardings(mesh, bspecs))


def build_decode_cell(cfg, shape, mesh):
    groups = shd.data_shards(mesh)
    ba = shd.batch_axes(mesh)
    bspec = ba if len(ba) > 1 else ba[0]

    moe_ep = cfg.family == "moe"
    params_sh = jax.eval_shape(lambda k: registry.init_params(k, cfg),
                               jax.random.PRNGKey(0))
    # expert STORAGE stays EP-sharded in decode (memory posture); compute-side
    # EP all-to-all for decode is a §Perf item (see EXPERIMENTS.md)
    pspecs = shd.param_specs(cfg, params_sh, ep_axes=(bspec if moe_ep else None))
    ins = input_specs(cfg, shape, groups)
    geom = ins.pop("geom")
    W = geom["W"]

    sv = ServingConfig(near_window=W, farview_cap=geom["cap"],
                       sv_chunk=geom["plan"].get("sv_chunk", 128),
                       enable_farview=geom["plan"].get("farview", False))
    cfg_dec = cfg.replace(serving=sv)
    kw = {}
    if cfg.family == "moe":
        kw = dict(token_groups=1, ep_axes=None)  # EP-decode: see §Perf

    def one_group(params, tokens_g, pools_g, descr_g):
        logits, pools2, fu = registry.decode_step(params, cfg_dec, tokens_g,
                                                  pools_g, descr_g, **kw)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), pools2, fu

    def fn(params, tokens, pools, descr):
        return jax.vmap(one_group, in_axes=(None, 0, 0, 0))(
            params, tokens, pools, descr)

    # shardings: leading G over batch axes; pool payload kv-heads over model
    pool_specs = shd.grouped_pool_specs(cfg, ins["pools"], bspec)
    descr_specs = jax.tree.map(
        lambda x: P(bspec, *([None] * (len(x.shape) - 1))), ins["descr"])
    tok_spec = P(bspec, None)
    pspecs = shd.sanitize_specs(mesh, params_sh, pspecs)
    pool_specs = shd.sanitize_specs(mesh, ins["pools"], pool_specs)
    descr_specs = shd.sanitize_specs(mesh, ins["descr"], descr_specs)
    tok_spec = shd.sanitize_specs(mesh, ins["tokens"], tok_spec)
    args = (params_sh, ins["tokens"], ins["pools"], ins["descr"])
    in_sh = (shd.to_shardings(mesh, pspecs),
             NamedSharding(mesh, tok_spec),
             shd.to_shardings(mesh, pool_specs),
             shd.to_shardings(mesh, descr_specs))
    return fn, args, in_sh


BUILDERS = {"train": build_train_cell, "prefill": build_prefill_cell,
            "decode": build_decode_cell}


# ---------------------------------------------------------------------------
# run one cell
# ---------------------------------------------------------------------------

def run_cell(arch: str, shape_name: str, mesh_name: str,
             out_dir: str = OUT_DIR, force: bool = False,
             variant: str = "", cfg_override=None) -> dict:
    os.makedirs(out_dir, exist_ok=True)
    tag = f"{arch}__{shape_name}__{mesh_name}" + (f"__{variant}" if variant else "")
    path = os.path.join(out_dir, tag + ".json")
    if os.path.exists(path) and not force:
        with open(path) as f:
            return json.load(f)

    cfg = cfg_override or get_config(arch)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=(mesh_name == "multi"))
    builder = BUILDERS[shape.kind]

    global VARIANT_OPTS
    VARIANT_OPTS = dict(VARIANTS.get(variant, {}))
    import jax.numpy as _jnp
    from repro.models import common as _cm
    from repro.models import moe as _moe
    _cm.set_score_dtype(_jnp.bfloat16 if VARIANT_OPTS.get("score_dtype") ==
                        "bfloat16" else _jnp.float32)
    _cm.set_rope_pairing(VARIANT_OPTS.get("rope_pairing", "half"))
    _moe.CAPACITY_FACTOR = VARIANT_OPTS.get("capacity_factor", 1.25)
    from repro.models import xlstm as _xl
    _xl.set_time_chunk(VARIANT_OPTS.get("time_chunk", 256))

    t0 = time.time()
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
           "variant": variant, "chips": int(mesh.size)}
    try:
        fn, args, in_sh = builder(cfg, shape, mesh)
        donate = {"decode": (2,), "train": (0, 1, 2), "prefill": ()}[shape.kind]
        from repro.distributed.act_sharding import use_batch_axes, use_model_axis
        ba = shd.batch_axes(mesh)
        act_axes = (ba if len(ba) > 1 else ba[0]) \
            if shape.kind in ("train", "prefill") else None
        q_model = ("model" if (VARIANT_OPTS.get("q_model_constraint")
                               and shape.kind == "decode") else None)
        with mesh_context(mesh), use_batch_axes(act_axes), \
                use_model_axis(q_model):
            lowered = jax.jit(fn, in_shardings=in_sh,
                              donate_argnums=donate).lower(*args)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower
        ma = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        hlo = compiled.as_text()
        vis = (serving_plan(cfg, shape)["near_window"]
               if shape.kind == "decode" else None)
        if shape.kind == "decode" and serving_plan(cfg, shape).get("farview"):
            plan = serving_plan(cfg, shape)
            vis = plan["near_window"] + plan["cap"] * plan["sv_chunk"]
        roof = analysis.summarize(cost, hlo, cfg, shape, arch, shape_name,
                                  mesh_name, int(mesh.size),
                                  visible_window=vis)
        rec.update({
            "ok": True,
            "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
            "memory": {
                "argument_bytes_per_device": int(ma.argument_size_in_bytes),
                "output_bytes_per_device": int(ma.output_size_in_bytes),
                "temp_bytes_per_device": int(ma.temp_size_in_bytes),
                "alias_bytes_per_device": int(ma.alias_size_in_bytes),
                "total_bytes_per_device": int(ma.argument_size_in_bytes
                                              + ma.output_size_in_bytes
                                              + ma.temp_size_in_bytes
                                              - ma.alias_size_in_bytes),
            },
            "roofline": roof.to_dict(),
            "semantics": (serving_plan(cfg, shape)["semantics"]
                          if shape.kind == "decode" else "dense"),
        })
        print(f"[OK] {tag}: compile {t_compile:.1f}s "
              f"mem/dev {rec['memory']['total_bytes_per_device']/2**30:.2f}GiB "
              f"bottleneck {roof.bottleneck} "
              f"roofline {roof.roofline_fraction:.3f}")
    except Exception as e:
        rec.update({"ok": False, "error": f"{type(e).__name__}: {e}",
                    "traceback": traceback.format_exc()[-2000:]})
        print(f"[FAIL] {tag}: {type(e).__name__}: {str(e)[:200]}")
    with open(path, "w") as f:
        json.dump(rec, f, indent=1, default=str)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, choices=list(ARCH_IDS) + [None])
    ap.add_argument("--shape", default=None, choices=list(SHAPES) + [None])
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--out", default=OUT_DIR)
    args = ap.parse_args()

    archs = list(ARCH_IDS) if (args.all or args.arch is None) else [args.arch]
    shapes = list(SHAPES) if (args.all or args.shape is None) else [args.shape]
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]

    n_ok = n_fail = 0
    for mesh_name in meshes:
        for arch in archs:
            for shape_name in shapes:
                rec = run_cell(arch, shape_name, mesh_name, out_dir=args.out,
                               force=args.force)
                n_ok += bool(rec.get("ok"))
                n_fail += not rec.get("ok")
    print(f"\ndone: {n_ok} ok, {n_fail} failed")
    return 0 if n_fail == 0 else 1


if __name__ == "__main__":
    raise SystemExit(main())
