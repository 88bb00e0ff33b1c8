"""KVRMEngine — fixed-shape decode serving under the descriptor transport
interface (paper §4), plus the static-arena baseline on the SAME executor.

Modes (Table 5 attribution rows):
  * arena       — static-graph baseline: worst-case contiguous per-slot
                  reservation, no paging, no merging.
  * paged       — + KV pager (RESERVE/ALIAS/TRIM/FRAME), unmerged transport.
  * paged_merge — + merge-staged descriptor transport (core KV-RM path).
  * full        — + far-view summarization (optional bounded-budget policy).

Invariants audited every run: the decode step is compiled ONCE (no retrace
after warm-up), exactly one Frame commit per step, bounded host control share.

Hot-path structure (DESIGN.md §3):
  * ``pipeline_depth >= 1`` (default) overlaps host descriptor assembly for
    step t+1 with device execution of step t. Sampled-token feedback flows
    device-side (the compiled step selects between host prompt tokens and the
    previous step's on-device sample), so host readback lags dispatch by one
    step. In legacy greedy mode (``greedy=True``) EOS is the gen_len token
    budget, retirement is host-predictable and happens at dispatch time, and
    the pager/transport timeline is bit-identical to the synchronous path.
  * ``greedy = False`` (DESIGN.md §13) turns on real on-device sampling
    (temperature/top-k/top-p, per-slot threefry keys derived from the
    control plane's rid row + descriptor seq_lens) and data-dependent EOS:
    per-request stop tokens end a request wherever they land. Retirement is
    then DETECTED at readback — under pipelining the host learns of a stop
    ``depth`` dispatches late, scrubs the overshot in-flight emissions, and
    reconciles pager/transport/kernel accounting exactly, so the depth-0
    and depth-d timelines still agree byte-for-byte.
  * ``pipeline_depth = 0`` preserves the exact seed behavior (per-slot
    descriptor assembly, blocking readback each step) for A/B measurement.
  * ``prefill_chunk = C > 0`` ingests prompts through a second fixed-shape
    chunked prefill executor at C tokens per engine step instead of one;
    the slots with chunk work are packed into its first rows and it runs at
    the smallest of a few row widths derived from B (each compiled once, all
    when the engine is built). The final prompt token always goes
    through the decode step so sampled-token semantics are unchanged.
  * ``prefix_cache`` (DESIGN.md §9) indexes committed full prompt blocks
    in a radix tree keyed on token-id block chunks; admissions that match
    COW-alias the cached chain (unaligned tails get an audited device-side
    COW copy) and skip the covered prefill entirely — a cached system
    prompt costs zero prefill steps. Eviction is refcount-aware LRU over
    unpinned leaves, preferring unshared (immediately freeable) blocks.
  * ``mesh`` (DESIGN.md §4) runs the SAME executors SPMD over a device mesh:
    params shard by the name-based TP rules, KV pools shard their kv-head
    axis over ``model``, and both executors compile ONCE with explicit
    in/out shardings (descriptor + token feedback replicated, donated pools
    keep their sharding). The host control plane — scheduler, pager,
    transport, the single flat descriptor commit — is untouched, so every
    audit invariant and the full token stream are identical to the
    single-device engine at every TP degree.
"""
from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass, field
from typing import Deque, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core import spans
from repro.core.descriptor import (FrameDescriptor, active_block_extents,
                                   chunk_flat_rows, chunk_flat_size,
                                   chunk_row_width, chunk_row_widths,
                                   control_plane_size, control_plane_views,
                                   descriptor_flat_size,
                                   empty_descriptor, flat_chunk_views,
                                   flat_descriptor_views, pack_chunk_rows,
                                   refresh_control_row,
                                   unflatten_chunk_descriptor,
                                   unflatten_descriptor)
from repro.core.farview import FarViewPolicy
from repro.core.pager import (RES_DEVICE, RES_HOST, BlockPager, SwapRefused)
from repro.core.prefix_cache import PrefixCache
from repro.core.sampling import make_sampler, slot_keys
from repro.core.scheduler import Request, Scheduler
from repro.core.transport import MergeStagedTransport, StagedDescriptor, merge_runs
from repro.kernels import ops
from repro.models import registry
from repro.serving.api import AuditReport

MODES = ("arena", "paged", "paged_merge", "full")


@dataclass
class EngineConfig:
    mode: str = "paged_merge"
    batch: int = 8                   # fixed slot width B
    max_seq: int = 512               # worst-case sequence length
    near_window: Optional[int] = None   # W* (kernel width); None = max_seq (dense)
    block_tokens: int = 16           # BLOCKALIGN quantum (tokens)
    pool_budget_frac: float = 1.0    # paged pool size vs worst case
    farview_cap: int = 16
    sv_chunk: int = 64
    span_blocks: int = 4             # placement span (BLOCKALIGN granularity)
    greedy: bool = True              # True = legacy bit-exact argmax decode
    #                                  with pure budget-EOS; False = on-device
    #                                  sampling + detected EOS (DESIGN.md §13)
    # --- sampling knobs (greedy=False only; static at trace time) ---
    temperature: float = 1.0         # <= 0 is an exact argmax branch
    top_k: int = 0                   # 0 = off (full vocab)
    top_p: float = 1.0               # 1.0 = off (no nucleus cut)
    sample_seed: int = 0             # base PRNG seed; per-slot keys are
    #                                  fold_in(fold_in(seed, rid), position)
    debug_logits: bool = False       # capture per-step logits (tests only)
    # --- host/device overlap + chunked prefill (DESIGN.md §3) ---
    pipeline_depth: int = 1          # 0 = seed-exact synchronous loop (A/B)
    prefill_chunk: int = 0           # tokens per prefill-executor call (0 = off)
    # --- SPMD decode (DESIGN.md §4): jax Mesh with a 'model' axis (TP);
    # None = single-device (seed-exact placement) ---
    mesh: Optional[object] = None
    # --- host KV tier + preemption-aware scheduling (DESIGN.md §8) ---
    host_pool_blocks: int = 0        # host backing pool (blocks); 0 = off
    kv_oversubscribe: float = 1.0    # derives host_pool_blocks when > 1.0:
    #                                  host = (ratio - 1) * device pool
    swap_high_watermark: float = 0.92  # device-pool fill that triggers
    swap_low_watermark: float = 0.80   # cold swap-out down to this fill
    admit_watermark: float = 0.85    # admission caps committed KV at
    #                                  admit_wm * device + host blocks
    # --- automatic shared-prefix KV reuse (radix prefix cache, §9) ---
    prefix_cache: bool = False       # index committed prompt blocks and
    #                                  COW-alias matches at admission
    prefix_cache_blocks: int = 0     # cache pin budget (blocks);
    #                                  0 = auto (half the device pool)
    # --- quantized KV-block storage tier (DESIGN.md §10) ---
    kv_dtype: str = "bf16"           # "bf16" | "fp8_e4m3" | "int8": narrow
    #                                  K/V storage + per-block per-head f32
    #                                  scale pools managed by the pager in
    #                                  lockstep with their data blocks
    # --- async movement engine (DESIGN.md §11) ---
    async_movement: bool = True      # double-buffered staging + deferred
    #                                  swap-out readback fences; False =
    #                                  per-event blocking movement (A/B)
    # --- work-skipping kernels (DESIGN.md §12) ---
    kernel_skip_extent: bool = True  # per-slot active-extent predication in
    #                                  the decode/prefill kernels; False =
    #                                  always-run masked baseline (A/B)
    # --- step-level (continuous) batching (DESIGN.md §15) ---
    continuous_batching: bool = True  # admit into freed slots at every
    #                                  decode step; False = round-based
    #                                  baseline (admit only once every
    #                                  active slot has drained) for A/B
    #                                  head-of-line-blocking measurement


@dataclass
class StepMetrics:
    wall: float = 0.0
    host: float = 0.0                # control-plane time (submit+frame)
    frame_commit: float = 0.0
    dma_groups: int = 0
    active: int = 0
    emitted: int = 0


class KVRMEngine:
    def __init__(self, cfg: ModelConfig, params, ecfg: EngineConfig):
        assert ecfg.mode in MODES
        self.cfg = cfg
        self.params = params
        self.e = ecfg
        self.paged_arch = registry.uses_paged_kv(cfg)

        bt = ecfg.block_tokens
        self.bt = bt
        self.W = ecfg.near_window or ecfg.max_seq
        self.NB = -(-self.W // bt) + 1
        self.MT = self.NB + 1
        self.blocks_per_seq = -(-ecfg.max_seq // bt) + 1
        worst = ecfg.batch * self.blocks_per_seq
        # Without a host tier the pool floor keeps every slot's near window
        # device-resident simultaneously (no swap path exists). WITH the
        # host tier (DESIGN.md §8) the floor drops to one window + growth
        # slack: the device pool may be genuinely oversubscribed — admission
        # watermarks and preemption keep the concurrent working set inside
        # it, and the host pool absorbs the rest.
        want_host = ecfg.mode != "arena" and (ecfg.host_pool_blocks > 0
                                              or ecfg.kv_oversubscribe > 1.0)
        if ecfg.mode == "arena":
            self.num_blocks = worst + 1
        else:
            floor = self.NB * ecfg.batch
            if want_host:
                floor = min(floor, self.NB + ecfg.span_blocks + 2)
            self.num_blocks = max(floor,
                                  int(worst * ecfg.pool_budget_frac)) + 1

        # --- quantized KV-block tier (DESIGN.md §10) --------------------
        # Narrow storage halves (or better) every per-block byte figure the
        # transport accounts — window trains, swaps, COW copies — plus the
        # reserved-KV audit; the per-block f32 scale pools are a sibling
        # physical resource whose overhead is accounted per block here.
        self._quant = ecfg.kv_dtype != "bf16"
        if self._quant:
            err = registry.quant_decode_error(cfg, ecfg.kv_dtype)
            if err is not None:
                raise ValueError(err)
            if ecfg.mode == "full":
                raise ValueError("kv_dtype != 'bf16' requires mode != 'full' "
                                 "(far-view summaries are stored full-width)")

        # per-layer payload bytes (transport accounting uses the real model)
        self.bytes_per_token = registry.paged_payload_bytes_per_token(
            cfg, ecfg.kv_dtype)
        # per-(layer, block) scale overhead: one f32 per kv head for each of
        # the k and v scale pools (0 when unquantized)
        self.scale_bytes_per_block = (2 * cfg.n_kv_heads * 4
                                      if self._quant else 0)
        self.block_bytes = bt * self.bytes_per_token + self.scale_bytes_per_block
        # what the same block costs at full bf16 width (quant savings basis)
        self._dense_block_bytes = bt * registry.paged_payload_bytes_per_token(cfg)
        n_layers_paged = max(1, registry.n_paged_layers(cfg))
        self.pool_bytes_total = (self.num_blocks - 1) * self.block_bytes * n_layers_paged

        self.farview = ecfg.mode == "full" and self.paged_arch and cfg.family != "hybrid"
        self.cap = ecfg.farview_cap if self.farview else 1
        self.max_chunks = (-(-max(1, ecfg.max_seq - self.W) // ecfg.sv_chunk) + 1
                           if self.farview else 0)
        self.chunk_blocks = max(1, ecfg.sv_chunk // bt)

        # --- device state ---
        self.pools = registry.init_decode_pools(
            cfg, batch=ecfg.batch, num_blocks=self.num_blocks, block_tokens=bt,
            max_chunks=self.max_chunks,
            enc_len=ecfg.max_seq if cfg.family == "encdec" else 0,
            kv_dtype=ecfg.kv_dtype)
        if cfg.family == "encdec":
            self.pools["enc_len"] = jnp.zeros((ecfg.batch,), jnp.int32)

        # --- host KV tier (DESIGN.md §8) -------------------------------
        hostb = int(ecfg.host_pool_blocks)
        if hostb == 0 and ecfg.kv_oversubscribe > 1.0:
            hostb = int(np.ceil((ecfg.kv_oversubscribe - 1.0)
                                * (self.num_blocks - 1)))
        self.host_pool_blocks = hostb if ecfg.mode != "arena" else 0
        self._host_tier = self.host_pool_blocks > 0
        if self._host_tier:
            # swap moves block-indexed pool payload only; families with
            # slot-indexed decode state (recurrent/conv/cross-KV) or
            # far-view summaries would lose it across a preemption
            if ecfg.mode == "full" or cfg.family not in ("dense", "vlm", "moe"):
                raise ValueError(
                    "host KV tier requires a block-paged family "
                    "(dense/vlm/moe) and mode != 'full'")
            if ecfg.mesh is not None:
                raise ValueError("host KV tier is single-device for now "
                                 "(sharded swap gather/scatter untested)")

        # --- radix prefix cache (DESIGN.md §9): shared-prefix KV reuse --
        # same scope rules as the host tier: block aliasing moves paged KV
        # only, so families with extra slot-indexed decode state (and the
        # far view's summaries) cannot skip prefill by block sharing
        self._prefix_on = ecfg.prefix_cache
        if self._prefix_on:
            if ecfg.mode == "arena" or self.farview \
                    or cfg.family not in ("dense", "vlm", "moe"):
                raise ValueError(
                    "prefix cache requires a paged mode (not 'arena' or "
                    "'full') and a block-paged family (dense/vlm/moe)")
            if ecfg.mesh is not None:
                raise ValueError("prefix cache is single-device for now "
                                 "(sharded COW tail copy untested)")

        # --- host control plane ---
        self.sched = Scheduler(ecfg.batch)
        self.pager = (BlockPager(self.num_blocks, bt, self.block_bytes,
                                 span_blocks=ecfg.span_blocks,
                                 host_pool_blocks=self.host_pool_blocks)
                      if ecfg.mode != "arena" else None)
        self.transport = MergeStagedTransport(
            block_bytes=self.block_bytes,
            merge_threshold_bytes=cfg.serving.merge_threshold_bytes,
            max_hold_steps=cfg.serving.max_hold_steps, max_trains=self.MT,
            dense_block_bytes=self._dense_block_bytes)
        self.fv = (FarViewPolicy(ecfg.batch, self.max_chunks, self.cap,
                                 ecfg.sv_chunk, bt) if self.farview else None)

        # --- prefix cache state (DESIGN.md §9) --------------------------
        self.prefix_cache = None
        if self._prefix_on:
            cap_blocks = ecfg.prefix_cache_blocks or \
                max(self.NB, (self.num_blocks - 1) // 2)
            self.prefix_cache = PrefixCache(self.pager, bt, cap_blocks)
        self._pinned_paths: Dict[int, list] = {}   # rid -> matched path
        self._indexed_rids: set = set()            # prompts already indexed
        self._cow_pairs_step: List = []            # COW tail copies to run
        self._cow_origin: Dict[int, int] = {}      # this round: dst -> src

        # --- SPMD placement (DESIGN.md §4) ------------------------------
        # Params shard by the name-based TP rules; paged KV pools shard the
        # kv-head axis over `model` (n_rep grouping preserved per shard, so
        # attention needs no collective — the one psum per layer is at the
        # output projection). Everything host-committed (descriptor, tokens,
        # feed mask) is replicated. mesh=None keeps seed-exact placement.
        self.mesh = ecfg.mesh
        self.tp_degree = 1
        self._kv_shards = 1
        self._repl = self._param_sh = self._pool_sh = None
        if self.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec
            from repro.distributed import sharding as shd
            tp = shd.model_shards(self.mesh)
            err = registry.tp_decode_error(cfg, tp)
            if err is not None:
                raise ValueError(err)
            self.tp_degree = tp
            pspecs = shd.sanitize_specs(self.mesh, self.params,
                                        shd.param_specs(cfg, self.params))
            self._param_sh = shd.to_shardings(self.mesh, pspecs)
            self.params = jax.device_put(self.params, self._param_sh)
            kvspecs = shd.sanitize_specs(
                self.mesh, self.pools,
                registry.decode_pool_partition_specs(cfg, self.pools))
            self._pool_sh = shd.to_shardings(self.mesh, kvspecs)
            self.pools = jax.device_put(self.pools, self._pool_sh)
            self._repl = NamedSharding(self.mesh, PartitionSpec())
            paged_key = "k" if "k" in kvspecs else (
                "lat" if "lat" in kvspecs else None)
            if paged_key is not None and shd.MODEL in tuple(kvspecs[paged_key]):
                self._kv_shards = tp

        # arena bookkeeping: slot -> fixed block range
        self._arena_base = [1 + i * self.blocks_per_seq for i in range(ecfg.batch)]
        self._slot_len = np.zeros(ecfg.batch, np.int64)   # tokens in cache
        self._slot_sid = -np.ones(ecfg.batch, np.int64)
        self._last_token = np.zeros(ecfg.batch, np.int64)

        # --- compiled decode step (ONE compilation; invariant audit) ---
        cfg_dec = cfg.replace(serving=cfg.serving.__class__(
            page_size=cfg.serving.page_size, near_window=self.W,
            farview_cap=self.cap, sv_chunk=ecfg.sv_chunk,
            merge_threshold_bytes=cfg.serving.merge_threshold_bytes,
            max_hold_steps=cfg.serving.max_hold_steps,
            enable_farview=self.farview,
            skip_extent=ecfg.kernel_skip_extent))
        self._cfg_dec = cfg_dec

        dbg = ecfg.debug_logits

        # --- on-device sampling (DESIGN.md §13) -------------------------
        # greedy=True keeps the exact legacy argmax executor; greedy=False
        # builds a static sampler closure (temperature/top-k/top-p baked at
        # trace time) whose per-slot keys derive from the control plane's
        # rid row and the committed seq_lens — tokens depend only on
        # (sample_seed, rid, position), invariant to slot placement, batch
        # composition, pipeline depth, preemption and mesh layout.
        self._sampled = not ecfg.greedy
        self.eos_detected = 0
        self.eos_overshoot_tokens = 0
        self.eos_reconciled_blocks = 0
        # per-token event hook (serving gateway, DESIGN.md §14): called as
        # ``token_hook(req, token, finished)`` wherever a token VALUE lands
        # host-side — the sync step's post-device loop and the pipelined
        # readback. Scrubbed overshoot emissions (§13) never fire it, and a
        # cancel's terminal event is the caller's to emit (no token lands).
        self.token_hook = None
        # the clock of the request timeline stamps (t_release, t_admit,
        # t_prompt_done); a gateway sets its own, as it sets token_hook
        self.clock = time.perf_counter
        self.cancelled = 0
        # --- step-level admission audit (DESIGN.md §15) -----------------
        # continuous_admits counts admissions that landed while at least
        # one other slot was mid-round (already decoding) — exactly the
        # admissions a round-based engine would have held at the barrier.
        # slot_idle_steps_saved integrates, per dispatched step, the slots
        # occupied by such a mid-round admission: the idle slot-steps the
        # barrier would have cost. Both are identically 0 when
        # continuous_batching=False — the A/B witness.
        self.continuous_admits = 0
        self.slot_idle_steps_saved = 0
        self._mid_round = np.zeros(ecfg.batch, bool)
        if self._sampled:
            if ecfg.temperature > 0 and not 0.0 < ecfg.top_p <= 1.0:
                raise ValueError(f"top_p must be in (0, 1]: {ecfg.top_p}")
            if ecfg.top_k < 0:
                raise ValueError(f"top_k must be >= 0: {ecfg.top_k}")
            sampler = make_sampler(ecfg.temperature, ecfg.top_k, ecfg.top_p)
            base_key = jax.random.PRNGKey(ecfg.sample_seed)

        # Token selection happens ON DEVICE so the pipelined loop can feed the
        # previous step's sampled tokens without a host readback: host prompt
        # tokens where feed_sampled=0, previous on-device sample where 1. The
        # synchronous path passes feed_sampled=0 everywhere — same semantics,
        # identical numerics for both paths.
        def _step_core(params, host_tokens, feed_sampled, rids, prev_nxt,
                       pools, descr):
            tokens = jnp.where(feed_sampled > 0, prev_nxt, host_tokens)
            with ops.kernel_mesh(self.mesh):
                logits, pools, fu = registry.decode_step(params, cfg_dec,
                                                         tokens, pools, descr)
            if self._sampled:
                keys = slot_keys(base_key, rids, descr.seq_lens)
                nxt = sampler(keys, logits)
            else:
                nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return nxt, pools, fu, (logits if dbg else jnp.zeros((), jnp.int32))

        self.depth = max(0, int(ecfg.pipeline_depth))
        B, NB, CAP, MT, CB = (ecfg.batch, self.NB, self.cap, self.MT,
                              self.chunk_blocks)
        self._flat_descr_size = descriptor_flat_size(B, NB, CAP, MT, CB)
        D = self._flat_descr_size
        # explicit executor shardings under a mesh: donated pools keep their
        # kv-head sharding, the control plane is replicated — exactly one
        # compilation per executor either way (audited)
        R, PS = self._repl, self._pool_sh
        if self.depth <= 0:
            # seed-exact executor: per-array descriptor operands
            kw = ({} if self.mesh is None else dict(
                in_shardings=(self._param_sh, R, R, R, R, PS, R),
                out_shardings=(R, PS, R, R)))
            self._step_fn = jax.jit(_step_core, donate_argnums=(5,), **kw)
        else:
            # pipelined executor: the whole control plane (descriptor + host
            # tokens + feed mask + rid row) arrives as ONE flat int32
            # operand — one device_put per step instead of ~18 (the
            # dominant host cost)
            def _step_flat(params, flat, prev_nxt, pools):
                descr = unflatten_descriptor(flat[:D], B, NB, CAP, MT, CB)
                host_tokens = flat[D:D + B]
                feed_sampled = flat[D + B:D + 2 * B]
                rids = flat[D + 2 * B:D + 3 * B]
                return _step_core(params, host_tokens, feed_sampled, rids,
                                  prev_nxt, pools, descr)
            kw = ({} if self.mesh is None else dict(
                in_shardings=(self._param_sh, R, R, PS),
                out_shardings=(R, PS, R, R)))
            self._step_fn = jax.jit(_step_flat, donate_argnums=(3,), **kw)
        self._step_sig = None
        self.debug_logits: List[np.ndarray] = []

        # --- chunked prefill executor (one compilation per row width) ---
        self._chunked = (ecfg.prefill_chunk > 0
                         and registry.supports_chunked_prefill(cfg)
                         and not self.farview)
        self.chunk = int(ecfg.prefill_chunk) if self._chunked else 0
        if self._chunked:
            C = self.chunk
            # the row width is the flat operand's length: jit keeps one
            # executable per width, all compiled at the end of __init__
            def _chunk_step(params, pools, cflat):
                rows = chunk_flat_rows(cflat.shape[0], C, NB)
                cdescr = unflatten_chunk_descriptor(cflat, rows, C, NB)
                with ops.kernel_mesh(self.mesh):
                    return registry.prefill_chunk(params, cfg_dec, pools,
                                                  cdescr)
            ckw = ({} if self.mesh is None else dict(
                in_shardings=(self._param_sh, PS, R), out_shardings=PS))
            # _chunk_fn is the one callable every chunk dispatch goes
            # through; _chunk_jit stays the jitted function (compile, audit)
            self._chunk_jit = jax.jit(_chunk_step, donate_argnums=(1,), **ckw)
            self._chunk_fn = self._chunk_jit
            self._chunk_widths = chunk_row_widths(B)
            # host views: B rows, this step's live rows packed first
            self._cflat = np.zeros(chunk_flat_size(B, C, NB), np.int32)
            self._cdescr = flat_chunk_views(self._cflat, B, C, NB)
        else:
            self._chunk_fn = self._chunk_jit = None
        # below this many remaining prompt tokens, ingestion rides the decode
        # step instead (zero marginal steps while other slots decode) — even
        # a narrow chunk call pays a fixed cost per call (the weights read,
        # the layer scan's pool slicing) that a tiny remainder doesn't earn.
        # Capped at a few blocks so an oversized C never disables chunking.
        self._chunk_min = (max(self.bt, min(self.chunk // 2, 4 * self.bt))
                           if self._chunked else 0)
        self._chunk_steps = 0
        self._chunk_wait = 0.0

        # --- work-skipping kernel audit (DESIGN.md §12): the fixed decode
        # grid walks NB window blocks per participating slot-step; the
        # descriptor-side extent derivation below mirrors the kernel's
        # scalar-prefetch meta, so `skipped` is exactly the predicated-off
        # share of `total` (0 when kernel_skip_extent is off).
        self._kernel_blocks_total = 0
        self._kernel_blocks_skipped = 0

        # --- pipelined dispatch state (DESIGN.md §3) ---
        self._inflight: Deque[dict] = deque()
        self._prev_nxt = jnp.zeros(ecfg.batch, jnp.int32)
        self._zero_feed = jnp.zeros(ecfg.batch, jnp.int32)
        if self.mesh is not None:
            # commit the device-side feedback chain to the replicated layout
            # up front: the executor's later outputs are committed replicated
            # arrays, and an uncommitted first-step operand would key a
            # second (spurious) compilation of the same executable
            self._prev_nxt = jax.device_put(self._prev_nxt, self._repl)
            self._zero_feed = jax.device_put(self._zero_feed, self._repl)
        # device-side feedback chain validity: True once a slot has emitted in
        # a step dispatched BY THIS ENGINE. A restored checkpoint starts with
        # a broken chain (no _prev_nxt) and re-seeds from host _last_token.
        self._feed_ok = np.zeros(ecfg.batch, bool)

        # --- persistent flat descriptor buffer + window-block cache -------
        # (vectorized assembly: numpy views into one flat buffer, rebuilt
        # incrementally, never reallocated)
        self._flat = np.zeros(D + control_plane_size(ecfg.batch), np.int32)
        self._pdescr = flat_descriptor_views(self._flat[:D], B, NB, CAP, MT, CB)
        self._cp = control_plane_views(self._flat, B, offset=D)
        self._tokens_buf = self._cp.host_tokens
        self._feed_buf = self._cp.feed_sampled
        self._rid_buf = self._cp.rids
        self._win_base_cache = np.full(ecfg.batch, -1, np.int64)
        self._win_dirty = np.ones(ecfg.batch, bool)
        self._win_groups = np.zeros(ecfg.batch, np.int64)
        self._win_nblocks = np.zeros(ecfg.batch, np.int64)
        self._merging = ecfg.mode != "paged"

        # --- host-tier swap machinery (DESIGN.md §8) --------------------
        # Block-indexed pools (block axis 1) are the swap payload; the host
        # backing store is allocated lazily on first swap-out. Gather and
        # scatter are padded to a fixed blocks_per_seq index width so each
        # direction compiles exactly once per pool key (padding targets
        # scratch block 0, whose contents are masked by contract).
        self.preemptions = 0
        self._committed_blocks = 0
        self._resume_pending = 0
        self._step_touched: set = set()
        self._host_kv: Dict[str, np.ndarray] = {}
        # block-indexed pool keys (block axis 1): the payload both the
        # host-tier swaps and the §9 COW tail copies move
        self._block_pool_keys = [k for k, v in self.pools.items()
                                 if getattr(v, "ndim", 0) >= 2
                                 and v.shape[1] == self.num_blocks] \
            if self.pager is not None else []
        self._swap_keys = self._block_pool_keys if self._host_tier else []
        if self._host_tier:
            self._swap_gather_fn = jax.jit(lambda pool, idx: pool[:, idx])
            self._swap_scatter_fn = jax.jit(
                lambda pool, idx, data: pool.at[:, idx].set(data),
                donate_argnums=(0,))
        # COW tail copy executor (§9): one padded block->block copy per
        # pool key, dispatched async on the donated pool chain (like
        # swap-in); padding copies scratch block 0 onto itself. Built for
        # ANY paged single-device engine — the legacy prefix_of hint path
        # needs it too whenever the shared prefix is not block-aligned.
        self._cow_copy_fn = None
        if self.pager is not None and self.mesh is None:
            self._cow_copy_fn = jax.jit(
                lambda pool, src, dst: pool.at[:, dst].set(pool[:, src]),
                donate_argnums=(0,))
        # fixed swap-transfer index width: a session can overshoot its token
        # need by up to a placement span (reserve takes whole spans while the
        # pool is comfortable), so the pad must cover blocks_per_seq + span
        self._swap_pad = self.blocks_per_seq + ecfg.span_blocks
        # async movement engine (DESIGN.md §11): double-buffered host
        # staging for swap-in scatters (one preallocated pair per pool key,
        # alternated across transfers) + cumulative blocking-movement time
        # (the per-step stall the deferred path is hiding)
        self._stage_in: Dict[str, List[np.ndarray]] = {}
        self._stage_sel = 0
        self.swap_stall_s = 0.0

        # metrics
        self.metrics: List[StepMetrics] = []
        self.frames_committed = 0
        self.steps_run = 0
        self.peak_reserved_kv = 0
        self.peak_active_kv = 0
        self.cum_wall = 0.0
        self._rid_to_sid: Dict[int, int] = {}

        # encdec: encoder-side prefill executor (separate from the audited
        # decode path; populates immutable cross-attention KV per admission)
        if cfg.family == "encdec":
            def _encode(params, enc_embeds):
                from repro.models import encdec as ed
                enc_out = ed.encode(params, cfg, enc_embeds)
                return ed.cross_kv(params, cfg, enc_out)
            self._encode_fn = jax.jit(_encode)
            self._set_cross = jax.jit(
                lambda pools, slot_onehot, ck, cv, elen: {
                    **pools,
                    "cross_k": jnp.where(slot_onehot[None, :, None, None, None],
                                         ck, pools["cross_k"]),
                    "cross_v": jnp.where(slot_onehot[None, :, None, None, None],
                                         cv, pools["cross_v"]),
                    "enc_len": jnp.where(slot_onehot, elen, pools["enc_len"]),
                })
        # set-up, not the first request with prompt work, pays these
        if self._chunked:
            self._compile_chunk_widths()

    # ------------------------------------------------------------------
    def submit(self, req: Request) -> None:
        if getattr(req, "stop_tokens", ()) and not self._sampled:
            raise ValueError(
                "per-request stop_tokens require sampled decode "
                "(greedy=False); legacy greedy mode is budget-EOS only. "
                "For argmax decode WITH stop tokens use greedy=False, "
                "temperature=0.")
        req.t_release = self.clock()
        self.sched.submit(req)

    def cancel(self, rid: int) -> bool:
        """Cancel a request wherever it lives (serving gateway, §14):

        * still waiting — drop it from the queue (it holds no resources);
        * preempted (host-resident, §8) — release its admission charge and
          prefix pins, close its swapped-out pager session (``trim`` frees
          host entries too);
        * active in a slot — drain the dispatch pipeline first (in-flight
          steps reference its blocks and still owe token readbacks), then
          retire the slot through the one retirement path, which frees
          device blocks, pins and the session exactly as an EOS would.

        ``finish_reason`` becomes "cancelled"; partial output stays on
        ``req.generated``. Returns False when rid is unknown or already
        finished. The pager's zero-leak invariant holds after any cancel
        (asserted in tests via ``pager.check_invariants()``)."""
        req = self.sched.requests.get(rid)
        if req is None or req.finish_reason:
            return False
        if req in self.sched.waiting:
            self.sched.waiting.remove(req)
            req.finish_reason = "cancelled"
            req.finish_wall = self.cum_wall
            req.finish_step = self.sched.step_idx
            self.sched.finished.append(req)
            self.cancelled += 1
            return True
        if req in self.sched.preempted:
            self.sched.preempted.remove(req)
            if self._host_tier:
                self._committed_blocks -= req.committed_blocks
            self._prefix_release(req)
            self._indexed_rids.discard(rid)
            if self.pager is not None and req.swap_sid >= 0:
                self._drain_out_fences()     # in-flight swap-outs must land
                self.pager.trim(req.swap_sid, close=True)
                req.swap_sid = -1
            req.finish_reason = "cancelled"
            req.finish_wall = self.cum_wall
            req.finish_step = self.sched.step_idx
            self.sched.finished.append(req)
            self.cancelled += 1
            return True
        for slot, st in enumerate(self.sched.slots):
            if st.rid == rid:
                self.flush()
                if self.sched.slots[slot].rid != rid:
                    return False             # the drain already retired it
                req.finish_reason = "cancelled"
                self._retire_slot(slot)
                self.cancelled += 1
                return True
        return False

    # ------------------------------------------------------------------
    def _admit(self, now: float) -> None:
        """Step-level admission gate (DESIGN.md §15). With continuous
        batching (the default) every call falls through to
        ``_admit_into_free_slots``: a slot freed by EOS retirement, cancel
        or preemption is refilled on the very next decode step, while the
        surviving slots keep stepping. The round-based baseline instead
        holds the scheduler at a barrier (``hold=True``, which audits the
        stall) until the current round has fully drained."""
        with spans.Span(spans.SCHED_ADMIT) as sp:
            if not self.e.continuous_batching and self.sched.active_slots():
                self.sched.admit(now, hold=True)
                n = 0
            else:
                n = self._admit_into_free_slots(now)
            sp.stats(admitted=n)

    def _admit_into_free_slots(self, now: float) -> int:
        """Admit into free slots; returns how many were admitted."""
        kv_ok = self._admission_ok if self._host_tier else None
        self._resume_pending = 0         # per-admit-call swap-in demand
        # an admission is "mid-round" when another slot is already decoding
        # — the case a round-based engine would have left this slot idle
        mid_round = bool(self.sched.active_slots())
        admitted = self.sched.admit(now, kv_ok=kv_ok)
        t_admit = self.clock() if admitted else 0.0
        for slot, req, sid in admitted:
            if req.t_admit is None:
                req.t_admit = t_admit    # a resume keeps its first admission
            self._win_dirty[slot] = True
            self._win_base_cache[slot] = -1
            self._feed_ok[slot] = False
            refresh_control_row(self._cp, slot, rid=req.rid)  # rng meta §13
            self._step_touched.add(slot)
            if mid_round:
                self.continuous_admits += 1
                self._mid_round[slot] = True
            if req.swap_sid >= 0 and req.swap_sid == sid:
                # resume from the host tier (DESIGN.md §8): swap the window
                # working set back onto device in merged groups and
                # re-attach — generation state rides the Request, so no
                # recompute. Blocks below the window stay host-resident.
                self._drain_out_fences()  # slots must hold real bytes
                s = self.pager.sessions[sid]
                assert s.swap_state == RES_HOST
                first_local = self._first_window_local(s, req.resume_len)
                pairs = self.pager.swap_in_begin(sid, first_local)
                if pairs:
                    self.transport.account_swap(pairs, direction="in")
                    self._swap_copy_in([p[0] for p in pairs],
                                       [p[1] for p in pairs])
                self.pager.swap_in_commit(sid)
                self._slot_sid[slot] = sid
                self._slot_len[slot] = req.resume_len
                self._last_token[slot] = req.resume_last_token
                req.swap_sid = -1
                if self.prefix_cache is not None:
                    # re-index (§9): the preempt dropped this prompt from
                    # the cache (swap eligibility required refcount 1);
                    # its device-resident full prompt blocks are committed
                    # KV again, so future admissions can share them
                    self._prefix_index(slot, req)
                continue
            self._slot_len[slot] = 0
            self._last_token[slot] = int(req.prompt[0]) if len(req.prompt) else 0
            if self.pager is not None:
                self.pager.open_session(sid)
                self._slot_sid[slot] = sid
                aliased = False
                if self.prefix_cache is not None:
                    # §9: automatic reuse — radix match over committed
                    # prompt blocks, COW alias, skip the covered prefill
                    aliased = self._prefix_admit(slot, req, sid)
                if not aliased and req.prefix_of is not None \
                        and req.prefix_len >= self.bt:
                    # legacy explicit hint path (trace-provided prefix_of)
                    src_sid = self._rid_to_sid.get(req.prefix_of)
                    if src_sid is not None and src_sid in self.pager.sessions \
                            and self._alias_src_resident(src_sid,
                                                         req.prefix_len):
                        n_share = req.prefix_len
                        if self._cow_copy_fn is None:
                            # no COW executor (sharded engine): share full
                            # blocks only, prefill the unaligned tail
                            n_share = (n_share // self.bt) * self.bt
                        if n_share >= self.bt:
                            self.pager.alias(src_sid, sid, n_share)
                            self._capture_cow(sid)
                            self._slot_len[slot] = \
                                self.pager.sessions[sid].length
                            req.prompt_pos = int(self._slot_len[slot])
                self._rid_to_sid[req.rid] = sid
            if self.fv is not None:
                self.fv.reset_slot(slot)
            if self.cfg.family == "encdec":
                enc = getattr(req, "enc_embeds", None)
                if enc is None:
                    enc = np.random.default_rng(req.rid).normal(
                        size=(1, 8, self.cfg.d_model)).astype(np.float32)
                ck, cv = self._encode_fn(self.params, jnp.asarray(enc))
                se = ck.shape[2]
                pad = self.pools["cross_k"].shape[2] - se
                ck = jnp.pad(ck, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))[:, 0]
                cv = jnp.pad(cv, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))[:, 0]
                onehot = jnp.arange(self.e.batch) == slot
                self.pools = self._set_cross(
                    self.pools, onehot, ck[:, None], cv[:, None],
                    jnp.full((self.e.batch,), se, jnp.int32))
                if self.mesh is not None:
                    # the (unsharded) encode path hands back single-device
                    # pools; restore the executor's expected placement
                    self.pools = jax.device_put(self.pools, self._pool_sh)
        if self._cow_pairs_step:
            # materialize this admit round's COW tails: ONE batched padded
            # copy per pool key, audited as its own group kind (§9)
            pairs, self._cow_pairs_step = self._cow_pairs_step, []
            self._cow_origin.clear()
            self.transport.account_cow(pairs)
            self._cow_copy(pairs)
        return len(admitted)

    # ------------------------------------------------------------------
    # prefix cache: admission match / prompt indexing / COW copies (§9)
    # ------------------------------------------------------------------
    def _capture_cow(self, sid: int) -> None:
        """Queue a fresh alias's pending COW tail copy for this admit
        round's batched execution (frame() would silently consume it).

        Chained same-round aliases (C aliases B which aliased A in the
        SAME round): C's copy source is B's dst block, which the batched
        scatter has not materialized yet — the gather reads the pre-update
        pool. COW copies are whole-block, so copying from the transitive
        ORIGIN block is exact; resolve the chain host-side."""
        cp = self.pager.sessions[sid].cow_pending
        if cp is not None and self._cow_copy_fn is not None:
            src, dst = cp
            src = self._cow_origin.get(src, src)
            self._cow_origin[dst] = src
            self._cow_pairs_step.append((src, dst))

    def _prefix_admit(self, slot: int, req, sid: int) -> bool:
        """Consult the radix index for req's prompt; on a usable match,
        COW-alias the matched chain and skip the covered prefill. At least
        the LAST prompt token always goes through the decode step (sampled
        -token semantics), so the alias covers min(match, len(prompt)-1)."""
        pc = self.prefix_cache
        m = pc.match(req.prompt)
        n_alias = min(m.tokens, max(0, len(req.prompt) - 1))
        if n_alias < self.bt:
            if len(req.prompt) > self.bt:
                pc.miss()                  # an indexable prompt found nothing
            self._reconcile_commit(req, 0)
            return False
        need = -(-n_alias // self.bt)
        try:
            self.pager.alias_blocks(sid, m.blocks[:need], n_alias)
        except (MemoryError, SwapRefused):
            # pool too tight for the COW tail block (or an impossible
            # host-resident cache block): forfeit the share — the normal
            # prefill path has its own pressure relief
            pc.miss()
            self._reconcile_commit(req, 0)
            return False
        self._capture_cow(sid)
        pc.hit(m.nodes[:need], n_alias)
        self._pinned_paths[req.rid] = m.nodes[:need]
        s = self.pager.sessions[sid]
        self._slot_len[slot] = s.length
        req.prompt_pos = int(s.length)
        self._reconcile_commit(req, (n_alias // self.bt))
        return True

    def _reconcile_commit(self, req, shared_blocks: int) -> None:
        """Re-stamp the §8 admission charge with the share that actually
        happened: the kv_ok gate discounted its own (earlier) cache peek,
        but the alias at admit time can cover fewer blocks — or none, when
        the COW tail allocation fails — and an under-charged request would
        let later bursts overshoot the watermark the host pool was sized
        by."""
        if not self._host_tier:
            return
        want = max(1, self._footprint_blocks(req) - shared_blocks)
        self._committed_blocks += want - req.committed_blocks
        req.committed_blocks = want

    def _prefix_index(self, slot: int, req) -> None:
        """Index a fully-prefilled prompt's committed full blocks. Called
        at the prefill->decode transition and again after a resume (§9
        re-index). Only the device-resident prefix is indexable: blocks
        cold-swapped to the host tier (or left there by a resume) stop the
        chain — the index must stay root-contiguous."""
        if req.rid in self._indexed_rids \
                or req.prompt_pos < len(req.prompt):
            return
        sid = int(self._slot_sid[slot])
        s = self.pager.sessions.get(sid)
        if s is None or s.trimmed_prefix_blocks:
            return
        npb = len(req.prompt) // self.bt
        dev = 0
        while dev < npb and dev < len(s.blocks) and s.blocks[dev] > 0:
            dev += 1
        if dev < 1:
            return
        self._indexed_rids.add(req.rid)
        self.prefix_cache.insert(np.asarray(req.prompt[:dev * self.bt]),
                                 s.blocks[:dev])

    def _prefix_release(self, req) -> None:
        """Unpin the request's matched path (retire/preempt); the cached
        blocks themselves stay indexed for the next match."""
        if self.prefix_cache is None:
            return
        path = self._pinned_paths.pop(req.rid, None)
        if path:
            self.prefix_cache.unpin_path(path)

    def _cow_copy(self, pairs) -> None:
        """Execute COW tail copies: one padded (src -> dst) block copy per
        block-indexed pool key, async on the donated pool chain — the next
        step consuming the pools orders after it, exactly like swap-in."""
        P = max(1, self.e.batch)
        for i0 in range(0, len(pairs), P):
            chunk = pairs[i0:i0 + P]
            src = np.zeros(P, np.int32)
            dst = np.zeros(P, np.int32)
            src[:len(chunk)] = [p[0] for p in chunk]
            dst[:len(chunk)] = [p[1] for p in chunk]
            jsrc, jdst = jnp.asarray(src), jnp.asarray(dst)
            for k in self._block_pool_keys:
                self.pools[k] = self._cow_copy_fn(self.pools[k], jsrc, jdst)

    # ------------------------------------------------------------------
    def _alias_src_resident(self, src_sid: int, prefix_len: int) -> bool:
        """COW aliasing shares PHYSICAL device blocks, so the source must
        have actually COMMITTED the prefix (a source admitted in the same
        step has written nothing yet — sharing its unwritten blocks would
        read uninitialized KV) and the whole shared prefix (including the
        partial-tail copy source) must be device-resident. A too-young,
        cold-swapped or preempted source (§8) simply forfeits the share —
        the new request prefills the prefix itself."""
        s = self.pager.sessions[src_sid]
        nb = prefix_len // self.bt + (1 if prefix_len % self.bt else 0)
        return (s.swap_state == RES_DEVICE
                and s.length >= prefix_len and len(s.blocks) >= nb
                and all(b > 0 for b in s.blocks[:nb]))

    # ------------------------------------------------------------------
    def _window_blocks(self, slot: int) -> (List[int], int):
        """Physical blocks covering the near window + window_base (tokens)."""
        t = int(self._slot_len[slot])              # position of current token
        lo = max(0, t + 1 - self.W)
        wb = (lo // self.bt) * self.bt
        if self.e.mode == "arena":
            base = self._arena_base[slot]
            first = wb // self.bt
            return [base + first + i for i in range(self.NB)], wb
        sid = int(self._slot_sid[slot])
        s = self.pager.sessions[sid]
        first_local = self._first_window_local(s, t)
        wb = (first_local + s.trimmed_prefix_blocks) * self.bt
        blocks = s.blocks[first_local:first_local + self.NB]
        # residency invariant (DESIGN.md §8): the compiled executor must
        # never observe a host-resident (sign-encoded) block
        assert all(b > 0 for b in blocks), \
            f"host-resident block in window: sid={sid} {blocks}"
        return blocks + [0] * (self.NB - len(blocks)), wb

    # ------------------------------------------------------------------
    def _farview_step(self, slot: int, t: int, descr) -> None:
        """Far-view policy for one slot/step: summarize + TRIM a completed
        chunk (sealed in this step's commit) and select the far table.
        Shared verbatim by the sync and pipelined paths so the depth A/B
        can never diverge here."""
        sid = int(self._slot_sid[slot])
        s = self.pager.sessions[sid]
        n_done = int(self.fv.n_chunks[slot])
        chunk_end = (n_done + 1) * self.e.sv_chunk
        if t + 1 - self.W >= chunk_end:
            first_local = (n_done * self.e.sv_chunk) // self.bt \
                - s.trimmed_prefix_blocks
            cb = s.blocks[first_local:first_local + self.chunk_blocks]
            descr.far_chunk_blocks[slot, :len(cb)] = cb
            descr.far_chunk_tokens[slot] = self.e.sv_chunk
            descr.far_do_summarize[slot] = 1
            descr.far_write_idx[slot] = self.fv.on_chunk_summarized(slot)
            # TRIM the summarized blocks (bounded budget)
            self.pager.trim(sid, prefix_blocks=first_local + self.chunk_blocks)
            self._win_dirty[slot] = True
        tbl, val = self.fv.select(slot)
        descr.far_table[slot] = tbl
        descr.far_valid[slot] = val

    # ------------------------------------------------------------------
    def _retire_slot(self, slot: int) -> None:
        """EOS retirement: return the slot + its blocks, clear caches."""
        req = self.sched.requests[self.sched.slots[slot].rid]
        req.finish_wall = self.cum_wall
        if not req.finish_reason:
            req.finish_reason = "budget"     # legacy dispatch-time budget EOS
        if self._host_tier:
            # release exactly what the admission gate charged (§9: the
            # charge was reduced by the aliased prefix at admission time)
            self._committed_blocks -= req.committed_blocks
        self._prefix_release(req)
        self._indexed_rids.discard(req.rid)      # rid never returns
        self.sched.retire(slot)
        if self.pager is not None:
            self.pager.trim(int(self._slot_sid[slot]), close=True)
            self._slot_sid[slot] = -1
        self._slot_len[slot] = 0
        self._feed_ok[slot] = False
        self._mid_round[slot] = False
        refresh_control_row(self._cp, slot, rid=0)
        d = self._pdescr
        d.block_table[slot, :] = 0
        d.train_len[slot, :] = 0
        d.window_base[slot] = 0
        self._win_base_cache[slot] = -1
        self._win_dirty[slot] = True
        self._win_groups[slot] = 0
        self._win_nblocks[slot] = 0

    # ------------------------------------------------------------------
    # host KV tier: swap data movement + preemption policy (DESIGN.md §8)
    # ------------------------------------------------------------------
    def _ensure_host_kv(self) -> None:
        if self._host_kv or not self._swap_keys:
            return
        for k in self._swap_keys:
            arr = self.pools[k]
            shp = (self.host_pool_blocks, arr.shape[0]) + tuple(arr.shape[2:])
            self._host_kv[k] = np.zeros(shp, arr.dtype)

    def _swap_copy_out(self, dev_blocks, host_slots, *, sid: int = -1) -> None:
        """Issue one swap-out transfer: ONE padded gather per pool key
        (device -> host). With ``async_movement`` (default) the host-side
        readback is DEFERRED behind a per-transfer fence (DESIGN.md §11):
        the gathers are dispatched now — XLA orders them before any later
        donated-pool overwrite, so the captured bytes are exact — and the
        host rows land only when something actually reads the host slots
        (resume, audit, or the next swap-in). ``sid >= 0`` marks a
        preemption transfer whose pager session must flip IN_FLIGHT_OUT ->
        HOST when the fence drains. With the flag off this is the PR-5
        blocking readback per pressure event."""
        self._ensure_host_kv()
        n = len(dev_blocks)
        idx = np.zeros(self._swap_pad, np.int32)
        idx[:n] = dev_blocks
        jidx = jnp.asarray(idx)
        gathers = {k: self._swap_gather_fn(self.pools[k], jidx)
                   for k in self._swap_keys}
        if self.e.async_movement:
            self.transport.fence_issue({"gathers": gathers, "n": n,
                                        "host_slots": list(host_slots),
                                        "sid": sid})
            return
        with spans.Span(spans.SWAP_WAIT) as sw:
            self._land_swap_out(gathers, host_slots, n)
        self.swap_stall_s += sw.seconds

    def _land_swap_out(self, gathers, host_slots, n: int) -> None:
        """Synchronize one swap-out's gathers into the host backing pool."""
        for k in self._swap_keys:
            got = np.asarray(gathers[k])
            self._host_kv[k][host_slots] = np.moveaxis(got[:, :n], 1, 0)

    def _drain_out_fences(self) -> None:
        """Synchronize every pending deferred swap-out readback, FIFO — a
        host slot freed and reallocated between two transfers must end up
        holding the LATER transfer's bytes, exactly like the synchronous
        schedule. Preemption transfers commit their session's
        IN_FLIGHT_OUT -> HOST edge here (DESIGN.md §11)."""
        pend = self.transport.fence_drain_all()
        if not pend:
            return
        with spans.Span(spans.SWAP_WAIT) as sw:
            for p in pend:
                self._land_swap_out(p["gathers"], p["host_slots"], p["n"])
                if p["sid"] >= 0:
                    self.pager.swap_out_commit(p["sid"])
        self.swap_stall_s += sw.seconds

    def _stage_buf(self, k: str) -> np.ndarray:
        """Preallocated, double-buffered host staging for one pool key's
        swap-in scatter (DESIGN.md §11): two fixed padded arrays alternated
        across transfers, so the device_put of transfer t can still be
        reading its buffer while transfer t+1 refills the other — and no
        per-event ``np.zeros`` allocation ever happens on the swap path."""
        bufs = self._stage_in.get(k)
        if bufs is None:
            arr = self.pools[k]
            shape = (arr.shape[0], self._swap_pad) + tuple(arr.shape[2:])
            bufs = [np.zeros(shape, self._host_kv[k].dtype) for _ in range(2)]
            self._stage_in[k] = bufs
        else:
            self.transport.account_staging_reuse(bufs[self._stage_sel].nbytes)
        return bufs[self._stage_sel]

    def _swap_copy_in(self, host_slots, dev_blocks) -> None:
        """Execute one swap-in transfer: ONE padded scatter per pool key
        (host -> device). The scatter is dispatched async on the pool chain
        (like token feedback), so it overlaps whatever the device is
        running; the next decode step consuming the pools orders after it.
        Staging rides the reusable double buffers (``_stage_buf``); any
        pending deferred swap-out drains first — these host slots may be
        exactly where its bytes land."""
        self._ensure_host_kv()
        self._drain_out_fences()
        n = len(dev_blocks)
        idx = np.zeros(self._swap_pad, np.int32)
        idx[:n] = dev_blocks
        jidx = jnp.asarray(idx)
        with spans.Span(spans.SWAP_WAIT) as sw:
            for k in self._swap_keys:
                arr = self.pools[k]
                if self.e.async_movement:
                    data = self._stage_buf(k)
                    data[:, :n] = np.moveaxis(
                        self._host_kv[k][host_slots], 0, 1)
                    data[:, n:] = 0      # padding targets scratch block 0
                else:
                    # A/B baseline: the PR-5 per-event allocation
                    data = np.zeros((arr.shape[0], self._swap_pad)
                                    + tuple(arr.shape[2:]),
                                    self._host_kv[k].dtype)
                    data[:, :n] = np.moveaxis(
                        self._host_kv[k][host_slots], 0, 1)
                # a snapshot of the staging buffer: the scatter may still be
                # queued when this buffer is reused two transfers later
                self.pools[k] = self._swap_scatter_fn(arr, jidx,
                                                      jnp.asarray(data.copy()))
            self._stage_sel ^= 1
        self.swap_stall_s += sw.seconds

    def _first_window_local(self, s, t: int) -> int:
        """Local block index where the near window starts for a session at
        logical length t (same math as _window_blocks)."""
        wb = (max(0, t + 1 - self.W) // self.bt) * self.bt
        wb = max(wb, s.trimmed_prefix_blocks * self.bt)
        return wb // self.bt - s.trimmed_prefix_blocks

    def _footprint_blocks(self, req) -> int:
        """Worst-case device blocks a request can reach. ``gen_len`` is a
        CAP, not a schedule: with sampled decode (§13) a detected stop
        token can retire the request much earlier, so this is an upper
        bound (exact up to span-placement slack only in legacy greedy mode,
        where budget-EOS makes the length deterministic)."""
        tokens = len(req.prompt) + req.gen_len + 1
        return -(-tokens // self.bt) + self.e.span_blocks

    def _admission_ok(self, req, is_resume: bool) -> bool:
        """Watermark admission gate (DESIGN.md §8). Fresh requests are
        admitted only while the committed worst-case footprint of all live
        requests fits in admit_wm * device + host blocks — this is what
        bounds host-tier demand so preemption can always find room. Resumes
        are already committed; they additionally need their window working
        set device-resident right now."""
        margin = self.e.span_blocks + 1
        if is_resume:
            s = self.pager.sessions[req.swap_sid]
            first_local = self._first_window_local(s, req.resume_len)
            need = sum(1 for b in s.blocks[first_local:] if b < 0)
            # reserve on accept: the swap-ins run only after ALL of this
            # admit() call's gate checks, so later resumes in the same call
            # must see earlier ones' demand or they jointly overshoot the
            # pool and swap_in_begin raises an uncatchable MemoryError
            if self.pager.free_blocks() < self._resume_pending + need + margin:
                # §9 pressure ladder, resume edition: prefix-cache pins can
                # hold the pool above the gate forever once nothing is
                # active to trigger reserve-time eviction — reclaim unshared
                # cached blocks before refusing, or the resume livelocks
                if self.prefix_cache is not None:
                    self.prefix_cache.evict(self._resume_pending + need
                                            + margin
                                            - self.pager.free_blocks())
                if self.pager.free_blocks() \
                        < self._resume_pending + need + margin:
                    return False
            self._resume_pending += need
            return True
        total_dev = self.num_blocks - 1
        capacity = (int(total_dev * self.e.admit_watermark)
                    + self.host_pool_blocks)
        # §9: blocks served from the prefix cache are SHARED — they are
        # already resident and charged (once) to the cache, so the gate
        # peeks the radix index and discounts them from both the committed
        # footprint and the immediate device headroom the prompt needs
        shared_tokens = 0
        if self.prefix_cache is not None:
            m = self.prefix_cache.match(req.prompt)
            shared_tokens = (min(m.tokens, max(0, len(req.prompt) - 1))
                             // self.bt) * self.bt
        footprint = max(1, self._footprint_blocks(req)
                        - shared_tokens // self.bt)
        if self._committed_blocks + footprint > capacity:
            return False
        # device headroom NOW: room for the (un-shared part of the) prompt
        # (capped at one window) plus growth slack, so a fresh admission
        # doesn't immediately preempt what it just queued behind
        need = min(-(-(len(req.prompt) + 1 - shared_tokens) // self.bt),
                   self.NB)
        if self.pager.free_blocks() < need + margin:
            return False
        # commit on accept (the scheduler admits immediately after a True):
        # later candidates in the SAME admit() call must see this request's
        # footprint or a burst could collectively overshoot the watermark
        self._committed_blocks += footprint
        req.committed_blocks = footprint
        return True

    def _cold_swap(self, target_free: int) -> None:
        """Swap below-window blocks to the host tier until ``target_free``
        device blocks are free: sessions with the largest cold backlog
        first, oldest (coldest) blocks within a session first. Shared (COW)
        and window blocks are never moved, so the compiled executor never
        observes the difference."""
        cands = []
        for slot in self.sched.active_slots():
            sid = int(self._slot_sid[slot])
            if sid < 0 or sid not in self.pager.sessions:
                continue
            s = self.pager.sessions[sid]
            fl = self._first_window_local(s, int(self._slot_len[slot]))
            cold = sum(1 for b in s.blocks[:fl]
                       if b > 0 and self.pager.refcount[b] == 1)
            if cold:
                cands.append((cold, slot, sid, fl))
        for cold, slot, sid, fl in sorted(cands, reverse=True):
            if self.pager.free_blocks() >= target_free:
                return
            try:
                pairs = self.pager.swap_out_cold(sid, fl)
            except MemoryError:
                return                        # host pool full: nothing to do
            if pairs:
                self.transport.account_swap(pairs, direction="out")
                self._swap_copy_out([p[0] for p in pairs],
                                    [p[1] for p in pairs])

    def _memory_pressure_pass(self) -> None:
        """Step-start watermark check: above the high watermark, cold-swap
        down toward the low watermark so reactive preemption stays rare."""
        if not self._host_tier:
            return
        total = self.num_blocks - 1
        with spans.Span(spans.PAGER_PRESSURE):
            if (total - self.pager.free_blocks()) / total \
                    > self.e.swap_high_watermark:
                self._cold_swap(int(np.ceil(
                    (1.0 - self.e.swap_low_watermark) * total)))

    def _swap_victim(self) -> Optional[int]:
        """Latest-admitted swap-eligible active slot (protects the oldest
        work, which is closest to completion); slots already assembled into
        THIS step's descriptor are never victims — their rows reference
        blocks the swap would free."""
        cands = []
        for slot in self.sched.active_slots():
            if slot in self._step_touched:
                continue
            sid = int(self._slot_sid[slot])
            if sid >= 0 and self.pager.swap_eligible(sid):
                req = self.sched.request_at(slot)
                cands.append((req.start_step, req.rid, slot))
        return max(cands)[2] if cands else None

    def _preempt_slot(self, slot: int) -> None:
        """Evict a request to the host tier: drain the pipeline (its
        sampled-token values must land before the slot state is captured),
        swap the whole session out, and re-queue the request for resume."""
        self.flush()
        req = self.sched.request_at(slot)
        if req is None:
            # sampled mode (§13): the flush's drained readbacks can detect
            # this victim's stop token and retire it — its blocks are
            # already free, which is exactly what the caller wanted
            return
        sid = int(self._slot_sid[slot])
        deferred = bool(self.e.async_movement)
        pairs = self.pager.swap_out_session(sid, deferred=deferred)
        assert pairs is not None, "victim was not swap-eligible"
        if pairs:
            self.transport.account_swap(pairs, direction="out")
            self._swap_copy_out([p[0] for p in pairs],
                                [p[1] for p in pairs],
                                sid=sid if deferred else -1)
        req.swap_sid = sid
        req.resume_len = int(self._slot_len[slot])
        req.resume_last_token = int(self._last_token[slot])
        # drop the prompt from the prefix index bookkeeping so the resume
        # path re-indexes what comes back device-resident (§9), and
        # re-stamp the admission charge at FULL footprint: swap-out gave
        # the session exclusive ownership of every block (prefix included,
        # now in host slots), so the shared-prefix discount no longer holds
        self._prefix_release(req)
        self._indexed_rids.discard(req.rid)      # resume re-indexes
        self._reconcile_commit(req, 0)
        self.sched.preempt(slot)
        self.preemptions += 1
        self._slot_sid[slot] = -1
        self._slot_len[slot] = 0
        self._feed_ok[slot] = False
        self._mid_round[slot] = False
        refresh_control_row(self._cp, slot, rid=0)
        d = self._pdescr
        d.block_table[slot, :] = 0
        d.train_len[slot, :] = 0
        d.window_base[slot] = 0
        self._win_base_cache[slot] = -1
        self._win_dirty[slot] = True
        self._win_groups[slot] = 0
        self._win_nblocks[slot] = 0

    def _ensure_step_capacity(self) -> None:
        """Preemption-aware scheduling pass (DESIGN.md §8), run BEFORE any
        token is consumed or descriptor row assembled: total up the device
        blocks this step's reservations will need (decode lookahead + prompt
        chunks) and, if the pool can't cover them, cold-swap then preempt
        latest-admitted victims until it can. Running it up front means a
        victim can be ANY active slot — once assembly starts, assembled
        slots are pinned (their descriptor rows reference their blocks)."""
        if not self._host_tier:
            return
        with spans.Span(spans.PAGER_PRESSURE):
            while True:
                need = 0
                for slot in self.sched.active_slots():
                    sid = int(self._slot_sid[slot])
                    if sid < 0:
                        continue
                    if self._chunked and \
                            self.sched.chunk_remaining(slot) >= self._chunk_min:
                        n_tok = min(self.chunk, self.sched.chunk_remaining(slot))
                    else:
                        n_tok = 2                  # this token + lookahead
                    need += self.pager.blocks_needed(sid, n_tok)
                if self.pager.free_blocks() >= need:
                    return
                self._cold_swap(need)
                if self.pager.free_blocks() >= need:
                    return
                # §9 pressure ladder: before preempting live work, reclaim
                # prefix-cache blocks — unpinned unshared cold leaves free
                # device blocks outright
                if self.prefix_cache is not None:
                    self.prefix_cache.evict(need - self.pager.free_blocks())
                    if self.pager.free_blocks() >= need:
                        return
                victim = self._swap_victim()
                if victim is None:
                    # no swap-eligible victim: cached shares may be what holds
                    # every session's refcounts above 1 — flush the index
                    # (sessions keep their own refs; only reuse is lost) and
                    # retry the whole ladder once more
                    if self.prefix_cache is not None \
                            and self.prefix_cache.flush_for_pressure():
                        continue
                    return                         # backstop: _reserve raises
                self._preempt_slot(victim)         # loop: recompute without it

    def _reserve(self, slot: int, sid: int, n_tokens: int):
        """pager.reserve with preemption-aware pressure relief: on device
        exhaustion, cold-swap first, then preempt latest-admitted eligible
        victims until the reservation fits (MemoryError only when neither
        can free enough — e.g. host pool exhausted too). The step-start
        capacity pass makes this a rare backstop."""
        if not self._host_tier and self.prefix_cache is None:
            return self.pager.reserve(sid, n_tokens)
        try:
            return self.pager.reserve(sid, n_tokens)
        except MemoryError:
            need = self.pager.blocks_needed(sid, n_tokens)
            if self._host_tier:
                self._cold_swap(need)
            if self.prefix_cache is not None \
                    and self.pager.free_blocks() < need:
                self.prefix_cache.evict(need - self.pager.free_blocks())
            while self.pager.free_blocks() < need:
                victim = self._swap_victim() if self._host_tier else None
                if victim is None or victim == slot:
                    if self.prefix_cache is not None \
                            and self.prefix_cache.flush_for_pressure():
                        continue             # un-shared: retry victims/free
                    raise
                self._preempt_slot(victim)   # may raise: host pool full
            return self.pager.reserve(sid, n_tokens)

    # ------------------------------------------------------------------
    def _prefill_chunks(self) -> None:
        """Ingest up to ``prefill_chunk`` prompt tokens per prefilling slot
        through the batched chunked prefill executor: ONE dispatch per engine
        step covering every slot with chunk work. Each such slot takes the
        next free row, in slot order; the call runs at the smallest compiled
        row width that holds them, its other rows masked by n_valid=0.
        Reservations are sealed by THIS step's single frame commit."""
        C = self.chunk
        cd = self._cdescr
        self._chunk_wait = 0.0
        with spans.Span(spans.PREFILL_ASSEMBLE) as sp:
            rows = tokens = 0
            for slot in self.sched.active_slots():
                if self.sched.chunk_remaining(slot) < self._chunk_min:
                    continue
                self._step_touched.add(slot)
                self.sched.request_at(slot).prompt_chunk_steps += 1
                toks = self.sched.consume_prompt_chunk(slot, C)
                n = len(toks)
                r = rows
                rows += 1
                tokens += n
                t0 = int(self._slot_len[slot])
                if self.e.mode == "arena":
                    base = self._arena_base[slot]
                    idx = t0 + np.arange(n)
                    wblk = (base + idx // self.bt).astype(np.int32)
                    woff = (idx % self.bt).astype(np.int32)
                else:
                    sid = int(self._slot_sid[slot])
                    self._reserve(slot, sid, n)
                    wblk, woff = self.pager.append_tokens(sid, n)
                # context = the near window as seen by the chunk's FIRST
                # query; later queries only need a suffix of it (masked
                # in-kernel)
                blocks, wb = self._window_blocks(slot)
                cd.tokens[r, :n] = toks
                cd.tokens[r, n:] = 0
                cd.start_pos[r] = t0
                cd.n_valid[r] = n
                cd.block_table[r] = blocks
                cd.window_base[r] = wb
                cd.write_block[r, :n] = wblk
                cd.write_block[r, n:] = 0
                cd.write_offset[r, :n] = woff
                cd.write_offset[r, n:] = 0
                self._slot_len[slot] += n
                self._win_dirty[slot] = True
                self._chunk_steps += 1
            cd.n_valid[rows:] = 0
            width = chunk_row_width(self._chunk_widths, rows) if rows else 0
            sp.stats(rows=rows, tokens=tokens, row_width=width)
        if rows:
            with spans.Span(spans.CHUNK_DISPATCH) as dsp:
                self.pools = self._chunk_fn(
                    self.params, self.pools,
                    jnp.asarray(pack_chunk_rows(cd, width)))
            # dispatch can block on the runtime's in-flight queue while the
            # PREVIOUS step still executes — that wait is device occupancy,
            # not host control work; the pipelined path subtracts it from
            # m.host so submit_share keeps measuring the control plane
            self._chunk_wait = dsp.seconds

    def _compile_chunk_widths(self) -> None:
        """Compile the chunk executor at every row width, so that no step
        compiles whichever width it needs. Compiling ahead of time fills
        the same cache the jitted function's calls look in."""
        for w in self._chunk_widths:
            flat = np.zeros(chunk_flat_size(w, self.chunk, self.NB), np.int32)
            self._chunk_jit.lower(self.params, self.pools,
                                  jnp.asarray(flat)).compile()

    # ------------------------------------------------------------------
    def step(self, now: float = float("inf")) -> StepMetrics:
        with spans.step_span(self.steps_run) as sp:
            if self.depth <= 0:
                m = self._step_sync(now, sp)
            else:
                m = self._step_pipelined(now, sp)
            sp.stats(active=m.active, emitted=m.emitted)
        return m

    # ------------------------------------------------------------------
    def _account_kernel_blocks(self, window_base, seq_lens, slot_active):
        """Integrate the decode kernel's padded-vs-active block counts over
        this step's participating slots (descriptor-side host math — the
        same derivation the kernel receives as scalar-prefetch meta).
        Returns the per-slot skipped counts (aligned with the input rows)
        when skip predication is on, else None — the pipelined sampled
        path records them per dispatch so a lagged-EOS scrub (§13) can
        reverse this step's share exactly."""
        n = len(window_base)
        if n == 0:
            return None
        self._kernel_blocks_total += self.NB * n
        if self.e.kernel_skip_extent:
            lo, hi = active_block_extents(
                window_base, seq_lens, slot_active,
                near_window=self.W, nb=self.NB, bt=self.bt)
            skipped = self.NB - (hi - lo)
            self._kernel_blocks_skipped += int(skipped.sum())
            return skipped
        return None

    # ------------------------------------------------------------------
    def _step_sync(self, now: float, sp: spans.Span) -> StepMetrics:
        """Seed-exact synchronous step: per-slot descriptor assembly, one
        blocking readback per step (pipeline_depth=0 A/B baseline). ``sp``
        is the step's span; its clock times ``m.host`` and ``m.wall``."""
        m = StepMetrics()
        self.sched.step_idx = self.steps_run

        # ---- Shift: retire EOS (handled at end of prev step), admit
        self._step_touched = set()
        self._memory_pressure_pass()
        self._admit(now)
        self._ensure_step_capacity()
        if self._chunked:
            self._prefill_chunks()
        active = self.sched.active_slots()
        m.active = len(active)

        with spans.Span(spans.DECODE_ASSEMBLE):
            descr, tokens, parts, prompt_done = self._assemble_sync(active, m)

        # ---- Frame: single atomic commit
        with spans.Span(spans.FRAME_COMMIT) as fc:
            if self.pager is not None:
                frame = self.pager.frame()
                descr = descr._replace(epoch=np.int32(frame["epoch"]))
                self.frames_committed += 1
            else:
                descr = descr._replace(epoch=np.int32(self.steps_run + 1))
        m.frame_commit = fc.seconds

        with spans.Span(spans.DECODE_DISPATCH):
            jdescr = FrameDescriptor(*[jnp.asarray(a) for a in descr])
            # chunk-dispatch queue wait is device occupancy, not control
            # work (zero when prefill_chunk=0, keeping the seed path
            # bit-exact)
            m.host = max(0.0, sp.elapsed() - self._chunk_wait)

            # ---- device: one engine call, fixed shapes
            self.transport.note_dispatch_overlap()
            nxt, self.pools, fu, lg = self._dispatch_step(
                self.params, jnp.asarray(tokens), self._zero_feed,
                jnp.asarray(self._rid_buf), self._prev_nxt, self.pools, jdescr)
        self._prev_nxt = nxt
        if prompt_done:
            t = self.clock()
            for req in prompt_done:
                req.t_prompt_done = t
        with spans.Span(spans.READBACK) as rb:
            with spans.Span(spans.DEVICE_WAIT):
                nxt = np.asarray(jax.block_until_ready(nxt))
            if self.e.debug_logits:
                self.debug_logits.append(np.asarray(lg, np.float32))
            self._post_sync(parts, nxt, lg, fu, descr, m)
            rb.stats(emits=m.emitted)

        self.steps_run += 1
        m.wall = sp.elapsed()
        self.cum_wall += m.wall
        self.peak_reserved_kv = max(self.peak_reserved_kv, self.reserved_kv_bytes())
        self.peak_active_kv = max(self.peak_active_kv, self.active_kv_bytes())
        self.metrics.append(m)
        return m

    def _assemble_sync(self, active, m: StepMetrics):
        """Per-slot descriptor assembly of the synchronous step. Returns the
        descriptor, the host tokens, the participating slots and the
        requests whose last prompt token rides this step."""
        B = self.e.batch
        descr = empty_descriptor(B, self.NB, self.cap, self.MT,
                                 chunk_blocks=self.chunk_blocks)
        tokens = np.zeros(B, np.int32)

        parts = []                       # slots participating in this step
        prompt_done = []
        for slot in active:
            req = self.sched.request_at(slot)
            if req is None:
                continue                 # preempted mid-step by a neighbour
            if self._chunked and \
                    self.sched.chunk_remaining(slot) >= self._chunk_min:
                continue                 # still mid-chunk: no decode this step
            parts.append(slot)
            self._step_touched.add(slot)
            was_prefilling = self.sched.is_prefilling(slot)
            tokens[slot] = self.sched.next_token(slot, int(self._last_token[slot]))
            if was_prefilling:
                req.prompt_tail_steps += 1
                if req.prompt_pos >= len(req.prompt):
                    prompt_done.append(req)
            if self.prefix_cache is not None and was_prefilling \
                    and req.prompt_pos >= len(req.prompt):
                self._prefix_index(slot, req)    # prompt committed: index
            t = int(self._slot_len[slot])
            descr.seq_lens[slot] = t
            descr.slot_active[slot] = 1

            # ---- Stage: BLOCKALIGN reservation (prefetch-1 lookahead)
            if self.e.mode == "arena":
                base = self._arena_base[slot]
                bi, off = divmod(t, self.bt)
                descr.write_block[slot] = base + bi
                descr.write_offset[slot] = off
            else:
                sid = int(self._slot_sid[slot])
                self._reserve(slot, sid, 2)       # this token + lookahead
                blk, off = self.pager.append_token(sid)
                descr.write_block[slot] = blk
                descr.write_offset[slot] = off

            # ---- far-view: chunk completion -> summarize + trim
            if self.fv is not None:
                self._farview_step(slot, t, descr)

            # ---- window table + Reduce (train merging)
            blocks, wb = self._window_blocks(slot)
            descr.block_table[slot, :len(blocks)] = blocks
            descr.window_base[slot] = wb
            trains, groups = self.transport.reduce(
                blocks, far_blocks=int(descr.far_valid[slot].sum() > 0),
                merging=self._merging)
            self.transport.fill_train_arrays(
                trains, descr.train_start, descr.train_len, descr.train_dst, slot)
            m.dma_groups += groups

        if parts:
            self._account_kernel_blocks(descr.window_base[parts],
                                        descr.seq_lens[parts],
                                        descr.slot_active[parts])
            # §15: each participating mid-round-admitted slot is one
            # slot-step a round barrier would have left idle
            self.slot_idle_steps_saved += int(self._mid_round[parts].sum())
        return descr, tokens, parts, prompt_done

    def _post_sync(self, parts, nxt, lg, fu, descr, m: StepMetrics) -> None:
        """The synchronous step's post-device bookkeeping: token values,
        EOS retirement (burst-safe), far-view utility."""
        for slot in parts:
            self._slot_len[slot] += 1
            if self.sched.is_prefilling(slot):
                continue
            self._last_token[slot] = int(nxt[slot])
            req_t = self.sched.request_at(slot)
            if req_t is not None and req_t.first_token_step < 0:
                req_t.ttft_wall = self.cum_wall
            if self.e.debug_logits:
                req = self.sched.request_at(slot)
                if not hasattr(req, "logit_trace"):
                    req.logit_trace = []
                req.logit_trace.append(np.asarray(lg[slot], np.float32))
            req_s = self.sched.request_at(slot)
            done = self.sched.record_output(slot, int(nxt[slot]))
            m.emitted += 1
            if done:
                if req_s is not None and req_s.eos_hit:
                    self.eos_detected += 1
                self._retire_slot(slot)
            if self.token_hook is not None and req_s is not None:
                self.token_hook(req_s, int(nxt[slot]), done)
        if self.fv is not None:
            self.fv.observe_utility(np.asarray(fu), np.asarray(descr.far_table))

    # ------------------------------------------------------------------
    def _step_pipelined(self, now: float, sp: spans.Span) -> StepMetrics:
        """Overlapped step: assemble + dispatch step t, then read back step
        t-depth while the device runs. Descriptor assembly is vectorized over
        slots with an incrementally maintained window-block/train cache —
        per-slot Python work happens only on admit/trim/alias/reserve or a
        window slide, not every step. ``sp`` is the step's span; its clock
        times ``m.host`` and ``m.wall``."""
        m = StepMetrics()
        self.sched.step_idx = self.steps_run

        self._step_touched = set()
        self._memory_pressure_pass()
        self._admit(now)
        self._ensure_step_capacity()
        if self._chunked:
            self._prefill_chunks()
        active = self.sched.active_slots()
        m.active = len(active)

        with spans.Span(spans.DECODE_ASSEMBLE):
            parts, emits, eos_meta, prompt_done = \
                self._assemble_pipelined(active, m)

        # ---- Frame: single atomic commit
        d = self._pdescr
        with spans.Span(spans.FRAME_COMMIT) as fc:
            if self.pager is not None:
                frame = self.pager.frame()
                d.epoch[...] = frame["epoch"]
                self.frames_committed += 1
            else:
                d.epoch[...] = self.steps_run + 1
        m.frame_commit = fc.seconds

        with spans.Span(spans.DECODE_DISPATCH):
            # ONE host->device transfer, of a snapshot: jax may read (or, on
            # the CPU, alias) a host array after dispatch returns, and _flat
            # is rewritten in place for the next step while this one is in
            # flight
            jflat = jnp.asarray(self._flat.copy())
            # chunk-dispatch queue wait is device occupancy, not control work
            m.host = max(0.0, sp.elapsed() - self._chunk_wait)

            # ---- device: dispatch step t (async), keep host moving
            self.transport.note_dispatch_overlap()
            nxt, self.pools, fu, lg = self._dispatch_step(
                self.params, jflat, self._prev_nxt, self.pools)
        self._prev_nxt = nxt
        if prompt_done:
            t = self.clock()
            for req in prompt_done:
                req.t_prompt_done = t

        # ---- structural bookkeeping at DISPATCH time. Legacy greedy: EOS
        # is the fixed gen_len budget, so retirement is host-predictable
        # here and pager/transport timelines stay bit-identical to the
        # synchronous path. Sampled (§13): EOS is data-dependent, so NOTHING
        # retires at dispatch — stop AND budget retirement both happen at
        # readback, where overshot dispatches are scrubbed via ``eos_meta``.
        # Token VALUES land at readback either way, ``depth`` steps later.
        m.emitted = len(emits)
        for slot in parts:
            self._slot_len[slot] += 1
        for slot, req in emits:
            self._feed_ok[slot] = True
            if self.sched.note_emit(slot) and not self._sampled:
                self._retire_slot(slot)

        self._inflight.append({
            "nxt": nxt, "lg": lg, "fu": fu, "emits": emits,
            "m": m, "eos": eos_meta,
            "far_table": d.far_table.copy() if self.fv is not None else None,
        })
        while len(self._inflight) > self.depth:
            self._readback(self._inflight.popleft())

        self.steps_run += 1
        m.wall = sp.elapsed()
        self.cum_wall += m.wall
        self.peak_reserved_kv = max(self.peak_reserved_kv, self.reserved_kv_bytes())
        self.peak_active_kv = max(self.peak_active_kv, self.active_kv_bytes())
        self.metrics.append(m)
        return m

    def _assemble_pipelined(self, active, m: StepMetrics):
        """Vectorized descriptor assembly of the pipelined step into the
        persistent flat buffer. Returns the participating slots, the
        (slot, request) pairs emitting this step, the sampled-mode
        overshoot snapshot and the requests whose last prompt token rides
        this step."""
        d = self._pdescr
        tokens = self._tokens_buf
        feed = self._feed_buf
        tokens[:] = 0
        feed[:] = 0
        d.slot_active[:] = 0
        if self.fv is not None:
            d.far_chunk_blocks[:] = 0
            d.far_chunk_tokens[:] = 0
            d.far_do_summarize[:] = 0
            d.far_write_idx[:] = 0

        parts: List[int] = []
        emits: List[tuple] = []          # (slot, req) emitting this step
        prompt_done: List[Request] = []
        resv: Dict[int, list] = {}       # slot -> blocks THIS step reserved
        kskip = None                     # per-slot kernel blocks predicated
        far_flags = None
        for slot in active:
            req = self.sched.request_at(slot)
            if req is None:
                continue                 # preempted mid-step by a neighbour
            if self._chunked and \
                    self.sched.chunk_remaining(slot) >= self._chunk_min:
                continue                 # still mid-chunk: no decode this step
            self._step_touched.add(slot)
            was_prefilling = req.prompt_pos < len(req.prompt)
            tokens[slot] = self.sched.next_token(slot, int(self._last_token[slot]))
            if was_prefilling:
                req.prompt_tail_steps += 1
            if not was_prefilling and req.emitted > 0 and self._feed_ok[slot]:
                # decode continuation: token comes from the device-side argmax
                # of the previous dispatched step (one-step lag, no readback).
                # _feed_ok is False right after checkpoint restore: the chain
                # re-seeds from the host _last_token mirror for one step.
                feed[slot] = 1
            d.slot_active[slot] = 1
            parts.append(slot)
            if req.prompt_pos >= len(req.prompt):
                emits.append((slot, req))
                if was_prefilling:
                    prompt_done.append(req)
                    if self.prefix_cache is not None:
                        self._prefix_index(slot, req)    # prompt committed

            t = int(self._slot_len[slot])
            if self.e.mode == "arena":
                base = self._arena_base[slot]
                bi, off = divmod(t, self.bt)
                d.write_block[slot] = base + bi
                d.write_offset[slot] = off
            else:
                sid = int(self._slot_sid[slot])
                newb = self._reserve(slot, sid, 2)  # this token + lookahead
                if newb:
                    self._win_dirty[slot] = True  # new tail block in window
                    if self._sampled:
                        resv[slot] = newb         # §13 overshoot reconcile
                blk, off = self.pager.append_token(sid)
                d.write_block[slot] = blk
                d.write_offset[slot] = off

            if self.fv is not None:
                self._farview_step(slot, t, d)

        # ---- vectorized window/train maintenance (dirty rows only)
        d.seq_lens[:] = self._slot_len
        if parts:
            pa = np.asarray(parts)
            lo = np.maximum(0, self._slot_len[pa] + 1 - self.W)
            wb_vec = (lo // self.bt) * self.bt
            # dirty when the window ADVANCES past the cached base; far-view
            # trims clamp the real base above wb_vec (those set _win_dirty
            # explicitly), so `>` avoids perpetual recomputes after a trim
            dirty = self._win_dirty[pa] | (wb_vec > self._win_base_cache[pa])
            dirty_slots = [int(s) for s in pa[dirty]]
            if dirty_slots:
                blocks_rows = []
                for slot in dirty_slots:
                    blocks, wb_s = self._window_blocks(slot)
                    d.block_table[slot, :] = blocks
                    d.window_base[slot] = wb_s
                    blocks_rows.append([b for b in blocks if b > 0])
                    self._win_base_cache[slot] = wb_s
                    self._win_dirty[slot] = False
                trains_rows = self.transport.reduce_batch(
                    blocks_rows, merging=self._merging)
                self.transport.fill_train_arrays_batch(
                    trains_rows, d.train_start, d.train_len, d.train_dst,
                    dirty_slots)
                for slot, nz, trains in zip(dirty_slots, blocks_rows,
                                            trains_rows):
                    self._win_groups[slot] = len(trains)
                    self._win_nblocks[slot] = len(nz)
            far_flags = ((d.far_valid[pa].sum(axis=1) > 0).astype(np.int64)
                         if self.fv is not None else np.zeros(len(pa), np.int64))
            self.transport.account_batch(self._win_nblocks[pa],
                                         self._win_groups[pa], far_flags)
            m.dma_groups = int(self._win_groups[pa].sum() + far_flags.sum())
            kskip = self._account_kernel_blocks(d.window_base[pa],
                                                d.seq_lens[pa],
                                                d.slot_active[pa])
            # §15: each participating mid-round-admitted slot is one
            # slot-step a round barrier would have left idle
            self.slot_idle_steps_saved += int(self._mid_round[pa].sum())

        # sampled decode (§13): snapshot each emitting slot's share of THIS
        # step's pager/transport/kernel accounting so a lagged detected-EOS
        # readback can reverse the overshoot dispatches exactly
        eos_meta = None
        if self._sampled and emits:
            idx = {slot: i for i, slot in enumerate(parts)}
            eos_meta = {}
            for slot, _req in emits:
                i = idx[slot]
                eos_meta[slot] = {
                    # ownership stamp (§15): a slot re-admitted inside the
                    # pipeline-lag window must never be scrubbed by its
                    # PREDECESSOR's overshoot — _scrub_overshoot checks it
                    "rid": _req.rid,
                    "sid": (int(self._slot_sid[slot])
                            if self.e.mode != "arena" else -1),
                    "newb": resv.get(slot, []),
                    "nblocks": int(self._win_nblocks[slot]),
                    "groups": int(self._win_groups[slot]),
                    "far": int(far_flags[i]) if far_flags is not None else 0,
                    "kskip": int(kskip[i]) if kskip is not None else 0,
                }
        return parts, emits, eos_meta, prompt_done

    # ------------------------------------------------------------------
    def _dispatch_step(self, *args):
        """Run the decode executor; the first call also records its
        operands' shapes and placements for :meth:`decode_executor_hlo`."""
        if self._step_sig is None:
            self._step_sig = jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                               sharding=a.sharding), args)
        return self._step_fn(*args)

    def decode_executor_hlo(self) -> str:
        """Compiled HLO text of the decode executor at its fixed shapes (on
        TPU the attention kernel shows as ``tpu_custom_call``). Compiles
        ahead of time; the executor's own cache and the audited compile
        count are untouched. Needs one dispatched step."""
        if self._step_sig is None:
            raise RuntimeError("no decode step dispatched yet")
        return self._step_fn.lower(*self._step_sig).compile().as_text()

    # ------------------------------------------------------------------
    def _readback(self, rec: dict) -> float:
        """Value bookkeeping for one in-flight step: sampled tokens, logit
        traces, far-view utility feedback (one step of lag under pipelining).
        Returns the seconds it took."""
        with spans.Span(spans.READBACK, emits=len(rec["emits"])) as sp:
            with spans.Span(spans.DEVICE_WAIT):
                nxt = np.asarray(jax.block_until_ready(rec["nxt"]))
            lg = None
            if self.e.debug_logits:
                lg = np.asarray(rec["lg"], np.float32)
                self.debug_logits.append(lg)
            for slot, req in rec["emits"]:
                tok = int(nxt[slot])
                req.generated.append(tok)
                # wall-clock latencies stamp when the VALUE is known (readback),
                # not at dispatch — comparable with the synchronous path and
                # never flattered by the one-step pipeline lag
                if len(req.generated) == 1:
                    req.ttft_wall = self.cum_wall
                fin = False
                if not self._sampled and req.emitted >= req.gen_len \
                        and len(req.generated) >= req.gen_len:
                    req.finish_wall = self.cum_wall
                    fin = True
                if lg is not None:
                    if not hasattr(req, "logit_trace"):
                        req.logit_trace = []
                    req.logit_trace.append(lg[slot])
                if self.sched.slots[slot].rid == req.rid:
                    self._last_token[slot] = tok
                if self._sampled:
                    # sampled decode (§13): ALL retirement is readback-side.
                    # The host learns of a stop ``depth`` steps late — scrub
                    # the overshoot dispatches still in flight, then retire.
                    stop = bool(req.stop_tokens) and tok in req.stop_tokens
                    if stop or len(req.generated) >= req.gen_len:
                        req.eos_hit = stop
                        req.finish_reason = "stop" if stop else "budget"
                        if stop:
                            self.eos_detected += 1
                        assert self.sched.slots[slot].rid == req.rid, \
                            "sampled mode never retires at dispatch"
                        self._scrub_overshoot(slot, req)
                        self._retire_slot(slot)
                        fin = True
                if self.token_hook is not None:
                    self.token_hook(req, tok, fin)
            if self.fv is not None:
                self.fv.observe_utility(np.asarray(rec["fu"]), rec["far_table"])
        return sp.seconds

    def _scrub_overshoot(self, slot: int, req) -> None:
        """Reverse every in-flight dispatch issued for ``req`` AFTER its
        finishing token (DESIGN.md §13). Each scrubbed emit undoes exactly
        what its dispatch accounted: the scheduler's structural emission,
        the pager's append (and any tail block that step's reserve
        committed), the transport's per-slot window traffic, and the
        kernel-block integrals. Newest-first so tail-block pops at
        depth > 1 unwind in LIFO order. Freeing a tail block that an
        in-flight device step still references is safe: donated-pool
        chaining serializes device steps, and a decode tail block is never
        shared or cold-swapped (refcount 1) — asserted by the pager.
        Known limit: pressure-relief side effects (cold-swap, preemption)
        triggered BY an overshoot step's reserve are not reversed."""
        for rec in reversed(self._inflight):
            hit = next((p for p in rec["emits"] if p[1] is req), None)
            if hit is None:
                continue
            rec["emits"].remove(hit)
            meta = rec["eos"][slot]
            # §15 slot-reuse-inside-lag-window guard: emits matched by
            # ``req`` identity above, so a successor admitted into this
            # slot while the overshoot was still in flight can never be
            # scrubbed here — the rid stamp makes that contract checkable
            assert meta["rid"] == req.rid, \
                (f"§15 scrub ownership violated: slot {slot} eos_meta "
                 f"stamped rid={meta['rid']} but scrubbing rid={req.rid}")
            req.emitted -= 1
            self._slot_len[slot] -= 1
            self.eos_overshoot_tokens += 1
            rec["m"].emitted -= 1
            rec["m"].dma_groups -= meta["groups"] + meta["far"]
            if self.pager is not None:
                self.pager.reconcile_overshoot(meta["sid"], meta["newb"])
                self.eos_reconciled_blocks += len(meta["newb"])
            self.transport.unaccount_slot(meta["nblocks"], meta["groups"],
                                          meta["far"])
            self._kernel_blocks_total -= self.NB
            self._kernel_blocks_skipped -= meta["kskip"]

    def flush(self) -> None:
        """Drain the dispatch pipeline (blocks on outstanding device steps).
        Drain time counts toward the wall so throughput/latency sums include
        the tail steps' device execution."""
        while self._inflight:
            dt = self._readback(self._inflight.popleft())
            self.cum_wall += dt
            if self.metrics:
                self.metrics[-1].wall += dt

    # ------------------------------------------------------------------
    def run(self, max_steps: int = 10_000, now_fn=None) -> None:
        while (self.sched.waiting or self.sched.preempted
               or self.sched.active_slots()) and self.steps_run < max_steps:
            self.step(now=now_fn() if now_fn else float("inf"))
        self.flush()

    # ------------------------------------------------------------------
    # audits & metrics
    # ------------------------------------------------------------------
    def audit(self) -> dict:
        """Legacy dict view of :meth:`audit_report` — every pre-§14
        ``audit()[key]`` call site keeps working unchanged."""
        return self.audit_report().as_dict()

    def audit_report(self) -> AuditReport:
        # audit reads host-slot state: deferred swap-out bytes must land
        # first (DESIGN.md §11) so the figures match the sync schedule
        self._drain_out_fences()
        steps = [m for m in self.metrics if m.active > 0]
        walls = np.array([m.wall for m in steps]) if steps else np.zeros(1)
        hosts = np.array([m.host for m in steps]) if steps else np.zeros(1)
        commits = np.array([m.frame_commit for m in steps]) if steps else np.zeros(1)
        ncomp = getattr(self._step_fn, "_cache_size", lambda: -1)()
        nc_prefill = (getattr(self._chunk_jit, "_cache_size", lambda: -1)()
                      if self._chunk_jit is not None else 0)
        # field-per-counter typed report (serving/api.py, §14): a counter
        # added here without an AuditReport field — or vice versa — raises
        # TypeError on every audit call, so the contract cannot drift
        return AuditReport(**{
            "mode": self.e.mode,
            "steps": len(steps),
            "compilations": ncomp,
            "prefill_compilations": nc_prefill,
            "pipeline_depth": self.depth,
            "prefill_chunk": self.chunk,
            "prefill_chunks_run": self._chunk_steps,
            "single_commit_per_step": (self.pager is None
                                       or self.frames_committed == self.steps_run),
            "frames_committed": self.frames_committed,
            "submit_share": float(hosts.sum() / max(walls.sum(), 1e-12)),
            "frame_commit_us": float(commits.mean() * 1e6),
            "dma_groups_per_step": self.transport.stats.groups_per_step,
            "avg_dma_bytes": self.transport.stats.avg_group_bytes,
            "unmerged_groups_per_step": self.transport.stats.unmerged_groups_per_step,
            "train_overflows": self.transport.stats.train_overflows,
            "reserved_kv_bytes": self.reserved_kv_bytes(),
            "active_kv_bytes": self.active_kv_bytes(),
            "peak_reserved_kv": self.peak_reserved_kv,
            "peak_active_kv": self.peak_active_kv,
            # --- SPMD decode (DESIGN.md §4): per-DEVICE memory pressure.
            # The logical totals above count the whole pool; with the kv-head
            # axis sharded over `model`, each device holds 1/kv_shards of it —
            # reporting the total as per-device overstates pressure by the TP
            # degree.
            # --- host KV tier + preemption (DESIGN.md §8). Byte figures are
            # per paged layer (same basis as the window-DMA transport stats);
            # admission-stall counters split compute-bound (no_slot) from
            # memory-bound (kv_watermark) queueing for operators.
            "host_pool_blocks": self.host_pool_blocks,
            "host_blocks_used": (self.pager.host_used if self.pager else 0),
            "host_blocks_peak": (self.pager.host_peak if self.pager else 0),
            "preemptions": self.preemptions,
            "swap_out_blocks": (self.pager.stats["swap_out_blocks"]
                                if self.pager else 0),
            "swap_in_blocks": (self.pager.stats["swap_in_blocks"]
                               if self.pager else 0),
            "swap_refusals": (self.pager.stats["swap_refusals"]
                              if self.pager else 0),
            "swap_groups": self.transport.stats.swap_groups,
            "swap_bytes": self.transport.stats.swap_bytes,
            "swap_out_bytes": self.transport.stats.swap_out_bytes,
            "swap_in_bytes": self.transport.stats.swap_in_bytes,
            "avg_swap_group_blocks": self.transport.stats.avg_swap_group_blocks,
            # --- async movement engine (DESIGN.md §11): overlap witnesses.
            # All three counters are zero with async_movement off — the A/B
            # identity gate checks exactly that invariance of everything
            # ABOVE this block while these move.
            # --- work-skipping decode kernel (DESIGN.md §12): padded grid
            # blocks walked vs blocks predicated off by the per-slot active
            # extent. total is the descriptor-side padded count (NB per
            # participating slot-step); skipped is 0 with the flag off.
            "kernel_skip_extent": bool(self.e.kernel_skip_extent),
            "kernel_blocks_total": self._kernel_blocks_total,
            "kernel_blocks_skipped": self._kernel_blocks_skipped,
            # --- sampled decode + detected-EOS retirement (DESIGN.md §13).
            # All three counters are zero in legacy greedy mode — the A/B
            # identity gates check exactly that.
            "greedy": bool(self.e.greedy),
            "eos_detected": self.eos_detected,
            "eos_overshoot_tokens": self.eos_overshoot_tokens,
            "eos_reconciled_blocks": self.eos_reconciled_blocks,
            "async_movement": bool(self.e.async_movement),
            "overlap_steps": self.transport.stats.overlap_steps,
            "deferred_readbacks": self.transport.stats.deferred_readbacks,
            "staging_reuse_bytes": self.transport.stats.staging_reuse_bytes,
            "swap_stall_ms": self.swap_stall_s * 1e3,
            "admit_blocked_no_slot": self.sched.admit_blocked["no_slot"],
            "admit_blocked_kv_watermark":
                self.sched.admit_blocked["kv_watermark"],
            "cancelled": self.cancelled,
            # --- step-level (continuous) batching (DESIGN.md §15).
            # continuous_admits / slot_idle_steps_saved count what a round
            # barrier would have cost; admit_blocked_round_barrier counts
            # what the barrier DID cost. Each triple's zero side is the
            # A/B witness for the opposite mode.
            "continuous_batching": bool(self.e.continuous_batching),
            "continuous_admits": self.continuous_admits,
            "slot_idle_steps_saved": self.slot_idle_steps_saved,
            "admit_blocked_round_barrier":
                self.sched.admit_blocked["round_barrier"],
            # --- radix prefix cache (DESIGN.md §9): shared-prefix reuse.
            # COW tail copies are their own transport group kind so prefix
            # traffic is auditable apart from window trains and swaps.
            "prefix_cache": self._prefix_on,
            "prefix_hits": (self.prefix_cache.stats["hits"]
                            if self.prefix_cache else 0),
            "prefix_misses": (self.prefix_cache.stats["misses"]
                              if self.prefix_cache else 0),
            "prefix_tokens_reused": (self.prefix_cache.stats["tokens_reused"]
                                     if self.prefix_cache else 0),
            "prefix_cached_blocks": (self.prefix_cache.blocks_cached
                                     if self.prefix_cache else 0),
            "prefix_evicted_blocks": (self.prefix_cache.stats["evicted_blocks"]
                                      if self.prefix_cache else 0),
            "cow_copies": self.transport.stats.cow_blocks,
            "cow_groups": self.transport.stats.cow_groups,
            "cow_bytes": self.transport.stats.cow_bytes,
            # --- quantized KV-block tier (DESIGN.md §10): narrow storage
            # width, scale-pool overhead inside the reserved figures, and
            # the bytes every accounted transfer saved vs bf16 width ---
            "kv_dtype": self.e.kv_dtype,
            "quant_bytes_saved": self.transport.stats.quant_bytes_saved,
            "quant_scale_bytes": ((self.num_blocks - 1)
                                  * self.scale_bytes_per_block
                                  * max(1, registry.n_paged_layers(self.cfg))),
            "mesh": (None if self.mesh is None
                     else "x".join(str(self.mesh.shape[a])
                                   for a in self.mesh.axis_names)),
            "tp_degree": self.tp_degree,
            "kv_shards": self._kv_shards,
            "per_device_reserved_kv": self.reserved_kv_bytes() // self._kv_shards,
            "per_device_active_kv": self.active_kv_bytes() // self._kv_shards,
            "per_device_peak_reserved_kv": self.peak_reserved_kv // self._kv_shards,
        })

    def reserved_kv_bytes(self) -> int:
        n_layers = max(1, registry.n_paged_layers(self.cfg))
        if self.e.mode == "arena":
            return (self.num_blocks - 1) * self.block_bytes * n_layers
        return self.pager.reserved_bytes() * n_layers

    def active_kv_bytes(self) -> int:
        n_layers = max(1, registry.n_paged_layers(self.cfg))
        if self.e.mode == "arena":
            return int(self._slot_len.sum()) * self.bytes_per_token * n_layers
        return self.pager.active_tokens() * self.bytes_per_token * n_layers

    def latency_stats(self, skip: int = 3) -> dict:
        active = [m for m in self.metrics if m.active > 0]
        walls = np.array([m.wall for m in active[skip:]])
        if walls.size == 0:
            walls = np.array([m.wall for m in active]) if active else np.zeros(1)
        q = lambda p: float(np.percentile(walls * 1e3, p))
        return {"p50_ms": q(50), "p95_ms": q(95), "p99_ms": q(99),
                "p999_ms": q(99.9), "mean_ms": float(walls.mean() * 1e3),
                "max_ms": float(walls.max() * 1e3)}

    def throughput(self, skip: int = 3) -> float:
        steps = [m for m in self.metrics if m.active > 0][skip:]
        if not steps:
            steps = [m for m in self.metrics if m.active > 0]
        tok = sum(m.emitted for m in steps)
        wall = sum(m.wall for m in steps)
        return tok / max(wall, 1e-12)

    def request_latency_stats(self) -> dict:
        """Request-level completion / time-to-first-token (wall seconds,
        relative to each request's ARRIVAL when present, engine start
        otherwise). Raw ``finish_wall``/``ttft_wall`` stamps are engine-start
        relative, so trace replay (arrivals gate admission) must subtract the
        arrival offset or late requests inflate the percentiles by their own
        arrival time; clamped at 0 for in-flight edge stamps.

        TTFT and TPOT are reported SEPARATELY: TPOT is the mean inter-token
        gap (finish - first token) / (n - 1), so the first-token wait —
        queueing + prefill — no longer folds into the per-token figure."""
        fin = self.sched.finished
        if not fin:
            return {}
        arr = np.array([getattr(r, "arrival", 0.0) or 0.0 for r in fin])
        finw = np.array([getattr(r, "finish_wall", 0.0) for r in fin])
        ttftw = np.array([getattr(r, "ttft_wall", 0.0) for r in fin])
        ngen = np.array([len(r.generated) for r in fin])
        comp = np.maximum(finw - arr, 0.0)
        ttft = np.maximum(ttftw - arr, 0.0)
        tpot = np.where(ngen > 1,
                        np.maximum(finw - ttftw, 0.0) / np.maximum(ngen - 1, 1),
                        0.0)
        q = lambda a, p: float(np.percentile(a * 1e3, p))
        return {"completion_p50_ms": q(comp, 50), "completion_p99_ms": q(comp, 99),
                "ttft_p50_ms": q(ttft, 50), "ttft_p99_ms": q(ttft, 99),
                "tpot_p50_ms": q(tpot, 50), "tpot_p99_ms": q(tpot, 99)}
