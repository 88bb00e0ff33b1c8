"""Typed public serving API (DESIGN.md §14).

Everything a client (or another subsystem) exchanges with the serving
layer is a frozen dataclass defined here — this module is the contract:

  * ``SLOClass``          — named latency class with TTFT/TPOT targets and
                            an admission queue-depth bound (the shed knob).
  * ``GenerationRequest`` — what a client submits (tenant + SLO attached).
  * ``TokenEvent``        — one streamed token, stamped on the gateway
                            clock at READBACK time (value known, §3/§13).
  * ``RequestResult``     — terminal summary: token stream + TTFT/TPOT.
  * ``AdmissionRejected`` — typed backpressure, extending the §8
                            ``admit_blocked_*`` taxonomy with the
                            gateway-level reasons (queue_full / slo_shed).
  * ``AuditReport``       — the engine audit as a frozen field-per-counter
                            dataclass; ``engine.audit()`` returns
                            ``audit_report().as_dict()`` so every legacy
                            dict call site keeps working while the FIELD
                            LIST is the single documented source of truth
                            (tests/test_docs.py diffs it against
                            docs/OPERATIONS.md).

Import discipline: this module may import ``core.scheduler`` (for the
``Request`` conversion) but never ``core.engine`` — the engine imports
``AuditReport`` from here, and the serving package keeps its heavier
modules (gateway/build) lazy in ``__init__`` to stay acyclic.
"""
from __future__ import annotations

from dataclasses import asdict, dataclass, fields
from typing import Optional, Tuple

import numpy as np

from repro.core.scheduler import Request

# ---------------------------------------------------------------------------
# SLO classes
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class SLOClass:
    """A named latency class. ``ttft_target_ms`` / ``tpot_target_ms`` define
    SLO attainment (goodput counts a request iff BOTH hold);
    ``max_queue_depth`` bounds how many requests of this class may be
    queued-or-running per lane before admission sheds new ones — the
    deterministic stand-in for "queueing deeper than this cannot meet the
    TTFT target" (0 = never shed on depth)."""
    name: str
    ttft_target_ms: float
    tpot_target_ms: float
    max_queue_depth: int = 0
    priority: int = 1                # lower = admitted/ordered first


INTERACTIVE = SLOClass("interactive", ttft_target_ms=500.0,
                       tpot_target_ms=100.0, max_queue_depth=8, priority=0)
STANDARD = SLOClass("standard", ttft_target_ms=2_000.0,
                    tpot_target_ms=200.0, max_queue_depth=0, priority=1)
BATCH = SLOClass("batch", ttft_target_ms=60_000.0,
                 tpot_target_ms=1_000.0, max_queue_depth=0, priority=2)

SLO_CLASSES = {c.name: c for c in (INTERACTIVE, STANDARD, BATCH)}


# ---------------------------------------------------------------------------
# request / event / result
# ---------------------------------------------------------------------------


@dataclass(frozen=True, eq=False)
class GenerationRequest:
    """A client-side generation request. ``arrival`` is optional trace time
    on the gateway clock (None = stamped at submit); ``stop_tokens`` needs
    sampled decode, exactly as on the engine ``Request``."""
    rid: int
    prompt: Tuple[int, ...]
    gen_len: int
    tenant: str = "default"
    slo: SLOClass = STANDARD
    arrival: Optional[float] = None
    stop_tokens: Tuple[int, ...] = ()

    def to_request(self, arrival: float) -> Request:
        return Request(rid=self.rid,
                       prompt=np.asarray(self.prompt, np.int32),
                       gen_len=int(self.gen_len), arrival=float(arrival),
                       stop_tokens=tuple(self.stop_tokens))


@dataclass(frozen=True)
class TokenEvent:
    """One streamed token. ``t`` is the gateway clock at readback (the
    moment the token VALUE is host-visible — never flattered by pipeline
    lag, DESIGN.md §3); ``index`` is the token's position in the stream.
    The terminal event has ``finished=True`` and a ``finish_reason``
    ("stop" | "budget" | "cancelled"); a cancel emits a synthetic terminal
    event with ``token = -1`` and ``index`` of the next unproduced token."""
    rid: int
    token: int
    index: int
    t: float
    finished: bool = False
    finish_reason: str = ""


@dataclass(frozen=True)
class RequestResult:
    """Terminal request summary, built by the gateway from the event
    stream. TTFT is first-token time minus arrival; TPOT is the mean
    inter-token gap (first token excluded — satellite fix: first-token
    wait no longer folds into per-token latency)."""
    rid: int
    tokens: Tuple[int, ...]
    finish_reason: str
    slo: SLOClass
    tenant: str
    arrival: float
    ttft_s: float
    tpot_s: float
    finish_t: float
    # where the first-token time went (``Gateway.timeline``); None where
    # the request never reached that stamp
    queue_s: Optional[float] = None        # arrival -> released to engine
    admit_wait_s: Optional[float] = None   # released -> first slot admission
    prefill_s: Optional[float] = None      # admitted -> last prompt token
    #                                        dispatched
    prompt_tail_steps: int = 0             # decode steps that ingested one
    #                                        prompt token

    @property
    def slo_attained(self) -> bool:
        if self.finish_reason == "cancelled":
            return False
        return (self.ttft_s * 1e3 <= self.slo.ttft_target_ms
                and self.tpot_s * 1e3 <= self.slo.tpot_target_ms)


# ---------------------------------------------------------------------------
# typed backpressure
# ---------------------------------------------------------------------------

# gateway-level extension of the engine's §8 admission-stall taxonomy
# (admit_blocked_no_slot / admit_blocked_kv_watermark): rejects happen at
# SUBMIT time, before a request ever reaches an engine queue
REJECT_QUEUE_FULL = "queue_full"     # tenant or gateway bound hit
REJECT_SLO_SHED = "slo_shed"         # class queue depth says TTFT unmeetable
REJECT_REASONS = (REJECT_QUEUE_FULL, REJECT_SLO_SHED)


class AdmissionRejected(Exception):
    """Typed admission backpressure: ``reason`` is one of
    ``REJECT_REASONS``; ``detail`` names the exhausted bound."""

    def __init__(self, reason: str, detail: str = ""):
        assert reason in REJECT_REASONS, reason
        self.reason = reason
        self.detail = detail
        super().__init__(f"{reason}: {detail}" if detail else reason)


# ---------------------------------------------------------------------------
# audit report
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class AuditReport:
    """``engine.audit()`` as a typed, frozen, field-per-counter report.

    The field list IS the audit contract: ``KVRMEngine.audit_report()``
    constructs this from its counter dict, so a counter added engine-side
    without a field here raises ``TypeError`` in every audit call
    (self-checking both ways), and tests/test_docs.py diffs these field
    names against the docs/OPERATIONS.md counter tables. Grouping mirrors
    the DESIGN.md sections each block of counters witnesses."""
    # --- executor / step invariants (§3) ---
    mode: str
    steps: int
    compilations: int
    prefill_compilations: int
    pipeline_depth: int
    prefill_chunk: int
    prefill_chunks_run: int
    single_commit_per_step: bool
    frames_committed: int
    submit_share: float
    frame_commit_us: float
    # --- descriptor transport (§2) ---
    dma_groups_per_step: float
    avg_dma_bytes: float
    unmerged_groups_per_step: float
    train_overflows: int
    # --- KV memory ---
    reserved_kv_bytes: int
    active_kv_bytes: int
    peak_reserved_kv: int
    peak_active_kv: int
    # --- host KV tier + preemption (§8) ---
    host_pool_blocks: int
    host_blocks_used: int
    host_blocks_peak: int
    preemptions: int
    swap_out_blocks: int
    swap_in_blocks: int
    swap_refusals: int
    swap_groups: int
    swap_bytes: int
    swap_out_bytes: int
    swap_in_bytes: int
    avg_swap_group_blocks: float
    # --- work-skipping kernels (§12) ---
    kernel_skip_extent: bool
    kernel_blocks_total: int
    kernel_blocks_skipped: int
    # --- sampled decode + detected EOS (§13) ---
    greedy: bool
    eos_detected: int
    eos_overshoot_tokens: int
    eos_reconciled_blocks: int
    # --- async movement engine (§11) ---
    async_movement: bool
    overlap_steps: int
    deferred_readbacks: int
    staging_reuse_bytes: int
    swap_stall_ms: float
    # --- admission stalls (§8) + gateway cancel (§14) ---
    admit_blocked_no_slot: int
    admit_blocked_kv_watermark: int
    cancelled: int
    # --- step-level (continuous) batching (§15) ---
    continuous_batching: bool
    continuous_admits: int
    slot_idle_steps_saved: int
    admit_blocked_round_barrier: int
    # --- radix prefix cache (§9) ---
    prefix_cache: bool
    prefix_hits: int
    prefix_misses: int
    prefix_tokens_reused: int
    prefix_cached_blocks: int
    prefix_evicted_blocks: int
    cow_copies: int
    cow_groups: int
    cow_bytes: int
    # --- quantized KV tier (§10) ---
    kv_dtype: str
    quant_bytes_saved: int
    quant_scale_bytes: int
    # --- SPMD decode (§4) ---
    mesh: Optional[str]
    tp_degree: int
    kv_shards: int
    per_device_reserved_kv: int
    per_device_active_kv: int
    per_device_peak_reserved_kv: int

    def as_dict(self) -> dict:
        """Legacy dict view — every pre-§14 ``eng.audit()[key]`` call site
        keeps working unchanged."""
        return asdict(self)

    @classmethod
    def field_names(cls) -> tuple:
        return tuple(f.name for f in fields(cls))
