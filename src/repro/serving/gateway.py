"""Asyncio serving gateway over the data-parallel engine lanes (§14).

``Gateway.submit(GenerationRequest)`` returns an async iterator of
:class:`~repro.serving.api.TokenEvent` — per-token streaming fed by the
engine's readback-side token hook, so an event fires exactly when the
token VALUE becomes host-visible (never flattered by pipeline lag, §3,
and never for a scrubbed overshoot emission, §13). Admission is checked
synchronously at submit (typed :class:`AdmissionRejected` backpressure);
accepted requests flow through per-(lane, tenant) FIFO queues that a
single background pump task releases round-robin across tenants, then
steps every busy lane — the open-system analogue of the closed-loop
``run_lanes`` replay driver, over the very same engines.

The pump is cooperative: one engine step per lane per cycle with an
``await asyncio.sleep(0)`` between cycles, so streams and submitters
interleave with decode. All timestamps are on one gateway clock
(``perf_counter`` - t0, overridable for tests), which the engines' request
timeline stamps share: ``timeline(rid)`` splits a first-token time into
queue, admission wait, prefill and first-token lag.
"""
from __future__ import annotations

import asyncio
import time
from collections import deque
from typing import AsyncIterator, Deque, Dict, List, Optional, Tuple

from repro.core import spans
from repro.serving.admission import AdmissionController, SLOOrderPolicy
from repro.serving.api import (GenerationRequest, RequestResult, TokenEvent)
from repro.serving.router import AffinityRouter, RoundRobinRouter  # noqa: F401


class Gateway:
    def __init__(self, engines: List, *, router=None, admission=None,
                 now_fn=None, slo_order: bool = True):
        assert engines, "gateway needs at least one engine lane"
        self.engines = list(engines)
        self.router = router if router is not None else AffinityRouter()
        self.admission = admission if admission is not None \
            else AdmissionController()
        self._t0 = time.perf_counter()
        self._now = now_fn or (lambda: time.perf_counter() - self._t0)
        # per-(lane, tenant) FIFO queues + a round-robin tenant cursor:
        # release order interleaves tenants so one chatty tenant cannot
        # starve the rest of a lane (fairness, §14)
        self._queues: List[Dict[str, Deque]] = [dict() for _ in self.engines]
        self._rr: List[int] = [0 for _ in self.engines]
        self._events: Dict[int, asyncio.Queue] = {}
        self._greqs: Dict[int, Tuple[GenerationRequest, object, int]] = {}
        self._meta: Dict[int, dict] = {}
        self._results: Dict[int, RequestResult] = {}
        self._out_class: Dict[str, int] = {}
        self.cancelled = 0
        self._wake = asyncio.Event()
        self._task: Optional[asyncio.Task] = None
        self._closed = False
        for lane, eng in enumerate(self.engines):
            eng.token_hook = self._hook_for(lane)
            eng.clock = self._now
            if slo_order and eng.sched.policy is None:
                eng.sched.policy = SLOOrderPolicy()

    # ------------------------------------------------------------------
    # public API: submit / stream / cancel / drain
    # ------------------------------------------------------------------
    def now(self) -> float:
        return self._now()

    def submit(self, greq: GenerationRequest) -> AsyncIterator[TokenEvent]:
        """Admit (or raise :class:`AdmissionRejected`) and return the
        request's token-event stream. Admission is decided HERE, at submit
        time — a returned iterator is a promise the request will run."""
        assert greq.rid not in self._greqs, f"rid {greq.rid} reused"
        self._ensure_pump()
        self.admission.check(greq, self)     # raises AdmissionRejected
        now = self._now()
        arrival = now if greq.arrival is None else float(greq.arrival)
        req = greq.to_request(arrival=arrival)
        req.slo_priority = greq.slo.priority     # for SLOOrderPolicy
        depths = [self._lane_depth(i) for i in range(len(self.engines))]
        lane = self.router.route(greq, self.engines, depths)
        self._events[greq.rid] = asyncio.Queue()
        self._greqs[greq.rid] = (greq, req, lane)
        self._meta[greq.rid] = {"arrival": arrival, "first_t": None,
                                "last_t": None, "n": 0}
        self._out_class[greq.slo.name] = \
            self._out_class.get(greq.slo.name, 0) + 1
        self._queues[lane].setdefault(greq.tenant, deque()).append(req)
        self._wake.set()
        return self._stream(greq.rid)

    async def generate(self, greq: GenerationRequest) -> RequestResult:
        """Submit and consume the whole stream; returns the terminal
        :class:`RequestResult`."""
        async for _ev in self.submit(greq):
            pass
        return self._results[greq.rid]

    def cancel(self, rid: int) -> bool:
        """Cancel a submitted request: dequeue it if the gateway still
        holds it, else hand off to ``engine.cancel`` (which drains the
        dispatch pipeline and retires through the one EOS path, freeing
        every pager block). A synthetic terminal TokenEvent closes the
        stream either way. False if unknown or already finished."""
        info = self._greqs.get(rid)
        if info is None or rid in self._results:
            return False
        greq, req, lane = info
        q = self._queues[lane].get(greq.tenant)
        if q is not None and req in q:
            q.remove(req)
            req.finish_reason = "cancelled"
        elif not self.engines[lane].cancel(rid):
            return False
        self.cancelled += 1
        self._finish(rid, req, synthetic=True)
        return True

    async def drain(self) -> None:
        """Wait until every accepted request has finished (the pump keeps
        stepping; this just parks until the outstanding count hits 0)."""
        self._ensure_pump()
        while self.outstanding() > 0:
            self._wake.set()
            await asyncio.sleep(0)

    def result(self, rid: int) -> Optional[RequestResult]:
        return self._results.get(rid)

    def timeline(self, rid: int) -> Optional[dict]:
        """The request's stamps so far, on the gateway clock: ``arrival``,
        ``t_release`` (handed to its engine), ``t_admit`` (first slot
        admission), ``t_prompt_done`` (dispatch of the step carrying its
        last prompt token), ``t_first`` (first token streamed), each None
        until reached; and its ``prompt_chunk_steps``,
        ``prompt_tail_steps`` and ``preemptions``. Consecutive stamps
        split ``ttft_s`` exactly. None for an unknown rid."""
        info = self._greqs.get(rid)
        if info is None:
            return None
        req, meta = info[1], self._meta[rid]
        return {"arrival": meta["arrival"], "t_release": req.t_release,
                "t_admit": req.t_admit, "t_prompt_done": req.t_prompt_done,
                "t_first": meta["first_t"],
                "prompt_chunk_steps": req.prompt_chunk_steps,
                "prompt_tail_steps": req.prompt_tail_steps,
                "preemptions": req.preempt_count}

    def close(self) -> None:
        self._closed = True
        self._wake.set()
        for eng in self.engines:
            eng.token_hook = None
        # the timelines of requests still running, as far as they got
        for rid in self._greqs:
            if rid not in self._results:
                spans.LOG.add_timeline(rid, self.timeline(rid))

    # ------------------------------------------------------------------
    # admission introspection (AdmissionController reads these)
    # ------------------------------------------------------------------
    def outstanding(self) -> int:
        return len(self._greqs) - len(self._results)

    def outstanding_in_class(self, cls: str) -> int:
        return self._out_class.get(cls, 0)

    def tenant_queued(self, tenant: str) -> int:
        """Requests a tenant has pending BEFORE a decode slot: gateway
        tenant queues plus the engines' own waiting queues (§15 —
        continuous release hands arrived requests to the engine
        immediately, so the engine-side queue must count toward the
        per-tenant admission bound or it would never trip)."""
        gw_q = sum(len(qs[tenant]) for qs in self._queues if tenant in qs)
        eng_rids = set()
        for eng in self.engines:
            eng_rids.update(r.rid for r in eng.sched.waiting)
        eng_q = sum(1 for rid, (greq, _r, _l) in self._greqs.items()
                    if greq.tenant == tenant and rid in eng_rids)
        return gw_q + eng_q

    def _lane_depth(self, lane: int) -> int:
        eng = self.engines[lane]
        return (sum(len(q) for q in self._queues[lane].values())
                + len(eng.sched.waiting) + len(eng.sched.preempted)
                + len(eng.sched.active_slots()))

    # ------------------------------------------------------------------
    # event plumbing
    # ------------------------------------------------------------------
    def _hook_for(self, lane: int):
        def hook(req, tok: int, fin: bool):
            rid = req.rid
            meta = self._meta.get(rid)
            if meta is None:
                return                   # not a gateway request (replay path)
            t = self._now()
            if meta["first_t"] is None:
                meta["first_t"] = t
            meta["last_t"] = t
            meta["n"] += 1
            ev = TokenEvent(rid=rid, token=tok, index=len(req.generated) - 1,
                            t=t, finished=fin,
                            finish_reason=req.finish_reason if fin else "")
            q = self._events.get(rid)
            if q is not None:
                q.put_nowait(ev)
            if fin:
                self._finish(rid, req)
        return hook

    def _finish(self, rid: int, req, synthetic: bool = False) -> None:
        if rid in self._results:
            return
        greq, _req, _lane = self._greqs[rid]
        meta = self._meta[rid]
        first, last, n = meta["first_t"], meta["last_t"], meta["n"]
        ttft = (first - meta["arrival"]) if first is not None else float("inf")
        tpot = ((last - first) / (n - 1)) if n and n > 1 else 0.0
        tl = self.timeline(rid)
        spans.LOG.add_timeline(rid, tl)
        stage = lambda a, b: (None if tl[a] is None or tl[b] is None
                              else tl[b] - tl[a])
        self._results[rid] = RequestResult(
            rid=rid, tokens=tuple(req.generated),
            finish_reason=req.finish_reason or "cancelled",
            slo=greq.slo, tenant=greq.tenant, arrival=meta["arrival"],
            ttft_s=max(0.0, ttft) if ttft != float("inf") else ttft,
            tpot_s=max(0.0, tpot),
            finish_t=last if last is not None else self._now(),
            queue_s=stage("arrival", "t_release"),
            admit_wait_s=stage("t_release", "t_admit"),
            prefill_s=stage("t_admit", "t_prompt_done"),
            prompt_tail_steps=req.prompt_tail_steps)
        self._out_class[greq.slo.name] -= 1
        if synthetic:
            q = self._events.get(rid)
            if q is not None:
                q.put_nowait(TokenEvent(
                    rid=rid, token=-1, index=len(req.generated), t=self._now(),
                    finished=True, finish_reason="cancelled"))

    async def _stream(self, rid: int) -> AsyncIterator[TokenEvent]:
        q = self._events[rid]
        while True:
            ev = await q.get()
            yield ev
            if ev.finished:
                break
        self._events.pop(rid, None)

    # ------------------------------------------------------------------
    # the pump: release fairly, step busy lanes, flush idle tails
    # ------------------------------------------------------------------
    def _ensure_pump(self) -> None:
        if self._task is None or self._task.done():
            self._closed = False
            self._task = asyncio.get_running_loop().create_task(self._pump())

    def _release(self, lane: int, now: float) -> None:
        """Move arrived requests from this lane's tenant queues into the
        engine, one per tenant per pass (round-robin). With a
        continuous-batching engine (§15) every arrived request is released
        immediately — the engine refills freed slots at each step, so
        holding requests at the gateway would only re-introduce the round
        barrier one layer up; RR order still decides WHO goes first. With
        the round-based baseline the release keeps the engine's waiting
        queue shallower than its slot width — deep enough to keep slots
        fed, shallow enough that gateway fairness ordering (not engine
        FIFO) decides who goes next."""
        eng = self.engines[lane]
        qs = self._queues[lane]
        tenants = sorted(qs)
        cap = float("inf") if eng.e.continuous_batching else eng.e.batch
        n = 0
        with spans.Span(spans.GATEWAY_RELEASE) as sp:
            while tenants and len(eng.sched.waiting) < cap:
                released = False
                for k in range(len(tenants)):
                    t = tenants[(self._rr[lane] + k) % len(tenants)]
                    q = qs[t]
                    if q and q[0].arrival <= now:
                        eng.submit(q.popleft())
                        self._rr[lane] = (self._rr[lane] + k + 1) \
                            % len(tenants)
                        released = True
                        n += 1
                        break
                if not released:
                    break
            sp.stats(released=n)

    def _pending(self) -> int:
        return sum(len(q) for qs in self._queues for q in qs.values())

    async def _pump(self) -> None:
        while not self._closed:
            now = self._now()
            busy = False
            for lane, eng in enumerate(self.engines):
                self._release(lane, now)
                if eng.sched.waiting or eng.sched.preempted \
                        or eng.sched.active_slots():
                    eng.step(now=now)
                    busy = True
            if busy:
                await asyncio.sleep(0)   # let streams/submitters run
                continue
            for eng in self.engines:
                eng.flush()              # tail of the pipeline -> last events
            if self._pending():
                await asyncio.sleep(0.002)   # queued, not yet arrived
                continue
            self._wake.clear()
            if self._closed:
                break
            await self._wake.wait()

    # ------------------------------------------------------------------
    # audit
    # ------------------------------------------------------------------
    def audit(self) -> dict:
        """Gateway-level counters + per-lane engine audits. Keys extend
        the operator taxonomy documented in docs/OPERATIONS.md §14."""
        out = {"lanes": len(self.engines), **self.admission.stats(),
               "cancelled": self.cancelled,
               "affinity_hits": getattr(self.router, "affinity_hits", 0),
               "affinity_misses": getattr(self.router, "affinity_misses", 0),
               "completed": len(self._results),
               "lane_audits": [e.audit() for e in self.engines]}
        return out

    def slo_stats(self) -> dict:
        """Per-class SLO attainment over finished requests: goodput is
        attained completions / offered (admitted + rejected + shed), the
        headline gateway metric."""
        per = {}
        for r in self._results.values():
            d = per.setdefault(r.slo.name, {"served": 0, "attained": 0,
                                            "cancelled": 0})
            if r.finish_reason == "cancelled":
                d["cancelled"] += 1
                continue
            d["served"] += 1
            d["attained"] += int(r.slo_attained)
        adm = self.admission
        out = {}
        for cls, d in per.items():
            offered = (adm.admitted_per_class.get(cls, 0)
                       + adm.rejected_per_class.get(cls, 0)
                       + adm.shed_per_class.get(cls, 0))
            out[cls] = {**d, "offered": offered,
                        "goodput": d["attained"] / max(1, offered)}
        return out
