"""Host spans and request timelines of the serving path (docs/OPERATIONS.md).

A span is a ``jax.profiler.TraceAnnotation`` named ``kvrm.<layer>.<part>``.
In a profiler trace it lies on the device trace's clock, beside the device
ops it waits for or feeds; with no trace being captured it costs about a
microsecond. While a trace is captured each span is also kept in memory
(``LOG.spans``) with its ``time.perf_counter`` bounds and stats, so a
process can read its own spans without parsing the trace. ``Span.seconds``
is the same interval: the host-clock fields that time the code a span
covers (``StepMetrics.host``/``frame_commit``, ``swap_stall_s``) take it
from the span instead of a second pair of clock reads.

Spans are per engine step or per pump cycle, never per token or per slot:
one step emits the same spans whatever it emits.

Request timelines are stamps on the engine's ``clock`` (the gateway's clock
when served through a gateway): released to the engine, first admitted to
a slot, prompt done, plus the prompt's chunk and tail step counts. The
gateway adds arrival and first token and logs each request's timeline in
``LOG`` when it finishes, or when the gateway closes.
"""
from __future__ import annotations

import time
from collections import OrderedDict, deque
from typing import Optional

from jax.profiler import StepTraceAnnotation, TraceAnnotation

ENGINE_STEP = "kvrm.engine.step"
PAGER_PRESSURE = "kvrm.pager.pressure"
SCHED_ADMIT = "kvrm.sched.admit"
PREFILL_ASSEMBLE = "kvrm.engine.prefill_assemble"
CHUNK_DISPATCH = "kvrm.engine.chunk_dispatch"
DECODE_ASSEMBLE = "kvrm.engine.decode_assemble"
FRAME_COMMIT = "kvrm.pager.frame_commit"
DECODE_DISPATCH = "kvrm.engine.decode_dispatch"
READBACK = "kvrm.engine.readback"
DEVICE_WAIT = "kvrm.engine.device_wait"
SWAP_WAIT = "kvrm.engine.swap_wait"
GATEWAY_RELEASE = "kvrm.gateway.release"
SPANS = (ENGINE_STEP, PAGER_PRESSURE, SCHED_ADMIT, PREFILL_ASSEMBLE,
         CHUNK_DISPATCH, DECODE_ASSEMBLE, FRAME_COMMIT, DECODE_DISPATCH,
         READBACK, DEVICE_WAIT, SWAP_WAIT, GATEWAY_RELEASE)

# a request's timeline, in the order its stamps fall
TIMELINE_STAMPS = ("arrival", "t_release", "t_admit", "t_prompt_done",
                   "t_first")


class SpanLog:
    """The spans captured while a profiler trace was on, and the last
    request timelines, bounded so a long-lived server stays flat."""

    def __init__(self, max_spans: int = 1 << 16,
                 max_timelines: int = 1 << 14):
        self.spans: deque = deque(maxlen=max_spans)   # (name, t0, t1, stats)
        self.max_timelines = max_timelines
        self._timelines: "OrderedDict[int, dict]" = OrderedDict()

    def add_timeline(self, rid: int, timeline: dict) -> None:
        self._timelines[rid] = timeline
        self._timelines.move_to_end(rid)
        while len(self._timelines) > self.max_timelines:
            self._timelines.popitem(last=False)

    def timeline(self, rid: int) -> Optional[dict]:
        return self._timelines.get(rid)


LOG = SpanLog()


class Span:
    """``with Span(name, **stats) as sp:`` emits the span; ``sp.stats``
    adds stats known only at its end; ``sp.elapsed()`` and, after the
    block, ``sp.seconds`` read its host-clock length."""
    __slots__ = ("name", "_ann", "_stats", "t0", "seconds")

    def __init__(self, name: str, _ann=TraceAnnotation, **stats):
        self.name = name
        self._stats = stats
        self._ann = _ann(name, **stats)

    def __enter__(self) -> "Span":
        self._ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def stats(self, **kw) -> None:
        if TraceAnnotation.is_enabled():
            self._stats.update(kw)
            self._ann.set_metadata(**kw)

    def elapsed(self) -> float:
        return time.perf_counter() - self.t0

    def __exit__(self, *exc) -> bool:
        t1 = time.perf_counter()
        self.seconds = t1 - self.t0
        self._ann.__exit__(*exc)
        if TraceAnnotation.is_enabled():
            LOG.spans.append((self.name, self.t0, t1, self._stats))
        return False


def step_span(step_num: int) -> Span:
    """The engine step's span: a ``StepTraceAnnotation``, so the profiler
    also groups device work by step."""
    return Span(ENGINE_STEP, StepTraceAnnotation, step_num=step_num)
