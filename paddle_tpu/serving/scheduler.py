"""Continuous batching — Orca-style iteration-level scheduling on the
host side of the compiled decode step.

The unit of scheduling is ONE decode iteration, not one request: after
every batched step the scheduler retires finished slots (EOS /
``max_new_tokens`` / cache-full) and immediately admits waiting requests
into the freed slots — the batch composition changes between iterations
while the decode program (fixed shape: all ``num_slots`` lanes every
step) never recompiles.

**Overlapped host/device loop (ISSUE 13 — the default).**  The loop
keeps ONE decode step in flight: iteration t dispatches the compiled
step threading iteration t-1's sampled tokens on DEVICE (jax dispatch
is async — the only blocking point is the token fetch), then consumes
t-1, so EOS/budget truncation, drafting, page bookkeeping, admission
and span/metric emission all overlap the device's compute of step t.
One-step-stale decisions are reconciled at consume time by IDENTITY:
a lane is credited only if the same request still occupies it — the
overshoot token a stale dispatch computed for a since-retired/
preempted/cancelled slot is discarded, its append lands in pages
``free_slot`` already reclaimed (length-masked reads keep stale rows
unreachable), and the host length mirror stays exact.  Greedy output
is BIT-IDENTICAL to the sync loop (``overlap=False`` /
``PADDLE_TPU_SERVE_OVERLAP=0``, kept for A/B); page pressure drains
the in-flight step before evicting.  ``host_gap_seconds`` /
``decode_steps_total`` expose the structural win the bench reports:
wall time per step with NO step in flight (the device-starvation
window) collapses from the whole per-step host budget to true
pipeline bubbles.

**Chunked prefill (paged engines — the default).**  Admission no longer
runs the whole prompt in one blocking call: it starts a
:class:`~.engine.PrefillTask` and each scheduler iteration advances
every admitting slot by ONE fixed-size chunk *between* decode steps, so
a 32k-token admission costs each in-flight request one chunk of extra
latency per token instead of one whole-prompt stall (TPOT
non-interference — tested).  A prefix-cache hit skips the shared pages
entirely (the counter ``serving.prefix_hit_pages`` meters it) and a
fully-cached prompt admits in a single 1-token chunk.

**Speculative decode (``spec_k`` engines — ISSUE 8).**  When the engine
was built with ``spec_k > 0`` the decode iteration becomes a *verify*
iteration: for every active slot the scheduler proposes ``spec_k``
tokens by prompt-lookup over the slot's own ``prompt + generated``
history (:mod:`.spec` — zero model FLOPs) and ONE compiled verify step
scores all ``spec_k + 1`` positions, accepting a per-slot prefix and
sampling one corrective token (``sampling.spec_accept``).  The
scheduler appends the emitted run, truncating at EOS and the
``max_new_tokens`` budget (truncation always retires the slot, so the
host token list and the device length mirror never diverge for live
slots).  Per-request ``spec_proposed``/``spec_accepted`` land on the
:class:`RequestResult` and on the ``serving.spec_proposed_tokens``/
``serving.spec_accepted_tokens`` counter pair (accept rate =
accepted/proposed).  TPOT keeps meaning seconds per decode-committed
token: a verify step's wall time is divided across every token it
emitted.

**Refcount-aware eviction, preemption by recompute.**  When the page
pool is dry (a decode append or a prefill chunk cannot map a page), the
victim is the active slot with the MOST unshared pages — freeing it
returns the most pages to the pool, whereas evicting a slot whose pages
are mostly shared prefix frees almost nothing (bare FIFO would thrash
exactly those slots under a prefix-heavy workload — tested).  Ties
break oldest-first.  The victim is not lost: it goes back to the front
of the waiting queue and, on re-admission, re-prefills
``prompt + generated-so-far`` (vLLM-style recompute preemption) — a
recompute that mostly prefix-hits the victim's own still-cached pages.
A request evicted more than ``max_preemptions`` times, or one whose
sequence the pool cannot hold even alone, finishes ``"cache_full"``.

States of a slot: ``free`` → (admit: begin prefill) → ``prefilling`` →
(final chunk samples the first token) → ``active`` → (EOS | budget |
``max_len`` | evicted-past-cap) → ``free``, with ``active``/
``prefilling`` → (preempted) → ``waiting`` → ``prefilling``.  Admission
is strict FIFO over the waiting queue.  Slotted engines
(``paged=False``) keep the PR-5 one-shot bucketed prefill.

**Request-scoped tracing (ISSUE 9).**  ``submit()`` mints a
``trace_id`` (threaded onto the :class:`RequestResult`) and opens a
``request`` root span; admission, each prefill chunk, each decode/
spec-verify iteration, preemption (``preempted`` event + ``requeue``
span + ``rework``-tagged recompute chunks), prefix hits, and finish all
land on that lane.  With tracing disabled (the default) the tracer is
the no-op singleton by identity and the decode hot loop spends nothing
(PR-6-style acceptance test); ``python -m paddle_tpu.observability
trace-report`` reconstructs the per-request timelines.

Per-request timing is recorded for the serving metrics the bench emits:
TTFT (submit → first token — still INCLUDES queue wait, for continuity
with the PR-5 trajectory), ``queue_wait`` (submit → admission, reported
separately so load tests can subtract it: under saturation TTFT is
dominated by queueing, not prefill), and TPOT (mean decode seconds per
subsequent token).  Every iteration also feeds the process-wide metrics
registry (paddle_tpu.observability); handles are fetched once at
construction, so with metrics disabled the per-token path is a no-op
method call with zero host allocation.
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Dict, List, Optional

import numpy as np

from ..observability import flight as _flight
from ..observability import hbm as _hbm
from ..observability import liveness as _liveness
from ..observability import registry as _metrics
from ..observability import tracing as _tracing
from ..robustness.faultpoints import declare as _declare, faultpoint
from .engine import PagePoolExhausted, PrefillTask
# importing the tier module also declares its faultpoint site and
# liveness beacon (the scheduler fetches the beacon handle at init)
from .kv_tier import TRANSPORT_ERRORS as _TIER_ERRORS
from .spec import propose as _propose_draft

__all__ = ["Request", "RequestResult", "RequeueState",
           "ContinuousBatchingScheduler"]

#: chaos site on the scheduler's hot iteration, INSIDE the liveness
#: beacon's guard: a scheduled ``Hang`` here simulates a wedged decode
#: loop (stuck collective / device hang) and must trip the watchdog
STEP_SITE = _declare(
    "serve.step",
    "fires at the top of every scheduler iteration (a Hang here "
    "simulates a wedged decode loop for the liveness watchdog)")

#: liveness beacon over one scheduler iteration; generous default —
#: the first iteration pays the decode/prefill XLA compiles
_declare_beacon = _liveness.declare_beacon
_declare_beacon("serve.scheduler_step",
                "one continuous-batching scheduler iteration (admit + "
                "prefill chunk + batched decode dispatch/consume)",
                deadline=600.0)


@dataclasses.dataclass
class Request:
    prompt: "np.ndarray"                 # 1-D int token ids
    max_new_tokens: int = 20
    temperature: float = 1.0
    top_k: int = 0
    top_p: float = 1.0
    eos_token_id: Optional[int] = None
    rid: Optional[int] = None            # assigned by submit()


@dataclasses.dataclass
class RequestResult:
    rid: int
    tokens: "np.ndarray"                 # generated ids (prompt excluded)
    finish_reason: str                   # "eos" | "length" |
                                         # "cache_full" | "cancelled"
                                         # (client gone — frontend)
    ttft: float                          # submit -> first token, seconds
    tpot: float                          # mean secs per timed decode step
                                         # (prefill-sampled tokens, incl. a
                                         # resume's, are excluded)
    queue_wait: float = 0.0              # submit -> admission, seconds
    prefix_hit_tokens: int = 0           # tokens served from the prefix
                                         # cache, all admissions (a
                                         # preemption resume's hits count)
    spec_proposed: int = 0               # draft tokens proposed for this
                                         # request (spec_k per verify step)
    spec_accepted: int = 0               # draft tokens the verify step
                                         # accepted (rate = accepted /
                                         # proposed; 0/0 when spec off)
    trace_id: int = 0                    # request lane in the span trace
                                         # (ISSUE 9; 0 = tracing disabled)


@dataclasses.dataclass
class RequeueState:
    """Portable snapshot of ONE unfinished request — the unit of
    scheduler-to-scheduler transfer (ISSUE 19 replica failover, and
    graceful replica decommission).  Produced by
    :meth:`ContinuousBatchingScheduler.export_requeue_state` or
    synthesized by the router from its own admission records when the
    owning replica died too hard to export anything; consumed by
    :meth:`ContinuousBatchingScheduler.import_requeue`, which feeds it
    through the existing recompute-preemption resume path — the
    survivor re-prefills ``prompt + generated`` and the stream picks up
    at the next token."""
    req: Request                          # rid already assigned
    generated: List[int] = dataclasses.field(default_factory=list)
    submit_t: float = 0.0                 # original perf_counter stamp
    first_tok_t: Optional[float] = None   # preserved across the hop
    requeues: int = 0                     # prior evictions + failovers
                                          # (seeds _preempt_count: one
                                          # max_preemptions-style bound
                                          # covers both)
    trace_id: int = 0
    root_span: object = None              # live "request" span, adopted
    queue_wait: Optional[float] = None    # None = never admitted (the
                                          # survivor observes it once)
    decode_s: float = 0.0
    decode_steps: int = 0
    prefix_hit_tokens: int = 0
    spec_proposed: int = 0
    spec_accepted: int = 0


class _ActiveSlot:
    __slots__ = ("req", "generated", "submit_t", "first_tok_t", "last_t",
                 "decode_s", "decode_steps", "queue_wait", "prefill_task",
                 "admit_order", "prefix_hit_tokens", "spec_proposed",
                 "spec_accepted", "cache_len")

    def __init__(self, req, submit_t, queue_wait, admit_order,
                 prefill_task=None):
        self.req = req
        self.generated: List[int] = []
        self.submit_t = submit_t
        self.first_tok_t = None
        self.last_t = None
        self.decode_s = 0.0
        self.decode_steps = 0          # timed decode-committed TOKENS
                                       # only (a verify step counts every
                                       # token it emitted): a preemption
                                       # resume's prefill-sampled token
                                       # adds no decode_s, so
                                       # len(generated)-1 would deflate
                                       # TPOT
        self.queue_wait = queue_wait
        self.prefill_task = prefill_task   # None once prefill completed
        self.admit_order = admit_order     # FIFO tie-break for eviction
        self.prefix_hit_tokens = (prefill_task.shared_tokens
                                  if prefill_task is not None else 0)
        self.spec_proposed = 0
        self.spec_accepted = 0
        # committed cache rows this request holds, mirrored host-side
        # from what the device programs actually advanced (prefill sets
        # it to the prompt length; each CONSUMED decode/verify step adds
        # its in-program advance, clamped at max_len exactly like the
        # device finalize).  The cache_full retire check reads this —
        # no per-iteration device fetch, and it stays exact in the
        # overlapped loop where the engine's dispatch-time mirror runs
        # one step ahead of consumed truth.
        self.cache_len = 0

    def first_token(self, tok, now):
        self.generated.append(int(tok))
        # a resumed (preempted) slot's recompute-prefill also lands
        # here: its true first-token time is the original one
        if self.first_tok_t is None:
            self.first_tok_t = now
        self.last_t = now


class _Inflight:
    """Scheduler-side record of ONE dispatched, unconsumed decode (or
    verify) step: the engine's :class:`~.engine.InflightDecode` plus the
    per-lane occupant identities at dispatch time.  Consume credits a
    lane ONLY if the same :class:`_ActiveSlot` object still occupies it
    — a slot retired (EOS/budget/cache-full), preempted, or cancelled
    after the dispatch simply has its overshoot token(s) discarded,
    which is the whole one-step-stale reconciliation rule."""
    __slots__ = ("rec", "lane_acts", "t0_ns")

    def __init__(self, rec, lane_acts, t0_ns):
        self.rec = rec
        self.lane_acts = lane_acts
        self.t0_ns = t0_ns


class _HostFetch:
    """One in-progress host-tier page fetch (ISSUE 17): the queue-head
    request's prompt misses the device prefix cache but hits the
    host-RAM tier, so its pages are being pulled back through
    ``kv_import`` chunk by chunk — interleaved between decode steps,
    ``is_ready()``-polled, never blocking a decode dispatch.  While the
    fetch runs the request lives HERE (not in ``waiting``, not in a
    slot); completion requeues it at the queue FRONT, where the next
    admission's prefix lookup finds every fetched page device-resident
    and admits in one 1-token chunk.  ``_submit_t`` stays in place
    throughout — TTFT includes the fetch, honestly."""

    __slots__ = ("req", "plan", "pos", "staged", "staged_digests",
                 "pages_in", "chunk_idx", "span", "t0")

    def __init__(self, req, plan, span, t0):
        self.req = req
        self.plan = plan              # [(page_index, digest)] to pull
        self.pos = 0                  # plan entries imported so far
        self.staged = None            # staged device arrays, or None
        self.staged_digests = None    # the digests the staging covers
        self.pages_in = 0             # pages landed (the hits metric)
        self.chunk_idx = 0            # faultpoint/trace chunk counter
        self.span = span              # "kv_tier" request child span
        self.t0 = t0                  # fetch begin, perf_counter


class ContinuousBatchingScheduler:
    # page-pressure evictions per request before the scheduler stops
    # requeueing it and finishes it "cache_full" — bounds wasted
    # recompute and keeps run()'s termination argument trivial
    max_preemptions = 3

    def __init__(self, engine, tracer=None, overlap=None, on_token=None,
                 on_finish=None):
        self.engine = engine
        # -- overlapped host/device decode loop (ISSUE 13) -----------------
        # overlap=True (the default; env escape hatch
        # PADDLE_TPU_SERVE_OVERLAP=0) keeps ONE decode step in flight:
        # each iteration dispatches step t (threading step t-1's sampled
        # tokens on DEVICE — jax dispatch is async) and only then blocks
        # on step t-1's token fetch, so host bookkeeping for step t-1
        # overlaps device compute for step t.  Host-visible effects lag
        # one step; consume reconciles by crediting a lane only when the
        # same request still occupies it (see _Inflight).  Greedy output
        # is BIT-IDENTICAL to the sync loop; seeded temperature>0
        # sampling is reproducible within a mode but not across modes
        # (overshoot steps consume threaded keys).
        import os as _os
        if overlap is None:
            overlap = _os.environ.get("PADDLE_TPU_SERVE_OVERLAP",
                                      "1") != "0"
        self.overlap = bool(overlap)
        self._inflight: Optional[_Inflight] = None
        self._drained_n = 0            # tokens consumed by implicit
                                       # drains (page pressure / cancel)
                                       # since step() last collected
        # host-gap accounting (the bench's A/B line): wall time during
        # which NO decode step was dispatched-and-unconsumed — the only
        # windows where the device can be token-starved by the host.
        # The sync loop pays the whole consume-to-dispatch host window
        # per step; the overlapped loop pays only true pipeline bubbles.
        self.host_gap_seconds = 0.0
        self.decode_steps_total = 0
        self._outstanding = 0          # dispatched, unconsumed steps
        self._last_fetch_ns = None
        self._last_step_end_ns = None
        # streaming hooks (the async front-end): called on the scheduler
        # thread — on_token(rid, [ids...]) per appended run (first
        # tokens included), on_finish(RequestResult) at retirement
        self._on_token = on_token
        self._on_finish = on_finish
        self.waiting: deque = deque()
        self.slots: List[Optional[_ActiveSlot]] = [None] * engine.num_slots
        self.finished: Dict[int, RequestResult] = {}
        self._next_rid = 0
        self._admit_seq = 0
        self._submit_t: Dict[int, float] = {}
        # request-scoped tracing (ISSUE 9): a trace_id minted at submit,
        # a root "request" span, and per-phase child spans.  With tracing
        # disabled (the default) the tracer is the module no-op singleton
        # BY IDENTITY and every call below is an empty method — the
        # PR-6-style acceptance test asserts it.  The decode hot loop
        # additionally short-circuits on `_tron` so the per-slot span
        # bookkeeping costs nothing when off.
        self._tracer = (tracer if tracer is not None
                        else _tracing.default_tracer())
        self._tron = bool(self._tracer.enabled)
        self._trace_ids: Dict[int, int] = {}       # rid -> trace lane
        self._req_spans: Dict[int, object] = {}    # rid -> root span
        self._wait_spans: Dict[int, object] = {}   # rid -> queue/requeue
        # rid -> parked _ActiveSlot (evicted, waiting to resume) and
        # rid -> times evicted; see _preempt()
        self._preempted: Dict[int, _ActiveSlot] = {}
        self._preempt_count: Dict[int, int] = {}
        # metric handles, fetched ONCE: with the registry disabled these
        # are the shared no-op singletons — the per-token hot path then
        # does nothing and allocates nothing (tests/test_observability.py
        # asserts the identity)
        self._m_ttft = _metrics.histogram("serving.ttft_seconds")
        self._m_queue_wait = _metrics.histogram("serving.queue_wait_seconds")
        self._m_tpot = _metrics.histogram("serving.tpot_seconds")
        self._m_decode_step = _metrics.histogram(
            "serving.decode_step_seconds")
        self._m_prefill_chunk = _metrics.histogram(
            "serving.prefill_chunk_seconds")
        self._m_tokens = _metrics.counter("serving.generated_tokens")
        self._m_bucket_hits = _metrics.counter(
            "serving.prefill_bucket_hits", ("bucket",))
        self._m_prefix_hits = _metrics.counter("serving.prefix_hit_pages")
        self._m_preempt = _metrics.counter("serving.preemptions")
        self._m_spec_prop = _metrics.counter(
            "serving.spec_proposed_tokens")
        self._m_spec_acc = _metrics.counter(
            "serving.spec_accepted_tokens")
        self._m_finished = _metrics.counter(
            "serving.finished_requests", ("reason",))
        self._m_occupancy = _metrics.gauge("serving.slot_occupancy")
        self._m_queue_depth = _metrics.gauge("serving.queue_depth")
        # tiered KV host-cache fetches (ISSUE 17): rid -> _HostFetch.
        # The scheduler owns the hit counter (a hit is a page that
        # LANDED) and the fetch histogram; the engine owns the
        # spill/miss/occupancy side.
        self._fetches: Dict[int, _HostFetch] = {}
        self._m_host_hits = _metrics.counter("serving.kv_host_hits")
        self._m_fetch_s = _metrics.histogram(
            "serving.kv_tier_fetch_seconds")
        self._kvt_beacon = _liveness.beacon("serve.kv_tier")
        # liveness beacon, fetched ONCE: disabled (the default) it is
        # the module NOOP_BEACON by identity — the per-iteration guard
        # is then two empty method calls (tests assert the identity)
        self._beacon = _liveness.beacon("serve.scheduler_step")

    # -- intake ------------------------------------------------------------

    def submit(self, req: Request, trace=None) -> int:
        prompt = np.asarray(req.prompt, np.int32).reshape(-1)
        if prompt.size < 1:
            raise ValueError("empty prompt")
        cap = self.engine.prompt_cap
        if prompt.size > cap:
            raise ValueError(
                "prompt length %d exceeds the engine's prompt capacity %d"
                % (prompt.size, cap))
        if req.max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        # a pre-assigned rid (the router tier mints fleet-unique ids so
        # a stream's rid survives failover to another replica) is
        # honored; local callers keep the auto-assigned counter
        if req.rid is None:
            req = dataclasses.replace(req, prompt=prompt,
                                      rid=self._next_rid)
        else:
            req = dataclasses.replace(req, prompt=prompt)
        self._next_rid = max(self._next_rid, req.rid + 1)
        self._submit_t[req.rid] = time.perf_counter()
        self.waiting.append(req)
        # the trace is born HERE: root "request" span + the initial
        # "queue" child (ended at admission) — unless the caller already
        # minted the lane (``trace=(trace_id, root_span)``: the router
        # owns the request root so the tree survives failover).  No-op
        # identity calls when tracing is disabled.
        if trace is None:
            tid = self._tracer.new_trace()
            root = self._tracer.span(
                "request", trace_id=tid, rid=req.rid,
                prompt_len=int(prompt.size),
                max_new_tokens=int(req.max_new_tokens))
        else:
            tid, root = trace
            if root is None:
                root = _tracing.NOOP_SPAN
        self._trace_ids[req.rid] = tid
        self._req_spans[req.rid] = root
        self._wait_spans[req.rid] = self._tracer.span("queue", parent=root)
        self._m_queue_depth.set(len(self.waiting))
        return req.rid

    # -- slot lifecycle ----------------------------------------------------

    def _finish(self, idx: int, reason: str):
        act = self.slots[idx]
        self.slots[idx] = None
        self.engine.free_slot(idx)     # paged: pages back to the pool
        self._retire(act, reason)

    def _retire(self, act: "_ActiveSlot", reason: str):
        """Result/metric/span bookkeeping of one retiring request —
        slot-list-free, so the disaggregated scheduler's prefill-side
        retirements build the SAME RequestResult (one code path for
        the contract the bench and the front-end consume)."""
        tpot = (act.decode_s / act.decode_steps) if act.decode_steps \
            else 0.0
        # a request evicted before producing any token (cache_full mid-
        # prefill) has no first-token time: its ttft is reported as 0.0
        # and NOT fed to the histogram — a fabricated eviction-time
        # sample would pollute the p50/p99 TTFT the bench reports
        got_first = act.first_tok_t is not None
        ttft = (act.first_tok_t - act.submit_t) if got_first else 0.0
        self.finished[act.req.rid] = RequestResult(
            rid=act.req.rid, tokens=np.asarray(act.generated, np.int32),
            finish_reason=reason, ttft=ttft, tpot=tpot,
            queue_wait=act.queue_wait,
            prefix_hit_tokens=act.prefix_hit_tokens,
            spec_proposed=act.spec_proposed,
            spec_accepted=act.spec_accepted,
            trace_id=self._trace_ids.pop(act.req.rid, 0))
        ws = self._wait_spans.pop(act.req.rid, None)
        if ws is not None:
            ws.end()
        self._req_spans.pop(act.req.rid, _tracing.NOOP_SPAN).end(
            reason=reason, tokens=len(act.generated))
        self._preempt_count.pop(act.req.rid, None)
        self._m_finished.labels(reason=reason).inc()
        if got_first:
            self._m_ttft.observe(ttft)
        if act.decode_steps:
            self._m_tpot.observe(tpot)
        if self._on_finish is not None:
            self._on_finish(self.finished[act.req.rid])

    def _check_finished(self, idx: int, lengths=None):
        """Retire the slot if its latest token ended the request.  The
        cache-full check reads the slot's host-tracked COMMITTED length
        (``act.cache_len`` — what consumed device programs actually
        advanced): no device fetch on the decode hot path, and exact in
        the overlapped loop too, where the engine's dispatch-time mirror
        runs one step ahead of consumed truth.  ``lengths`` is accepted
        for backward compatibility and ignored."""
        act = self.slots[idx]
        req = act.req
        if not act.generated:
            return
        tok = act.generated[-1]
        if req.eos_token_id is not None and tok == int(req.eos_token_id):
            self._finish(idx, "eos")
        elif len(act.generated) >= req.max_new_tokens:
            self._finish(idx, "length")
        elif act.cache_len >= self.engine.max_len:
            # no room for another append — retire rather than overflow
            self._finish(idx, "cache_full")

    # -- refcount-aware eviction (page pool pressure) ----------------------

    def _preempt(self, idx: int):
        """vLLM-style recompute preemption: park the slot's state, free
        its pages, and put the request back at the FRONT of the waiting
        queue.  On re-admission the request re-prefills
        ``prompt + generated`` — greedy continuation is unchanged and
        the recompute mostly prefix-hits the victim's own still-cached
        (refcount-0 but hash-reachable) pages — instead of being
        finished with whatever it had: a victim evicted mid-prefill
        would otherwise silently return an EMPTY token array through
        ``generate()``."""
        act = self.slots[idx]
        rid = act.req.rid
        self.slots[idx] = None
        self.engine.free_slot(idx)     # pages back (shared: refcount--)
        act.prefill_task = None        # chunk state is page-bound: drop
        self.waiting.appendleft(act.req)
        self._submit_t[rid] = act.submit_t
        self._preempted[rid] = act
        # trace: mark the eviction on the request lane and open the
        # "requeue" rework-wait span (ended at re-admission)
        root = self._req_spans.get(rid, _tracing.NOOP_SPAN)
        root.event("preempted", slot=idx, generated=len(act.generated))
        self._wait_spans[rid] = self._tracer.span("requeue", parent=root,
                                                  rework=True)
        self._m_preempt.inc()
        self._m_queue_depth.set(len(self.waiting))

    def _evict_for_pages(self, requester_idx: int) -> bool:
        """Free pages by preempting one slot.  Victim: the occupied
        slot with the MOST unshared pages (what eviction actually
        returns to the pool — a prefix-heavy slot's shared pages only
        drop a refcount), preferring slots other than the requester;
        ties break oldest-admitted-first.  The victim is requeued for
        recompute unless it has already been evicted
        ``max_preemptions`` times (then it finishes "cache_full" — the
        cap bounds wasted recompute and preserves termination).
        Returns False only when the requester itself was the last
        occupant: a sequence the pool cannot hold alone is finished
        "cache_full", never requeued (it would loop forever)."""
        candidates = [i for i, a in enumerate(self.slots)
                      if a is not None and i != requester_idx]
        if not candidates:
            self._finish(requester_idx, "cache_full")
            return False
        victim = max(candidates,
                     key=lambda i: (self.engine.unshared_pages(i),
                                    -self.slots[i].admit_order))
        rid = self.slots[victim].req.rid
        n = self._preempt_count.get(rid, 0) + 1
        self._preempt_count[rid] = n
        if n > self.max_preemptions:
            self._finish(victim, "cache_full")
        else:
            self._preempt(victim)
        return True

    # -- admission ---------------------------------------------------------

    def _begin_paged(self, idx: int, req: Request, ids, engine=None):
        """Start a chunked-prefill admission of ``ids`` into ``idx`` —
        the one place for the prefill_begin call and its prefix-hit
        metric (fresh admissions and preemption resumes both land
        here).  ``engine`` defaults to the decode engine; the
        disaggregated scheduler passes its prefill engine."""
        engine = self.engine if engine is None else engine
        task = engine.prefill_begin(
            idx, ids, temperature=req.temperature,
            top_k=req.top_k, top_p=req.top_p)
        if task.shared_pages:
            self._m_prefix_hits.inc(task.shared_pages)
            self._req_spans.get(req.rid, _tracing.NOOP_SPAN).event(
                "prefix_hit", pages=task.shared_pages,
                tokens=task.shared_tokens)
        return task

    def _admit_paged(self, idx: int, req: Request, engine=None,
                     slots=None):
        """Pop-side bookkeeping for ONE paged admission (fresh or
        preemption resume) into slot ``idx`` of ``slots`` against
        ``engine`` — defaults are the decode engine/slot list; the
        disaggregated scheduler routes admissions to its prefill
        engine through the same path so spans, queue-wait and the
        resume contract cannot drift between roles.  Returns the
        (fresh or resumed) :class:`_ActiveSlot`."""
        engine = self.engine if engine is None else engine
        slots = self.slots if slots is None else slots
        submit_t = self._submit_t.pop(req.rid)
        resumed = self._preempted.pop(req.rid, None)
        order = self._admit_seq
        self._admit_seq += 1
        # close the wait span (initial "queue", or a preemption's
        # "requeue") and mark the admission on the request lane
        ws = self._wait_spans.pop(req.rid, None)
        if ws is not None:
            ws.end()
        root = self._req_spans.get(req.rid, _tracing.NOOP_SPAN)
        root.event("readmitted" if resumed is not None else "admitted",
                   slot=idx)
        if resumed is not None:
            # recompute-resume a preempted request: re-prefill
            # prompt + generated so the next sampled token continues
            # the sequence; timing state (ttft, decode_s) and the
            # token list survive on the parked slot.  queue_wait is
            # NOT re-observed — one histogram sample per request.
            ids = req.prompt
            if resumed.generated:
                ids = np.concatenate(
                    [ids, np.asarray(resumed.generated, np.int32)])
            task = self._begin_paged(idx, req, ids, engine=engine)
            # keep the per-request field consistent with the
            # registry counter: resume hits are cache-served work too
            resumed.prefix_hit_tokens += task.shared_tokens
            resumed.prefill_task = task
            resumed.admit_order = order
            slots[idx] = resumed
            return resumed
        admit_t = time.perf_counter()
        queue_wait = admit_t - submit_t
        self._m_queue_wait.observe(queue_wait)
        task = self._begin_paged(idx, req, req.prompt, engine=engine)
        act = _ActiveSlot(req, submit_t, queue_wait, order,
                          prefill_task=task)
        slots[idx] = act
        return act

    def admit(self) -> int:
        """Fill free slots from the waiting queue (FIFO).  Paged engines
        only BEGIN the prefill here (chunks run in :meth:`step`,
        interleaved with decode); slotted engines run their one-shot
        bucketed prefill.  Returns how many requests were admitted."""
        n = 0
        for idx in range(self.engine.num_slots):
            if self.slots[idx] is not None or not self.waiting:
                continue
            req = self.waiting.popleft()
            # a request whose prompt+budget exceeds max_len is still
            # admissible — generation just ends early with "cache_full"
            if self.engine.paged:
                if (req.rid not in self._preempted
                        and self._begin_host_fetch(req)):
                    # diverted to the host-tier fetch lane: the slot
                    # stays free this round (a later request may take
                    # it next iteration — accepted FIFO relaxation
                    # while the head's pages stream back in)
                    continue
                self._admit_paged(idx, req)
                n += 1
                continue
            submit_t = self._submit_t.pop(req.rid)
            order = self._admit_seq
            self._admit_seq += 1
            ws = self._wait_spans.pop(req.rid, None)
            if ws is not None:
                ws.end()
            root = self._req_spans.get(req.rid, _tracing.NOOP_SPAN)
            root.event("admitted", slot=idx)
            admit_t = time.perf_counter()
            queue_wait = admit_t - submit_t
            self._m_queue_wait.observe(queue_wait)
            self._m_bucket_hits.labels(
                bucket=self.engine.bucket_for(req.prompt.size)).inc()
            sp = self._tracer.span("prefill", parent=root, slot=idx)
            tok, _logits = self.engine.prefill(
                idx, req.prompt, temperature=req.temperature,
                top_k=req.top_k, top_p=req.top_p)
            sp.end()
            root.event("first_token")
            act = _ActiveSlot(req, submit_t, queue_wait, order)
            act.cache_len = int(req.prompt.size)
            act.first_token(tok, time.perf_counter())
            self.slots[idx] = act
            self._notify_tokens(req.rid, act.generated[-1:])
            self._check_finished(idx)
            n += 1
        if n:
            self._m_queue_depth.set(len(self.waiting))
            self._m_occupancy.set(
                sum(a is not None for a in self.slots))
        return n

    # -- tiered KV host-cache fetch (ISSUE 17) -----------------------------
    # The disagg handoff discipline, pointed at a tier instead of a
    # second engine: one phase per fetch per iteration (stage, then
    # ready-poll, then import+adopt), interleaved between decode steps
    # so a fetch in flight never blocks a decode dispatch.

    def _begin_host_fetch(self, req) -> bool:
        """Divert the popped queue-head request into the fetch lane when
        the host tier can extend its device-resident prefix coverage.
        Preemption resumes never divert (their recompute ids already
        mostly prefix-hit their own still-cached pages)."""
        plan = self.engine.host_fetch_plan(req.prompt)
        if not plan:
            return False
        root = self._req_spans.get(req.rid, _tracing.NOOP_SPAN)
        span = self._tracer.span("kv_tier", parent=root,
                                 pages=len(plan))
        self._fetches[req.rid] = _HostFetch(req, plan, span,
                                            time.perf_counter())
        self._m_queue_depth.set(len(self.waiting))
        return True

    def _fetch_advance(self):
        """Advance every in-flight host-tier fetch by ONE phase."""
        for rid in list(self._fetches):
            f = self._fetches.get(rid)
            if f is None:
                continue
            with self._kvt_beacon:
                self._fetch_advance_one(rid, f)

    def _fetch_advance_one(self, rid, f):
        eng = self.engine
        if f.staged is None:
            # phase 1: read the tier entries, npz-roundtrip them through
            # the serve.kv_tier chaos site, and dispatch the device
            # placement (async — the poll below is the only wait)
            digs = [d for _i, d in
                    f.plan[f.pos:f.pos + eng.handoff_pages]]
            try:
                f.staged = eng.host_fetch_stage(digs, rid=rid,
                                                chunk=f.chunk_idx)
            except (KeyError,) + _TIER_ERRORS as e:
                self._fetch_abort(rid, f, digs, e)
                return
            f.staged_digests = digs
            f.chunk_idx += 1
            return
        # phase 2: non-blocking readiness poll — a chunk still in
        # flight just waits another iteration, the decode loop keeps
        # dispatching
        if not all(a.is_ready() for a in f.staged if a is not None):
            return
        # phase 3: land the chunk — allocate destination pages, scatter
        # through the ONE compiled kv_import program (donating the pool;
        # device execution order sequences it against any in-flight
        # decode step, the disagg discipline), and adopt each page as
        # free-but-cached content reachable under its digest
        digs = f.staged_digests
        pids = self._fetch_alloc(rid, f, len(digs))
        if pids is None:
            return                 # aborted, or parked for pages
        try:
            eng.import_pages(f.staged, pids)
        except Exception as e:
            # the scatter tore (device dispatch / staging decode): the
            # fresh pages were never adopted, so release them
            # refcount-exactly and degrade this fetch to recompute —
            # same discipline as a phase-1 transport tear
            for pid in pids:
                eng._alloc._release(pid)
            self._fetch_abort(rid, f, digs, e)
            return
        for pid, d in zip(pids, digs):
            eng._alloc.adopt_page(pid, [d])
        eng._m_pool.set(eng._alloc.pages_used())
        f.pages_in += len(digs)
        f.pos += len(digs)
        f.staged = None
        f.staged_digests = None
        if f.pos >= len(f.plan):
            self._fetch_complete(rid, f)

    def _fetch_alloc(self, rid, f, n):
        """Allocate ``n`` destination pages for a fetch chunk.  Pool
        pressure drains the in-flight decode step first (its
        retirements may free pages); still dry, the fetch PARKS —
        partial allocations released refcount-exactly, the chunk
        retried next iteration once decodes retire — rather than
        preempting active slots for a request that is still waiting.
        A pool that cannot hold the chunk even empty aborts the fetch
        to recompute."""
        alloc = self.engine._alloc
        pids = []
        try:
            for _ in range(n):
                pids.append(alloc.alloc())
            return pids
        except PagePoolExhausted as e:
            for pid in pids:
                alloc._release(pid)
            if self._drain_inflight():
                return None        # retry next iteration
            if any(a is not None for a in self.slots):
                return None        # parked: decodes will free pages
            self._fetch_abort(rid, f, f.staged_digests, e)
            return None

    def _fetch_abort(self, rid, f, digests, exc):
        """A fetch chunk tore (transport error at the ``serve.kv_tier``
        site, a vanished LRU entry, or an unservable pool): degrade to
        recompute.  Earlier chunks' adopted pages REMAIN valid cached
        content; the torn chunk's digests are discarded from the tier
        so the retry's plan is strictly smaller — degradation
        terminates structurally.  The request requeues at the queue
        FRONT (the ``serve.handoff`` requeue discipline) and the next
        admission recomputes whatever the tier no longer covers."""
        tier = self.engine._host_tier
        if tier is not None:
            for d in digests or ():
                tier.discard(d)
            self.engine._m_host_bytes.set(tier.bytes_used())
        _flight.record("kv_tier_abort", rid=rid,
                       error=type(exc).__name__, chunk=f.chunk_idx,
                       pages_in=f.pages_in, planned=len(f.plan))
        _flight.crash_dump({"kind": "kv_tier_abort", "rid": rid,
                            "error": repr(exc)})
        f.span.end(aborted=True, error=type(exc).__name__,
                   pages=f.pages_in)
        del self._fetches[rid]
        self.waiting.appendleft(f.req)
        self._m_queue_depth.set(len(self.waiting))

    def _fetch_complete(self, rid, f):
        """Every planned page landed: requeue at the queue FRONT so the
        next admission's prefix lookup finds the whole prompt device-
        resident and admits it as a full prefix hit (one 1-token
        chunk).  ``kv_host_hits`` counts pages that LANDED — the
        honest hit metric."""
        del self._fetches[rid]
        self.waiting.appendleft(f.req)
        self._m_host_hits.inc(f.pages_in)
        self._m_fetch_s.observe(time.perf_counter() - f.t0)
        f.span.end(pages=f.pages_in)
        self._m_queue_depth.set(len(self.waiting))

    def _run_prefill_chunk(self, act, task, engine, evict, sync=True):
        """ONE chunked-prefill advance — span selection (recompute
        chunks after a preemption are REWORK-tagged so the trace
        analyzer attributes them separately from first-admission
        prefill; rid stays in _preempt_count until finish), the
        PagePoolExhausted retry loop, and the chunk-histogram
        accounting.  Shared by the decode-side loop and the
        disaggregated scheduler's prefill side so none of that can
        drift between roles.  ``evict()`` returns True to retry the
        chunk after freeing pages, False to give up (the requester was
        retired, or parks to wait).  Returns ``prefill_step``'s
        ``done``, or None when evict gave up."""
        rid = act.req.rid
        root = self._req_spans.get(rid, _tracing.NOOP_SPAN)
        sp = (self._tracer.span("prefill_chunk", parent=root,
                                pos=task.pos, rework=True)
              if rid in self._preempt_count else
              self._tracer.span("prefill_chunk", parent=root,
                                pos=task.pos))
        t0 = time.perf_counter()
        while True:
            try:
                done = engine.prefill_step(task, sync=sync)
                break
            except PagePoolExhausted:
                if not evict():
                    done = None
                    break
        sp.end()
        if done is not None:
            self._m_prefill_chunk.observe(time.perf_counter() - t0)
        return done

    def prefill_once(self) -> int:
        """Advance every admitting slot by ONE chunk (the chunked-
        prefill interleave).  A chunk that cannot map pages evicts the
        max-unshared victim and retries.  Returns chunks run."""
        n = 0
        for idx, act in enumerate(self.slots):
            if act is None or act.prefill_task is None:
                continue
            task = act.prefill_task
            if not isinstance(task, PrefillTask):
                # a disaggregated handoff parks its (non-chunk) task in
                # the same field so the slot stays un-decodable; the
                # disagg scheduler advances it, not this loop
                continue

            def evict(idx=idx):
                # drain any in-flight decode step FIRST: its
                # retirements may free enough pages, and a preempted
                # victim must never have an undrained step (the
                # parked token list would then lag the device)
                if self._drain_inflight():
                    return True
                return self._evict_for_pages(idx)

            done = self._run_prefill_chunk(act, task, self.engine,
                                           evict)
            if done is None:
                continue
            n += 1
            if done:
                act.prefill_task = None
                act.cache_len = int(task.ids.size)
                root = self._req_spans.get(act.req.rid,
                                           _tracing.NOOP_SPAN)
                if act.first_tok_t is None:
                    root.event("first_token")
                act.first_token(task.first_token, time.perf_counter())
                self._notify_tokens(act.req.rid, act.generated[-1:])
                self._check_finished(idx)
        return n

    # -- decode ------------------------------------------------------------

    def _active_mask(self):
        return [a is not None and a.prefill_task is None
                for a in self.slots]

    def _notify_tokens(self, rid, toks):
        if self._on_token is not None and toks:
            self._on_token(rid, [int(t) for t in toks])

    def _dispatch_decode(self) -> Optional[_Inflight]:
        """Dispatch ONE batched decode (or speculative verify) step over
        the active, fully-prefilled slots — without consuming it.  When
        an unconsumed step is in flight (the overlapped loop), its
        device-side sampled tokens are threaded straight into this
        dispatch (no host round-trip); lanes that joined since (fresh
        prefills) merge their host-known first token in with one eager
        ``where``.  Page pressure drains the in-flight step FIRST (its
        retirements may free pages, and an eviction victim must never
        carry an undrained step), then evicts refcount-aware.  Returns
        the in-flight record, or None when nothing is active."""
        with _tracing.annotation("sched", "decode_dispatch"):
            spec_k = int(getattr(self.engine, "spec_k", 0))
            active = self._active_mask()
            if not any(active):
                return None
            if self.engine.paged:
                # pre-step page bookkeeping: every append (k+1 of them per
                # slot for a verify step) needs a mapped private page.  A
                # verify step's advance is data-dependent, so while one is
                # unconsumed the engine mirror lags it — cover BOTH steps'
                # worst case (non-spec steps advance the mirror at dispatch:
                # no slack needed).
                while True:
                    slack = (spec_k + 1
                             if spec_k and self._inflight is not None else 0)
                    blocked = self.engine.ensure_decode_ready(
                        active, steps=spec_k + 1 + slack)
                    if blocked is None:
                        break
                    if self._drain_inflight():
                        active = self._active_mask()
                    else:
                        self._evict_for_pages(blocked)
                        active = self._active_mask()
                    if not any(active):
                        return None
            S = self.engine.num_slots
            tokens = np.zeros((S,), np.int32)
            fresh = np.zeros((S,), bool)
            temps = np.ones((S,), np.float32)
            top_ks = np.zeros((S,), np.int32)
            top_ps = np.ones((S,), np.float32)
            drafts = np.zeros((S, max(spec_k, 1)), np.int32)
            prev = self._inflight
            if prev is not None and prev.rec.consumed:
                prev = None
            for i, act in enumerate(self.slots):
                if not active[i]:
                    continue
                if (prev is None or not prev.rec.active[i]
                        or prev.lane_acts[i] is not act):
                    # no in-flight step holds this lane's next token: feed
                    # the host-known last token (first dispatch, a fresh
                    # prefill, or a drained pipeline)
                    tokens[i] = act.generated[-1]
                    fresh[i] = True
                temps[i] = act.req.temperature
                top_ks[i] = act.req.top_k
                top_ps[i] = act.req.top_p
                if spec_k:
                    # self-speculative prompt-lookup draft over the slot's
                    # OWN history — host-side, zero model FLOPs; a miss just
                    # pads (the verify step then emits one token, like
                    # decode).  With a step in flight the history lags by
                    # its unconsumed emit — draft quality moves throughput,
                    # never correctness (greedy accept is history-free).
                    hist = np.concatenate(
                        [act.req.prompt,
                         np.asarray(act.generated, np.int32)])
                    drafts[i], _hit = _propose_draft(
                        hist, spec_k, getattr(self.engine, "spec_ngram", 3))
            if prev is not None and not bool(fresh.all()):
                # thread the in-flight step's sampled tokens on DEVICE: for
                # a verify step the last committed token of lane i is
                # emitted[i, counts[i]-1] (an eager gather on futures)
                import jax.numpy as jnp
                if prev.rec.kind == "spec":
                    prev_last = jnp.take_along_axis(
                        prev.rec.emitted,
                        jnp.maximum(prev.rec.counts, 1)[:, None] - 1,
                        axis=1)[:, 0]
                else:
                    prev_last = prev.rec.tok
                tok_in = (jnp.where(jnp.asarray(fresh), jnp.asarray(tokens),
                                    prev_last)
                          if bool(fresh.any()) else prev_last)
            else:
                tok_in = tokens
            # host-gap accounting: with nothing in flight, the whole window
            # since the last fetch starved the device (the sync loop pays
            # this every step; the overlapped loop only on true bubbles)
            t0_ns = time.perf_counter_ns()
            if self._outstanding == 0 and self._last_fetch_ns is not None:
                self.host_gap_seconds += (t0_ns - self._last_fetch_ns) * 1e-9
            if spec_k:
                rec = self.engine.decode_spec_submit(
                    tok_in, drafts, active, temps, top_ks, top_ps,
                    pages_ready=True)
            else:
                rec = self.engine.decode_submit(tok_in, active, temps,
                                                top_ks, top_ps,
                                                pages_ready=True)
            self._outstanding += 1
            return _Inflight(rec=rec,
                             lane_acts=[self.slots[i] if active[i] else None
                                        for i in range(S)],
                             t0_ns=t0_ns)

    def _consume_inflight(self, infl: _Inflight) -> int:
        """Consume one dispatched step: fetch its sampled tokens (the
        only blocking device sync of an iteration) and run the host-side
        bookkeeping — extend token lists, truncate at EOS/budget, retire
        finished slots, notify streams.  A lane is credited ONLY if the
        same request still occupies it (see :class:`_Inflight`): the
        overshoot token a one-step-stale dispatch computed for a
        since-retired slot is discarded here, and its cache rows are
        reclaimed by the retire's ``free_slot`` — the host length mirror
        stays exact without a rollback program."""
        rec = infl.rec
        spec_k = self.engine.spec_k if rec.kind == "spec" else 0
        # the wait that ends host_gap_seconds
        with _tracing.annotation("sched", "fetch"):
            if rec.kind == "spec":
                emitted, counts, _logits = self.engine.decode_spec_fetch(rec)
            else:
                next_tok, _logits = self.engine.decode_fetch(rec)
        t1_ns = time.perf_counter_ns()
        with _tracing.annotation("sched", "deliver"):
            self._outstanding -= 1
            self._last_fetch_ns = t1_ns
            self.decode_steps_total += 1
            # the step interval: clipped at the previous consume so
            # consecutive overlapped steps never double-charge wall time
            # (per-request decode_s must sum to drain wall, not 2x it);
            # feeds the histogram AND every involved request's trace span,
            # so trace-report TPOT reproduces the metric exactly
            t0_ns = (infl.t0_ns if self._last_step_end_ns is None
                     else max(infl.t0_ns, self._last_step_end_ns))
            self._last_step_end_ns = t1_ns
            step_s = (t1_ns - t0_ns) * 1e-9
            t1 = t1_ns * 1e-9                      # last_t bookkeeping
            n = 0
            spec_prop = spec_acc = 0               # per-ITERATION counter incs
            for i, act in enumerate(self.slots):
                if (not rec.active[i] or act is None
                        or infl.lane_acts[i] is not act):
                    continue               # retired/preempted/cancelled since
                if spec_k:
                    raw = int(counts[i])
                    emit = [int(t) for t in emitted[i, :raw]]
                    act.spec_proposed += spec_k
                    act.spec_accepted += len(emit) - 1
                    spec_prop += spec_k
                    spec_acc += len(emit) - 1
                    # mirror the program's finalize: the device committed
                    # `raw` rows for this lane (clamped in-program)
                    act.cache_len = min(act.cache_len + raw,
                                        self.engine.max_len)
                    # truncate at the budget and at EOS — both retire the
                    # slot in _check_finished, so a truncated host token
                    # list never belongs to a live (still-decoding) slot
                    room = act.req.max_new_tokens - len(act.generated)
                    emit = emit[:max(room, 0)]
                    if act.req.eos_token_id is not None:
                        eos = int(act.req.eos_token_id)
                        if eos in emit:
                            emit = emit[:emit.index(eos) + 1]
                else:
                    emit = [int(next_tok[i])]
                    act.cache_len = min(act.cache_len + 1,
                                        self.engine.max_len)
                act.generated.extend(emit)
                act.decode_s += step_s
                act.decode_steps += len(emit)   # TPOT = secs per token
                act.last_t = t1
                n += len(emit)
                self._notify_tokens(act.req.rid, emit)
                if self._tron:
                    # one span per involved request per iteration, stamped
                    # with the shared step interval; `tokens` is the
                    # decode-committed count (post-truncation), matching the
                    # TPOT accounting exactly
                    self._tracer.add_span(
                        "spec_verify" if spec_k else "decode", t0_ns, t1_ns,
                        parent=self._req_spans.get(act.req.rid),
                        tokens=len(emit))
                self._check_finished(i)
            # per-ITERATION metrics (not per token): one histogram observe,
            # one counter inc, one gauge set per batched step
            self._m_decode_step.observe(step_s)
            self._m_tokens.inc(n)
            if spec_prop:
                self._m_spec_prop.inc(spec_prop)
                self._m_spec_acc.inc(spec_acc)
            self._m_occupancy.set(sum(a is not None for a in self.slots))
            return n

    def _drain_inflight(self) -> bool:
        """Consume the in-flight step now, if any (page pressure, a
        cancel, or an external caller needing consistent host state).
        Tokens it credited land in ``self._drained_n`` for step() to
        collect; returns whether a step was drained."""
        infl = self._inflight
        if infl is None or infl.rec.consumed:
            self._inflight = None
            return False
        self._inflight = None
        self._drained_n += self._consume_inflight(infl)
        return True

    def decode_once(self) -> int:
        """One SYNCHRONOUS batched decode (or speculative verify)
        iteration over the active slots: dispatch + immediate consume
        (the ``overlap=False`` loop, and the direct-caller API).  Any
        leftover overlapped step is drained first; returns the number
        of tokens appended to live requests by THIS iteration."""
        self._drain_inflight()
        infl = self._dispatch_decode()
        if infl is None:
            return 0
        return self._consume_inflight(infl)

    def step(self) -> int:
        """One scheduler iteration: admit into free slots, advance every
        admitting slot by one prefill chunk, then one batched decode.
        Overlapped (the default): dispatch step t BEFORE consuming step
        t-1, so the host bookkeeping below overlaps the device's compute
        for step t.  Returns decode tokens produced this iteration
        (prefill first-tokens excluded).

        The whole iteration runs inside the ``serve.scheduler_step``
        liveness beacon's guard: an iteration that wedges (hung
        collective, injected ``Hang`` at the ``serve.step`` site) is a
        stall the watchdog can attribute, while an idle scheduler
        (between ``run()`` drives) is simply unwatched."""
        with self._beacon:
            faultpoint(STEP_SITE, scheduler=self)
            return self._step_inner()

    def _step_inner(self) -> int:
        self._drained_n = 0
        with _tracing.annotation("sched", "admit"):
            self.admit()
            self._fetch_advance()
        with _tracing.annotation("sched", "prefill_dispatch"):
            self.prefill_once()
        if self.overlap:
            prev = self._inflight
            nxt = self._dispatch_decode()   # threads prev's device toks
            self._inflight = nxt
            n = 0
            if prev is not None and not prev.rec.consumed:
                n = self._consume_inflight(prev)
        else:
            n = self.decode_once()
        n += self._drained_n
        self._drained_n = 0
        if self._inflight is None and not self.has_work():
            # pipeline fully idle with NO backlog (drain end / between
            # traffic): the window until the next dispatch is ARRIVAL
            # time, not host work — charging it would book a load
            # test's Poisson gaps as host gap.  A drained pipeline
            # with requests still waiting keeps the clock: that window
            # IS host-side serialization (admission + prefill).
            self._last_fetch_ns = None
        # HBM ledger sample at the ITERATION boundary (host-side, after
        # the batched step dispatched — never inside a trace).  One
        # module-global None check while the ledger is disarmed, the
        # default (tests assert the noop path).
        _hbm.maybe_sample("serving.iteration")
        return n

    def has_work(self) -> bool:
        """Anything left to drive: waiting requests, occupied slots, or
        an unconsumed in-flight step.  ``run()`` and the front-end's
        scheduler thread poll this one predicate (the disaggregated
        scheduler extends it with its prefill-side and handoff
        state)."""
        return bool(self.waiting
                    or self._fetches
                    or any(a is not None for a in self.slots)
                    or self._inflight is not None)

    def run(self) -> Dict[int, RequestResult]:
        """Drive to completion; returns {rid: RequestResult}.  Always
        terminates: with work pending, admit() either fills a free slot
        or all slots are occupied; prefill_once() advances every
        admitting prompt by one (finite) chunk — evicting on page
        pressure rather than blocking — and each consumed decode step
        appends a token to every credited request, each of which is
        finite (max_new_tokens / max_len eviction).  Preemption cannot
        spin forever: each request is requeued at most
        ``max_preemptions`` times before it finishes "cache_full", and a
        requester that is the sole occupant is finished, never requeued.
        The overlapped loop adds one tail iteration that only consumes
        the final in-flight step."""
        while self.has_work():
            self.step()
        return self.finished

    def cancel(self, rid: int) -> bool:
        """Abort a request (a disconnected streaming client): frees its
        slot AND its pages immediately (refcount-exact — a shared prefix
        page only drops a refcount), or removes it from the waiting
        queue / the preemption-parking area.  Tokens generated so far
        ride the ``"cancelled"`` :class:`RequestResult`.  Returns False
        when the rid is unknown or already finished.  Must run on the
        scheduler's thread (the front-end routes cancels through its
        command queue)."""
        if rid in self.finished:
            return False
        # an in-flight step may hold a lane for this request: drain
        # first so the consume's identity check stays meaningful and
        # the engine's spec length mirror (advanced at fetch by the
        # DISPATCH mask) never credits a freed lane
        self._drain_inflight()
        if rid in self.finished:       # the drain itself retired it
            return True
        f = self._fetches.pop(rid, None)
        if f is not None:
            # mid-fetch cancel: no device pages are held between phases
            # (alloc+import+adopt are atomic within one phase call — a
            # staged, unimported chunk holds only transfer buffers),
            # and already-adopted pages are valid shared cache content
            # that simply stays.  Fetches never cover preemption
            # resumes, so there are no parked tokens to report.
            f.span.end(aborted=True, error="cancelled",
                       pages=f.pages_in)
            self._submit_t.pop(rid, None)
            res = RequestResult(
                rid=rid, tokens=np.asarray([], np.int32),
                finish_reason="cancelled", ttft=0.0, tpot=0.0,
                trace_id=self._trace_ids.pop(rid, 0))
            self.finished[rid] = res
            ws = self._wait_spans.pop(rid, None)
            if ws is not None:
                ws.end()
            self._req_spans.pop(rid, _tracing.NOOP_SPAN).end(
                reason="cancelled", tokens=0)
            self._m_finished.labels(reason="cancelled").inc()
            if self._on_finish is not None:
                self._on_finish(res)
            return True
        for idx, act in enumerate(self.slots):
            if act is not None and act.req.rid == rid:
                self._finish(idx, "cancelled")
                return True
        for req in list(self.waiting):
            if req.rid == rid:
                self.waiting.remove(req)
                self._m_queue_depth.set(len(self.waiting))
                parked = self._preempted.pop(rid, None)
                self._submit_t.pop(rid, None)
                self._preempt_count.pop(rid, None)
                got_first = (parked is not None
                             and parked.first_tok_t is not None)
                res = RequestResult(
                    rid=rid,
                    tokens=np.asarray(
                        parked.generated if parked is not None else [],
                        np.int32),
                    finish_reason="cancelled",
                    ttft=((parked.first_tok_t - parked.submit_t)
                          if got_first else 0.0),
                    tpot=((parked.decode_s / parked.decode_steps)
                          if parked is not None and parked.decode_steps
                          else 0.0),
                    queue_wait=(parked.queue_wait
                                if parked is not None else 0.0),
                    prefix_hit_tokens=(parked.prefix_hit_tokens
                                       if parked is not None else 0),
                    trace_id=self._trace_ids.pop(rid, 0))
                self.finished[rid] = res
                ws = self._wait_spans.pop(rid, None)
                if ws is not None:
                    ws.end()
                self._req_spans.pop(rid, _tracing.NOOP_SPAN).end(
                    reason="cancelled", tokens=int(res.tokens.size))
                self._m_finished.labels(reason="cancelled").inc()
                if self._on_finish is not None:
                    self._on_finish(res)
                return True
        return False

    # -- replica failover: in-flight state transfer (ISSUE 19) -------------

    def import_requeue(self, state: "RequeueState") -> int:
        """Adopt one transferred request through the recompute-preemption
        resume path (ISSUE 19 failover / decommission).  The request
        lands at the FRONT of the waiting queue (it already waited on
        its old replica); when it has partial generated tokens a parked
        :class:`_ActiveSlot` is reconstructed so re-admission
        re-prefills ``prompt + generated`` exactly like a page-pressure
        eviction resume — the stream continues at the next token,
        mostly prefix-hitting whatever of the prompt this engine's
        cache already covers.  Timing state (submit_t, first_tok_t,
        decode_s) and the trace lane travel with it; ``state.requeues``
        seeds ``_preempt_count`` so failovers and evictions share one
        ``max_preemptions``-style budget.  Must run on the scheduler's
        thread.  Returns the rid."""
        req = state.req
        rid = req.rid
        assert rid is not None, "RequeueState.req must carry its rid"
        self._next_rid = max(self._next_rid, rid + 1)
        self._submit_t[rid] = state.submit_t
        if state.requeues:
            self._preempt_count[rid] = state.requeues
        root = state.root_span
        if root is None:
            root = _tracing.NOOP_SPAN
        self._trace_ids[rid] = state.trace_id
        self._req_spans[rid] = root
        if state.queue_wait is not None:
            # it was admitted before: park a reconstructed slot so the
            # resume path restores tokens + timing and queue_wait is
            # NOT observed a second time
            act = _ActiveSlot(req, state.submit_t, state.queue_wait,
                              admit_order=0)
            act.generated = list(state.generated)
            act.first_tok_t = state.first_tok_t
            act.decode_s = state.decode_s
            act.decode_steps = state.decode_steps
            act.prefix_hit_tokens = state.prefix_hit_tokens
            act.spec_proposed = state.spec_proposed
            act.spec_accepted = state.spec_accepted
            self._preempted[rid] = act
            root.event("failover_import", tokens=len(act.generated))
            self._wait_spans[rid] = self._tracer.span(
                "requeue", parent=root, rework=True)
        else:
            root.event("failover_import", tokens=0)
            self._wait_spans[rid] = self._tracer.span("queue",
                                                      parent=root)
        self.waiting.appendleft(req)
        self._m_queue_depth.set(len(self.waiting))
        return rid

    def export_requeue_state(self) -> List["RequeueState"]:
        """Drain EVERY unfinished request into portable
        :class:`RequeueState` records, leaving this scheduler empty —
        the graceful half of replica failover (decommission / drain);
        the crash half is synthesized router-side from its admission
        records, since a dead replica exports nothing.  Slots and
        fetch-lane requests free their pages refcount-exactly on the
        way out.  Must run on the scheduler's thread."""
        self._drain_inflight()
        out: List[RequeueState] = []

        def _carry(req, act, queue_wait):
            rid = req.rid
            ws = self._wait_spans.pop(rid, None)
            if ws is not None:
                ws.end()
            root = self._req_spans.pop(rid, None)
            if root is not None and root is not _tracing.NOOP_SPAN:
                root.event("exported")
            st = RequeueState(
                req=req,
                submit_t=self._submit_t.pop(rid, 0.0),
                requeues=self._preempt_count.pop(rid, 0),
                trace_id=self._trace_ids.pop(rid, 0),
                root_span=root,
                queue_wait=queue_wait)
            if act is not None:
                st.generated = list(act.generated)
                st.first_tok_t = act.first_tok_t
                st.queue_wait = act.queue_wait
                st.decode_s = act.decode_s
                st.decode_steps = act.decode_steps
                st.prefix_hit_tokens = act.prefix_hit_tokens
                st.spec_proposed = act.spec_proposed
                st.spec_accepted = act.spec_accepted
            out.append(st)

        for idx, act in enumerate(self.slots):
            if act is None:
                continue
            self.slots[idx] = None
            self.engine.free_slot(idx)
            act.prefill_task = None
            _carry(act.req, act, act.queue_wait)
        for rid, f in list(self._fetches.items()):
            del self._fetches[rid]
            f.span.end(aborted=True, error="exported", pages=f.pages_in)
            _carry(f.req, None, None)
        for req in list(self.waiting):
            parked = self._preempted.pop(req.rid, None)
            _carry(req, parked,
                   parked.queue_wait if parked is not None else None)
        self.waiting.clear()
        self._m_queue_depth.set(0)
        self._m_occupancy.set(0)
        return out

    def request_span(self, rid: int):
        """The live root span of an unfinished request (the front-end
        parents its ``http`` span here so the trace tree stays
        connected); the no-op span when tracing is off or the request
        already retired."""
        return self._req_spans.get(rid, _tracing.NOOP_SPAN)
