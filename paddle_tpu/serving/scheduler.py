"""Continuous (in-flight) batching scheduler over the decode engine.

Requests join and leave the static ``[max_batch]`` decode batch at TOKEN
boundaries: each :meth:`Scheduler.step` (one tick of the serving loop)
first evicts finished/expired slots, then admits queued requests into the
freed slots (prefill through the bucket ladder), then runs exactly one
generation step for every live slot — one token per slot on the plain
engine, up to ``k+1`` on the speculative wrapper. No shape ever changes,
so a warmed engine ticks forever without a recompile — Orca-style
iteration-level scheduling (the same contract vLLM's continuous batching
popularized), implemented host-side against the AOT executables.

Admission is FIFO with a bounded head-of-line bypass: when the head's
prompt does not fit the current slot/page budget (paged engines meter
pages, not slots), the scheduler admits the NEXT fitting request instead
of stalling the queue — but a head that has been bypassed
``hol_starvation_limit`` times pins the queue until it fits, so a big
prompt is delayed, never starved.

Paged engines can run the pool dry mid-generation (a slot crossing a
page boundary with no free page): the scheduler preempts the YOUNGEST
active request — frees its pages, requeues it at the queue head with its
generated tokens folded into the prompt (recompute-style resume; with
the prefix cache warm, the recompute is usually a suffix prefill) — and
retries. ``paddle_serve_preemptions_total{reason}`` meters it.

Early dispatch (docs/serving.md "The tick's anatomy"): a plain engine asks
the scheduler for the next tick (:meth:`Scheduler._plan_next`) as soon as a
tick's sampled tokens are on the host, and dispatches it before it fetches
the logits; ``_emit``, the evictions, the step's close and the next step's
opening then run while the device works. (A tick the step fed itself, the
first or one beside a prefill, hands its tokens out first and has its
successor planned after the emit.) The scheduler answers "none", and
the next step proceeds as it always did, whenever that step could decide
something the tick would pre-empt: an admission, a preemption, the end.

Threading contract: ``submit``/``cancel`` may be called from any thread
(the HTTP front door's handler pool); ``step``/``drain`` run on exactly
one loop thread. Request completion is signaled through a per-request
``threading.Event``. ``abort_all(refuse_new=True)`` — the poisoned-
engine fail-fast path — is safe against racing submits: the refusal
flag is set under the queue lock before the queue drains, so a
concurrent submit is either failed with everyone else or cleanly
refused, never parked on a queue no step will serve again.

The scheduler also measures its own drain rate (terminal requests per
second over a trailing window): ``queue_eta_s``/``retry_after_s`` feed
the front door's deadline-aware shedding and Retry-After responses
(docs/serving.md "Resilience").
"""
from __future__ import annotations

import dataclasses
import itertools
import threading
import time
from collections import deque
from typing import Deque, Dict, List, Optional, Sequence

import numpy as np

from ..observability import goodput as _goodput
from ..observability import spans as _spans
from . import metrics as smetrics
from .engine import DecodeEngine, PromptTooLongError
from .paged_kv import CacheFullError, PagePoolFullError
from .sampling import GREEDY, SamplingParams

__all__ = ["Request", "Scheduler", "SchedulerConfig", "QueueFullError"]


class QueueFullError(RuntimeError):
    """Admission queue at capacity — the front door maps this to 429."""


# request lifecycle
QUEUED, ACTIVE, DONE, EXPIRED, FAILED, CANCELLED = (
    "queued", "active", "done", "expired", "failed", "cancelled")

_ids = itertools.count(1)


@dataclasses.dataclass
class Request:
    prompt: List[int]
    max_new_tokens: int
    deadline: float                       # absolute time.monotonic()
    sampling: SamplingParams = GREEDY
    id: int = dataclasses.field(default_factory=lambda: next(_ids))
    submitted: float = dataclasses.field(default_factory=time.monotonic)
    state: str = QUEUED
    slot: Optional[int] = None
    tokens: List[int] = dataclasses.field(default_factory=list)
    token_times: List[float] = dataclasses.field(default_factory=list)
    ttft_ms: Optional[float] = None
    error: Optional[str] = None
    # head-of-line bookkeeping: how many times a fitting request was
    # admitted past this one while it sat at the queue head
    hol_skips: int = 0
    # preemption (page pool dry): the request resumes by re-prefilling
    # prompt + generated-so-far — True marks it so admission knows
    preempted: bool = False
    # phase disaggregation (ISSUE 17): a prefill_only request finishes
    # at the first-token boundary with its KV serialized into
    # ``handoff`` (serving/kv_transfer.py); on the decode side the same
    # field carries the payload awaiting adoption at the next tick.
    # ``prefix_blob`` is a gang-shared prefix-index record to adopt
    # into the local pool before this request prefills.
    prefill_only: bool = False
    handoff: Optional[dict] = None
    prefix_blob: Optional[dict] = None
    finished: threading.Event = dataclasses.field(
        default_factory=threading.Event)
    # span identity (docs/observability.md): every lifecycle span of this
    # request — queue wait, prefill, eviction — carries trace_id, parented
    # under root_span ("serve/request").  The ticks it rode are the loop's
    # records, one a tick: first_step..last_step (Scheduler.steps at its
    # prefill and at its eviction) is the range of serve/decode_tick
    # records to look through, each naming its riders, so a slow p99
    # still walks straight back to the tick that caused it
    trace_id: int = dataclasses.field(default_factory=_spans.gen_id)
    root_span: int = dataclasses.field(default_factory=_spans.gen_id)
    # cross-process propagation (ISSUE 18): a request arriving with wire
    # trace context keeps the originating trace_id and parents its local
    # "serve/request" span under the sender's span instead of rooting a
    # fresh trace — one request stays ONE trace across router, prefill
    # replica, KV transfer, decode replica, and every failover retry
    parent_span: Optional[int] = None
    # ``submitted`` on the tracer's clock: one stamp, two units
    submit_ns: Optional[int] = None
    first_step: Optional[int] = None
    last_step: Optional[int] = None

    def __post_init__(self):
        if self.submit_ns is None:
            self.submit_ns = _spans.monotonic_to_ns(self.submitted)

    def wait(self, timeout: Optional[float] = None) -> bool:
        return self.finished.wait(timeout)

    def gen_prompt(self) -> List[int]:
        """The token stream a (re-)prefill must cover: the original
        prompt plus everything generated before a preemption."""
        return self.prompt + self.tokens

    @property
    def tpot_ms(self) -> Optional[float]:
        """Mean per-token latency after the first token."""
        if len(self.token_times) < 2:
            return None
        spans = np.diff(self.token_times)
        return float(np.mean(spans) * 1e3)


@dataclasses.dataclass(frozen=True)
class SchedulerConfig:
    max_queue: int = 64               # queued (not yet admitted) requests
    default_timeout_s: float = 30.0   # per-request deadline when unset
    max_new_tokens_cap: int = 1024    # server-side clamp
    # how many times the FIFO head may be bypassed by later, fitting
    # requests before it pins the queue (the starvation bound)
    hol_starvation_limit: int = 32


class Scheduler:
    def __init__(self, engine: DecodeEngine,
                 cfg: Optional[SchedulerConfig] = None):
        self.engine = engine
        self.cfg = cfg or SchedulerConfig()
        self._queue: Deque[Request] = deque()
        self._active: Dict[int, Request] = {}     # slot -> request
        self._next_token: Dict[int, int] = {}     # slot -> token to feed
        self._admit_order: List[int] = []         # slots, oldest first
        self._lock = threading.Lock()
        # every span the loop thread opens for its own work (serve/step
        # and what lies under it) belongs to this one trace and carries
        # the step's number; a request's own spans stay on its trace
        self.loop_trace = _spans.gen_id()
        self._draining = False
        # set by abort_all(refuse_new=True) — the fail-fast path for a
        # poisoned engine: later submits get a clean error instead of
        # queueing onto a scheduler that can never serve them
        self._refusing: Optional[str] = None
        self.steps = 0
        self.occupancy_sum = 0.0                  # for mean occupancy
        self.preemptions = 0
        self.completed = 0                        # requests finished DONE
        # terminal-event timestamps feeding the measured drain rate that
        # deadline-aware shedding / Retry-After are computed from
        # (own lock: _finish runs under self._lock on some paths)
        self._rate_lock = threading.Lock()
        self._done_times: Deque[float] = deque(maxlen=256)
        # migrated requests waiting for KV adoption — drained at the
        # START of each tick, on the loop thread (cache writes must
        # never race a decode step's array swap)
        self._pending_handoffs: Deque[Request] = deque()
        # TTFT/TPOT children resolved once: phase is structural (TTFT
        # ends prefill, TPOT is decode cadence), role is this engine's
        self.role = getattr(engine, "role", "colocated")
        # early dispatch: only the plain engine runs a tick ahead (the
        # speculative wrapper emits a window a step, and a target compiled
        # with a verify window is driven by one)
        self._plain = (isinstance(engine, DecodeEngine)
                       and not engine.ecfg.verify_window)
        self.early_dispatch: Dict[str, int] = {}   # outcome -> ticks
        self._settling = False
        # [first step, start (clock_ns), steps] of the stretch of steps
        # that found work pending and could do none, while it lasts
        self._stall: Optional[List[int]] = None
        self._ttft_hist = smetrics.m_ttft_ms.labels("prefill", self.role)
        self._tpot_hist = smetrics.m_tpot_ms.labels("decode", self.role)

    # ------------------------------------------------------------------
    # producer side (any thread)
    # ------------------------------------------------------------------
    def submit(self, prompt: Sequence[int], max_new_tokens: int = 16,
               timeout_s: Optional[float] = None,
               sampling: Optional[SamplingParams] = None,
               prefill_only: bool = False,
               prefix_blob: Optional[dict] = None,
               trace_ctx: Optional[_spans.Context] = None) -> Request:
        """Enqueue a request; raises QueueFullError on backpressure,
        PromptTooLongError for prompts above the bucket ladder, and
        RuntimeError once draining.

        ``prefill_only=True`` (disaggregated serving) stops the request
        at the first-token boundary: its KV state is serialized into
        ``req.handoff`` and the slot is released — the caller migrates
        the payload to a decode replica via :meth:`submit_handoff`.
        ``prefix_blob`` is a gang-shared prefix record adopted into the
        local pool right before prefill (best-effort).

        ``trace_ctx`` (ISSUE 18) joins this request to an existing trace
        — (trace_id, parent_span) extracted from the wire — instead of
        rooting a fresh one."""
        prompt = [int(t) for t in prompt]
        if not prompt:
            raise ValueError("empty prompt")
        # validate against the ladder NOW so the caller gets a 400, not a
        # request that dies at admission time
        self.engine.bucket_for(len(prompt))
        max_new = max(1, min(int(max_new_tokens),
                             self.cfg.max_new_tokens_cap))
        timeout = (self.cfg.default_timeout_s if timeout_s is None
                   else float(timeout_s))
        kw = {}
        if trace_ctx is not None:
            kw = {"trace_id": int(trace_ctx[0]),
                  "parent_span": int(trace_ctx[1])}
        req = Request(prompt=prompt, max_new_tokens=max_new,
                      deadline=time.monotonic() + timeout,
                      sampling=sampling or GREEDY,
                      prefill_only=bool(prefill_only),
                      prefix_blob=prefix_blob, **kw)
        with self._lock:
            if self._refusing is not None:
                raise RuntimeError(self._refusing)
            if self._draining:
                raise RuntimeError("scheduler is draining")
            if len(self._queue) >= self.cfg.max_queue:
                raise QueueFullError(
                    f"admission queue at capacity ({self.cfg.max_queue})")
            self._queue.append(req)
            smetrics.m_queue_depth.set(len(self._queue))
        # open-sentinel root span (dur 0, attrs.open; superseded by the
        # full "serve/request" record in _finish): a process SIGKILLed
        # mid-request has already flushed its children's parent to disk,
        # so the partial trace still stitches orphan-free
        _spans.record("serve/request", req.submit_ns, 0,
                      trace=req.trace_id, parent=req.parent_span,
                      span_id=req.root_span, attrs={"open": True})
        return req

    def submit_handoff(self, handoff: dict, first_token: int,
                       max_new_tokens: int = 16,
                       timeout_s: Optional[float] = None,
                       sampling: Optional[SamplingParams] = None,
                       prompt: Optional[Sequence[int]] = None,
                       trace_ctx: Optional[_spans.Context] = None
                       ) -> Request:
        """Enqueue a MIGRATED request (disaggregated serving): the
        prefill replica already produced ``first_token`` and serialized
        its KV into ``handoff``; this scheduler adopts the payload at
        the start of its next tick and decodes from there. The request
        is seeded with the first token so finish counting and greedy
        output match the colocated path bit-for-bit."""
        prompt = [int(t) for t in
                  (prompt if prompt is not None
                   else (handoff.get("tokens") or []))]
        if not prompt:
            raise ValueError("handoff carries no prompt tokens — "
                             "preemption resume would be impossible")
        max_new = max(1, min(int(max_new_tokens),
                             self.cfg.max_new_tokens_cap))
        timeout = (self.cfg.default_timeout_s if timeout_s is None
                   else float(timeout_s))
        if trace_ctx is None:
            # the handoff frame itself carries the originating trace
            # (kv_transfer stamps it at export) — adopt it so the decode
            # half of a migrated request lands in the SAME trace
            trace_ctx = _spans.extract(handoff)
        kw = {}
        if trace_ctx is not None:
            kw = {"trace_id": int(trace_ctx[0]),
                  "parent_span": int(trace_ctx[1])}
        req = Request(prompt=prompt, max_new_tokens=max_new,
                      deadline=time.monotonic() + timeout,
                      sampling=sampling or GREEDY, handoff=handoff,
                      **kw)
        req.tokens.append(int(first_token))
        req.token_times.append(time.monotonic())
        with self._lock:
            if self._refusing is not None:
                raise RuntimeError(self._refusing)
            if self._draining:
                raise RuntimeError("scheduler is draining")
            if len(self._pending_handoffs) >= self.cfg.max_queue:
                raise QueueFullError(
                    f"handoff queue at capacity ({self.cfg.max_queue})")
            self._pending_handoffs.append(req)
        # same open-sentinel contract as submit(): the decode half of a
        # migrated request leaves its root on disk at admission
        _spans.record("serve/request", req.submit_ns, 0,
                      trace=req.trace_id, parent=req.parent_span,
                      span_id=req.root_span, attrs={"open": True})
        return req

    def cancel(self, req: Request) -> bool:
        """Cancel a QUEUED request (active ones finish their current
        token and are evicted by deadline instead)."""
        with self._lock:
            if req.state == QUEUED and req in self._queue:
                self._queue.remove(req)
                smetrics.m_queue_depth.set(len(self._queue))
                self._finish(req, CANCELLED)
                return True
        return False

    # ------------------------------------------------------------------
    # loop side (one thread)
    # ------------------------------------------------------------------
    def step(self) -> bool:
        """One serving tick: evict -> admit -> decode. Returns True when
        any work happened (False = idle, the loop may sleep)."""
        if self.stalled_step():
            return False
        self._end_stall()
        attrs = {"step": self.steps}
        # the engine's spans (serve/prefill) name the step they ran in
        self.engine.sched_step = self.steps
        with _spans.span("serve/step", trace=self.loop_trace, attrs=attrs):
            now = time.monotonic()
            self._expire_queued(now)
            # settling collects the tick in flight and starts nothing
            ingested = 0 if self._settling else self._ingest_handoffs(now)
            admitted = 0 if self._settling else self._admit(now)
            decoded = self._decode(now)
            self._count_step()
            worked = bool(ingested or admitted or decoded)
            attrs.update(worked=worked, prefills=admitted,
                         active=len(self._active))
        return worked

    def _count_step(self) -> None:
        self.steps += 1
        occ = self.engine.cache.occupancy
        self.occupancy_sum += occ
        smetrics.m_occupancy.set(occ)
        smetrics.m_active.set(len(self._active))

    def stalled_step(self) -> bool:
        """Count a step that can do nothing, where this one is such:
        requests are queued, and nothing rides, nothing is in flight, no
        handoff waits, no queued request is overdue and none can be
        admitted. True where it was; nothing else of the step is run
        then. A whole stretch of such steps (500 a second from
        ``EngineLoop``, for as long as a prompt waits for room that is
        not freed) leaves ONE ``serve/step`` record when it ends, {step:
        the first, steps: how many, worked: False}: it must not wash the
        requests' records out of the ring."""
        if (self._active or self._pending_handoffs
                or (self._plain and self.engine.ahead_feed is not None)):
            return False
        now = time.monotonic()
        with self._lock:
            if (not self._queue
                    or any(req.deadline <= now for req in self._queue)
                    or (self.engine.cache.free_slot_count() > 0
                        and self._first_admissible() is not None)):
                return False
        if self._stall is None:
            self._stall = [self.steps, _spans.clock_ns(), 0]
        self._stall[2] += 1
        self._count_step()
        return True

    def _end_stall(self) -> None:
        stall, self._stall = self._stall, None
        if stall is not None:
            first, t0, n = stall
            _spans.record("serve/step", t0, _spans.clock_ns() - t0,
                          trace=self.loop_trace,
                          attrs={"step": first, "steps": n, "worked": False,
                                 "prefills": 0, "active": 0})

    def _ingest_handoffs(self, now: float) -> int:
        """Adopt migrated requests' KV payloads into the cache — at the
        tick START, on the loop thread, because adoption swaps the cache
        arrays and must never race a decode step doing the same."""
        n = 0
        while True:
            with self._lock:
                if not self._pending_handoffs:
                    break
                req = self._pending_handoffs[0]
            if req.deadline <= now:
                with self._lock:
                    self._pending_handoffs.popleft()
                self._finish(req, EXPIRED,
                             "deadline exceeded before KV adoption")
                continue
            try:
                with _spans.default_tracer().context(
                        (req.trace_id, req.root_span)):
                    slot = self.engine.adopt_request_kv(req.handoff)
            except (CacheFullError, PagePoolFullError):
                break              # slot/pool pressure — retry next tick
            except Exception as e:
                with self._lock:
                    self._pending_handoffs.popleft()
                self._finish(req, FAILED, f"{type(e).__name__}: {e}")
                continue
            with self._lock:
                self._pending_handoffs.popleft()
            req.handoff = None
            req.state = ACTIVE
            req.slot = slot
            req.first_step = self.steps
            self._active[slot] = req
            self._next_token[slot] = req.tokens[-1]
            self._admit_order.append(slot)
            n += 1
        return n

    def drain(self, timeout_s: float = 60.0) -> bool:
        """Stop admitting new requests and run the loop until every
        queued+active request finished (or the timeout hits). Returns
        True when fully drained."""
        with self._lock:
            self._draining = True
        end = time.monotonic() + timeout_s
        # drain wall time is its own goodput category: the engine is
        # finishing old work but admitting nothing
        with _goodput.timer("drain"):
            while time.monotonic() < end:
                with self._lock:
                    idle = (not self._queue and not self._active
                            and not self._pending_handoffs)
                if idle:
                    return True
                self.step()
            self.settle()
        return False

    def settle(self) -> bool:
        """Collect the tick in flight, if there is one: hand its tokens
        out and dispatch no other (loop thread only: what a loop does as
        it stops). Returns whether there was one."""
        self._end_stall()
        if not self._plain or self.engine.ahead_feed is None:
            return False
        self._settling = True
        try:
            self.step()
        finally:
            self._settling = False
        return True

    def abort_all(self, reason: str, refuse_new: bool = False) -> int:
        """Fail every queued and active request (the loop's fault path —
        a step() exception must not leave waiters hanging on events that
        will never fire). Slots are freed; returns how many requests were
        failed.

        ``refuse_new=True`` (the poisoned-engine fail-fast path) also
        flips the scheduler into refusal: the flag is set under the lock
        BEFORE the queue is drained, so a ``submit`` racing this call
        either lands in the drained snapshot (and is failed here) or
        raises the refusal error — it can never be parked on a queue no
        step will ever serve again."""
        with self._lock:
            if refuse_new:
                self._refusing = reason
            queued = list(self._queue)
            self._queue.clear()
            queued += list(self._pending_handoffs)
            self._pending_handoffs.clear()
            smetrics.m_queue_depth.set(0)
        if self._plain:
            self.engine.drop_ahead()   # its riders go with everyone else
        n = 0
        for slot in list(self._active):
            self._evict(slot, FAILED, reason)
            n += 1
        for req in queued:
            self._finish(req, FAILED, reason)
            n += 1
        smetrics.m_active.set(0)
        return n

    @property
    def refusing(self) -> Optional[str]:
        return self._refusing

    @property
    def draining(self) -> bool:
        return self._draining

    def pending(self) -> int:
        with self._lock:
            return (len(self._queue) + len(self._active)
                    + len(self._pending_handoffs))

    def queue_depth(self) -> int:
        with self._lock:
            return len(self._queue)

    @property
    def mean_occupancy(self) -> float:
        return self.occupancy_sum / self.steps if self.steps else 0.0

    # ------------------------------------------------------------------
    # measured drain rate -> deadline-aware shedding / Retry-After
    # (docs/serving.md "Resilience": the front door rejects requests
    # whose queue-drain ETA already exceeds their deadline, and tells
    # the client when to come back instead of a flat 429)
    # ------------------------------------------------------------------
    def drain_rate(self, window_s: float = 10.0) -> Optional[float]:
        """Terminal requests per second over the trailing window — the
        rate the admission queue is actually draining at. None until two
        requests have finished (no measurable rate yet)."""
        now = time.monotonic()
        with self._rate_lock:
            recent = [t for t in self._done_times if t >= now - window_s]
        if len(recent) < 2:
            return None
        span = max(now - recent[0], 1e-6)
        return len(recent) / span

    def queue_eta_s(self) -> Optional[float]:
        """Estimated seconds until a request submitted NOW reaches a
        decode slot: queue depth over the measured drain rate. 0.0 for an
        empty queue; None when the rate is not yet measurable."""
        with self._lock:
            depth = len(self._queue)
        if depth == 0:
            return 0.0
        rate = self.drain_rate()
        if rate is None or rate <= 0:
            return None
        return depth / rate

    def retry_after_s(self, cap_s: float = 60.0) -> int:
        """Whole seconds a shed client should wait before retrying,
        from the measured drain rate (>= 1; capped)."""
        eta = self.queue_eta_s()
        if eta is None:
            return 1
        return int(min(max(1.0, np.ceil(eta)), cap_s))

    # ------------------------------------------------------------------
    def _expire_queued(self, now: float) -> None:
        with self._lock:
            keep: Deque[Request] = deque()
            for req in self._queue:
                if req.deadline <= now:
                    self._finish(req, EXPIRED,
                                 "deadline exceeded while queued")
                else:
                    keep.append(req)
            self._queue = keep
            smetrics.m_queue_depth.set(len(self._queue))

    def _first_admissible(self) -> Optional[int]:
        """Place in the queue of the request admission would take next
        (under ``self._lock``): the first whose prompt fits the current
        slot/page budget, none past a head bypassed to the starvation
        limit, which pins the queue until it fits."""
        if not self._queue:
            return None
        head = self._queue[0]
        for i, req in enumerate(self._queue):
            if i > 0 and head.hol_skips >= self.cfg.hol_starvation_limit:
                return None           # head pinned: wait for its budget
            if self.engine.can_admit(len(req.gen_prompt())):
                return i
        return None

    def _pop_admissible(self) -> Optional[Request]:
        """FIFO pop with bounded head-of-line bypass."""
        with self._lock:
            i = self._first_admissible()
            if i is None:
                return None
            head, req = self._queue[0], self._queue[i]
            del self._queue[i]
            smetrics.m_queue_depth.set(len(self._queue))
            if i > 0:
                head.hol_skips += 1
                smetrics.m_hol_admits.inc()
            return req

    def _admit(self, now: float) -> int:
        """Prefill queued requests into free slots — FIFO with the
        head-of-line bypass above."""
        if not self._queue:
            return 0
        attrs = {"admitted": 0}
        with _spans.span("serve/admit", attrs=attrs):
            attrs["admitted"] = admitted = self._admit_queued()
        return admitted

    def _admit_queued(self) -> int:
        admitted = 0
        while self.engine.cache.free_slot_count() > 0:
            req = self._pop_admissible()
            if req is None:
                break
            t_admit = _spans.clock_ns()
            if req.prefix_blob is not None:
                # gang-shared prefix record: adopt into the local pool
                # first so the prefill below hits instead of recomputing.
                # Best-effort — any failure just means a cold prefill.
                blob, req.prefix_blob = req.prefix_blob, None
                try:
                    from .kv_transfer import adopt_prefix

                    adopt_prefix(self.engine, blob)
                except Exception:
                    pass
            try:
                # prefill runs inside the request's span context so the
                # engine's serve/prefill span parents under its root
                with _spans.default_tracer().context(
                        (req.trace_id, req.root_span)):
                    if req.preempted:
                        # recompute resume: may exceed the ladder — the
                        # engine chunk-replays the known stream
                        slot, logits, first = \
                            self.engine.resume_sequence_sampled(
                                req.gen_prompt(), req.sampling)
                    else:
                        slot, logits, first = \
                            self.engine.start_sequence_sampled(
                                req.gen_prompt(), req.sampling)
            except (CacheFullError, PagePoolFullError):
                # raced headroom / pool pressure — requeue in order
                with self._lock:
                    self._queue.appendleft(req)
                break
            except Exception as e:
                self._finish(req, FAILED, f"{type(e).__name__}: {e}")
                continue
            # queue wait: submit -> prefill start (span + histogram)
            smetrics.m_queue_wait_ms.observe(
                (t_admit - req.submit_ns) / 1e6)
            _spans.record("serve/queue_wait", req.submit_ns,
                          t_admit - req.submit_ns,
                          trace=req.trace_id, parent=req.root_span)
            t = time.monotonic()
            req.state = ACTIVE
            req.slot = slot
            if req.first_step is None:
                req.first_step = self.steps
            resumed = req.preempted
            req.preempted = False
            if not resumed:
                req.tokens.append(int(first))
                req.token_times.append(t)
                req.ttft_ms = (t - req.submitted) * 1e3
                self._ttft_hist.observe(req.ttft_ms)
                self.engine.note_tokens(1)
                last = int(first)
            else:
                # resumed prefill covered prompt+generated; the sampled
                # continuation token is the next output token
                req.tokens.append(int(first))
                req.token_times.append(t)
                last = int(first)
            if req.prefill_only:
                # first-token boundary of a disaggregated request:
                # serialize the prompt's KV here on the loop thread
                # (the only context allowed to touch the cache arrays),
                # release the slot, and finish — the router migrates
                # req.handoff to a decode replica
                self._active[slot] = req
                admitted += 1
                try:
                    req.handoff = self.engine.export_request_kv(
                        slot, tokens=req.prompt)
                    # the handoff frame carries the trace so the decode
                    # replica's subtree lands in the SAME trace whether
                    # it arrives over the socket channel or inline
                    req.handoff[_spans.WIRE_KEY] = _spans.inject(
                        (req.trace_id, req.root_span))
                except Exception as e:
                    self._evict(slot, FAILED,
                                f"{type(e).__name__}: {e}")
                    continue
                self._evict(slot, DONE, reason="handoff")
                continue
            self._active[slot] = req
            self._next_token[slot] = last
            self._admit_order.append(slot)
            admitted += 1
            if self._should_finish(req, last):
                self._evict(slot, DONE)
            elif self._out_of_room(slot):
                # prompt filled the slot to (near) max_seq: the prefill
                # logits already produced the one token that fits, and
                # the next generation step could not run — finish here
                self._evict(slot, DONE, "max_seq reached",
                            reason="max_seq")
        return admitted

    def _preempt_youngest(self, exclude_slot: Optional[int] = None) -> bool:
        """Free the most recently admitted active request's pages and
        requeue it at the queue head for recompute-resume. Returns False
        when there is nothing (else) to preempt."""
        for slot in reversed(self._admit_order):
            if slot == exclude_slot or slot not in self._active:
                continue
            req = self._active.pop(slot)
            self._next_token.pop(slot, None)
            self._admit_order.remove(slot)
            self.engine.free_sequence(slot)
            req.state = QUEUED
            req.slot = None
            req.preempted = True
            smetrics.m_preemptions.labels("page_pool").inc()
            self.preemptions += 1
            with self._lock:
                self._queue.appendleft(req)
                smetrics.m_queue_depth.set(len(self._queue))
            return True
        return False

    def _ensure_step_capacity(self) -> None:
        """Paged engines: map the pages this tick will write BEFORE the
        batched call; preempt the youngest request(s) while the pool
        cannot cover a slot."""
        for slot in sorted(self._active, key=self._admit_order.index):
            if slot not in self._active:      # preempted by an earlier
                continue                      # iteration's pool squeeze
            while not self.engine.ensure_decode_capacity(slot):
                if not self._preempt_youngest(exclude_slot=slot):
                    # nothing left to preempt: this request alone
                    # exceeds the pool — fail it rather than livelock
                    self._evict(slot, FAILED,
                                "KV page pool exhausted", reason="failed")
                    break

    def _decode(self, now: float) -> bool:
        # evict deadline-blown active requests at the token boundary
        for slot in list(self._active):
            req = self._active[slot]
            if req.deadline <= now:
                self._evict(slot, EXPIRED,
                            "deadline exceeded mid-generation")
        eng = self.engine
        # the tick the step before dispatched ahead: this step collects
        # it for those of its riders that are still here (one expired just
        # now has its lane dropped); a request prefilled since rides from
        # the next tick on
        ahead = eng.ahead_feed if self._plain else None
        if ahead is not None:
            feed = {s: t for s, t in ahead.items() if s in self._active}
            if not feed:
                eng.drop_ahead()
                ahead = None
        if ahead is None:
            if not self._active:
                return False
            self._ensure_step_capacity()
            if not self._active:
                return False
            feed = {slot: self._next_token[slot] for slot in self._active}
        params = {slot: self._active[slot].sampling for slot in feed}
        # ONE record a tick on the loop's trace: the whole batch shares
        # one executable call, so the tick names its riders and a request
        # finds its ticks by step (first_step..last_step), not the other
        # way round
        state_bytes = eng.state_bytes(feed)
        cached = sum(eng.cache.length(s) for s in feed)
        attrs = {
                "step": self.steps, "batch": len(feed),
                "riders": [self._active[s].id for s in feed],
                "cached_tokens": cached,
                # how the tick reads the cache, and how many pages of it
                "kv_path": eng.kv_path,
                "live_pages": eng.live_pages(feed),
                # riders whose recurrent state the tick advances, and the
                # bytes of it they hold (0 where no layer is recurrent)
                "state_slots": len(feed) if state_bytes else 0,
                "state_bytes": state_bytes,
                # dispatched by the tick before it, ahead of this step
                "ahead": ahead is not None}
        if eng.latent_token_bytes:
            # the latent rows of the riders' cached tokens, all layers
            attrs["latent_bytes"] = cached * eng.latent_token_bytes
        if len(eng.cache.groups) > 1:
            # window and global layers: the rows a layer of each page
            # group reads for these riders (the row this tick writes among
            # them, a window group's clipped to its window), and the pages
            # x layers the live slots hold over what one table would
            attrs.update({f"rows_{name}": n for name, n in
                          eng.cache.live_rows(feed, extra=1).items()})
            attrs["held_over_one_table"] = eng.cache.held_over_one_table()
        # a tick found in flight plans its successor inside the call, as
        # soon as its tokens are on the host; a tick this step fed itself
        # (the first, one after a prefill or a held decision) has its
        # riders' longest gap behind it and hands its tokens out first:
        # its successor is planned after the emit, below
        if ahead is not None:
            eng.next_tick = self._plan_next      # for this call alone
        elif not self._plain:
            self._count("held_engine")
        with _spans.span("serve/decode_tick", attrs=attrs):
            try:
                out = eng.generate_step(feed, params)
            finally:
                if ahead is not None:
                    eng.next_tick = None
            # what the tick's expert layers reported, off the device with
            # its logits: routings that fell on held experts, held experts
            # with a token (summed over layers), the fullest one's load
            load = getattr(eng, "last_expert_load", None)
            if load is not None:
                attrs.update(
                    {k: load[k] for k in ("expert_tokens", "experts_hit",
                                          "expert_load_max")})
        attrs = {"emitted": 0, "finished": 0}
        with _spans.span("serve/emit", attrs=attrs):
            self._emit(out, attrs)
        if self._plain and ahead is None:
            with _spans.span("decode/plan"):
                plan = self._plan_next({})
                if plan is not None:
                    eng.dispatch_ahead(*plan)
        return True

    # ------------------------------------------------------------------
    # early dispatch: the engine's question, asked inside a tick
    # ------------------------------------------------------------------
    def _count(self, outcome: str) -> None:
        self.early_dispatch[outcome] = self.early_dispatch.get(outcome, 0) + 1
        smetrics.m_early_dispatch.labels(outcome).inc()

    def early_dispatch_share(self) -> Optional[float]:
        """Share of decode ticks whose successor was dispatched ahead."""
        ticks = sum(self.early_dispatch.values())
        return self.early_dispatch.get("ahead", 0) / ticks if ticks else None

    def _plan_next(self, sampled: Dict[int, int]):
        """The engine's hook (``DecodeEngine.next_tick``): the tokens a
        tick sampled are on the host, its rows committed, nothing else of
        it done. Answer with the next tick, ``({slot: input token}, {slot:
        sampling})``, for the engine to dispatch now, or None to leave it
        to the next step. Asked with no tokens after a step's own tick
        has been emitted: every rider then feeds its ``_next_token``."""
        outcome, plan = self._next_tick(sampled)
        self._count(outcome)
        return plan

    def _next_tick(self, sampled: Dict[int, int]):
        eng = self.engine
        if (self._draining or self._settling or self._refusing is not None
                or eng.poisoned is not None):
            return "held_idle", None
        now = time.monotonic()
        feed: Dict[int, int] = {}
        stops = False
        for slot, req in self._active.items():
            tok = sampled.get(slot)
            if tok is None:
                # prefilled while the tick was in flight: its first token
                # is the one to feed
                tok = self._next_token[slot]
            elif (self._should_finish(req, tok, len(req.tokens) + 1)
                  or self._out_of_room(slot)):
                stops = True          # _emit evicts it, after the dispatch
                continue
            if req.deadline <= now:
                stops = True          # the next step expires it
                continue
            feed[slot] = tok
        if not feed:
            return "held_idle", None
        # a request the next step could admit must not find a tick queued
        # in front of its prefill: hold when a slot is free, or is freed by
        # this tick's stops, and the pool takes a waiting prompt
        with self._lock:
            if self._pending_handoffs or self._queue:
                if (stops or (eng.cache.free_slot_count() > 0 and (
                        self._pending_handoffs
                        or self._first_admissible() is not None))):
                    return "held_admission", None
        # the next rows' pages; a dry pool is the next step's to settle
        # (_ensure_step_capacity preempts, or fails a request)
        for slot in feed:
            if not eng.ensure_decode_capacity(slot):
                return "held_capacity", None
        return "ahead", (feed, {slot: self._active[slot].sampling
                                for slot in feed})

    def _emit(self, out, counts) -> None:
        """Hand a tick's tokens to their requests; finish and evict."""
        t = time.monotonic()
        for slot, emitted in out.items():
            req = self._active.get(slot)
            if req is None:
                continue
            finished = False
            for tok in emitted:
                tok = int(tok)
                req.tokens.append(tok)
                counts["emitted"] += 1
                if req.token_times:
                    self._tpot_hist.observe(
                        (t - req.token_times[-1]) * 1e3)
                req.token_times.append(t)
                self._next_token[slot] = tok
                if self._should_finish(req, tok):
                    self._evict(slot, DONE)
                    finished = True
                    break
            if not finished and self._out_of_room(slot):
                self._evict(slot, DONE, "max_seq reached",
                            reason="max_seq")
                finished = True
            counts["finished"] += finished

    def _should_finish(self, req: Request, last_token: int,
                       n_tokens: Optional[int] = None) -> bool:
        """Does ``last_token`` end the request (``n_tokens``: how many it
        has with that one, where it is not yet in ``req.tokens``)?"""
        eos = self.engine.ecfg.eos_id
        if eos is not None and last_token == eos:
            return True
        if n_tokens is None:
            n_tokens = len(req.tokens)
        return n_tokens >= req.max_new_tokens

    def _out_of_room(self, slot: int) -> bool:
        """The slot is at (or too near) max_seq for one more step."""
        return self.engine.cache.headroom(slot) < getattr(
            self.engine, "min_headroom", 1)

    _EVICT_REASONS = {DONE: "done", EXPIRED: "deadline", FAILED: "failed"}

    def _evict(self, slot: int, state: str,
               detail: Optional[str] = None,
               reason: Optional[str] = None) -> None:
        req = self._active.pop(slot)
        self._next_token.pop(slot, None)
        if slot in self._admit_order:
            self._admit_order.remove(slot)
        reason = reason or self._EVICT_REASONS.get(state, state)
        req.last_step = self.steps
        with _spans.span("serve/evict", trace=req.trace_id,
                         parent=req.root_span,
                         attrs={"reason": reason, "slot": slot}):
            self.engine.free_sequence(slot)
        smetrics.m_evictions.labels(reason).inc()
        self._finish(req, state, detail)

    def _finish(self, req: Request, state: str,
                detail: Optional[str] = None) -> None:
        req.state = state
        if detail and state in (EXPIRED, FAILED):
            req.error = detail
        if state == DONE:
            self.completed += 1
        with self._rate_lock:
            self._done_times.append(time.monotonic())
        # close the request's root span: submit -> terminal state.  The
        # explicit span_id is what the lifecycle children parented to;
        # parent_span (when the request arrived with wire trace context)
        # links this process's subtree under the sender's span.
        end = _spans.clock_ns()
        _spans.record("serve/request", req.submit_ns,
                      end - req.submit_ns, trace=req.trace_id,
                      parent=req.parent_span, span_id=req.root_span,
                      attrs={"state": state, "tokens": len(req.tokens),
                             "request_id": req.id,
                             "first_step": req.first_step,
                             "last_step": req.last_step})
        req.finished.set()