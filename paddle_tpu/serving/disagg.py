"""Phase-disaggregated serving: in-process replica set + router
(ISSUE 17, docs/serving.md "Disaggregation").

Prefill is compute-bound and bursty; decode is HBM-bound and steady.
Colocated, they fight over the same chip — a long prompt's prefill
stalls every rider's decode tick, which is exactly the p99-TTFT/TPOT
interference the disagg split removes. This module is the in-process
form of the split (one Python process, one engine per role), used by
``tools/serve_bench.py --disagg``, the parity tests, and as the
reference implementation of the router policy the subprocess gang
(serving/gang.py) mirrors over HTTP:

- :class:`LocalReplica` — engine + scheduler + serving loop with the
  engine's role stamped on it;
- :class:`SharedPrefixIndex` — the pool-level prefix cache: a
  gang-shared, token-hash-keyed index of serialized prefix pages, so a
  system prompt prefilled on ANY replica is adoptable by all (metered
  per phase by ``paddle_serve_pool_prefix_cache_total{event,phase}``);
- :class:`DisaggRouter` — queue-depth + drain-rate placement per role,
  first-token migration over serving/kv_transfer.py, and the
  degrade-never-drop rule: an empty phase fleet or a failed handoff
  falls back to colocated dispatch
  (``paddle_serve_disagg_fallback_total{reason}``).
"""
from __future__ import annotations

import threading
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

from ..observability import spans as _spans
from . import metrics as smetrics
from .kv_transfer import export_prefix
from .sampling import SamplingParams
from .scheduler import Scheduler, SchedulerConfig

__all__ = ["LocalReplica", "SharedPrefixIndex", "DisaggRouter",
           "DisaggResult"]


class SharedPrefixIndex:
    """Gang-shared prefix index: token-hash -> serialized prefix pages
    (kv_transfer blob). Plugs into an engine's ``prefix_store`` slot
    (duck-typed — the engine only calls ``maybe_publish``), so every
    prefill publish lands here as well as in the replica-local cache;
    consumers :meth:`fetch` the longest blob for a prompt and hand it
    to ``Scheduler.submit(prefix_blob=...)`` for pool adoption."""

    def __init__(self, max_records: int = 256):
        self.max_records = int(max_records)
        self._lock = threading.Lock()
        # insertion-ordered key -> blob (LRU-ish: re-publish refreshes)
        self._blobs: "Dict[Tuple[int, ...], Dict[str, Any]]" = {}
        self.hits = 0
        self.misses = 0
        self.published = 0

    def binding(self, role: str) -> "_IndexBinding":
        """A phase-stamping adapter suitable as ``engine.prefix_store``."""
        return _IndexBinding(self, role)

    def publish(self, tokens: Sequence[int], table_row, pool,
                phase: str = "colocated") -> bool:
        blob = export_prefix(pool, tokens, table_row)
        if blob is None:
            return False
        key = tuple(blob["tokens"])
        with self._lock:
            if key in self._blobs:
                return False
            self._blobs[key] = blob
            while len(self._blobs) > self.max_records:
                self._blobs.pop(next(iter(self._blobs)))
            self.published += 1
        smetrics.m_pool_prefix.labels("publish", phase).inc()
        return True

    def fetch(self, tokens: Sequence[int],
              phase: str = "colocated") -> Optional[Dict[str, Any]]:
        """Longest indexed page-aligned prefix of ``tokens`` that
        leaves at least one suffix token to prefill. Counts hit/miss
        per phase."""
        tokens = [int(t) for t in tokens]
        with self._lock:
            if not self._blobs:
                best = None
            else:
                best = None
                for key, blob in self._blobs.items():
                    n = len(key)
                    if (n < len(tokens) and tuple(tokens[:n]) == key
                            and (best is None
                                 or n > len(best["tokens"]))):
                        best = blob
        if best is None:
            self.misses += 1
            smetrics.m_pool_prefix.labels("miss", phase).inc()
            return None
        self.hits += 1
        smetrics.m_pool_prefix.labels("hit", phase).inc()
        return best

    def __len__(self) -> int:
        with self._lock:
            return len(self._blobs)


class _IndexBinding:
    """One replica's view of the shared index — stamps its role on the
    publish metric and satisfies the engine's prefix_store duck type."""

    def __init__(self, index: SharedPrefixIndex, role: str):
        self.index = index
        self.role = role

    def maybe_publish(self, tokens, table_row, pool) -> bool:
        return self.index.publish(tokens, table_row, pool,
                                  phase=self.role)


class LocalReplica:
    """One in-process serving replica: engine + continuous-batching
    scheduler + loop thread, with the engine's role on the tin."""

    def __init__(self, engine, scfg: Optional[SchedulerConfig] = None,
                 prefix_index: Optional[SharedPrefixIndex] = None,
                 name: Optional[str] = None):
        from .server import EngineLoop

        self.engine = engine
        self.role = getattr(engine, "role", "colocated")
        self.name = name or f"{self.role}-{id(engine) & 0xffff:x}"
        self.scheduler = Scheduler(engine, scfg)
        self.prefix_index = prefix_index
        if (prefix_index is not None and engine.prefix is not None
                and engine.prefix_store is None):
            engine.prefix_store = prefix_index.binding(self.role)
        self.loop = EngineLoop(self.scheduler).start()

    def wake(self) -> None:
        self.loop.wake()

    def stop(self) -> None:
        self.loop.stop()

    # -- placement signals (queue-depth + drain-rate policy) -----------
    def load_eta_s(self) -> float:
        """Placement score: seconds of work already committed here —
        queued + active over the measured drain rate (depth itself when
        no rate is measurable yet, so cold replicas still spread)."""
        sched = self.scheduler
        with sched._lock:
            depth = len(sched._queue) + len(sched._pending_handoffs)
        depth += len(sched._active)
        rate = sched.drain_rate()
        if rate is None or rate <= 0:
            return float(depth)
        return depth / rate


class DisaggResult:
    """What the router hands back — enough for parity checks (tokens)
    and latency accounting (prefill-side TTFT, decode-side cadence)."""

    __slots__ = ("tokens", "ttft_ms", "token_times", "state", "error",
                 "migrated", "fallback_reason", "handoff_ms", "trace_id")

    def __init__(self, tokens, ttft_ms, token_times, state,
                 error=None, migrated=False, fallback_reason=None,
                 handoff_ms=None, trace_id=None):
        self.tokens = tokens
        self.ttft_ms = ttft_ms
        self.token_times = token_times
        self.state = state
        self.error = error
        self.migrated = migrated
        self.fallback_reason = fallback_reason
        self.handoff_ms = handoff_ms
        self.trace_id = trace_id

    @property
    def tpot_ms(self) -> Optional[float]:
        if len(self.token_times) < 2:
            return None
        import numpy as np

        return float(np.mean(np.diff(self.token_times)) * 1e3)


class DisaggRouter:
    """Routes a request prefill-replica -> decode-replica at the
    first-token boundary; any failure degrades to colocated dispatch on
    whatever fleet can still serve (never drops)."""

    def __init__(self, replicas: Sequence[LocalReplica],
                 prefix_index: Optional[SharedPrefixIndex] = None):
        self.replicas = list(replicas)
        self.prefill_fleet = [r for r in self.replicas
                              if r.role == "prefill"]
        self.decode_fleet = [r for r in self.replicas
                             if r.role == "decode"]
        self.colocated_fleet = [r for r in self.replicas
                                if r.role == "colocated"]
        self.prefix_index = prefix_index
        self.migrated = 0
        self.fallbacks = 0

    @staticmethod
    def _pick(fleet: Sequence[LocalReplica]) -> LocalReplica:
        return min(fleet, key=lambda r: r.load_eta_s())

    def _fallback_fleet(self) -> List[LocalReplica]:
        # colocated replicas first; else any full engine can serve both
        # phases (roles are routing policy, not capability)
        return self.colocated_fleet or (self.decode_fleet
                                        + self.prefill_fleet)

    def generate(self, prompt: Sequence[int], max_new_tokens: int = 16,
                 timeout_s: float = 30.0,
                 sampling: Optional[SamplingParams] = None,
                 trace_ctx: Optional[_spans.Context] = None
                 ) -> DisaggResult:
        """Serve one request end to end (blocking — callers thread)."""
        deadline = time.monotonic() + timeout_s
        # ISSUE 18: one trace per routed request — prefill, KV handoff,
        # decode, AND the colocated fallback all inherit the context
        # minted here (a degraded request is the same trace, not a new
        # one)
        trace_id = trace_ctx[0] if trace_ctx is not None \
            else _spans.gen_id()
        route_span = _spans.gen_id()
        ctx = (trace_id, route_span)
        t0 = time.perf_counter_ns()
        try:
            res = self._generate(prompt, max_new_tokens, deadline,
                                 sampling, ctx)
        finally:
            attrs = {"router": "disagg"}
            if trace_ctx is not None:
                attrs["remote_parent"] = True
            _spans.record(
                "serve/route", t0, time.perf_counter_ns() - t0,
                trace=trace_id, span_id=route_span,
                parent=trace_ctx[1] if trace_ctx is not None else None,
                attrs=attrs)
        res.trace_id = trace_id
        return res

    def _generate(self, prompt, max_new_tokens, deadline, sampling,
                  ctx: _spans.Context) -> DisaggResult:
        if not self.prefill_fleet or not self.decode_fleet:
            return self._colocated(prompt, max_new_tokens, deadline,
                                   sampling, "no_phase_fleet", ctx)
        # -- phase 1: prefill to the first token -----------------------
        pr = self._pick(self.prefill_fleet)
        blob = (self.prefix_index.fetch(prompt, "prefill")
                if self.prefix_index is not None else None)
        try:
            preq = pr.scheduler.submit(
                prompt, max_new_tokens=max_new_tokens,
                timeout_s=max(0.1, deadline - time.monotonic()),
                sampling=sampling, prefill_only=True, prefix_blob=blob,
                trace_ctx=ctx)
        except Exception:
            return self._colocated(prompt, max_new_tokens, deadline,
                                   sampling, "prefill_refused", ctx)
        pr.wake()
        preq.wait(timeout=max(0.1, deadline - time.monotonic()) + 1.0)
        if preq.state != "done" or preq.handoff is None:
            return self._colocated(prompt, max_new_tokens, deadline,
                                   sampling, "prefill_failed", ctx)
        first = preq.tokens[0]
        if max_new_tokens <= 1:
            self.migrated += 1       # nothing left to decode
            return DisaggResult([first], preq.ttft_ms,
                                list(preq.token_times), "done",
                                migrated=True, handoff_ms=0.0)
        # -- phase 2: migrate KV, decode the rest ----------------------
        t_h0 = time.monotonic()
        dr = self._pick(self.decode_fleet)
        try:
            dreq = dr.scheduler.submit_handoff(
                preq.handoff, first, max_new_tokens=max_new_tokens,
                timeout_s=max(0.1, deadline - time.monotonic()),
                sampling=sampling, prompt=prompt)
        except Exception:
            return self._colocated(prompt, max_new_tokens, deadline,
                                   sampling, "handoff_refused", ctx)
        dr.wake()
        dreq.wait(timeout=max(0.1, deadline - time.monotonic()) + 1.0)
        if dreq.state != "done":
            return self._colocated(prompt, max_new_tokens, deadline,
                                   sampling, "decode_failed", ctx)
        handoff_ms = ((dreq.token_times[1] - t_h0) * 1e3
                      if len(dreq.token_times) > 1 else 0.0)
        self.migrated += 1
        return DisaggResult(list(dreq.tokens), preq.ttft_ms,
                            list(dreq.token_times), "done",
                            migrated=True, handoff_ms=handoff_ms)

    def _colocated(self, prompt, max_new_tokens, deadline, sampling,
                   reason: str,
                   ctx: Optional[_spans.Context] = None) -> DisaggResult:
        """Degrade, never drop: full re-dispatch on the fallback fleet.
        The retry inherits the original request's trace context — it
        shows up as a child span of the SAME trace (ISSUE 18)."""
        smetrics.m_disagg_fallback.labels(reason).inc()
        self.fallbacks += 1
        fleet = self._fallback_fleet()
        if not fleet:
            return DisaggResult([], None, [], "failed",
                                error="no replica can serve",
                                fallback_reason=reason)
        rep = self._pick(fleet)
        try:
            req = rep.scheduler.submit(
                prompt, max_new_tokens=max_new_tokens,
                timeout_s=max(0.1, deadline - time.monotonic()),
                sampling=sampling, trace_ctx=ctx)
        except Exception as e:
            return DisaggResult([], None, [], "failed",
                                error=f"{type(e).__name__}: {e}",
                                fallback_reason=reason)
        rep.wake()
        req.wait(timeout=max(0.1, deadline - time.monotonic()) + 1.0)
        return DisaggResult(list(req.tokens), req.ttft_ms,
                            list(req.token_times), req.state,
                            error=req.error, fallback_reason=reason)
