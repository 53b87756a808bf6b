"""Serving metric families (docs/serving.md, gated by tools/metrics_check.py).

All families live in the same default registry as the training telemetry,
so one Prometheus exposition carries both sides of the system. Children
are resolved once at import/call-site-build time per the registry's
hot-path cost model (observability/metrics.py).
"""
from __future__ import annotations

from ..observability import metrics as _obs

__all__ = [
    "m_requests", "m_queue_depth", "m_active", "m_occupancy",
    "m_ttft_ms", "m_tpot_ms", "m_tokens", "m_tokens_per_s",
    "m_prefill_ms", "m_decode_ms", "m_evictions", "m_queue_wait_ms",
    "m_prefix_cache", "m_prefill_tokens", "m_page_occupancy",
    "m_page_fragmentation", "m_kv_pages", "m_window_released",
    "m_spec_accepted", "m_spec_proposed",
    "m_spec_windows", "m_preemptions", "m_hol_admits",
    "m_shed", "m_replica_restarts", "m_failover", "m_prefix_store",
    "m_kv_transfer_bytes", "m_kv_transfer_ms", "m_pool_prefix",
    "m_disagg_fallback", "m_sampler_path", "m_early_dispatch",
    "m_moe_routed",
    "m_moe_load_max", "m_moe_dropped", "request_code",
]

_REG = _obs.default_registry()

# request outcomes by HTTP-style code ("200", "400", "429", "500", "503",
# "504") — the front door stamps every response; engine-level drivers
# (tools/serve_bench.py) stamp the logical equivalent
m_requests = _REG.counter(
    "paddle_serve_requests_total",
    "Serving requests by response code", ("code",))
m_queue_depth = _REG.gauge(
    "paddle_serve_queue_depth",
    "Requests waiting for a decode slot (admission queue)")
m_active = _REG.gauge(
    "paddle_serve_active_requests",
    "Requests currently holding a decode slot")
m_occupancy = _REG.gauge(
    "paddle_serve_batch_occupancy",
    "Live decode slots / max_batch at the last scheduler tick")
# TTFT spans prefill + queueing; TPOT is the per-token decode cadence —
# sub-ms buckets matter there. Both are split by the serving phase that
# produced the sample and the role of the replica that ran it (ISSUE 17:
# disaggregated serving needs per-phase latency, not a blended number).
m_ttft_ms = _REG.histogram(
    "paddle_serve_ttft_ms",
    "Time to first token (submit -> first generated token), ms",
    ("phase", "role"))
m_tpot_ms = _REG.histogram(
    "paddle_serve_tpot_ms",
    "Per-output-token latency after the first token, ms",
    ("phase", "role"))
m_tokens = _REG.counter(
    "paddle_serve_tokens_total", "Generated tokens")
m_tokens_per_s = _REG.gauge(
    "paddle_serve_tokens_per_s",
    "Generated tokens per second over the last scheduler window")
m_prefill_ms = _REG.histogram(
    "paddle_serve_prefill_ms",
    "Prefill executable wall time (bucket-padded prompt), ms")
m_decode_ms = _REG.histogram(
    "paddle_serve_decode_step_ms",
    "Decode tick round trip (the call's dispatch to its sampled tokens "
    "on the host, one token across the batch), ms")
m_evictions = _REG.counter(
    "paddle_serve_slot_evictions_total",
    "Decode-slot evictions by reason", ("reason",))
# queue wait is the request's pre-TTFT tax: submit -> decode-slot
# admission (the span tracer stamps the same window as serve/queue_wait)
m_queue_wait_ms = _REG.histogram(
    "paddle_serve_queue_wait_ms",
    "Admission-queue wait (submit -> prefill start), ms")


# prefix cache (serving/paged_kv.py): a hit means the shared prompt
# prefix attached by refcount instead of prefilling again
m_prefix_cache = _REG.counter(
    "paddle_serve_prefix_cache_total",
    "Prefix-cache lookups by outcome", ("event",))
# VALID tokens prefilled (bucket padding excluded) — with prefix caching
# a repeated system prompt's second request only adds its suffix here,
# which is how metrics_check proves "a shared prefix prefills once"
m_prefill_tokens = _REG.counter(
    "paddle_serve_prefill_tokens_total",
    "Prompt tokens actually prefilled (prefix-cache hits excluded)")
m_page_occupancy = _REG.gauge(
    "paddle_serve_page_pool_occupancy",
    "Allocated KV pages / allocatable pages (scratch page excluded)")
m_page_fragmentation = _REG.gauge(
    "paddle_serve_page_pool_fragmentation",
    "Internal page waste: 1 - used rows / allocated rows")
# page groups (serving/paged_kv.py, docs/serving.md "Window and global
# layers"): pages held in each group of a manager that has several, and
# the pages a window group gave back while their slot was still decoding
m_kv_pages = _REG.gauge(
    "paddle_serve_kv_pages",
    "KV pages held, by page group (full | window)", ("group",))
m_window_released = _REG.counter(
    "paddle_serve_window_pages_released_total",
    "Pages a window group gave back because they left a live slot's window")
# which of the sampler's three paths an engine call took inside its
# executable (serving/sampling.py: the host computes it from the same
# predicate before the call), so an operator sees what share of ticks
# pays the vocabulary sort
m_sampler_path = _REG.counter(
    "paddle_serve_sampler_path_total",
    "Engine calls by the sampler path their batch took "
    "(greedy|temperature|filtered) and program (decode|prefill|verify)",
    ("path", "program"))
# early dispatch (serving/scheduler.py:_plan_next, docs/serving.md "The
# tick's anatomy"): once a tick's tokens are on the host, was the next
# tick dispatched at once (``ahead``), or held for the next step to decide
# (``held_admission`` | ``held_capacity`` | ``held_idle`` |
# ``held_engine``); ``dropped_lanes`` counts lanes of ticks in flight
# whose rider was gone at collection
m_early_dispatch = _REG.counter(
    "paddle_serve_early_dispatch_total",
    "Decode ticks by whether their successor was dispatched before their "
    "logits were fetched (ahead) or held and why; and lanes dropped",
    ("outcome",))
# recurrent state beside the pages (hybrid models, serving/paged_kv.py):
# what the live slots hold, and how often a slot's state was born anew
m_state_bytes = _REG.gauge(
    "paddle_serve_state_bytes",
    "Recurrent state (conv + scan) held by live decode slots, bytes")
m_state_resets = _REG.counter(
    "paddle_serve_state_resets_total",
    "Slot allocations that start a recurrent state from nothing")
# sparse experts on one chip of an expert-parallel group (ops/moe.py,
# models/kimi_k2.py): where the (token, choice) pairs of the expert layers
# went, how unevenly the held experts were loaded in the last call, and
# the held pairs that reached no expert: 0 by construction (no capacity),
# counted so that it is seen and not assumed
m_moe_routed = _REG.counter(
    "moe_routed_tokens_total",
    "Routed (token, choice) pairs of the expert layers, by whether the "
    "chosen expert is held here or on another chip", ("where",))
m_moe_load_max = _REG.gauge(
    "moe_expert_load_max",
    "Most tokens on one held expert of one layer in the last engine call")
m_moe_dropped = _REG.counter(
    "moe_dropped_tokens_total",
    "Pairs routed to a held expert that no expert computed (always 0)")
# speculative decoding (serving/spec_decode.py): the acceptance histogram
# IS the speedup meter — mean accepted/window vs the draft+verify cost
m_spec_accepted = _REG.histogram(
    "paddle_serve_spec_accepted_tokens",
    "Draft tokens accepted per verify window")
m_spec_proposed = _REG.counter(
    "paddle_serve_spec_proposed_tokens_total",
    "Draft tokens proposed to the verifier")
m_spec_windows = _REG.counter(
    "paddle_serve_spec_windows_total", "Speculative verify windows run")
# scheduler preemptions (page pool dry mid-generation -> recompute
# requeue) and head-of-line bypass admissions
m_preemptions = _REG.counter(
    "paddle_serve_preemptions_total",
    "Active requests preempted (recompute-requeued) by reason",
    ("reason",))
m_hol_admits = _REG.counter(
    "paddle_serve_hol_bypass_admits_total",
    "Requests admitted past a head-of-line prompt that did not fit")


# resilience families (ISSUE 15, docs/serving.md "Resilience") -----------
# adaptive overload control: requests rejected up front instead of being
# queued into a guaranteed 504 — "deadline" = drain ETA beyond the
# request deadline, "queue_full" = admission queue at capacity
m_shed = _REG.counter(
    "paddle_serve_shed_total",
    "Requests shed by the overload control, by reason", ("reason",))
# gang supervisor (serving/gang.py): replica recycles by cause — crash
# (nonzero exit / signal death), hang (exit 43 or stale health probe),
# poisoned (exit 44 or /health status poisoned)
m_replica_restarts = _REG.counter(
    "paddle_serve_replica_restarts_total",
    "Serving replica recycles by cause (crash, hang, poisoned)",
    ("cause",))
# in-flight requests re-dispatched to a sibling replica after their
# replica died mid-request (partials discarded, the retry re-prefills)
m_failover = _REG.counter(
    "paddle_serve_failover_requests_total",
    "Requests re-dispatched to a sibling replica after a replica fault")
# warm restart (serving/prefix_store.py): published prefix-cache records
# persisted / restored through the elastic checkpoint store
m_prefix_store = _REG.counter(
    "paddle_serve_prefix_store_total",
    "Prefix-store operations (save, restore, restore_skipped)", ("op",))


# disaggregation families (ISSUE 17, docs/serving.md "Disaggregation") ---
# KV handoff volume/latency between prefill and decode replicas. These
# move ONLY on disagg runs — tools/metrics_check.py asserts they stay
# flat through a plain colocated serve.
m_kv_transfer_bytes = _REG.counter(
    "paddle_kv_transfer_bytes_total",
    "KV page bytes shipped between replicas, by direction",
    ("direction",))
m_kv_transfer_ms = _REG.histogram(
    "paddle_kv_transfer_ms",
    "Wall time of one request's KV handoff (export+ship+adopt), ms")
# gang-shared prefix index: a hit means a prompt prefix prefilled on ANY
# replica was reused here without recompute
m_pool_prefix = _REG.counter(
    "paddle_serve_pool_prefix_cache_total",
    "Pool-level (gang-shared) prefix index events, by phase",
    ("event", "phase"))
# disagg router degradations: a failed handoff or an empty phase fleet
# falls back to colocated dispatch — degrade, never drop
m_disagg_fallback = _REG.counter(
    "paddle_serve_disagg_fallback_total",
    "Disagg requests degraded to colocated dispatch, by reason",
    ("reason",))


def request_code(code: int) -> None:
    """Count one request outcome."""
    m_requests.labels(str(int(code))).inc()
