#!/usr/bin/env python
"""Serving-engine load bench: Poisson open-loop arrivals against the
continuous-batching scheduler -> SERVE_BENCH.json (docs/serving.md).

Open-loop on purpose: arrivals follow a Poisson process at each target
rate regardless of completions (the closed-loop trap understates tail
latency under overload). Per lane — a (weight_dtype, sharding, sampling,
spec-decode) config x arrival rate — the bench reports:

  * TTFT p50/p99 ms (submit -> first token, queueing included)
  * per-output-token latency (TPOT) p50/p99 ms
  * tokens/s and tokens/s/chip
  * mean decode-batch occupancy
  * spec-decode acceptance rate + tokens/window (spec lanes)
  * steady_state_recompiles — the PR 4 ``paddle_recompiles_total`` delta
    across the whole warmed load phase, REQUIRED to be exactly 0

plus the int8-vs-f32 quality bar (serving/quant.py) and the CLOSED-LOOP
capacity lanes (ISSUE 13): per config, ramp the arrival rate until the
measured p99 TTFT breaks the SLO — ``max_sustainable_rps`` makes "how
many chips for N users" a measured number (chips x max_rps / per-user
rate).

``--smoke`` is the CPU correctness lane, labeled ``cpu_smoke`` —
dispatch-bound, it validates the mechanism and the zero-recompile
contract, not throughput. Without ``--smoke`` the bench measures, and
refuses a backend that is not a TPU.

  JAX_PLATFORMS=cpu python tools/serve_bench.py --smoke --out SERVE_BENCH.json
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# the tp lanes need a multi-device view on CPU (same trick as
# tests/conftest.py); must land before jax import
if "host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8")

import numpy as np  # noqa: E402

# tokens per KV page at this bench's tiny geometry (buckets of 16 and 32)
PAGE_SIZE = 8


def _pct(vals, q):
    if not vals:
        return None
    return float(np.percentile(np.asarray(vals, np.float64), q))


def _recompile_total():
    from paddle_tpu.observability import metrics as om

    snap = om.default_registry().snapshot()
    return sum(s["value"] for s in
               snap.get("paddle_recompiles_total", {}).get("series", []))


def decode_logits_stream(engine, seq):
    """Teacher-forced decode over ``seq`` through the serving path:
    prefill the first token, then feed the ground-truth stream one token
    at a time. Returns [len(seq), V] next-token logits."""
    slot, l0 = engine.start_sequence(seq[:1])
    logits = [l0]
    for tok in seq[1:]:
        out = engine.decode_step({slot: int(tok)})
        logits.append(out[slot])
    engine.free_sequence(slot)
    return np.stack(logits)


def parity_lane(params, cfg, ecfg_kw, seed: int, eval_len: int):
    """int8 (and bf16) decode quality vs the f32 engine."""
    from paddle_tpu import serving
    from paddle_tpu.serving import quant as squant

    rng = np.random.RandomState(seed)
    seq = rng.randint(0, cfg.vocab_size, size=eval_len).astype(np.int64)
    engines = {}
    for wd in ("f32", "int8", "bf16"):
        engines[wd] = serving.DecodeEngine(
            params, cfg, serving.EngineConfig(weight_dtype=wd, **ecfg_kw))
        engines[wd].warmup()
    streams = {wd: decode_logits_stream(e, seq)
               for wd, e in engines.items()}
    labels = seq[1:]
    out = {"eval_tokens": int(eval_len),
           "logit_tol": squant.INT8_LOGIT_TOL,
           "ppl_rel_tol": squant.INT8_PPL_REL_TOL}
    ppl_f32 = squant.perplexity(streams["f32"][:-1], labels)
    out["ppl_f32"] = round(ppl_f32, 6)
    for wd in ("int8", "bf16"):
        stats = squant.logit_error_stats(streams["f32"], streams[wd])
        ppl = squant.perplexity(streams[wd][:-1], labels)
        rel = abs(ppl / ppl_f32 - 1.0)
        stats.update(ppl=round(ppl, 6), ppl_rel_drift=round(rel, 6))
        if wd == "int8":
            stats["pass"] = bool(
                stats["max_rel_err"] < squant.INT8_LOGIT_TOL
                and rel < squant.INT8_PPL_REL_TOL)
        out[wd] = {k: (round(v, 6) if isinstance(v, float) else v)
                   for k, v in stats.items()}
        engines[wd].drop_reference_params()
    # weight residency (the other half of the int8 story)
    out["weight_bytes"] = {wd: int(e.weight_nbytes)
                           for wd, e in engines.items()}
    return out


def engine_parity_lane(params, cfg, ecfg_kw, seed: int, n_tokens: int):
    """The engine's acceptance bar: at f32 its greedy tokens are those of
    greedy decoding through ``reference_logits`` (the cache-free full
    forward), and the tp=2 decode logits match single-chip."""
    import jax

    from paddle_tpu import serving

    rng = np.random.RandomState(seed)
    prompt = rng.randint(0, cfg.vocab_size, size=6).tolist()

    def greedy(engine):
        slot, logits = engine.start_sequence(prompt)
        toks = [int(np.argmax(logits))]
        first_logits = np.asarray(logits)
        for _ in range(n_tokens - 1):
            out = engine.decode_step({slot: toks[-1]})
            toks.append(int(np.argmax(out[slot])))
        engine.free_sequence(slot)
        return toks, first_logits

    one = serving.DecodeEngine(params, cfg,
                               serving.EngineConfig(**ecfg_kw))
    one.warmup()
    toks, logits = greedy(one)
    ref, stream = [], list(prompt)
    for _ in range(n_tokens):
        ref.append(int(np.argmax(one.reference_logits(stream)[-1])))
        stream.append(ref[-1])
    out = {"tokens": int(n_tokens),
           "tokens_match_reference": toks == ref}
    if jax.device_count() >= 2:
        tp = serving.DecodeEngine(params, cfg, serving.EngineConfig(
            sharding="tp", tp=2, **ecfg_kw))
        tp.warmup()
        tp_toks, tp_logits = greedy(tp)
        out["tp2_tokens_match"] = tp_toks == toks
        out["tp2_max_logit_diff"] = float(
            np.max(np.abs(tp_logits - logits)))
    return out


def build_engine(params, cfg, ecfg_kw, lane):
    """One engine per lane config dict: {weight_dtype, num_pages,
    sharding, spec(k or 0)} (+ the shared geometry)."""
    from paddle_tpu import serving
    from paddle_tpu.models import gpt

    kw = dict(ecfg_kw)
    kw["weight_dtype"] = lane.get("weight_dtype", "f32")
    if lane.get("num_pages"):
        kw["num_pages"] = int(lane["num_pages"])
    if lane.get("fused_decode"):
        kw["fused_decode"] = True
    if lane.get("sharding") == "tp":
        kw.update(sharding="tp", tp=lane.get("tp", 2))
    k = int(lane.get("spec", 0))
    if k > 0:
        target = serving.DecodeEngine(params, cfg, serving.EngineConfig(
            verify_window=k + 1, **kw))
        dcfg = cfg.scaled(num_layers=max(1, cfg.num_layers // 4))
        import jax

        dparams = gpt.init_params(jax.random.PRNGKey(99), dcfg)
        draft = serving.DecodeEngine(dparams, dcfg,
                                     serving.EngineConfig(**kw))
        return serving.SpecDecodeEngine(target, draft)
    return serving.DecodeEngine(params, cfg, serving.EngineConfig(**kw))


def _slo_stamp(done, rejected: int, failed: int):
    """Replay the lane's per-request outcomes through the live SLO
    engine (observability.slo) — the same declarative objectives the
    serving gang burn-rate alerts on — and return its verdict, so a
    bench lane and a production ``slo_status()`` read off one ruler."""
    from paddle_tpu.observability import slo as _slo

    eng = _slo.SLOEngine(min_events=1)
    t = 1000.0
    for r in done:
        tpot = None
        if len(r.token_times) > 1:
            tpot = float(np.median(np.diff(r.token_times)) * 1e3)
        eng.note_request(ttft_ms=r.ttft_ms, tpot_ms=tpot, code=200, t=t)
        t += 0.001
    for _ in range(rejected):
        eng.note_request(code=429, shed=True, t=t)
        t += 0.001
    for _ in range(failed):
        eng.note_request(code=500, t=t)
        t += 0.001
    st = eng.evaluate(t)
    return {
        "ok": st["ok"],
        "objectives": {
            name: {"measured": o["measured"], "target": o["target"],
                   "meets_target": o["meets_target"],
                   "burn_rate_fast": o["burn_rate"]["fast"]}
            for name, o in st["objectives"].items()
        },
    }


def load_lane(params, cfg, ecfg_kw, lane, rate_rps: float,
              n_requests: int, max_new_tokens: int, prompt_len_max: int,
              seed: int, queue_cap: int):
    """One Poisson open-loop lane at ``rate_rps`` requests/second."""
    import jax

    from paddle_tpu import serving

    engine = build_engine(params, cfg, ecfg_kw, lane)
    warm_ms = engine.warmup()
    sched = serving.Scheduler(engine, serving.SchedulerConfig(
        max_queue=queue_cap, default_timeout_s=120.0))
    loop = serving.EngineLoop(sched).start()

    sampling = None
    if lane.get("sampling"):
        s = lane["sampling"]
        sampling = serving.SamplingParams(
            temperature=s.get("temperature", 0.8),
            top_k=s.get("top_k", 0), top_p=s.get("top_p", 1.0))
    rng = np.random.RandomState(seed)
    gaps = rng.exponential(1.0 / rate_rps, size=n_requests)
    prompts = [rng.randint(0, cfg.vocab_size,
                           size=int(rng.randint(2, prompt_len_max + 1)))
               .tolist() for _ in range(n_requests)]
    requests, rejected = [], 0
    rc0 = _recompile_total()
    t_start = time.monotonic()
    for i, (gap, prompt) in enumerate(zip(gaps, prompts)):
        time.sleep(gap)
        try:
            sp = sampling
            if sp is not None:
                sp = serving.SamplingParams(
                    temperature=sp.temperature, top_k=sp.top_k,
                    top_p=sp.top_p, seed=i)
            requests.append(sched.submit(prompt,
                                         max_new_tokens=max_new_tokens,
                                         sampling=sp))
            loop.wake()
        except serving.QueueFullError:
            rejected += 1
    for req in requests:
        req.wait(timeout=180.0)
    t_span = time.monotonic() - t_start
    loop.stop()
    recompiles = _recompile_total() - rc0

    done = [r for r in requests if r.state == "done"]
    ttfts = [r.ttft_ms for r in done if r.ttft_ms is not None]
    tpots = []
    for r in done:
        tpots.extend((np.diff(r.token_times) * 1e3).tolist())
    total_tokens = sum(len(r.tokens) for r in done)
    n_chips = (lane.get("tp", 2) if lane.get("sharding") == "tp"
               else 1) if jax.default_backend() == "cpu" \
        else jax.device_count()
    result = {
        **{k: v for k, v in lane.items() if k != "sampling"},
        "sampled": bool(lane.get("sampling")),
        "rate_rps": rate_rps,
        "requests": n_requests,
        "completed": len(done),
        "rejected_429": rejected,
        "failed": len(requests) - len(done),
        "ttft_ms": {"p50": round(_pct(ttfts, 50), 3),
                    "p99": round(_pct(ttfts, 99), 3)},
        "tpot_ms": {"p50": round(_pct(tpots, 50), 3) if tpots else None,
                    "p99": round(_pct(tpots, 99), 3) if tpots else None},
        "tokens_per_s": round(total_tokens / t_span, 2),
        "tokens_per_s_per_chip": round(
            total_tokens / t_span / n_chips, 2),
        "mean_batch_occupancy": round(sched.mean_occupancy, 4),
        "scheduler_steps": sched.steps,
        "preemptions": sched.preemptions,
        "steady_state_recompiles": int(recompiles),
        "warmup_ms": {k: round(v, 1) for k, v in warm_ms.items()},
        "slo": _slo_stamp(done, rejected, len(requests) - len(done)),
    }
    if lane.get("spec", 0) > 0:
        st = engine.stats
        result["spec"] = {
            "k": int(lane["spec"]),
            "windows": st.windows,
            "acceptance_rate": round(st.acceptance_rate, 4),
            "tokens_per_window": round(st.tokens_per_window, 3),
        }
    return result


def capacity_lane(params, cfg, ecfg_kw, lane, slo_ttft_p99_ms: float,
                  rate_ladder, n_requests: int, max_new_tokens: int,
                  prompt_len_max: int, seed: int, queue_cap: int):
    """CLOSED-LOOP capacity search: ramp the arrival rate up the ladder,
    measure p99 TTFT at each rung, stop at the first SLO violation.
    ``max_sustainable_rps`` is the last passing rung — the "how many
    chips for N users" number per (chip count, dtype, spec on/off)."""
    probes = []
    max_ok = None
    for rate in rate_ladder:
        probe = load_lane(params, cfg, ecfg_kw, lane, rate, n_requests,
                          max_new_tokens, prompt_len_max, seed,
                          queue_cap)
        ok = (probe["ttft_ms"]["p99"] is not None
              and probe["ttft_ms"]["p99"] <= slo_ttft_p99_ms
              and probe["failed"] == 0 and probe["rejected_429"] == 0)
        probes.append({"rate_rps": rate,
                       "ttft_p99_ms": probe["ttft_ms"]["p99"],
                       "tokens_per_s": probe["tokens_per_s"],
                       "recompiles": probe["steady_state_recompiles"],
                       "slo_ok": ok})
        if not ok:
            break
        max_ok = rate
    return {
        **{k: v for k, v in lane.items() if k != "sampling"},
        "slo_ttft_p99_ms": slo_ttft_p99_ms,
        "max_sustainable_rps": max_ok,
        "probes": probes,
        "steady_state_recompiles": max(
            p["recompiles"] for p in probes),
    }


def _family_total(name):
    from paddle_tpu.observability import metrics as om

    snap = om.default_registry().snapshot()
    return sum(s["value"] for s in
               snap.get(name, {}).get("series", []))


def disagg_lane(params, cfg, ecfg_kw, rate_rps: float, n_requests: int,
                max_new_tokens: int, seed: int):
    """Disaggregated-vs-colocated A/B at EQUAL chips (ISSUE 17).

    Same mixed long/short Poisson trace against two 2-engine
    topologies: [prefill, decode] with first-token KV migration
    (serving/disagg.py) vs [colocated, colocated] with least-loaded
    placement (equal chips — per-role batch geometry is the tuning
    freedom the split buys: the prefill replica's slots recycle at
    export so it keeps the base batch, while the decode replica runs
    2x to absorb the pooled decode stream). The rate is chosen to
    saturate the colocated
    pair's slot budget: once every colocated slot is held by a decoding
    request, new prompts queue behind decode completions and colocated
    p99 TTFT is slot-wait, not prefill time. The split removes exactly
    that coupling — the prefill replica's prefill-only slots recycle at
    export, so TTFT never waits on a decode stream. The cost shows up
    where disaggregation really pays it: the decode replica absorbs the
    pooled stream, and a request's post-migration slot wait lands in
    its first token gap (the TPOT tail, reported below), never in
    TTFT."""
    import threading as _threading

    from paddle_tpu import serving
    from paddle_tpu.serving.disagg import (DisaggRouter, LocalReplica,
                                           SharedPrefixIndex)

    base_batch = int(ecfg_kw.get("max_batch", 8))
    kw = {k: v for k, v in ecfg_kw.items() if k != "max_batch"}

    def make(role, max_batch):
        e = serving.DecodeEngine(params, cfg, serving.EngineConfig(
            max_batch=max_batch, role=role, **kw))
        e.warmup()
        return e

    # -- mixed long/short Poisson trace (shared by both topologies) ----
    buckets = sorted(ecfg_kw["prefill_buckets"])
    long_len = buckets[-1] - 2
    short_max = max(4, buckets[0] - 4)
    rng = np.random.RandomState(seed)
    prompts = []
    for _ in range(n_requests):
        ln = long_len if rng.rand() < 0.3 else int(
            rng.randint(2, short_max + 1))
        prompts.append(rng.randint(0, cfg.vocab_size, size=ln).tolist())
    gaps = rng.exponential(1.0 / rate_rps, size=n_requests)

    def drive(generate_fn):
        """Open-loop replay: one thread per arrival (generate blocks)."""
        results = [None] * n_requests
        threads = []
        rc0 = _recompile_total()
        t0 = time.monotonic()
        for i, (gap, prompt) in enumerate(zip(gaps, prompts)):
            time.sleep(gap)
            th = _threading.Thread(
                target=lambda i=i, p=prompt: results.__setitem__(
                    i, generate_fn(p)), daemon=True)
            th.start()
            threads.append(th)
        for th in threads:
            th.join(timeout=180.0)
        span = time.monotonic() - t0
        return results, span, _recompile_total() - rc0

    def summarize(res, span, recompiles):
        done = [r for r in res if r is not None and r.state == "done"]
        ttfts = [r.ttft_ms for r in done if r.ttft_ms is not None]
        tpots = []
        for r in done:
            tpots.extend((np.diff(r.token_times) * 1e3).tolist())
        total = sum(len(r.tokens) for r in done)
        return {
            "requests": n_requests, "completed": len(done),
            "failed": n_requests - len(done),
            "ttft_ms": {"p50": round(_pct(ttfts, 50), 3),
                        "p99": round(_pct(ttfts, 99), 3)},
            "tpot_ms": {"p50": round(_pct(tpots, 50), 3),
                        "p99": round(_pct(tpots, 99), 3)},
            "tokens_per_s": round(total / span, 2),
            "steady_state_recompiles": int(recompiles),
        }

    timeout_s = 120.0
    parity_idx = list(range(min(4, n_requests)))

    # -- topology A: two colocated engines, least-loaded placement -----
    colo = [LocalReplica(make("colocated", base_batch), name=f"colo{i}")
            for i in range(2)]

    def colo_generate(prompt):
        rep = min(colo, key=lambda r: r.load_eta_s())
        req = rep.scheduler.submit(prompt,
                                   max_new_tokens=max_new_tokens,
                                   timeout_s=timeout_s)
        rep.wake()
        req.wait(timeout=timeout_s + 1.0)
        return req

    parity_colo = [list(colo_generate(prompts[i]).tokens)
                   for i in parity_idx]
    colo_res, colo_span, colo_rc = drive(colo_generate)
    colo_sum = summarize(colo_res, colo_span, colo_rc)
    for rep in colo:
        rep.stop()

    # -- topology B: prefill -> decode with KV migration ---------------
    # (the prefix index sits out of the timed load — the random trace
    # has no shared prefixes, so publishing would be pure prefill-path
    # drag; its counters are exercised in the dedicated phase below)
    reps = [LocalReplica(make("prefill", base_batch), name="prefill0"),
            LocalReplica(make("decode", base_batch), name="decode0")]
    router = DisaggRouter(reps)
    bytes0 = _family_total("paddle_kv_transfer_bytes_total")

    def disagg_generate(prompt):
        return router.generate(prompt, max_new_tokens=max_new_tokens,
                               timeout_s=timeout_s)

    parity_disagg = [list(disagg_generate(prompts[i]).tokens)
                     for i in parity_idx]
    dis_res, dis_span, dis_rc = drive(disagg_generate)
    dis_sum = summarize(dis_res, dis_span, dis_rc)
    handoffs = [r.handoff_ms for r in dis_res
                if r is not None and r.migrated
                and r.handoff_ms is not None]
    kv_bytes = _family_total("paddle_kv_transfer_bytes_total") - bytes0

    # -- pool-level prefix cache exercise (gang-shared index) ----------
    index = SharedPrefixIndex()
    router.prefix_index = index
    for rep in reps:
        rep.engine.prefix_store = index.binding(rep.role)
    shared = rng.randint(0, cfg.vocab_size, size=16).tolist()
    for i in range(3):
        tail = rng.randint(0, cfg.vocab_size, size=4 + i).tolist()
        router.generate(shared + tail, max_new_tokens=4,
                        timeout_s=timeout_s)
    for rep in reps:
        rep.stop()

    dis_sum["migrated"] = router.migrated
    dis_sum["fallbacks"] = router.fallbacks
    dis_sum["handoff_ms"] = {
        "p50": round(_pct(handoffs, 50), 3) if handoffs else None,
        "p99": round(_pct(handoffs, 99), 3) if handoffs else None}
    dis_sum["kv_transfer_bytes"] = int(kv_bytes)
    dis_sum["pool_prefix"] = {"hits": index.hits,
                              "misses": index.misses,
                              "published": index.published}

    tokens_match = parity_disagg == parity_colo
    ttft_win = (dis_sum["ttft_ms"]["p99"] is not None
                and colo_sum["ttft_ms"]["p99"] is not None
                and dis_sum["ttft_ms"]["p99"]
                < colo_sum["ttft_ms"]["p99"])
    # p50 for the no-regress bar: CPU-smoke p99 TPOT is a single-tick
    # noise sample at these request counts; 1.15x absorbs that jitter
    tpot_ok = (dis_sum["tpot_ms"]["p50"] is not None
               and dis_sum["tpot_ms"]["p50"]
               <= colo_sum["tpot_ms"]["p50"] * 1.15)
    return {
        "rate_rps": rate_rps, "max_new_tokens": max_new_tokens,
        "n_engines_per_topology": 2,
        "long_prompt_len": long_len, "long_frac": 0.3,
        "colocated": colo_sum, "disagg": dis_sum,
        "greedy_tokens_match": bool(tokens_match),
        "ttft_p99_win": bool(ttft_win),
        "tpot_no_regress": bool(tpot_ok),
        "disagg_pass": bool(tokens_match and ttft_win and tpot_ok
                            and dis_sum["failed"] == 0
                            and colo_sum["failed"] == 0),
    }


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=os.path.join(REPO, "SERVE_BENCH.json"))
    ap.add_argument("--smoke", action="store_true",
                    help="short CPU-sized correctness run (the only "
                         "lane that runs without a TPU)")
    ap.add_argument("--d", type=int, default=64)
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--nh", type=int, default=4)
    ap.add_argument("--ff", type=int, default=128)
    ap.add_argument("--vocab", type=int, default=256)
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--max-seq", type=int, default=64)
    ap.add_argument("--buckets", default="16,32")
    ap.add_argument("--rates", default="8,32,128")
    ap.add_argument("--requests", type=int, default=60)
    ap.add_argument("--max-new-tokens", type=int, default=16)
    ap.add_argument("--prompt-len-max", type=int, default=16)
    ap.add_argument("--weight-dtypes", default="f32,int8")
    ap.add_argument("--tp", type=int, default=2,
                    help="tp size for the tensor-parallel lane (0 skips)")
    ap.add_argument("--spec-k", type=int, default=3,
                    help="draft tokens for the spec-decode lane (0 skips)")
    ap.add_argument("--eval-len", type=int, default=48,
                    help="token stream length for the parity lane")
    ap.add_argument("--queue-cap", type=int, default=256)
    ap.add_argument("--slo-ttft-ms", type=float, default=500.0)
    ap.add_argument("--capacity-rates", default="4,16,64,256")
    ap.add_argument("--capacity-requests", type=int, default=16)
    ap.add_argument("--disagg", action="store_true",
                    help="run the disaggregated-vs-colocated A/B lane "
                         "(ISSUE 17) and gate on disagg_pass")
    ap.add_argument("--disagg-rate", type=float, default=160.0,
                    help="arrival rate for the disagg A/B — picked to "
                         "saturate the colocated pair's slot budget")
    ap.add_argument("--disagg-requests", type=int, default=48)
    ap.add_argument("--disagg-max-new", type=int, default=32,
                    help="decode length for the disagg A/B (long "
                         "decodes are what makes slots scarce)")
    ap.add_argument("--tuned", default=None,
                    help="TUNED.json from tools/autotune.py: apply the "
                         "serve-space winner (geometry knobs only where "
                         "the flags above were left at their defaults; "
                         "explicit flags beat the tuner). Fingerprint-"
                         "gated — a mismatched document warns and the "
                         "defaults run.")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    from paddle_tpu.framework.core import ensure_compile_cache
    from paddle_tpu.models import gpt
    from paddle_tpu.tuning.probe import require_tpu

    require_tpu("tools/serve_bench.py", args.smoke)
    ensure_compile_cache()
    if args.smoke:
        args.rates, args.requests = "16,64", 24
        args.eval_len = 24
        args.capacity_rates, args.capacity_requests = "8,64", 12
        args.disagg_requests = 32

    tuned_doc = None
    if args.tuned:
        from paddle_tpu.tuning import probe as tuning_probe
        from paddle_tpu.tuning import tuned as tuned_mod

        tuned_doc = tuned_mod.load_for_device(
            args.tuned, tuning_probe.device_info())
        print(f"[serve_bench] tuned config "
              f"{'applied' if tuned_doc else 'REFUSED'} from "
              f"{args.tuned}", flush=True)
    if tuned_doc is not None:
        # geometry knobs apply only where the flag was left at its
        # argparse default — an explicit flag always beats the tuner
        ek = tuned_mod.engine_kwargs(tuned_doc)
        lk = tuned_mod.serve_lane_kwargs(tuned_doc)
        if args.max_batch == ap.get_default("max_batch") and \
                ek.get("max_batch"):
            args.max_batch = ek["max_batch"]
        if args.buckets == ap.get_default("buckets") and \
                ek.get("prefill_buckets"):
            args.buckets = ",".join(str(b) for b in ek["prefill_buckets"])
        if args.spec_k == ap.get_default("spec_k") and "spec" in lk:
            args.spec_k = lk["spec"]

    import jax.numpy as jnp

    compute_dtype = (jnp.float32 if jax.default_backend() == "cpu"
                     else jnp.bfloat16)
    cfg = gpt.GPTConfig(
        vocab_size=args.vocab, max_seq_len=max(args.max_seq, 64),
        num_layers=args.layers, num_heads=args.nh, d_model=args.d,
        d_ff=args.ff, dtype=compute_dtype, remat=False)
    params = gpt.init_params(jax.random.PRNGKey(args.seed), cfg)
    ecfg_kw = dict(
        max_batch=args.max_batch, max_seq=args.max_seq,
        prefill_buckets=tuple(int(b) for b in args.buckets.split(",")),
        page_size=PAGE_SIZE)

    backend = jax.default_backend()
    result = {
        "lane": "tpu" if backend == "tpu" else "cpu_smoke",
        "backend": backend,
        "device_kind": jax.devices()[0].device_kind,
        "n_chips": jax.device_count(),
        "model": {"d_model": args.d, "num_layers": args.layers,
                  "num_heads": args.nh, "d_ff": args.ff,
                  "vocab": args.vocab},
        "engine": {"max_batch": args.max_batch, "max_seq": args.max_seq,
                   "prefill_buckets": [int(b) for b in
                                       args.buckets.split(",")],
                   "max_new_tokens": args.max_new_tokens},
        # dispatch-bound off-TPU: the lane validates mechanism + the
        # zero-recompile contract, not absolute tokens/s
        "degraded": backend != "tpu",
    }
    if tuned_doc is not None:
        # full tuned-knob vector + artifact provenance (ISSUE 20)
        result["tuned"] = tuned_mod.config_stamp(tuned_doc, args.tuned)
    print(f"[serve_bench] parity lane ({args.eval_len} tokens)...",
          flush=True)
    result["quant_parity"] = parity_lane(
        params, cfg, ecfg_kw, args.seed + 1, args.eval_len)
    print("[serve_bench] reference/tp parity lane...", flush=True)
    result["engine_parity"] = engine_parity_lane(
        params, cfg, ecfg_kw, args.seed + 1, max(args.eval_len // 2, 8))

    # lane matrix: open-loop rates per dtype, plus one lane each for
    # tp, sampled, and spec-decode configs
    lane_cfgs = [{"weight_dtype": wd.strip()}
                 for wd in args.weight_dtypes.split(",")]
    if args.tp and jax.device_count() >= args.tp:
        lane_cfgs.append({"weight_dtype": "f32",
                          "sharding": "tp", "tp": args.tp})
    lane_cfgs.append({"weight_dtype": "f32",
                      "sampling": {"temperature": 0.8, "top_p": 0.9}})
    if args.spec_k:
        lane_cfgs.append({"weight_dtype": "f32", "spec": args.spec_k})
    if tuned_doc is not None:
        # one lane at the tuner's full serve winner (dtype + page pool +
        # fused decode + sharding + spec window)
        scfg = (tuned_doc.get("spaces") or {}).get("serve", {}).get(
            "config") or {}
        tuned_lane = {"weight_dtype": scfg.get("weight_dtype", "f32")}
        if scfg.get("num_pages"):
            tuned_lane["num_pages"] = int(scfg["num_pages"])
        if scfg.get("fused_decode"):
            tuned_lane["fused_decode"] = True
        if scfg.get("sharding", "none") != "none" and \
                jax.device_count() >= int(scfg.get("tp", 2)):
            tuned_lane.update(sharding=scfg["sharding"],
                              tp=int(scfg.get("tp", 2)))
        if scfg.get("spec"):
            tuned_lane["spec"] = int(scfg["spec"])
        if tuned_lane not in lane_cfgs:
            lane_cfgs.append(tuned_lane)

    lanes = []
    for lane in lane_cfgs:
        for rate in (float(r) for r in args.rates.split(",")):
            desc = ",".join(f"{k}={v}" for k, v in lane.items())
            print(f"[serve_bench] load lane {desc} rate={rate}/s "
                  f"({args.requests} requests)...", flush=True)
            lanes.append(load_lane(
                params, cfg, ecfg_kw, lane, rate, args.requests,
                args.max_new_tokens, args.prompt_len_max,
                args.seed + 2, args.queue_cap))
    result["load"] = lanes

    # closed-loop capacity: per (chip count, dtype, spec on/off)
    cap_ladder = [float(r) for r in args.capacity_rates.split(",")]
    cap_cfgs = [{"weight_dtype": "f32"}, {"weight_dtype": "int8"}]
    if args.spec_k:
        cap_cfgs.append({"weight_dtype": "f32", "spec": args.spec_k})
    capacity = []
    for lane in cap_cfgs:
        desc = ",".join(f"{k}={v}" for k, v in lane.items())
        print(f"[serve_bench] capacity lane {desc} "
              f"(SLO p99 TTFT <= {args.slo_ttft_ms}ms)...", flush=True)
        capacity.append(capacity_lane(
            params, cfg, ecfg_kw, lane, args.slo_ttft_ms, cap_ladder,
            args.capacity_requests, args.max_new_tokens,
            args.prompt_len_max, args.seed + 3, args.queue_cap))
    result["capacity"] = capacity

    if args.disagg:
        print(f"[serve_bench] disagg A/B lane "
              f"(rate={args.disagg_rate}/s, "
              f"{args.disagg_requests} requests)...", flush=True)
        result["disagg"] = disagg_lane(
            params, cfg, ecfg_kw, args.disagg_rate,
            args.disagg_requests, args.disagg_max_new, args.seed + 4)
        result["disagg_pass"] = result["disagg"]["disagg_pass"]

    all_recompiles = ([l["steady_state_recompiles"] for l in lanes]
                      + [c["steady_state_recompiles"] for c in capacity])
    result["steady_state_recompiles"] = max(all_recompiles)
    result["zero_recompile_pass"] = result["steady_state_recompiles"] == 0
    result["int8_pass"] = bool(result["quant_parity"]["int8"]["pass"])
    ep = result["engine_parity"]
    result["engine_parity_pass"] = bool(
        ep["tokens_match_reference"]
        and ep.get("tp2_tokens_match", True))

    with open(args.out, "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps({k: v for k, v in result.items()
                      if k not in ("load", "capacity")}, indent=1))
    print(f"[serve_bench] wrote {args.out}")
    if not (result["zero_recompile_pass"] and result["int8_pass"]
            and result["engine_parity_pass"]
            and result.get("disagg_pass", True)):
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
