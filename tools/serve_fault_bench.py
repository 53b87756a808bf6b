#!/usr/bin/env python
"""Serving fault-injection harness (ISSUE 15, docs/serving.md
"Resilience") — the tools/fault_bench.py discipline pointed at the
serving stack: replicas are killed, hung, and poisoned UNDER LOAD, and
the gang must keep every client whole — zero lost responses, zero
duplicated responses, warm prefix cache across restarts.

Scenarios (full mode; ``--smoke`` runs the starred subset, ~40 s, the
tier-1 slow lane in tests/test_serving_resilience.py):

  replica_sigkill  * 2-replica gang under a concurrent request stream;
                     the busiest replica is SIGKILL'd mid-decode. Every
                     request completes on a sibling (failover re-prefills
                     — partials from the dead replica are discarded),
                     greedy tokens match the single-engine reference,
                     an idempotent retry returns the recorded response
                     under the ORIGINAL trace id, the killed
                     incarnation's span JSONL survives the SIGKILL
                     (flush-per-record) and stitches orphan-free via
                     tools/trace_assemble.py, and the gang recycles the
                     replica with cause=crash.
  engine_poisoned  * one replica self-poisons after N requests (the
                     donation-failure stand-in); its engine loop fails
                     fast — abort + refuse + exit 44 — and the gang
                     recycles it with cause=poisoned while the sibling
                     keeps serving. No request is lost or doubled.
  engine_hang        one replica's engine loop wedges mid-run; its hang
                     watchdog (the PADDLE_HEALTH_* contract the gang
                     exports) fires within the deadline and exits 43;
                     the gang recycles with cause=hang and in-flight
                     requests fail over.
  overload_storm     page-pool exhaustion + queue pressure on one
                     engine: preemption kicks in, deadline-aware
                     shedding rejects with Retry-After instead of
                     queueing into guaranteed 504s, nothing deadlocks,
                     and completed-request latency stays bounded by the
                     deadline contract. Zero steady-state recompiles.
  warm_restart_prefix  single replica with a persistent prefix store:
                     after SIGKILL + gang recycle, the restarted replica
                     restores its published pages and a repeated system
                     prompt STILL prefills suffix-only — gated on the
                     replica's own ``paddle_serve_prefill_tokens_total``
                     exposition (the PR 13 prefill-once gate, now across
                     a process boundary).

Writes SERVE_FAULT_BENCH.json. Usage:

  python tools/serve_fault_bench.py [--smoke] [--out SERVE_FAULT_BENCH.json]
"""
from __future__ import annotations

import argparse
import concurrent.futures
import json
import os
import re
import signal
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "tools"))

os.environ.setdefault("JAX_PLATFORMS", "cpu")


def _log(msg):
    print(f"[serve_fault_bench] {msg}", file=sys.stderr, flush=True)


# tiny deterministic model: every replica (and the in-process reference
# engine) builds identical weights from the seed, so greedy tokens are
# comparable across processes
MODEL = {"d_model": 32, "num_layers": 1, "num_heads": 2, "d_ff": 64,
         "vocab_size": 128, "max_seq_len": 64, "seed": 5}
ENGINE = {"max_batch": 4, "max_seq": 32, "prefill_buckets": [8, 16],
          "page_size": 8}


def _worker_config(**over):
    cfg = {"model": dict(MODEL), "engine": dict(ENGINE),
           "scheduler": {"max_queue": 64, "default_timeout_s": 60.0},
           "request_timeout_s": 60.0}
    cfg.update(over)
    return cfg


def _reference_engine():
    import jax

    from paddle_tpu import serving
    from paddle_tpu.models import gpt

    m = MODEL
    cfg = gpt.GPTConfig(
        vocab_size=m["vocab_size"], max_seq_len=m["max_seq_len"],
        num_layers=m["num_layers"], num_heads=m["num_heads"],
        d_model=m["d_model"], d_ff=m["d_ff"], remat=False)
    params = gpt.init_params(jax.random.PRNGKey(m["seed"]), cfg)
    ekw = dict(ENGINE)
    ekw["prefill_buckets"] = tuple(ekw["prefill_buckets"])
    engine = serving.DecodeEngine(params, cfg,
                                  serving.EngineConfig(**ekw))
    engine.warmup()
    return engine


def _reference_tokens(engine, prompt, n):
    import numpy as np

    slot, logits = engine.start_sequence(list(prompt))
    toks = [int(np.argmax(logits))]
    for _ in range(n - 1):
        out = engine.decode_step({slot: toks[-1]})
        toks.append(int(np.argmax(out[slot])))
    engine.free_sequence(slot)
    return toks


def _post(port, body, timeout=60.0):
    import urllib.error
    import urllib.request

    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/generate",
        data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, json.loads(r.read().decode())
    except urllib.error.HTTPError as e:
        try:
            return e.code, json.loads(e.read().decode())
        except ValueError:
            return e.code, {"error": f"HTTP {e.code}"}


def _replica_counter(handle, name):
    """Scrape one counter value off a replica's own /metrics."""
    text = handle.get_text("/metrics")
    total = 0.0
    for line in text.splitlines():
        if line.startswith(name) and not line.startswith("#"):
            m = re.match(rf"{name}(?:{{[^}}]*}})?\s+([0-9.eE+-]+)", line)
            if m:
                total += float(m.group(1))
    return total


def _gang(work, name, n_replicas=2, per_replica=None, prefix_store=False,
          hang_deadline_s=4.0, **cfg_over):
    from paddle_tpu.serving.gang import GangConfig, ReplicaGang

    return ReplicaGang(
        _worker_config(), os.path.join(work, name),
        GangConfig(n_replicas=n_replicas, hang_deadline_s=hang_deadline_s,
                   probe_interval_s=0.25, ready_timeout_s=300.0,
                   default_timeout_s=60.0, **cfg_over),
        prefix_store=prefix_store, per_replica=per_replica)


def _stream(gang, prompts, max_new, request_prefix, workers=6):
    """Fire the prompt list concurrently through gang.dispatch; returns
    {request_id: (code, payload)} — one entry per id by construction."""
    results = {}

    def one(i, prompt):
        rid = f"{request_prefix}-{i}"
        code, payload = gang.dispatch(
            {"prompt": prompt, "max_new_tokens": max_new,
             "request_id": rid, "timeout_s": 60.0})
        return rid, code, payload

    with concurrent.futures.ThreadPoolExecutor(workers) as ex:
        futs = [ex.submit(one, i, p) for i, p in enumerate(prompts)]
        for f in concurrent.futures.as_completed(futs):
            rid, code, payload = f.result()
            results[rid] = (code, payload)
    return results


def _check_stream(results, expected, n_sent):
    """Zero-lost / zero-duplicated / token-correct accounting."""
    lost = n_sent - len(results)
    bad_codes = {rid: c for rid, (c, _p) in results.items() if c != 200}
    wrong = {rid: p.get("tokens") for rid, (c, p) in results.items()
             if c == 200 and expected.get(rid) is not None
             and p.get("tokens") != expected[rid]}
    return {
        "sent": n_sent, "answered": len(results),
        "lost_responses": lost,
        "non_200": bad_codes,
        "wrong_tokens": wrong,
        "ok": lost == 0 and not bad_codes and not wrong,
    }


# ---------------------------------------------------------------------------
# Scenarios
# ---------------------------------------------------------------------------

def scenario_replica_sigkill(work, ref):
    import numpy as np

    rng = np.random.RandomState(11)
    n_req, max_new = 16, 24
    prompts = [rng.randint(0, MODEL["vocab_size"],
                           size=int(rng.randint(3, 9))).tolist()
               for _ in range(n_req)]
    expected = {f"sk-{i}": _reference_tokens(ref, p, max_new)
                for i, p in enumerate(prompts)}
    gang = _gang(work, "sigkill", n_replicas=2)
    try:
        t0 = time.time()
        gang.start()
        spawn_s = time.time() - t0
        killed = {}

        def killer():
            # SIGKILL a replica the moment it is observed mid-request —
            # the in-flight dispatch MUST fail over, not quietly finish.
            # Wait until the victim has ANSWERED at least one request so
            # its span JSONL deterministically holds flushed records the
            # assembly gate below can demand survive the kill.
            deadline = time.time() + 20
            while time.time() < deadline:
                busy = max(gang.replicas, key=lambda r: r.inflight)
                if busy.inflight >= 1 and busy.port is not None:
                    try:
                        served = _replica_counter(
                            busy, "paddle_serve_requests_total")
                    except Exception:
                        served = 0.0
                    if served < 1:
                        time.sleep(0.001)
                        continue
                    killed["index"] = busy.index
                    killed["pid"] = busy.proc.pid
                    _log(f"SIGKILL replica {busy.index} "
                         f"(pid {busy.proc.pid}) mid-decode")
                    busy.kill(signal.SIGKILL)
                    return
                time.sleep(0.001)

        import threading

        kt = threading.Thread(target=killer)
        kt.start()
        results = _stream(gang, prompts, max_new, "sk", workers=4)
        kt.join()
        acct = _check_stream(results, expected, n_req)
        # idempotent retry: re-dispatching an answered id must return
        # the RECORDED response, not run a second generation
        rid = "sk-0"
        code, payload = gang.dispatch(
            {"prompt": prompts[0], "max_new_tokens": max_new,
             "request_id": rid})
        retry_ok = (code == 200 and payload.get("deduplicated") is True
                    and payload["tokens"] == results[rid][1]["tokens"])
        # wait for the supervisor to notice the death AND the respawned
        # incarnation to come back ready
        deadline = time.time() + 45
        while time.time() < deadline:
            h = gang.health()
            if h["restarts"].get("crash", 0) >= 1 and h["ready"] == 2:
                break
            time.sleep(0.2)
        h = gang.health()
        # ISSUE 18: every span is flushed the moment it is recorded, so
        # the SIGKILLed incarnation's partial trace file must survive
        # the kill and still stitch cleanly with the rest of the fleet
        import trace_assemble
        report = trace_assemble.assemble_dir(gang.trace_dir)
        killed_files = [f for f in report["files"]
                        if f.endswith(f"-{killed.get('pid')}.jsonl")]
        killed_spans = sum(report["files"][f] for f in killed_files)
        trace_ok = (bool(killed_files) and killed_spans >= 1
                    and report["n_orphans"] == 0
                    and report["n_duplicates"] == 0)
        # the dedup retry must come back under the ORIGINAL trace id —
        # failover/retry re-dispatch never mints a fresh trace
        retry_same_trace = (payload.get("trace_id") is not None
                            and payload.get("trace_id")
                            == results[rid][1].get("trace_id"))
        s = {
            "spawn_s": round(spawn_s, 1),
            "killed_replica": killed,
            **acct,
            "failovers": gang.failovers,
            "restarts": h["restarts"],
            "idempotent_retry_ok": retry_ok,
            "retry_same_trace": retry_same_trace,
            "gang_recovered": h["ready"] == 2,
            "killed_replica_span_files": killed_files,
            "killed_replica_spans": killed_spans,
            "trace_orphans": report["n_orphans"],
            "trace_duplicates": report["n_duplicates"],
            "killed_trace_stitchable": trace_ok,
        }
        s["pass"] = bool(acct["ok"] and gang.failovers >= 1
                         and h["restarts"].get("crash", 0) >= 1
                         and retry_ok and retry_same_trace
                         and s["gang_recovered"] and trace_ok)
        return s
    finally:
        gang.stop()


def scenario_engine_poisoned(work, ref):
    import numpy as np

    rng = np.random.RandomState(13)
    n_req, max_new = 10, 8
    prompts = [rng.randint(0, MODEL["vocab_size"],
                           size=int(rng.randint(3, 12))).tolist()
               for _ in range(n_req)]
    expected = {f"po-{i}": _reference_tokens(ref, p, max_new)
                for i, p in enumerate(prompts)}
    # replica 0 self-poisons after 2 completed requests — the stand-in
    # for an executable dying after cache donation; replica 1 is clean
    gang = _gang(work, "poisoned", n_replicas=2,
                 per_replica={0: {"inject": {"poison_after": 2}}})
    try:
        gang.start()
        results = _stream(gang, prompts, max_new, "po", workers=3)
        acct = _check_stream(results, expected, n_req)
        deadline = time.time() + 30
        while time.time() < deadline and \
                gang.health()["restarts"].get("poisoned", 0) < 1:
            time.sleep(0.2)
        # recycled replica must come back clean
        while time.time() < deadline and gang.health()["ready"] < 2:
            time.sleep(0.2)
        h = gang.health()
        s = {
            **acct,
            "restarts": h["restarts"],
            "sibling_kept_serving": acct["ok"],
            "gang_recovered": h["ready"] == 2,
        }
        s["pass"] = bool(acct["ok"]
                         and h["restarts"].get("poisoned", 0) >= 1
                         and s["gang_recovered"])
        return s
    finally:
        gang.stop()


def scenario_engine_hang(work, ref):
    import numpy as np

    rng = np.random.RandomState(17)
    n_req, max_new = 8, 8
    prompts = [rng.randint(0, MODEL["vocab_size"],
                           size=int(rng.randint(3, 12))).tolist()
               for _ in range(n_req)]
    expected = {f"hg-{i}": _reference_tokens(ref, p, max_new)
                for i, p in enumerate(prompts)}
    # replica 0 wedges its engine loop after 2 requests; its watchdog
    # (armed from the gang's PADDLE_HEALTH_* env) must exit 43 inside
    # the deadline and the gang recycles with cause=hang
    gang = _gang(work, "hang", n_replicas=2, hang_deadline_s=3.0,
                 per_replica={0: {"inject": {"hang_after": 2}}})
    try:
        gang.start()
        results = _stream(gang, prompts, max_new, "hg", workers=3)
        acct = _check_stream(results, expected, n_req)
        deadline = time.time() + 30
        while time.time() < deadline and gang.health()["ready"] < 2:
            time.sleep(0.2)
        h = gang.health()
        s = {
            **acct,
            "restarts": h["restarts"],
            "gang_recovered": h["ready"] == 2,
        }
        s["pass"] = bool(acct["ok"] and h["restarts"].get("hang", 0) >= 1
                         and s["gang_recovered"])
        return s
    finally:
        gang.stop()


def scenario_overload_storm(ref_params_cfg):
    """In-process page-pool exhaustion + queue pressure: preemption and
    deadline-aware shedding must keep the engine live and every client
    answered inside its deadline contract — no deadlock, no unbounded
    tail."""
    import numpy as np

    from paddle_tpu import serving
    from paddle_tpu.observability import default_registry

    params, cfg = ref_params_cfg

    def shed_by_reason():
        snap = default_registry().snapshot()
        return {tuple(s["labels"])[0]: s["value"] for s in
                snap.get("paddle_serve_shed_total", {}).get("series", [])}

    def counter(name):
        snap = default_registry().snapshot()
        return sum(s["value"] for s in
                   snap.get(name, {}).get("series", []))

    # pool far below worst case: 4 slots x up to 4 pages each vs 9
    # usable pages -> guaranteed mid-decode exhaustion
    # prefix_cache off: its pool-pressure reclaim would quietly absorb
    # the exhaustion this scenario exists to provoke — the storm tests
    # the PREEMPTION path, not the cache's elasticity
    engine = serving.DecodeEngine(params, cfg, serving.EngineConfig(
        max_batch=4, max_seq=32, prefill_buckets=(8, 16),
        page_size=8, num_pages=10,
        prefix_cache=False))
    engine.warmup()
    # the queue is deep on purpose: pressure must land on the PAGE POOL
    # (preemption) and on the drain-ETA (deadline shedding), not be
    # absorbed by a shallow queue-full rejection up front
    sched = serving.Scheduler(engine, serving.SchedulerConfig(
        max_queue=64, default_timeout_s=8.0))
    front = serving.FrontDoor(scheduler=sched, max_queue=64,
                              request_timeout_s=8.0).start()
    rng = np.random.RandomState(23)
    shed0 = shed_by_reason()
    rc0 = counter("paddle_recompiles_total")

    def one(timeout_s, gen):
        prompt = rng.randint(0, cfg.vocab_size,
                             size=int(rng.randint(9, 14))).tolist()
        t0 = time.time()
        try:
            code, payload = _post(front.port, {
                "prompt": prompt, "max_new_tokens": gen,
                "timeout_s": timeout_s}, timeout=30.0)
        except Exception as e:       # transport-level flake: one retry
            try:
                code, payload = _post(front.port, {
                    "prompt": prompt, "max_new_tokens": gen,
                    "timeout_s": timeout_s}, timeout=30.0)
            except Exception:
                code, payload = 599, {"error": f"{type(e).__name__}: {e}"}
        return code, payload, time.time() - t0

    try:
        # pre-wave: give the drain-rate estimator completions to measure
        for _ in range(6):
            one(8.0, 8)
        t_start = time.time()
        # phase A — page-pool exhaustion: moderate concurrency so the
        # queue never rejects, but every admitted request grows to ~4
        # pages against the 9-page pool -> mid-decode exhaustion that
        # MUST preempt (recompute-requeue), not deadlock
        with concurrent.futures.ThreadPoolExecutor(10) as ex:
            out = list(ex.map(lambda _i: one(6.0, 16), range(40)))
        preempt_a = sched.preemptions
        # phase B — shed pressure: a 32-wide submit burst piles the
        # queue deep, then short-deadline probes arrive: their drain
        # ETA exceeds the 10 ms deadline -> deadline shed with a
        # measured Retry-After instead of a doomed 504 (queue-full
        # sheds may also fire; the deadline path is the one REQUIRED)
        with concurrent.futures.ThreadPoolExecutor(32) as ex:
            futs = [ex.submit(one, 6.0, 18) for _ in range(80)]
            time.sleep(0.05)
            probes = [ex.submit(one, 0.01, 18) for _ in range(20)]
            out += [f.result() for f in futs + probes]
        wall = time.time() - t_start
    finally:
        front.stop()
    n_req = len(out)
    codes = {}
    for code, _p, _el in out:
        codes[code] = codes.get(code, 0) + 1
    lat = sorted(el for code, _p, el in out if code == 200)
    p99 = lat[int(0.99 * (len(lat) - 1))] if lat else None
    shed1 = shed_by_reason()
    shed_delta = {k: shed1.get(k, 0) - shed0.get(k, 0)
                  for k in set(shed0) | set(shed1)}
    sheds_with_retry_after = [p for code, p, _el in out
                              if code == 429 and "retry_after_s" in p]
    s = {
        "requests": n_req,
        "answered": len(out),
        "codes": {str(k): v for k, v in sorted(codes.items())},
        "completed": codes.get(200, 0),
        "preemptions_pool_phase": preempt_a,
        "shed_by_reason": {k: v for k, v in shed_delta.items() if v},
        "sheds_carry_retry_after":
            len(sheds_with_retry_after) == codes.get(429, 0),
        "preemptions": sched.preemptions,
        "p99_completed_latency_s": round(p99, 3) if p99 else None,
        "wall_s": round(wall, 1),
        "steady_state_recompiles":
            int(counter("paddle_recompiles_total") - rc0),
        "engine_poisoned": engine.poisoned,
    }
    # bounded degradation: every client answered (no deadlock), the
    # excess was shed with Retry-After (deadline-aware, not just
    # queue-full) or expired at its own deadline — never hung; the
    # pool storm preempted instead of deadlocking; completions inside
    # deadline + dispatch slack; engine alive and zero-recompile
    s["pass"] = bool(
        len(out) == n_req and codes.get(200, 0) >= 1
        and codes.get(599, 0) == 0
        and shed_delta.get("deadline", 0) >= 1
        and s["sheds_carry_retry_after"]
        and preempt_a >= 1
        and (p99 is None or p99 <= 8.0 + 2.0)
        and s["steady_state_recompiles"] == 0
        and engine.poisoned is None)
    return s


def scenario_warm_restart_prefix(work):
    """Kill -> restart -> the prefix cache survives: the restarted
    replica's OWN prefill-token counter moves by only the suffix on a
    repeated system prompt."""
    system_prompt = [9] * 8 + [4, 2, 7, 1]      # 12 tokens = 1 full page
    max_new = 4
    gang = _gang(work, "warm_restart", n_replicas=1, prefix_store=True)
    try:
        gang.start()
        r = gang.replicas[0]
        # first request publishes the page-aligned prefix (and persists
        # it); counter moves by the full 12 tokens
        c0 = _replica_counter(r, "paddle_serve_prefill_tokens_total")
        code1, p1 = gang.dispatch({"prompt": system_prompt,
                                   "max_new_tokens": max_new,
                                   "request_id": "wr-1"})
        d1 = _replica_counter(r, "paddle_serve_prefill_tokens_total") - c0
        # repeat pre-kill: suffix-only (the PR 13 in-process gate)
        code2, p2 = gang.dispatch({"prompt": system_prompt,
                                   "max_new_tokens": max_new,
                                   "request_id": "wr-2"})
        d2 = _replica_counter(r, "paddle_serve_prefill_tokens_total") \
            - c0 - d1
        first_incarnation = r.incarnation
        _log(f"SIGKILL warm-restart replica (pid {r.proc.pid})")
        r.kill(signal.SIGKILL)
        deadline = time.time() + 60
        while time.time() < deadline and not (
                r.incarnation > first_incarnation and r.alive
                and r.check_ready()):
            time.sleep(0.2)
        restored = r.restored_prefix_records
        # the restarted replica is a NEW process: its counter starts at
        # 0 — a warm cache means the repeated prompt adds only its
        # 4-token suffix, never the full 12
        c0 = _replica_counter(r, "paddle_serve_prefill_tokens_total")
        code3, p3 = gang.dispatch({"prompt": system_prompt,
                                   "max_new_tokens": max_new,
                                   "request_id": "wr-3"})
        d3 = _replica_counter(r, "paddle_serve_prefill_tokens_total") - c0
        h = gang.health()
        s = {
            "prefill_tokens_first": d1,
            "prefill_tokens_repeat": d2,
            "restarts": h["restarts"],
            "restored_prefix_records": restored,
            "prefill_tokens_post_restart": d3,
            "tokens_consistent": (code1 == code2 == code3 == 200
                                  and p1["tokens"] == p2["tokens"]
                                  == p3["tokens"]),
        }
        s["pass"] = bool(d1 == 12 and d2 == 4 and d3 == 4
                         and restored >= 1
                         and h["restarts"].get("crash", 0) >= 1
                         and s["tokens_consistent"])
        return s
    finally:
        gang.stop()


# ---------------------------------------------------------------------------

def harness(smoke, out_path):
    t0 = time.time()
    work = tempfile.mkdtemp(prefix="serve_fault_bench_")
    _log(f"workdir {work} (smoke={smoke})")
    import jax

    _log("building the in-process reference engine...")
    ref = _reference_engine()

    scenarios = {}
    ok = True

    def run(name, fn, *args):
        nonlocal ok
        _log(f"scenario {name}...")
        t = time.time()
        s = fn(*args)
        s["elapsed_s"] = round(time.time() - t, 1)
        scenarios[name] = s
        ok &= s["pass"]
        _log(f"{name}: pass={s['pass']} ({s['elapsed_s']}s)")

    run("replica_sigkill", scenario_replica_sigkill, work, ref)
    run("engine_poisoned", scenario_engine_poisoned, work, ref)
    if not smoke:
        run("engine_hang", scenario_engine_hang, work, ref)
        run("overload_storm", scenario_overload_storm,
            (ref._ref_params, ref.cfg))
        run("warm_restart_prefix", scenario_warm_restart_prefix, work)

    # supervisor-side counters accumulated across the gang scenarios
    from paddle_tpu.observability import default_registry

    snap = default_registry().snapshot()
    restarts = {tuple(s["labels"])[0]: s["value"] for s in
                snap.get("paddle_serve_replica_restarts_total",
                         {}).get("series", [])}
    failovers = sum(s["value"] for s in
                    snap.get("paddle_serve_failover_requests_total",
                             {}).get("series", []))
    out = {
        "mode": "smoke" if smoke else "full",
        "backend": jax.default_backend(),
        "degraded": jax.default_backend() != "tpu",
        "model": MODEL, "engine": ENGINE,
        "replica_restarts_total": restarts,
        "failover_requests_total": failovers,
        "elapsed_s": round(time.time() - t0, 1),
        "scenarios": scenarios,
        "pass": bool(ok),
    }
    with open(out_path, "w") as f:
        json.dump(out, f, indent=1)
    _log(f"wrote {out_path} pass={ok} in {out['elapsed_s']}s")
    print(json.dumps({"serve_fault_bench": out_path, "pass": bool(ok),
                      "mode": out["mode"],
                      "elapsed_s": out["elapsed_s"]}))
    return 0 if ok else 1


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="SIGKILL + poison scenarios only (~40 s, the "
                         "tier-1 slow lane)")
    ap.add_argument("--out",
                    default=os.path.join(REPO, "SERVE_FAULT_BENCH.json"))
    args = ap.parse_args()
    return harness(args.smoke, args.out)


if __name__ == "__main__":
    sys.exit(main())
