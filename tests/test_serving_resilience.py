"""Serving resilience (ISSUE 15, docs/serving.md "Resilience"):
replicated gang failover, poisoned-engine fail-fast, deadline-aware
shedding with Retry-After, abort_all/submit races, and warm restart
through the persistent prefix store.

Fast tests use either a fake engine (scheduler-level races, shed math)
or the stdlib-only STUB replica (gang mechanics without jax warmup per
subprocess); the real-engine end-to-end matrix is the slow-marked
``tools/serve_fault_bench.py --smoke`` lane at the bottom — mirroring
how fault_bench smoke rides tests/test_elastic.py.
"""
import json
import os
import signal
import subprocess
import sys
import threading
import time
import types
import urllib.error
import urllib.request

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

class _FakeCache:
    occupancy = 0.0

    def free_slot_count(self):
        return 0


class _FakeEngine:
    """Just enough surface for Scheduler paths that never decode."""

    ecfg = types.SimpleNamespace(eos_id=None, max_batch=4)
    cache = _FakeCache()
    poisoned = None

    def bucket_for(self, n):
        return 16

    def can_admit(self, n):
        return False


def _post(port, body, timeout=15.0):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/generate",
        data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, json.loads(r.read().decode()), dict(r.headers)
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read().decode()), dict(e.headers)


@pytest.fixture(scope="module")
def tiny_engine_factory():
    """Shared tiny GPT params; engines are cheap after the first build
    thanks to jax's in-process compile cache reuse of identical shapes."""
    import jax

    from paddle_tpu import serving
    from paddle_tpu.models import gpt

    cfg = gpt.GPT_TINY.scaled(num_layers=1, max_seq_len=64)
    params = gpt.init_params(jax.random.PRNGKey(3), cfg)

    def make(**ekw):
        kw = dict(max_batch=2, max_seq=32, prefill_buckets=(8, 16),
                  page_size=8)
        kw.update(ekw)
        e = serving.DecodeEngine(params, cfg, serving.EngineConfig(**kw))
        return e

    return make


# ---------------------------------------------------------------------------
# Scheduler: abort_all racing concurrent submit (the ISSUE 15 satellite)
# ---------------------------------------------------------------------------

def test_abort_all_racing_submits_no_hung_waiter():
    """abort_all(refuse_new=True) racing a storm of concurrent submits:
    every accepted request must reach a terminal state (no waiter hangs
    on an event that never fires) and every late submit must get a clean
    refusal error — never a silent park on a dead queue."""
    from paddle_tpu.serving import Scheduler, SchedulerConfig

    sched = Scheduler(_FakeEngine(), SchedulerConfig(max_queue=10_000))
    accepted, refused, surprises = [], [], []
    start = threading.Barrier(9)
    stop = threading.Event()

    def submitter():
        start.wait()
        while not stop.is_set():
            try:
                accepted.append(sched.submit([1, 2, 3]))
            except RuntimeError as e:
                refused.append(str(e))
                return          # refusal is sticky — no point looping on
            except Exception as e:   # anything else is a bug
                surprises.append(repr(e))
                return

    threads = [threading.Thread(target=submitter) for _ in range(8)]
    for t in threads:
        t.start()
    start.wait()
    time.sleep(0.05)                 # let the storm build a real queue
    n_failed = sched.abort_all("engine poisoned: test", refuse_new=True)
    stop.set()
    for t in threads:
        t.join(timeout=10)
        assert not t.is_alive()
    assert not surprises, surprises
    assert n_failed > 0
    # every accepted request terminated — event fired, state terminal
    for req in accepted:
        assert req.wait(timeout=5), f"request {req.id} waiter hung"
        assert req.state == "failed"
        assert "poisoned" in (req.error or "")
    # late submits were refused with the abort reason
    assert refused and all("poisoned" in r for r in refused)
    assert sched.queue_depth() == 0
    with pytest.raises(RuntimeError, match="poisoned"):
        sched.submit([1, 2, 3])


# ---------------------------------------------------------------------------
# Drain rate / queue ETA / shed decision
# ---------------------------------------------------------------------------

def test_drain_rate_and_queue_eta():
    from paddle_tpu.serving import Scheduler, SchedulerConfig

    sched = Scheduler(_FakeEngine(), SchedulerConfig(max_queue=16))
    assert sched.drain_rate() is None          # no completions yet
    assert sched.queue_eta_s() == 0.0          # empty queue
    assert sched.retry_after_s() == 1
    now = time.monotonic()
    with sched._rate_lock:
        sched._done_times.extend([now - 8, now - 6, now - 4, now - 2])
    rate = sched.drain_rate()
    assert rate is not None and 0.3 < rate < 0.7    # ~4 events / ~8 s
    for _ in range(4):
        sched.submit([1, 2, 3])
    eta = sched.queue_eta_s()
    assert eta is not None and 4 / rate * 0.9 <= eta <= 4 / rate * 1.1
    assert sched.retry_after_s() >= int(np.floor(eta))
    assert sched.retry_after_s(cap_s=3.0) == 3


def test_shed_decision_deadline_aware():
    from paddle_tpu import serving
    from paddle_tpu.observability import default_registry

    sched = serving.Scheduler(_FakeEngine(),
                              serving.SchedulerConfig(max_queue=16))
    now = time.monotonic()
    with sched._rate_lock:
        # drain rate ~0.5/s with 6 queued -> ETA ~12 s
        sched._done_times.extend([now - 8, now - 6, now - 4, now - 2])
    for _ in range(6):
        sched.submit([1, 2, 3])

    def shed_total():
        snap = default_registry().snapshot()
        return {tuple(s["labels"])[0]: s["value"] for s in
                snap.get("paddle_serve_shed_total", {}).get("series", [])}

    before = shed_total()
    verdict = serving.shed_decision(sched, timeout_s=1.0)
    assert verdict is not None
    reason, retry_after = verdict
    assert reason == "deadline"
    assert retry_after >= 1
    assert shed_total().get("deadline", 0) == before.get("deadline", 0) + 1
    # a request that CAN make its deadline is admitted
    assert serving.shed_decision(sched, timeout_s=120.0) is None
    # immeasurable rate -> never shed on deadline (no evidence)
    fresh = serving.Scheduler(_FakeEngine())
    fresh.submit([1, 2, 3])
    assert serving.shed_decision(fresh, timeout_s=0.001) is None


def test_front_door_429_carries_retry_after(tiny_engine_factory):
    """Queue-full 429s (and drain 503s) carry a Retry-After header AND
    a retry_after_s JSON field — standalone, no gang required."""
    from paddle_tpu import serving

    # a scheduler that can never admit (fake engine): queued requests
    # stay queued, so queue-full is deterministic
    sched = serving.Scheduler(_FakeEngine(),
                              serving.SchedulerConfig(max_queue=1))
    front = serving.FrontDoor(scheduler=sched, max_queue=1).start()
    try:
        results = []

        def bg():
            results.append(_post(front.port, {
                "prompt": [1, 2, 3], "max_new_tokens": 2,
                "timeout_s": 2.0}))

        t = threading.Thread(target=bg)
        t.start()
        deadline = time.time() + 5
        while time.time() < deadline and sched.queue_depth() < 1:
            time.sleep(0.01)
        code, body, headers = _post(front.port, {
            "prompt": [1, 2, 3], "max_new_tokens": 2, "timeout_s": 2.0})
        assert code == 429
        assert body["retry_after_s"] >= 1
        assert int(headers["Retry-After"]) == body["retry_after_s"]
        t.join(timeout=15)
        # the parked request expired at ITS deadline with a 504 — the
        # shed never blocks the queue's own drain contract
        assert results and results[0][0] == 504
    finally:
        front.stop()


# ---------------------------------------------------------------------------
# Poisoned engine: /health surfacing + EngineLoop fail-fast
# ---------------------------------------------------------------------------

def test_poisoned_engine_fails_fast(tiny_engine_factory):
    from paddle_tpu import serving

    engine = tiny_engine_factory()
    engine.warmup()
    sched = serving.Scheduler(engine)
    fired = []
    front = serving.FrontDoor(scheduler=sched,
                              on_poison=fired.append).start()
    try:
        code, body, _h = _post(front.port, {"prompt": [1, 2, 3],
                                            "max_new_tokens": 2})
        assert code == 200 and len(body["tokens"]) == 2
        assert front.health()["status"] == "ok"
        # simulate the donation-failure state engine.py guards against
        engine.poisoned = "decode failed after cache-buffer donation"
        deadline = time.time() + 5
        while time.time() < deadline and not fired:
            time.sleep(0.01)
        assert fired == ["decode failed after cache-buffer donation"]
        h = front.health()
        assert h["status"] == "poisoned"
        assert "donation" in h["engine_poisoned"]
        # late submit: clean 503 with Retry-After, not a hang or a 500
        code, body, headers = _post(front.port, {"prompt": [1, 2],
                                                 "max_new_tokens": 2})
        assert code == 503
        assert "poisoned" in body["error"]
        assert "Retry-After" in headers
        assert sched.refusing is not None
    finally:
        front.stop()


def test_gang_exit_cause_mapping():
    from paddle_tpu.parallel.health import HANG_EXIT_CODE
    from paddle_tpu.serving import POISONED_EXIT_CODE
    from paddle_tpu.serving.gang import _exit_cause

    assert _exit_cause(HANG_EXIT_CODE) == "hang"
    assert _exit_cause(POISONED_EXIT_CODE) == "poisoned"
    assert _exit_cause(1) == "crash"
    assert _exit_cause(-signal.SIGKILL) == "crash"
    assert _exit_cause(-signal.SIGTERM) == "crash"


# ---------------------------------------------------------------------------
# Prefix store: publish-time persistence, boot-time restore
# ---------------------------------------------------------------------------

def test_prefix_store_warm_restart_in_process(tmp_path,
                                              tiny_engine_factory):
    """Engine A publishes a system prompt's pages -> engine B (fresh
    process stand-in: fresh pool, same store dir) restores them and
    prefills ONLY the suffix — the ROADMAP 2(c) contract, gated on
    paddle_serve_prefill_tokens_total like PR 13."""
    from paddle_tpu import serving
    from paddle_tpu.observability import default_registry

    def prefill_tokens():
        snap = default_registry().snapshot()
        s = snap.get("paddle_serve_prefill_tokens_total",
                     {}).get("series", [])
        return s[0]["value"] if s else 0.0

    system_prompt = [7] * 8 + [3, 5, 2, 9]     # 12 tokens = 1 full page
    store_a = serving.PrefixStore(str(tmp_path / "store"))
    eng_a = tiny_engine_factory()
    assert eng_a.attach_prefix_store(store_a) == 0
    eng_a.warmup()
    sched_a = serving.Scheduler(eng_a)
    t0 = prefill_tokens()
    ra = sched_a.submit(system_prompt, max_new_tokens=3)
    while sched_a.pending():
        sched_a.step()
    assert prefill_tokens() - t0 == 12
    store_a.wait()
    assert store_a.saved == 1 and store_a.record_count() == 1
    # a REPEATED prompt adds nothing to the store (hash-deduped)
    rb = sched_a.submit(system_prompt, max_new_tokens=3)
    while sched_a.pending():
        sched_a.step()
    store_a.wait()
    assert store_a.saved == 1 and ra.tokens == rb.tokens

    # "restart": a brand-new engine over the same store directory
    store_b = serving.PrefixStore(str(tmp_path / "store"))
    eng_b = tiny_engine_factory()
    assert eng_b.attach_prefix_store(store_b) == 1
    assert store_b.restored == 1
    eng_b.warmup()
    sched_b = serving.Scheduler(eng_b)
    t0 = prefill_tokens()
    rc = sched_b.submit(system_prompt, max_new_tokens=3)
    while sched_b.pending():
        sched_b.step()
    # suffix-only: 4 of 12 tokens prefilled on the restarted engine
    assert prefill_tokens() - t0 == 4
    assert rc.tokens == ra.tokens


@pytest.mark.parametrize("entry", ["attach_prefix_store", "adopt_prefix"])
def test_prefix_store_and_adoption_need_the_prefix_cache(
        tmp_path, tiny_engine_factory, entry):
    """The two ways pages come into an engine from outside (a restored
    store, a gang-shared prefix record) need the prefix cache and say so;
    they no longer ask which layout the engine has."""
    from paddle_tpu import serving
    from paddle_tpu.serving import kv_transfer as kvt

    eng = tiny_engine_factory(prefix_cache=False)
    assert eng.prefix is None and eng.cache.prefix_cache is None
    with pytest.raises(ValueError, match="needs prefix_cache enabled"):
        if entry == "attach_prefix_store":
            eng.attach_prefix_store(
                serving.PrefixStore(str(tmp_path / "store")))
        else:
            kvt.adopt_prefix(eng, {"fingerprint": eng.cache_fingerprint()})
    assert eng.prefix_store is None


def test_prefix_store_rejects_mismatched_geometry(tmp_path,
                                                  tiny_engine_factory):
    """A record written for a different cache config is REFUSED with a
    field-by-field :class:`CacheConfigMismatch` at attach time (ISSUE
    17) — geometry drift across a redeploy fails loudly instead of
    silently skipping records or half-applying them."""
    import pytest

    from paddle_tpu import serving

    store = serving.PrefixStore(str(tmp_path / "store"))
    eng = tiny_engine_factory()
    eng.attach_prefix_store(store)
    eng.warmup()
    sched = serving.Scheduler(eng)
    sched.submit([7] * 12, max_new_tokens=2)
    while sched.pending():
        sched.step()
    store.wait()
    assert store.saved == 1

    # different page_size -> fingerprint mismatch names the field
    store2 = serving.PrefixStore(str(tmp_path / "store"))
    eng2 = tiny_engine_factory(page_size=16,
                               prefill_buckets=(16, 32))
    with pytest.raises(serving.CacheConfigMismatch) as ei:
        eng2.attach_prefix_store(store2)
    assert "page_size" in str(ei.value)
    # serving cold after refusing the store still works (the replica
    # supervisor detaches the store on this error — replica.py)
    eng2.prefix_store = None
    eng2.warmup()
    sched2 = serving.Scheduler(eng2)
    r = sched2.submit([7] * 12, max_new_tokens=2)
    while sched2.pending():
        sched2.step()
    assert r.state == "done"


def test_prefix_store_skips_legacy_record_shape_drift(
        tmp_path, tiny_engine_factory, monkeypatch):
    """Fingerprint-less records (written before the fingerprint field
    existed) keep the old behavior: shape drift is skipped and counted,
    never half-applied."""
    from paddle_tpu import serving
    from paddle_tpu.serving import kv_transfer

    store = serving.PrefixStore(str(tmp_path / "store"))
    # simulate an old writer: records carry no fingerprint
    monkeypatch.setattr(kv_transfer, "cache_fingerprint",
                        lambda cache: None)
    monkeypatch.setattr("paddle_tpu.serving.prefix_store"
                        ".cache_fingerprint", lambda cache: None)
    eng = tiny_engine_factory()
    eng.attach_prefix_store(store)
    eng.warmup()
    sched = serving.Scheduler(eng)
    sched.submit([7] * 12, max_new_tokens=2)
    while sched.pending():
        sched.step()
    store.wait()
    assert store.saved == 1
    monkeypatch.undo()

    store2 = serving.PrefixStore(str(tmp_path / "store"))
    eng2 = tiny_engine_factory(page_size=16,
                               prefill_buckets=(16, 32))
    assert eng2.attach_prefix_store(store2) == 0
    assert store2.restore_skipped == 1
    eng2.warmup()
    sched2 = serving.Scheduler(eng2)
    r = sched2.submit([7] * 12, max_new_tokens=2)
    while sched2.pending():
        sched2.step()
    assert r.state == "done"


# ---------------------------------------------------------------------------
# Gang mechanics over STUB replicas (stdlib-only workers — fast spawns)
# ---------------------------------------------------------------------------

def _stub_gang(tmp_path, name, n=2, per_replica=None, **cfg_over):
    from paddle_tpu.serving.gang import GangConfig, ReplicaGang

    kw = dict(n_replicas=n, probe_interval_s=0.1, hang_deadline_s=2.0,
              ready_timeout_s=30.0, restart_backoff_s=0.1,
              default_timeout_s=20.0)
    kw.update(cfg_over)
    return ReplicaGang({"stub": {}}, str(tmp_path / name),
                       GangConfig(**kw), per_replica=per_replica)


def test_gang_failover_dedup_and_crash_recycle(tmp_path):
    """SIGKILL a stub replica mid-request: the in-flight request fails
    over to the sibling (one response, correct tokens), the id is
    deduplicated on retry, and the gang recycles the dead replica with
    cause=crash."""
    from paddle_tpu.serving.gang import GangFrontDoor

    gang = _stub_gang(tmp_path, "failover")
    try:
        gang.start()
        front = GangFrontDoor(gang).start()
        code, p1, _h = _post(front.port, {
            "prompt": [1, 2, 3], "max_new_tokens": 4,
            "request_id": "t1"})
        assert code == 200 and len(p1["tokens"]) == 4

        results = {}

        def bg():
            results["slow"] = _post(front.port, {
                "prompt": [9, 9], "max_new_tokens": 3,
                "request_id": "slow", "stub_delay_s": 5.0,
                "timeout_s": 20.0}, timeout=30.0)

        t = threading.Thread(target=bg)
        t.start()
        deadline = time.time() + 10
        busy = None
        while time.time() < deadline:
            busy = max(gang.replicas, key=lambda r: r.inflight)
            if busy.inflight >= 1:
                break
            time.sleep(0.005)
        assert busy is not None and busy.inflight >= 1
        busy.kill(signal.SIGKILL)
        t.join(timeout=30)
        code, p, _h = results["slow"]
        assert code == 200, p
        # the failover re-ran the request; the sibling's answer is the
        # same deterministic token stream (stub: prompt-derived)
        assert p["tokens"] == [(sum([9, 9]) * 31 + i * 7) % 97
                               for i in range(3)]
        assert gang.failovers >= 1
        # ISSUE 18: the failover re-dispatch carries the ORIGINATING
        # trace context — the sibling's spans land in the SAME trace
        assert p.get("trace_id") is not None
        # idempotent retry returns the RECORDED response
        code, p2, _h = _post(front.port, {
            "prompt": [9, 9], "max_new_tokens": 3, "request_id": "slow"})
        assert code == 200 and p2.get("deduplicated") is True
        assert p2["tokens"] == p["tokens"]
        # ... and comes back under the original trace id, not a new one
        assert p2.get("trace_id") == p["trace_id"]
        sys.path.insert(0, os.path.join(REPO, "tools"))
        import trace_assemble
        report = trace_assemble.assemble_dir(gang.trace_dir)
        assert report["n_orphans"] == 0, report["orphans"]
        assert report["n_duplicates"] == 0, report["duplicates"]
        slow = [t for t in report["traces"]
                if t["trace"] == f"{p['trace_id']:x}"]
        assert slow, (p["trace_id"], report["traces"])
        # gang route span + the surviving sibling's stub span: the one
        # trace spans at least two processes' files
        assert len(slow[0]["files"]) >= 2, slow[0]
        assert "gang" in slow[0]["roles"], slow[0]
        deadline = time.time() + 15
        while time.time() < deadline:
            h = gang.health()
            if h["restarts"].get("crash", 0) >= 1 and h["ready"] == 2:
                break
            time.sleep(0.1)
        h = gang.health()
        assert h["restarts"].get("crash", 0) >= 1
        assert h["ready"] == 2, h
        front.stop()
    finally:
        gang.stop()


def test_gang_recycles_poisoned_replica_from_health_probe(tmp_path):
    """A replica whose /health reports ``poisoned`` (the probe path —
    the exit-44 path is covered by the fault bench) is recycled with
    cause=poisoned while the sibling keeps serving."""
    gang = _stub_gang(tmp_path, "poison",
                      per_replica={0: {"stub": {"poison_after": 1}}})
    try:
        gang.start()
        # land one request on replica 0 specifically (its own port) so
        # it flips to poisoned regardless of routing luck
        r0 = gang.replicas[0]
        code, _p = r0.post_generate({"prompt": [1], "max_new_tokens": 2},
                                    timeout_s=10.0)
        assert code == 200
        deadline = time.time() + 15
        while time.time() < deadline:
            h = gang.health()
            if h["restarts"].get("poisoned", 0) >= 1 and h["ready"] == 2:
                break
            time.sleep(0.1)
        h = gang.health()
        assert h["restarts"].get("poisoned", 0) >= 1, h
        assert h["ready"] == 2, h
        # service stayed up throughout
        code, payload = gang.dispatch({"prompt": [4, 5],
                                       "max_new_tokens": 2})
        assert code == 200, payload
    finally:
        gang.stop()


def test_gang_recycles_hung_replica_from_stale_heartbeat(tmp_path):
    """A wedged replica (handler + heartbeat frozen, process alive) is
    detected by the supervisor's liveness probe and recycled with
    cause=hang — the backstop for hangs the worker's own watchdog
    cannot see."""
    gang = _stub_gang(tmp_path, "hang", hang_deadline_s=1.5,
                      per_replica={0: {"stub": {"hang_after": 0}}})
    try:
        gang.start()
        r0 = gang.replicas[0]

        def poke():
            try:
                r0.post_generate({"prompt": [1], "max_new_tokens": 1},
                                 timeout_s=30.0)
            except Exception:
                pass

        t = threading.Thread(target=poke, daemon=True)
        t.start()                  # wedges replica 0's handler + hb
        deadline = time.time() + 20
        while time.time() < deadline:
            h = gang.health()
            if h["restarts"].get("hang", 0) >= 1 and h["ready"] == 2:
                break
            time.sleep(0.1)
        h = gang.health()
        assert h["restarts"].get("hang", 0) >= 1, h
        assert h["ready"] == 2, h
    finally:
        gang.stop()


# ---------------------------------------------------------------------------
# The real-engine fault matrix (slow lane, mirrors fault_bench smoke)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_serve_fault_bench_smoke(tmp_path):
    """SIGKILL-mid-decode failover + poisoned-engine recycle end-to-end
    with REAL engine replicas (~40 s); the full five-scenario matrix is
    `python tools/serve_fault_bench.py`."""
    out = str(tmp_path / "SERVE_FAULT_BENCH.json")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools",
                                      "serve_fault_bench.py"),
         "--smoke", "--out", out],
        capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    data = json.load(open(out))
    assert data["pass"] is True
    sk = data["scenarios"]["replica_sigkill"]
    assert sk["lost_responses"] == 0 and not sk["non_200"] \
        and not sk["wrong_tokens"]
    assert sk["failovers"] >= 1 and sk["idempotent_retry_ok"]
    po = data["scenarios"]["engine_poisoned"]
    assert po["restarts"].get("poisoned", 0) >= 1 and po["ok"]
