"""Serving-stack tier-1 coverage (ISSUE 9, docs/serving.md): cache slot
reuse, bucket-ladder prefill, decode-vs-reference logit parity (f32 and
int8 weights), zero-recompile steady state, continuous-batching scheduler
semantics (join/evict/ordering/deadline), and the HTTP front door's
production behaviors (429 backpressure, 504 deadlines, 500 error bodies,
SIGTERM drain). All CPU-sized: GPT_TINY-scale engines, seconds per test.
"""
import json
import os
import signal
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import jax

from paddle_tpu import serving
from paddle_tpu.models import gpt
from paddle_tpu.observability import metrics as om
from paddle_tpu.serving import quant as squant
from paddle_tpu.serving.paged_kv import CacheFullError, PagedKVCache

from serving_helpers import greedy_engine as _greedy_engine
from serving_helpers import greedy_reference as _greedy_reference


@pytest.fixture(scope="module")
def tiny_model():
    cfg = gpt.GPT_TINY.scaled(num_layers=2, max_seq_len=64)
    params = gpt.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def make_engine(tiny_model, **kw):
    cfg, params = tiny_model
    kw.setdefault("max_batch", 4)
    kw.setdefault("max_seq", 32)
    kw.setdefault("prefill_buckets", (8, 16))
    kw.setdefault("page_size", 8)
    return serving.DecodeEngine(params, cfg, serving.EngineConfig(**kw))


def _recompile_total():
    snap = om.default_registry().snapshot()
    return sum(s["value"] for s in
               snap.get("paddle_recompiles_total", {}).get("series", []))


# ---------------------------------------------------------------------------
# KV cache
# ---------------------------------------------------------------------------

def test_cache_slot_alloc_free_generation():
    """The host truth the scheduler plans against: lowest free slot
    first, ``CacheFullError`` beyond the slots, and a freed slot's
    storage reused under a bumped generation counter."""
    c = PagedKVCache(num_layers=2, max_slots=3, max_seq=8, num_heads=2,
                     head_dim=4, page_size=4)
    s0, s1, s2 = c.alloc(2), c.alloc(5), c.alloc(1)
    assert (s0, s1, s2) == (0, 1, 2)
    assert c.occupancy == 1.0 and c.free_slot_count() == 0
    with pytest.raises(CacheFullError):
        c.alloc()
    gen1 = c.generation(s1)
    pages1 = [int(p) for p in c.table_row(s1)[:2]]
    c.free(s1)
    assert c.free_slot_count() == 1 and not c.is_live(s1)
    assert c.length(s1) == 0 and not c.table_row(s1).any()
    # lowest free slot is reused, with a bumped generation, on the pages
    # the free gave back
    again = c.alloc(3)
    assert again == s1 and c.generation(again) == gen1 + 1
    assert int(c.table_row(again)[0]) == pages1[0]
    assert c.lengths_vector().tolist() == [2, 3, 1]
    assert c.headroom(s0) == 6


def test_cache_host_guards():
    c = PagedKVCache(num_layers=1, max_slots=2, max_seq=4, num_heads=1,
                     head_dim=2, page_size=2)
    free0 = c.free_page_count()
    with pytest.raises(ValueError):
        c.alloc(length=5)                    # beyond max_seq
    assert c.free_slot_count() == 2 and c.free_page_count() == free0
    s = c.alloc(1)
    with pytest.raises(ValueError):
        c.set_length(s, 9)                   # beyond max_seq
    with pytest.raises(ValueError):
        c.set_length(s, 3)                   # beyond the mapped pages
    c.free(s)
    with pytest.raises(ValueError):
        c.free(s)                            # double free
    assert c.free_page_count() == free0      # and it freed nothing twice
    with pytest.raises(ValueError):          # page_size must divide max_seq
        PagedKVCache(num_layers=1, max_slots=1, max_seq=6, num_heads=1,
                     head_dim=2, page_size=4)


# ---------------------------------------------------------------------------
# bucket ladder
# ---------------------------------------------------------------------------

def test_bucket_ladder(tiny_model):
    assert serving.default_bucket_ladder(256) == (16, 32, 64, 128, 256)
    assert serving.default_bucket_ladder(48) == (16, 32, 48)
    eng = make_engine(tiny_model)
    assert eng.buckets == (8, 16)
    assert eng.bucket_for(1) == 8
    assert eng.bucket_for(8) == 8
    assert eng.bucket_for(9) == 16
    with pytest.raises(serving.PromptTooLongError):
        eng.bucket_for(17)


def test_engine_config_validation(tiny_model):
    cfg, params = tiny_model
    with pytest.raises(ValueError):          # bucket beyond max_seq
        serving.DecodeEngine(params, cfg, serving.EngineConfig(
            max_seq=16, prefill_buckets=(32,)))
    with pytest.raises(ValueError):          # engine beyond wpe table
        serving.DecodeEngine(params, cfg, serving.EngineConfig(
            max_seq=4096))
    with pytest.raises(ValueError, match="page_size"):   # rung of 8, page 16
        serving.DecodeEngine(params, cfg, serving.EngineConfig(
            max_seq=32, prefill_buckets=(8, 16)))


def test_default_engine_is_paged_and_one_layout(tiny_model):
    """``EngineConfig()`` is the paged engine with its prefix cache; the
    field ``kv_layout`` accepts its one value (the benchmark's
    configurations still pass it) and nothing else."""
    cfg, params = tiny_model
    eng = serving.DecodeEngine(params, cfg, serving.EngineConfig(max_seq=64))
    assert isinstance(eng.cache, PagedKVCache)
    assert eng.cache.page_size == 16 and eng.buckets == (16, 32, 64)
    assert eng.cache.num_pages == 8 * (64 // 16) + 1
    assert eng.prefix is not None and eng.cache.prefix_cache is eng.prefix
    assert eng.kv_path == "xla_gather"       # off the TPU
    same = serving.DecodeEngine(params, cfg, serving.EngineConfig(
        max_seq=64, kv_layout="paged"))
    assert same.cache_fingerprint() == eng.cache_fingerprint()
    with pytest.raises(ValueError, match="expected 'paged'"):
        serving.DecodeEngine(params, cfg, serving.EngineConfig(
            max_seq=64, kv_layout="slab"))


# ---------------------------------------------------------------------------
# decode vs reference parity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("page_size", [8, 16])
@pytest.mark.parametrize("kv_path", ["xla_gather", "pallas_paged"])
def test_decode_matches_reference_f32(tiny_model, kv_path, page_size):
    """Both lowerings of the tick (the gather, and the page-table kernel
    in interpret mode: the test steers the engine's choice before
    anything compiles) at two page sizes, against the cache-free full
    forward."""
    cfg, _ = tiny_model
    eng = make_engine(tiny_model, page_size=page_size,
                      prefill_buckets=(16,))
    assert eng.kv_path == "xla_gather"
    eng.kv_path = kv_path
    eng.warmup()
    rng = np.random.RandomState(0)
    prompt = rng.randint(0, cfg.vocab_size, size=6).tolist()
    slot, logits = eng.start_sequence(prompt)
    # prefill logits == full-forward logits at the last prompt position
    ref_last = eng.reference_logits(prompt)[-1]
    np.testing.assert_allclose(logits, ref_last, rtol=1e-4, atol=1e-4)
    # greedy continuation token-for-token vs the reference forward
    toks = [int(np.argmax(logits))]
    seq = list(prompt)
    for _ in range(7):
        seq.append(toks[-1])
        out = eng.decode_step({slot: toks[-1]})
        ref = eng.reference_logits(seq)[-1]
        np.testing.assert_allclose(out[slot], ref, rtol=1e-3, atol=1e-3)
        toks.append(int(np.argmax(out[slot])))
    assert toks[:-1] == _greedy_reference(eng, prompt, 7)


def test_interleaved_slots_are_isolated(tiny_model):
    """Two sequences decoded in the SAME batch steps must produce exactly
    what each produces alone — the continuous-batching correctness core."""
    cfg, _ = tiny_model
    eng = make_engine(tiny_model)
    eng.warmup()
    rng = np.random.RandomState(1)
    p_a = rng.randint(0, cfg.vocab_size, size=5).tolist()
    p_b = rng.randint(0, cfg.vocab_size, size=9).tolist()
    sa, la = eng.start_sequence(p_a)
    sb, lb = eng.start_sequence(p_b)
    ta, tb = [int(np.argmax(la))], [int(np.argmax(lb))]
    for _ in range(5):
        out = eng.decode_step({sa: ta[-1], sb: tb[-1]})
        ta.append(int(np.argmax(out[sa])))
        tb.append(int(np.argmax(out[sb])))
    assert ta == _greedy_reference(eng, p_a, 6)
    assert tb == _greedy_reference(eng, p_b, 6)
    eng.free_sequence(sa)
    eng.free_sequence(sb)


def test_slot_reuse_after_eviction_is_clean(tiny_model):
    """A freed slot re-prefilled for a new request must not leak the old
    request's cache rows."""
    cfg, _ = tiny_model
    eng = make_engine(tiny_model, max_batch=1, prefill_buckets=(8,))
    eng.warmup()
    rng = np.random.RandomState(2)
    p1 = rng.randint(0, cfg.vocab_size, size=8).tolist()
    p2 = rng.randint(0, cfg.vocab_size, size=3).tolist()
    got1 = _greedy_engine(eng, p1, 4)
    got2 = _greedy_engine(eng, p2, 4)      # reuses slot 0
    assert got1 == _greedy_reference(eng, p1, 4)
    assert got2 == _greedy_reference(eng, p2, 4)
    assert eng.cache.generation(0) >= 2


def test_int8_and_bf16_weight_parity(tiny_model):
    cfg, _ = tiny_model
    f32 = make_engine(tiny_model)
    q8 = make_engine(tiny_model, weight_dtype="int8")
    b16 = make_engine(tiny_model, weight_dtype="bf16")
    rng = np.random.RandomState(3)
    seq = rng.randint(0, cfg.vocab_size, size=16).tolist()

    def stream(eng):
        slot, l0 = eng.start_sequence(seq[:1])
        ls = [l0]
        for t in seq[1:]:
            ls.append(eng.decode_step({slot: t})[slot])
        eng.free_sequence(slot)
        return np.stack(ls)

    ref, s8, s16 = stream(f32), stream(q8), stream(b16)
    stats = squant.logit_error_stats(ref, s8)
    assert stats["max_rel_err"] < squant.INT8_LOGIT_TOL, stats
    assert stats["top1_agreement"] >= 0.95, stats
    ppl_ref = squant.perplexity(ref[:-1], seq[1:])
    ppl_q = squant.perplexity(s8[:-1], seq[1:])
    assert abs(ppl_q / ppl_ref - 1.0) < squant.INT8_PPL_REL_TOL
    # bf16 weights sit strictly inside the int8 bar
    assert squant.logit_error_stats(ref, s16)["max_rel_err"] < \
        squant.INT8_LOGIT_TOL
    # and the int8 residency really is ~4x smaller
    assert q8.weight_nbytes < f32.weight_nbytes / 3.5


# ---------------------------------------------------------------------------
# zero-recompile steady state
# ---------------------------------------------------------------------------

def test_zero_recompile_steady_state(tiny_model):
    cfg, _ = tiny_model
    eng = make_engine(tiny_model)
    eng.warmup()
    compiles_after_warmup = eng.compiles
    sched = serving.Scheduler(eng)
    before = _recompile_total()
    rng = np.random.RandomState(4)
    reqs = [sched.submit(
        rng.randint(0, cfg.vocab_size,
                    size=int(rng.randint(1, 16))).tolist(),
        max_new_tokens=int(rng.randint(1, 6))) for _ in range(12)]
    while sched.pending():
        sched.step()
    assert all(r.state == "done" for r in reqs)
    # the guardrail: mixed lengths, joins and evictions — zero recompiles
    assert _recompile_total() - before == 0
    assert eng.compiles == compiles_after_warmup
    assert eng.steady_state_recompiles == 0


def test_engine_recompile_is_explained(tiny_model):
    """The negative control: an engine that DOES rebuild a same-name
    executable under a new signature must tick paddle_recompiles_total
    through the PR 4 explainer and its own steady-state counter."""
    eng = make_engine(tiny_model)
    eng._decode_exec()
    eng._warm = True
    before = _recompile_total()
    # same program name, drifted signature — the exact failure the
    # steady-state contract forbids (the prefill rungs' twin of this
    # control is tests/test_paged_serving.py's)
    fn, example = eng._prefill_program(16)
    eng._compile("decode", fn, example, donate_argnums=(1,))
    assert _recompile_total() - before == 1
    assert eng.steady_state_recompiles == 1


# ---------------------------------------------------------------------------
# scheduler semantics
# ---------------------------------------------------------------------------

def test_scheduler_fifo_join_and_slot_turnover(tiny_model):
    cfg, _ = tiny_model
    eng = make_engine(tiny_model, max_batch=2)
    eng.warmup()
    sched = serving.Scheduler(eng)
    rng = np.random.RandomState(5)
    reqs = [sched.submit(rng.randint(0, cfg.vocab_size, size=4).tolist(),
                         max_new_tokens=3) for _ in range(5)]
    # first tick admits exactly max_batch requests, FIFO
    sched.step()
    assert reqs[0].state == "active" and reqs[1].state == "active"
    assert reqs[2].state == "queued"
    while sched.pending():
        sched.step()
    assert [r.state for r in reqs] == ["done"] * 5
    for r in reqs:
        assert len(r.tokens) == 3
        assert r.ttft_ms is not None and r.ttft_ms >= 0
    # 5 requests through 2 slots -> slots were reused
    assert eng.cache.free_slot_count() == 2


def test_scheduler_queue_full_and_deadline_expiry(tiny_model):
    cfg, _ = tiny_model
    eng = make_engine(tiny_model)
    sched = serving.Scheduler(eng, serving.SchedulerConfig(max_queue=1))
    r1 = sched.submit([1, 2, 3])
    with pytest.raises(serving.QueueFullError):
        sched.submit([4, 5, 6])
    assert sched.cancel(r1)
    assert r1.state == "cancelled"
    # deadline blown while queued -> expired at the next tick, never run
    r2 = sched.submit([1, 2], timeout_s=0.0)
    time.sleep(0.01)
    sched.step()
    assert r2.state == "expired" and "queued" in r2.error
    assert r2.tokens == []


def test_scheduler_deadline_mid_generation_evicts(tiny_model):
    cfg, _ = tiny_model
    eng = make_engine(tiny_model)
    eng.warmup()
    sched = serving.Scheduler(eng)
    req = sched.submit([1, 2, 3], max_new_tokens=500, timeout_s=0.05)
    sched.step()                              # admit + first decode
    assert req.state == "active"
    time.sleep(0.07)
    sched.step()                              # deadline hit -> evict
    assert req.state == "expired"
    assert len(req.tokens) >= 1               # partial generation kept
    assert eng.cache.free_slot_count() == eng.ecfg.max_batch


def test_scheduler_eos_stop(tiny_model):
    cfg, params = tiny_model
    probe = make_engine(tiny_model)
    prompt = [7, 11, 13]
    ref = _greedy_reference(probe, prompt, 3)
    eng = serving.DecodeEngine(params, cfg, serving.EngineConfig(
        max_batch=2, max_seq=32, prefill_buckets=(8,), page_size=8,
        eos_id=ref[1]))
    sched = serving.Scheduler(eng)
    req = sched.submit(prompt, max_new_tokens=50)
    while sched.pending():
        sched.step()
    assert req.state == "done"
    assert req.tokens == ref[:2]              # stopped ON the eos token


def test_scheduler_prompt_at_max_seq_finishes(tiny_model):
    """Regression: a prompt that fills its slot to max_seq (headroom 0)
    must finish at admission with the one token prefill produced — not
    stay active and blow up the next decode tick (which would hang the
    request forever and leak the slot)."""
    eng = make_engine(tiny_model, max_batch=2, max_seq=8,
                      prefill_buckets=(8,))
    eng.warmup()
    sched = serving.Scheduler(eng)
    req = sched.submit(list(range(1, 9)), max_new_tokens=4)
    sched.step()
    sched.step()                              # previously raised here
    assert req.state == "done" and req.error is None
    assert len(req.tokens) == 1
    assert eng.cache.free_slot_count() == 2
    # and the eviction is attributed to max_seq, not "done"/"deadline"
    snap = om.default_registry().snapshot()
    by_reason = {s["labels"][0]: s["value"] for s in
                 snap["paddle_serve_slot_evictions_total"]["series"]}
    assert by_reason.get("max_seq", 0) >= 1


def test_engine_loop_survives_step_fault(tiny_model):
    """Regression: a step() exception must fail the waiting requests and
    surface in /health — not silently kill the loop thread while the
    HTTP server keeps accepting work."""
    eng = make_engine(tiny_model)
    eng.warmup()
    sched = serving.Scheduler(eng)
    f = serving.FrontDoor(scheduler=sched).start()
    try:
        def boom():
            raise RuntimeError("boom")

        sched.step = boom
        code, body = _post_err(f.port, "/generate",
                               {"prompt": [1, 2, 3], "max_new_tokens": 4})
        assert code == 500
        assert "engine loop fault" in body["error"]
        assert "boom" in body["error"]
        health = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{f.port}/health", timeout=10).read())
        assert health["status"] == "ok"          # loop thread still alive
        assert health["loop_alive"] is True
        assert health["loop_faults"] >= 1
        assert "boom" in health["loop_last_fault"]
    finally:
        f.stop()


def test_engine_poisoned_after_donation_failure(tiny_model):
    """Regression: an executable failure AFTER buffer donation leaves the
    cache pools invalidated — the engine must refuse further work instead
    of reading donated buffers. Without donation (CPU) the pools survive
    and the engine stays usable."""
    eng = make_engine(tiny_model)
    eng.warmup()

    def raiser(*a, **k):
        raise RuntimeError("device OOM")

    eng._donate = True              # simulate the TPU donation contract
    orig = eng._exec["prefill_b8"]
    eng._exec["prefill_b8"] = raiser
    with pytest.raises(RuntimeError, match="device OOM"):
        eng.start_sequence([1, 2, 3])
    assert eng.poisoned is not None
    with pytest.raises(RuntimeError, match="poisoned"):
        eng.start_sequence([1, 2, 3])
    with pytest.raises(RuntimeError, match="poisoned"):
        eng.decode_step({0: 1})

    eng2 = make_engine(tiny_model)  # CPU path: no donation, no poison
    eng2.warmup()
    orig2 = eng2._exec["prefill_b8"]
    eng2._exec["prefill_b8"] = raiser
    with pytest.raises(RuntimeError, match="device OOM"):
        eng2.start_sequence([1, 2, 3])
    assert eng2.poisoned is None
    eng2._exec["prefill_b8"] = orig2
    slot, logits = eng2.start_sequence([1, 2, 3])
    assert logits.shape[-1] == eng2.cfg.vocab_size
    eng2.free_sequence(slot)


def test_scheduler_drain(tiny_model):
    cfg, _ = tiny_model
    eng = make_engine(tiny_model)
    eng.warmup()
    sched = serving.Scheduler(eng)
    reqs = [sched.submit([1, 2, 3, 4], max_new_tokens=4)
            for _ in range(3)]
    assert sched.drain(timeout_s=30.0)
    assert all(r.state == "done" for r in reqs)
    with pytest.raises(RuntimeError):
        sched.submit([1, 2])


# ---------------------------------------------------------------------------
# HTTP front door
# ---------------------------------------------------------------------------

def _post(port, path, obj, timeout=30):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}", data=json.dumps(obj).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.status, json.loads(r.read().decode())


def _post_err(port, path, obj, timeout=30):
    try:
        return _post(port, path, obj, timeout)
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read().decode())


@pytest.fixture()
def front(tiny_model):
    eng = make_engine(tiny_model)
    eng.warmup()
    sched = serving.Scheduler(eng)
    f = serving.FrontDoor(scheduler=sched).start()
    yield f
    f.stop()


def test_front_door_generate_and_metrics(front, tiny_model):
    cfg, _ = tiny_model
    code, body = _post(front.port, "/generate",
                       {"prompt": [5, 6, 7], "max_new_tokens": 4})
    assert code == 200
    assert len(body["tokens"]) == 4 and body["num_tokens"] == 4
    assert body["ttft_ms"] >= 0
    with urllib.request.urlopen(
            f"http://127.0.0.1:{front.port}/metrics", timeout=10) as r:
        text = r.read().decode()
    assert "paddle_serve_requests_total" in text
    assert "paddle_serve_ttft_ms" in text
    health = json.loads(urllib.request.urlopen(
        f"http://127.0.0.1:{front.port}/health", timeout=10).read())
    assert health["status"] == "ok"
    assert health["max_batch"] == 4 and health["buckets"] == [8, 16]
    # the leaves the engine holds in another shape than they are stored
    assert health["held_shapes"] == {
        "blocks/w_qkv": [cfg.num_layers, cfg.d_model, 3 * cfg.d_model],
        "blocks/b_qkv": [cfg.num_layers, 3 * cfg.d_model]}


def test_front_door_client_errors(front):
    code, body = _post_err(front.port, "/generate", {"prompt": []})
    assert code == 400 and "error" in body
    code, body = _post_err(front.port, "/generate", {"prompt": "nope"})
    assert code == 400
    code, body = _post_err(front.port, "/generate",
                           {"prompt": list(range(64))})
    assert code == 400 and "bucket" in body["error"]
    code, body = _post_err(front.port, "/nope", {})
    assert code == 404
    # malformed JSON
    req = urllib.request.Request(
        f"http://127.0.0.1:{front.port}/generate", data=b"{not json",
        headers={"Content-Type": "application/json"})
    try:
        urllib.request.urlopen(req, timeout=10)
        raise AssertionError("malformed JSON accepted")
    except urllib.error.HTTPError as e:
        assert e.code == 400
        assert "error" in json.loads(e.read().decode())


def test_front_door_backpressure_429(tiny_model):
    eng = make_engine(tiny_model)
    sched = serving.Scheduler(eng, serving.SchedulerConfig(max_queue=0))
    f = serving.FrontDoor(scheduler=sched).start()
    try:
        code, body = _post_err(f.port, "/generate", {"prompt": [1, 2]})
        assert code == 429 and "capacity" in body["error"]
    finally:
        f.stop()


def test_front_door_deadline_504(tiny_model):
    eng = make_engine(tiny_model)
    sched = serving.Scheduler(eng)
    f = serving.FrontDoor(scheduler=sched).start()
    f.loop.stop()          # nobody ticks -> the deadline must fire
    try:
        code, body = _post_err(
            f.port, "/generate",
            {"prompt": [1, 2], "timeout_s": 0.05}, timeout=10)
        assert code == 504 and "error" in body
        assert body["partial_tokens"] == []
    finally:
        f.stop()


def test_front_door_internal_error_500():
    class BrokenPredictor:
        def get_input_names(self):
            return ["x"]

        def get_output_names(self):
            return ["y"]

        def run(self, feed):
            raise RuntimeError("kaboom")

    f = serving.FrontDoor(predictor=BrokenPredictor()).start()
    try:
        code, body = _post_err(f.port, "/predict",
                               {"inputs": {"x": [1.0]}})
        assert code == 500
        assert "RuntimeError" in body["error"]
        assert "kaboom" in body["error"]
    finally:
        f.stop()


def test_front_door_sigterm_drains(tiny_model):
    """SIGTERM mid-request: the in-flight generation completes with 200,
    new work is refused, the listener closes."""
    eng = make_engine(tiny_model)
    eng.warmup()
    sched = serving.Scheduler(eng)
    f = serving.FrontDoor(scheduler=sched).start()
    f.install_signal_handlers(drain_timeout_s=30.0)
    results = {}

    def client():
        results["resp"] = _post_err(
            f.port, "/generate",
            {"prompt": [3, 4, 5], "max_new_tokens": 20}, timeout=30)

    t = threading.Thread(target=client)
    try:
        t.start()
        time.sleep(0.05)                      # request in flight
        os.kill(os.getpid(), signal.SIGTERM)
        t.join(timeout=30)
        assert not t.is_alive()
        code, body = results["resp"]
        assert code == 200 and len(body["tokens"]) == 20
        # server is now draining or already closed: new work refused
        deadline = time.monotonic() + 10
        refused = False
        while time.monotonic() < deadline:
            try:
                code2, body2 = _post_err(f.port, "/generate",
                                         {"prompt": [1]}, timeout=2)
                if code2 == 503:
                    refused = True
                    break
            except (urllib.error.URLError, ConnectionError, OSError):
                refused = True                # listener closed
                break
            time.sleep(0.02)
        assert refused, "drained server still accepts work"
    finally:
        f.restore_signal_handlers()
        try:
            f.stop()
        except Exception:
            pass
    assert sched.pending() == 0


def test_model_server_engine_mode(tiny_model):
    """inference.serving.ModelServer fronts the engine too (the rewritten
    production path), while the artifact mode stays available (covered by
    tests/test_serving.py)."""
    from paddle_tpu.inference.serving import ModelServer

    eng = make_engine(tiny_model)
    eng.warmup()
    sched = serving.Scheduler(eng)
    srv = ModelServer(scheduler=sched).start()
    try:
        code, body = _post(srv.port, "/generate",
                           {"prompt": [9, 8], "max_new_tokens": 3})
        assert code == 200 and len(body["tokens"]) == 3
    finally:
        srv.stop()


def test_request_metrics_flow(tiny_model):
    """paddle_serve_* series move under traffic (exact counts are owned by
    tools/metrics_check.py's isolated smoke serve; here: deltas >= )."""
    from paddle_tpu.serving import metrics as sm

    def _count(metric):
        return sum(c.value for c in metric.children())

    before_req = _count(sm.m_requests)
    before_tok = sm.m_tokens._unlabeled().value
    eng = make_engine(tiny_model)
    eng.warmup()
    sched = serving.Scheduler(eng)
    f = serving.FrontDoor(scheduler=sched).start()
    try:
        code, _ = _post(f.port, "/generate",
                        {"prompt": [2, 3], "max_new_tokens": 5})
        assert code == 200
    finally:
        f.stop()
    assert _count(sm.m_requests) >= before_req + 1
    assert sm.m_tokens._unlabeled().value >= before_tok + 5
    # ttft is split by {phase, role} since ISSUE 17 — sum the children
    assert sum(c.count for c in sm.m_ttft_ms.children()) >= 1
