"""ISSUE 17 disaggregated-serving coverage (docs/serving.md
"Disaggregation"): KV handoff wire format (CRC + jsonable + socket
channels), colocated-vs-disagg greedy parity,
the degrade-never-drop fallback matrix, the pool-level prefix index,
the tp=2 -> tp=1 page-wise redistribution (page-exact, bounded
transient residency), and the subprocess gang's mid-transfer kill with
zero loss / zero duplication. All CPU-sized: GPT_TINY-scale engines,
the 8-device CPU mesh from conftest for the tp lane, stdlib-only stub
replicas for the gang lane.
"""
import json
import threading
import time

import numpy as np
import pytest

import jax

from paddle_tpu import serving
from paddle_tpu.models import gpt
from paddle_tpu.serving import kv_transfer as kvt
from paddle_tpu.serving.disagg import (DisaggRouter, LocalReplica,
                                       SharedPrefixIndex)

from serving_helpers import greedy_engine as _greedy


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


def _f32(a):
    return np.asarray(a).astype(np.float32)


# ---------------------------------------------------------------------------
# handoff wire format
# ---------------------------------------------------------------------------

def test_handoff_jsonable_roundtrip_and_crc_tamper(tiny_model):
    """A handoff survives the JSON (base64) channel bit-for-bit — the
    adopted slot continues the greedy stream exactly — and a flipped
    payload byte is caught by the per-frame CRC, not written."""
    src = make_engine(tiny_model, role="prefill")
    dst = make_engine(tiny_model, role="decode")
    prompt = [3, 1, 4, 1, 5, 9]
    slot, logits = src.start_sequence(prompt)
    tok = int(np.argmax(logits))
    handoff = src.export_request_kv(slot, tokens=prompt)

    wire = json.dumps(kvt.handoff_to_jsonable(handoff))
    adopted = kvt.handoff_from_jsonable(json.loads(wire))
    dslot = dst.adopt_request_kv(adopted)
    a_tok, b_tok = tok, tok
    for _ in range(4):
        a_out = src.decode_step({slot: a_tok})
        b_out = dst.decode_step({dslot: b_tok})
        a_tok = int(np.argmax(a_out[slot]))
        b_tok = int(np.argmax(b_out[dslot]))
        assert a_tok == b_tok, "greedy diverged across the JSON channel"
    dst.free_sequence(dslot)

    # tamper one payload byte -> CRC rejects, nothing adopted
    bad = src.export_request_kv(slot, tokens=prompt)
    frame = bad["chunks"][0]["shards"][0]
    frame["data"] = bytes([frame["data"][0] ^ 0xFF]) + frame["data"][1:]
    free_before = dst.cache.free_slot_count()
    with pytest.raises(ValueError, match="CRC"):
        dst.adopt_request_kv(bad)
    assert dst.cache.free_slot_count() == free_before
    src.free_sequence(slot)


def test_kv_socket_channel_roundtrip(tiny_model):
    """The frame-stream socket channel (prefill replica -> decode
    replica's KVTransferServer) delivers a committed handoff exactly
    once; the adopted KV decodes identically to the source."""
    src = make_engine(tiny_model, role="prefill")
    dst = make_engine(tiny_model, role="decode")
    server = kvt.KVTransferServer().start()
    try:
        prompt = [2, 7, 1, 8, 2, 8, 1, 8, 2, 8]
        slot, logits = src.start_sequence(prompt)
        tok = int(np.argmax(logits))
        handoff = src.export_request_kv(slot, tokens=prompt)
        kvt.send_handoff(server.host, server.port, handoff)
        landed = server.pop(handoff["transfer_id"], timeout_s=10.0)
        assert landed["committed"] is True
        dslot = dst.adopt_request_kv(landed)
        a_tok = b_tok = tok
        for _ in range(4):
            a_out = src.decode_step({slot: a_tok})
            b_out = dst.decode_step({dslot: b_tok})
            a_tok = int(np.argmax(a_out[slot]))
            b_tok = int(np.argmax(b_out[dslot]))
            assert a_tok == b_tok, "greedy diverged across the socket"
        # exactly-once: a second pop of the same id times out
        with pytest.raises(TimeoutError):
            server.pop(handoff["transfer_id"], timeout_s=0.2)
        src.free_sequence(slot)
        dst.free_sequence(dslot)
    finally:
        server.close()


def test_handoff_of_another_layout_is_refused(tiny_model):
    """Input from outside the process: a hand-off whose fingerprint names
    a layout this engine does not have (an older peer's ``slab``) is
    refused field by field before a page is claimed."""
    src = make_engine(tiny_model, role="prefill")
    dst = make_engine(tiny_model, role="decode")
    assert src.cache_fingerprint()["layout"] == "paged"
    slot, _ = src.start_sequence([3, 1, 4, 1, 5, 9])
    handoff = src.export_request_kv(slot)
    handoff["fingerprint"] = dict(handoff["fingerprint"], layout="slab")
    pages_before = dst.cache.free_page_count()
    with pytest.raises(kvt.CacheConfigMismatch,
                       match="layout: expected 'paged', got 'slab'"):
        dst.adopt_request_kv(handoff)
    assert dst.cache.free_page_count() == pages_before
    assert dst.cache.free_slot_count() == dst.ecfg.max_batch
    src.free_sequence(slot)


# ---------------------------------------------------------------------------
# router parity + fallback matrix (in-process replicas)
# ---------------------------------------------------------------------------

def _stop_all(replicas):
    for r in replicas:
        r.stop()


def test_disagg_router_greedy_parity(tiny_model):
    """Phase-split serving is a pure routing change: the disagg router
    (prefill replica -> KV migration -> decode replica) must emit the
    colocated engine's exact greedy tokens."""
    colo = make_engine(tiny_model)
    reps = [LocalReplica(make_engine(tiny_model, role="prefill")),
            LocalReplica(make_engine(tiny_model, role="decode"))]
    router = DisaggRouter(reps)
    rng = np.random.RandomState(17)
    try:
        for _ in range(3):
            plen = int(rng.randint(3, 12))
            prompt = rng.randint(0, tiny_model[0].vocab_size,
                                 size=plen).tolist()
            want = _greedy(colo, prompt, 6)
            got = router.generate(prompt, max_new_tokens=6,
                                  timeout_s=60.0)
            assert got.state == "done", got.error
            assert got.migrated and got.fallback_reason is None
            assert got.tokens == want, \
                f"disagg tokens {got.tokens} != colocated {want}"
        assert router.migrated == 3 and router.fallbacks == 0
        # the prefill fleet released every exported slot at the
        # first-token boundary — nothing leaks across migrations
        assert reps[0].engine.cache.occupancy == 0.0
        assert reps[1].engine.cache.occupancy == 0.0
    finally:
        _stop_all(reps)


def test_disagg_router_empty_phase_fleet_degrades(tiny_model):
    """No prefill/decode fleet -> colocated dispatch, correct tokens,
    reason counted: degrade, never drop."""
    colo_engine = make_engine(tiny_model)
    reps = [LocalReplica(make_engine(tiny_model))]     # colocated only
    router = DisaggRouter(reps)
    prompt = [5, 3, 8, 1]
    try:
        want = _greedy(colo_engine, prompt, 5)
        got = router.generate(prompt, max_new_tokens=5, timeout_s=60.0)
        assert got.state == "done" and got.tokens == want
        assert not got.migrated
        assert got.fallback_reason == "no_phase_fleet"
        assert router.fallbacks == 1 and router.migrated == 0
    finally:
        _stop_all(reps)


def test_disagg_router_mid_transfer_fault_degrades(tiny_model):
    """The decode replica's KV adoption dies mid-transfer: the request
    degrades to a full colocated re-dispatch with the exact colocated
    tokens — no loss, no duplicated tokens, no leaked prefill slot."""
    colo_engine = make_engine(tiny_model)
    pre = LocalReplica(make_engine(tiny_model, role="prefill"))
    dec = LocalReplica(make_engine(tiny_model, role="decode"))
    reps = [pre, dec]

    def broken_adopt(handoff):
        raise RuntimeError("injected mid-transfer fault")

    dec.engine.adopt_request_kv = broken_adopt
    router = DisaggRouter(reps)
    prompt = [9, 2, 6, 5, 3]
    try:
        want = _greedy(colo_engine, prompt, 6)
        got = router.generate(prompt, max_new_tokens=6, timeout_s=60.0)
        assert got.state == "done", got.error
        assert got.fallback_reason == "decode_failed"
        assert not got.migrated
        assert got.tokens == want, "fallback lost or duplicated tokens"
        assert len(got.tokens) == 6
        assert router.fallbacks == 1
        # the failed handoff freed the prefill-side slot (the export
        # releases it at the first-token boundary) and the decode side
        # adopted nothing
        time.sleep(0.1)
        assert pre.engine.cache.occupancy == 0.0
        assert dec.engine.cache.occupancy == 0.0
    finally:
        _stop_all(reps)


def test_shared_prefix_index_cross_replica_hit(tiny_model):
    """The pool-level prefix index: a system prompt prefilled on the
    prefill replica is published gang-wide; the next request's fetch
    hits it (per-phase counters move) and the tokens stay exact."""
    colo = make_engine(tiny_model)
    index = SharedPrefixIndex()
    reps = [LocalReplica(make_engine(tiny_model, role="prefill"),
                         prefix_index=index),
            LocalReplica(make_engine(tiny_model, role="decode"),
                         prefix_index=index)]
    router = DisaggRouter(reps, prefix_index=index)
    system_prompt = [7] * 10 + [3, 5]          # 12 tokens -> 1 full page
    try:
        want = _greedy(colo, system_prompt, 4)
        first = router.generate(system_prompt, max_new_tokens=4,
                                timeout_s=60.0)
        assert first.state == "done" and first.tokens == want
        assert index.published >= 1 and index.misses >= 1
        hits_before = index.hits
        second = router.generate(system_prompt, max_new_tokens=4,
                                 timeout_s=60.0)
        assert second.state == "done" and second.tokens == want, \
            "pool prefix adoption changed the greedy stream"
        assert index.hits > hits_before, \
            "second request missed the gang-shared prefix"
        assert router.fallbacks == 0
    finally:
        _stop_all(reps)


# ---------------------------------------------------------------------------
# tp=2 -> tp=1 redistribution
# ---------------------------------------------------------------------------

def test_tp2_to_tp1_handoff_page_exact_bounded_residency(tiny_model):
    """A tp=2 prefill replica hands off to a tp=1 decode replica: the
    wire carries one frame per mesh shard, the adopted pages are
    BIT-exact against the source's canonical pages, and the transient
    canonical footprint never exceeds the per-chunk budget (let alone
    both layouts at once) — arXiv:2112.01075's discipline."""
    src = make_engine(tiny_model, sharding="tp", tp=2, role="prefill")
    dst = make_engine(tiny_model, role="decode")
    prompt = list(range(2, 14))                # 12 tokens -> 2 pages
    slot, logits = src.start_sequence(prompt)
    n_pages = src.cache.pages_for(len(prompt))
    src_pages = [int(p) for p in src.cache.table_row(slot)[:n_pages]]
    k_src, v_src = src.cache.read_pages(src_pages)

    handoff = src.export_request_kv(slot, tokens=prompt)
    # per-shard wire frames: 2 shards per projection per chunk
    for ch in handoff["chunks"]:
        ks = [f for f in ch["shards"] if f["proj"] == "k"]
        assert sorted(f["shard"] for f in ks) == [0, 1]
        assert all(f["nshards"] == 2 for f in ch["shards"])
    exp = kvt.last_stats("export")
    assert exp.peak_bytes <= exp.budget_bytes < exp.full_cache_bytes

    dslot = dst.adopt_request_kv(handoff)
    adp = kvt.last_stats("adopt")
    assert adp.peak_bytes <= adp.budget_bytes < adp.full_cache_bytes, \
        (adp.peak_bytes, adp.budget_bytes, adp.full_cache_bytes)
    assert dst.cache.length(dslot) == len(prompt)
    dst_pages = [int(p)
                 for p in dst.cache.table_row(dslot)[:n_pages]]
    k_dst, v_dst = dst.cache.read_pages(dst_pages)
    assert np.array_equal(_f32(k_src), _f32(k_dst)), \
        "tp=2 -> tp=1 K pages not bit-exact after redistribution"
    assert np.array_equal(_f32(v_src), _f32(v_dst)), \
        "tp=2 -> tp=1 V pages not bit-exact after redistribution"
    # the adopted slot actually decodes
    out = dst.decode_step({dslot: int(np.argmax(logits))})
    assert int(np.argmax(out[dslot])) >= 0
    src.free_sequence(slot)
    dst.free_sequence(dslot)


# ---------------------------------------------------------------------------
# subprocess gang: mid-transfer replica kill (stub workers)
# ---------------------------------------------------------------------------

def test_gang_mid_transfer_kill_zero_loss_zero_duplication(tmp_path):
    """The decode replica dies WHILE the migrated request is in its
    hands (/resume): the gang counts a transfer_fault fallback, re-runs
    the request colocated on a surviving replica (exact deterministic
    stub tokens — zero loss), and the request id stays idempotent
    (zero duplication); the dead replica is recycled with cause=crash."""
    from paddle_tpu.serving.gang import GangConfig, ReplicaGang

    gang = ReplicaGang(
        {"stub": {}}, str(tmp_path / "midkill"),
        GangConfig(n_replicas=2, roles=("prefill", "decode"),
                   probe_interval_s=0.1, hang_deadline_s=2.0,
                   ready_timeout_s=30.0, restart_backoff_s=0.1,
                   default_timeout_s=20.0),
        per_replica={1: {"stub": {"die_on_resume": True}}})
    try:
        gang.start()
        assert gang.disaggregated
        prompt = [9, 9, 4]
        code, payload = gang.dispatch({
            "prompt": prompt, "max_new_tokens": 3,
            "request_id": "midkill-1"})
        assert code == 200, payload
        # the colocated retry's tokens are the stub's deterministic
        # prompt-derived stream — nothing lost, nothing made up
        assert payload["tokens"] == [(sum(prompt) * 31 + i * 7) % 97
                                     for i in range(3)]
        assert payload.get("disagg") is not True
        assert gang.disagg_fallbacks >= 1
        assert gang.disagg_requests == 0
        # idempotency: the same id replays the RECORDED response
        code2, replay = gang.dispatch({
            "prompt": prompt, "max_new_tokens": 3,
            "request_id": "midkill-1"})
        assert code2 == 200 and replay.get("deduplicated") is True
        assert replay["tokens"] == payload["tokens"]
        # the supervisor recycles the killed decode replica
        deadline = time.time() + 15
        while time.time() < deadline:
            h = gang.health()
            if h["restarts"].get("crash", 0) >= 1 and h["ready"] == 2:
                break
            time.sleep(0.1)
        h = gang.health()
        assert h["restarts"].get("crash", 0) >= 1, h
    finally:
        gang.stop()
