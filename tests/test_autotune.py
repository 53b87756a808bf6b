"""Measurement-driven autotuner (ISSUE 20, docs/autotune.md): knob-space
enumeration + validity predicates, the static roofline pruner against
hand-computed numbers, the successive-halving driver's probe accounting,
SIGKILL-resume through the probe log, and the TUNED.json round trip
through every applier lane."""
import json
import math
import os
import signal
import subprocess
import sys
import textwrap
import warnings
from pathlib import Path

import numpy as np
import pytest

import jax

from paddle_tpu.parallel.comm_opt import wire_bytes
from paddle_tpu.tuning import (
    BaseStats, Candidate, HwModel, ProbeLog, SpaceContext,
    TrainProbeGeometry, ServeProbeGeometry, driver, enumerate_space,
    predict_serve, predict_train, run_serve_probe, run_train_probe,
    serve_axes, serve_incumbent, train_axes, train_incumbent, tune, tuned,
    validate_serve, validate_train)
from paddle_tpu.tuning import probe as probe_mod
from paddle_tpu.tuning.static_cost import (
    INTERPRET_PENALTY, REMAT_ACT_FACTOR, REMAT_FLOP_FACTOR)

REPO = Path(__file__).resolve().parents[1]

CPU1 = SpaceContext(dp=1, n_devices=1, platform="cpu", vocab_size=256,
                    max_seq=64, max_batch=8, page_size=8, on_acc=False)
CPU_DP2 = SpaceContext(dp=2, n_devices=2, platform="cpu", vocab_size=256,
                       max_seq=64, max_batch=8, page_size=8, on_acc=False)


def _counter_total(name, label_value=None):
    from paddle_tpu.observability import metrics as om

    fam = om.default_registry().snapshot().get(name, {})
    total = 0.0
    for row in fam.get("series", []):
        if label_value is not None and label_value not in tuple(
                row.get("labels", ())):
            continue
        total += row["value"]
    return total


# ---------------------------------------------------------------------------
# Candidate identity
# ---------------------------------------------------------------------------

def test_candidate_key_canonical():
    a = Candidate.make("train", remat="dots", fused_ln=True, bucket_mb=8.0)
    b = Candidate.make("train", bucket_mb=8.0, fused_ln=True, remat="dots")
    assert a == b and a.key == b.key
    # bools format as 1/0, tuples join with "/" — stable across runs
    assert "fused_ln=1" in a.key
    c = Candidate.make("serve", buckets=(8, 16))
    assert "buckets=8/16" in c.key
    assert c.as_dict()["buckets"] == [8, 16]
    d = a.replace(remat="full")
    assert d.get("remat") == "full" and d.get("bucket_mb") == 8.0
    assert d.key != a.key


# ---------------------------------------------------------------------------
# enumeration + validity predicates
# ---------------------------------------------------------------------------

def test_train_space_dp1_refuses_comm_levers():
    valid, refused = enumerate_space("train", train_axes(CPU1), CPU1)
    assert valid and refused
    reasons = {r for _, r in refused}
    assert "invalid:reduce_scatter_needs_dp" in reasons
    assert "invalid:quantized_comm_needs_dp" in reasons
    # a dp=1 lane has NO valid comm-lever candidates at all
    for c in valid:
        assert c.get("grad_reduce") == "psum"
        assert c.get("comm_dtype") == "f32"
        # psum configs have the bucket cap pinned (normalize) — no
        # phantom bucket-only distinctions
        assert c.get("bucket_mb") == 32.0


def test_train_space_dp2_predicates():
    valid, refused = enumerate_space("train", train_axes(CPU_DP2), CPU_DP2)
    reasons = {r for _, r in refused}
    assert "invalid:fused_opt_multidev_psum" in reasons
    for c, r in refused:
        if r == "invalid:fused_opt_multidev_psum":
            assert c.get("fused_opt") and c.get("grad_reduce") == "psum"
    # int8 wire dtype pairs with error feedback, forced by normalize
    int8 = [c for c in valid if c.get("comm_dtype") == "int8"]
    assert int8 and all(c.get("error_feedback") for c in int8)
    assert all(c.get("grad_reduce") == "reduce_scatter" or
               not c.get("fused_opt") for c in valid)


def test_train_space_vchunk_ge_vocab_refused():
    axes = train_axes(CPU1, vchunks=(0, 64, 256, 300))
    valid, refused = enumerate_space("train", axes, CPU1)
    bad = [c for c, r in refused if r == "invalid:vchunk_ge_vocab"]
    assert bad and all(c.get("ce_vocab_chunk") >= 256 for c in bad)
    assert all(c.get("ce_vocab_chunk") < 256 for c in valid)


def test_serve_space_predicates():
    ctx = SpaceContext(dp=1, n_devices=8, platform="cpu", vocab_size=256,
                       max_seq=64, max_batch=8, page_size=8)
    valid, refused = enumerate_space("serve", serve_axes(ctx), ctx)
    reasons = {r for _, r in refused}
    assert "invalid:int8_tp_headshard" in reasons
    assert "invalid:spec_plus_fused_decode" in reasons
    assert "invalid:disagg_spec_unsupported" in reasons
    assert "invalid:disagg_tp_unsupported" in reasons
    for c in valid:
        assert not (c.get("weight_dtype") == "int8" and
                    c.get("sharding") == "tp")
        assert not (c.get("spec", 0) and c.get("fused_decode"))
        # normalize: a colocated lane has no decode-batch multiplier
        if c.get("disagg", "off") == "off":
            assert c.get("disagg_decode_batch") == 1


def test_serve_space_has_one_layout_and_committed_tuned_still_loads():
    """The serve space's axes are these nine and no layout among them;
    the committed TUNED.json, written when there was one, still loads,
    and what the appliers take from it builds an engine (a knob the
    code no longer has is not read)."""
    import dataclasses

    from paddle_tpu import serving
    from paddle_tpu.models import gpt as G

    ctx = SpaceContext(dp=1, n_devices=8, platform="cpu", vocab_size=256,
                       max_seq=64, max_batch=8, page_size=8)
    assert set(serve_axes(ctx)) == {
        "buckets", "max_batch", "num_pages", "fused_decode", "spec",
        "weight_dtype", "sharding", "disagg", "disagg_decode_batch"}
    inc = serve_incumbent(ctx)
    assert set(inc.as_dict()) == set(serve_axes(ctx)) | {"tp"}
    assert validate_serve(inc.as_dict(), ctx) is None
    _, refused = enumerate_space("serve", serve_axes(ctx), ctx)
    assert "invalid:disagg_needs_paged" not in {r for _, r in refused}

    doc = tuned.load(os.path.join(REPO, "TUNED.json"))
    ek = tuned.engine_kwargs(doc, page_size=8)
    geometry = {"prefill_buckets", "max_batch", "page_size", "num_pages",
                "fused_decode", "weight_dtype", "sharding", "tp"}
    assert ek and set(ek) <= geometry
    assert geometry <= {f.name
                        for f in dataclasses.fields(serving.EngineConfig)}
    assert set(tuned.serve_lane_kwargs(doc)) == {
        "spec", "disagg", "disagg_decode_batch"}
    cfg = G.GPT_TINY.scaled(num_layers=1, max_seq_len=64)
    eng = serving.DecodeEngine(
        G.init_params(jax.random.PRNGKey(0), cfg), cfg,
        serving.EngineConfig(max_seq=64, **ek))
    assert eng.buckets == tuple(ek["prefill_buckets"])


def test_serve_disagg_ratio_bounds():
    ctx = SpaceContext(n_devices=8, max_seq=64, page_size=8)
    base = dict(buckets=(16, 32), max_batch=4, num_pages=0,
                fused_decode=False, spec=0,
                weight_dtype="f32", sharding="none",
                disagg_decode_batch=1)
    assert validate_serve(dict(base, disagg="1:2"), ctx) is None
    for bad in ("0:1", "1:0", "2:3", "junk:x"):
        assert validate_serve(dict(base, disagg=bad), ctx) \
            == "invalid:disagg_ratio_bounds", bad


def test_serve_paged_geometry_predicates():
    ctx = SpaceContext(n_devices=1, max_seq=64, max_batch=8, page_size=8)
    base = dict(max_batch=8, num_pages=0, fused_decode=False, spec=0,
                weight_dtype="f32",
                sharding="none", disagg="off", disagg_decode_batch=1)
    assert validate_serve(dict(base, buckets=(12, 32)), ctx) \
        == "invalid:bucket_page_align"
    # pool must cover max_batch sequences at the smallest bucket:
    # 8 seqs * (16 // 8) pages = 16 pages minimum
    assert validate_serve(dict(base, buckets=(16, 32), num_pages=8),
                          ctx) == "invalid:page_pool_too_small"
    assert validate_serve(dict(base, buckets=(16, 32), num_pages=16),
                          ctx) is None
    assert validate_serve(dict(base, buckets=(16, 128)), ctx) \
        == "invalid:bucket_gt_max_seq"


def test_tp_needs_devices():
    ctx = SpaceContext(n_devices=1, max_seq=64, page_size=8)
    knobs = dict(buckets=(16,), max_batch=4, num_pages=0,
                 fused_decode=False, spec=0,
                 weight_dtype="f32", sharding="tp", tp=2, disagg="off",
                 disagg_decode_batch=1)
    assert validate_serve(knobs, ctx) == "invalid:tp_needs_devices"


def test_incumbents_are_valid_members():
    for ctx in (CPU1, CPU_DP2):
        inc = train_incumbent(ctx)
        assert validate_train(dict(inc.knobs), ctx) is None
        valid, _ = enumerate_space("train", train_axes(ctx), ctx)
        assert inc.key in {c.key for c in valid}
    ctx = SpaceContext(n_devices=8, max_seq=64, max_batch=8, page_size=8)
    sinc = serve_incumbent(ctx)
    assert validate_serve(dict(sinc.knobs), ctx) is None
    svalid, _ = enumerate_space("serve", serve_axes(ctx), ctx)
    assert sinc.key in {c.key for c in svalid}


# ---------------------------------------------------------------------------
# static cost model vs hand-computed rooflines
# ---------------------------------------------------------------------------

def _train_inc():
    return Candidate.make("train", remat="none", grad_reduce="psum",
                          comm_dtype="f32", bucket_mb=32.0,
                          fused_opt=False, fused_ln=False,
                          ce_vocab_chunk=0, error_feedback=False)


def _train_base(inc):
    return BaseStats(flops=1e9, bytes_accessed=4e8, peak_hbm_bytes=1e9,
                     param_bytes=4e6, tokens_per_step=128, vocab_size=256,
                     incumbent=inc)


def test_static_train_roofline_hand_math():
    inc = _train_inc()
    base = _train_base(inc)
    hw = HwModel(peak_flops=1e12, peak_hbm_bps=1e11, ici_bps=1e10,
                 on_acc=True)
    # incumbent: flops leg 1e9/1e12*1e3 = 1.0 ms, bytes leg
    # 4e8/1e11*1e3 = 4.0 ms -> bytes-bound at 4.0 ms
    est = predict_train(inc, base, hw)
    assert est.ms == pytest.approx(4.0) and est.bound == "bytes"
    assert est.peak_hbm_bytes == pytest.approx(1e9)
    assert not est.over_hbm          # no capacity -> rule off

    # remat=full: flops *= 1.33 (leg 1.33 ms) — still bytes-bound;
    # activation share halves the peak: 1e9*(0.5 + 0.5*0.12) = 5.6e8
    full = inc.replace(remat="full")
    est = predict_train(full, base, hw)
    assert est.detail["flops"] == pytest.approx(1e9 * 1.33)
    assert est.ms == pytest.approx(4.0)
    assert est.peak_hbm_bytes == pytest.approx(
        1e9 * (0.5 + 0.5 * REMAT_ACT_FACTOR["full"]))

    # fused_opt + fused_ln: bytes *= 0.97^2 -> 3.7636 ms (on-acc: no
    # interpret penalty)
    fused = inc.replace(fused_opt=True, fused_ln=True)
    est = predict_train(fused, base, hw)
    assert est.ms == pytest.approx(4.0 * 0.97 * 0.97)

    # off-acc the Pallas fused_ln runs interpreted: 6x penalty
    hw_cpu = HwModel(peak_flops=1e12, peak_hbm_bps=1e11, on_acc=False)
    est = predict_train(inc.replace(fused_ln=True), base, hw_cpu)
    assert est.ms == pytest.approx(4.0 * 0.97 * INTERPRET_PENALTY)


def test_static_train_wire_term_hand_math():
    inc = _train_inc()
    base = _train_base(inc)
    hw = HwModel(peak_flops=1e12, peak_hbm_bps=1e11, ici_bps=1e10,
                 on_acc=True)
    # psum at dp=2, f32 payload 4e6: ring all-reduce moves
    # 2*(2-1)/2 * 4e6 = 4e6 B -> 0.4 ms on a 1e10 B/s link
    est = predict_train(inc, base, hw, dp=2)
    assert est.detail["wire_bytes"] == wire_bytes("psum", 4_000_000, 2) \
        == 4_000_000
    assert est.ms == pytest.approx(4.0 + 0.4)

    # reduce_scatter at bf16 halves the payload (2e6): RS leg 1e6 + AG
    # leg 1e6 = 2e6 B -> 0.2 ms; the flat bucket double-buffer adds
    # bucket_mb * 2^20 * 2 to the peak
    rs = inc.replace(grad_reduce="reduce_scatter", comm_dtype="bf16",
                     bucket_mb=8.0)
    est = predict_train(rs, base, hw, dp=2)
    assert est.detail["wire_bytes"] == 2_000_000
    assert est.ms == pytest.approx(4.0 + 0.2)
    assert est.peak_hbm_bytes == pytest.approx(
        1e9 + 8.0 * (1 << 20) * 2)

    # dp=1: no gradient reduction, no wire term
    est = predict_train(inc, base, hw, dp=1)
    assert est.detail["wire_bytes"] == 0 and est.detail["wire_ms"] == 0.0


def test_static_train_vchunk_and_hbm_budget():
    inc = _train_inc()
    base = _train_base(inc)
    # vocab-chunked CE drops the [tokens, V] f32 logits residency:
    # 128*256*4 = 131072 B scaled by (1 - 64/256)
    vc = inc.replace(ce_vocab_chunk=64)
    est = predict_train(vc, base, HwModel(1e12, 1e11, on_acc=True))
    assert est.peak_hbm_bytes == pytest.approx(
        1e9 - 131072 * (1.0 - 64 / 256))

    # budget rule: incumbent peak 1e9 > 0.95 * 1e9 cap -> over; the
    # remat=full candidate (5.6e8) fits the same cap
    hw_cap = HwModel(1e12, 1e11, hbm_capacity_bytes=1e9, on_acc=True)
    assert predict_train(inc, base, hw_cap).over_hbm
    assert not predict_train(inc.replace(remat="full"), base,
                             hw_cap).over_hbm


def test_static_serve_hand_math():
    inc = Candidate.make("serve", buckets=(16, 32), max_batch=8,
                         num_pages=0, fused_decode=False,
                         spec=0, weight_dtype="f32", sharding="none",
                         disagg="off", disagg_decode_batch=1, tp=1)
    base = BaseStats(flops=1e9, bytes_accessed=8e8, peak_hbm_bytes=2e9,
                     incumbent=inc)
    hw = HwModel(peak_flops=1e12, peak_hbm_bps=1e11, on_acc=True)
    # incumbent: bytes leg 8e8/1e11*1e3 = 8.0 ms (flops leg 1.0)
    assert predict_serve(inc, base, hw).ms == pytest.approx(8.0)
    # int8 weights: bytes *= 0.4 -> 3.2 ms
    assert predict_serve(inc.replace(weight_dtype="int8"), base, hw).ms \
        == pytest.approx(8.0 * 0.4)
    # doubling the static batch halves per-token bytes; peak scales up
    est = predict_serve(inc.replace(max_batch=16), base, hw)
    assert est.ms == pytest.approx(4.0)
    assert est.peak_hbm_bytes == pytest.approx(4e9)
    # spec window k=3: optimistic acceptance bound /(1 + 0.5*3)
    assert predict_serve(inc.replace(spec=3), base, hw).ms \
        == pytest.approx(8.0 / 2.5)
    # disagg 1:2 with decode-batch x2: ms * (1+2)/max(2*2,1)
    dis = inc.replace(disagg="1:2", disagg_decode_batch=2)
    assert predict_serve(dis, base, hw).ms == pytest.approx(8.0 * 3 / 4)
    # page pool counts against the budget: 100 pages * 1e6 B on a 2e9
    # cap -> 2.1e9 > 1.9e9
    pool = inc.replace(num_pages=100)
    est = predict_serve(pool, base,
                        HwModel(1e12, 1e11, hbm_capacity_bytes=2e9,
                                on_acc=True), kv_page_bytes=1e6)
    assert est.peak_hbm_bytes == pytest.approx(2.1e9)
    assert est.over_hbm
    # off-acc fused_decode runs interpreted
    hw_cpu = HwModel(1e12, 1e11, on_acc=False)
    assert predict_serve(inc.replace(fused_decode=True), base, hw_cpu).ms \
        == pytest.approx(8.0 * INTERPRET_PENALTY)


# ---------------------------------------------------------------------------
# successive-halving driver
# ---------------------------------------------------------------------------

def _scripted(scores):
    calls = []

    def probe_fn(cand, steps, rung):
        calls.append((cand.get("name"), rung, steps))
        return {"score": scores[cand.get("name")]}
    return probe_fn, calls


def test_halving_schedule_and_probe_accounting():
    inc = Candidate.make("train", name="inc")
    pool = [Candidate.make("train", name=n) for n in "abcd"]
    scores = {"inc": 10.0, "a": 5.0, "b": 6.0, "c": 20.0, "d": 30.0}
    probe_fn, calls = _scripted(scores)
    res = tune(space="train", candidates=[inc] + pool, incumbent=inc,
               probe_fn=probe_fn, rungs=((1, 0.5), (2, 1.0)))
    # rung 0: incumbent anchor + 4 pool = 5 probes; keep ceil(4*0.5)=2;
    # rung 1: incumbent re-probe + 2 survivors = 3 -> 8 total
    assert res.probes_executed == len(calls) == 8
    assert [c[:2] for c in calls].count(("inc", 0)) == 1   # not re-probed
    assert ("inc", 1, 2) in calls
    r1 = {c[0] for c in calls if c[1] == 1}
    assert r1 == {"inc", "a", "b"}
    assert res.pruned == {"measured_worse": 2}
    assert res.improved and res.winner.get("name") == "a"
    # 5.0 < 10.0 * (1 - 0.03): beats the margin
    assert res.winner_result["score"] == 5.0
    # every probed candidate has probe ids, one per rung it reached
    assert len(res.probe_ids[inc.key]) == 2
    assert len(res.probe_ids[pool[2].key]) == 1


def test_winner_must_beat_margin_else_incumbent_stays():
    inc = Candidate.make("train", name="inc")
    a = Candidate.make("train", name="a")
    probe_fn, _ = _scripted({"inc": 10.0, "a": 9.9})   # <3% better
    res = tune(space="train", candidates=[inc, a], incumbent=inc,
               probe_fn=probe_fn, rungs=((2, 1.0),))
    assert not res.improved and res.winner.key == inc.key


def test_refusals_and_static_pruning_counted():
    inc = Candidate.make("train", name="inc")
    worse = Candidate.make("train", name="worse")
    heavy = Candidate.make("train", name="heavy")
    ok = Candidate.make("train", name="ok")
    bad = Candidate.make("train", name="bad")
    ests = {
        "inc": (1.0, False), "worse": (1.3, False),    # > 1.2x: pruned
        "heavy": (0.5, True),                          # over budget
        "ok": (1.1, False),                            # survives
    }

    def static_fn(cand, inc_result):
        ms, over = ests[cand.get("name")]
        from paddle_tpu.tuning.static_cost import StaticEstimate
        return StaticEstimate(ms=ms, peak_hbm_bytes=0.0, over_hbm=over,
                              bound="flops", detail={})
    probe_fn, calls = _scripted({"inc": 10.0, "ok": 8.0})
    res = tune(space="train", candidates=[inc, worse, heavy, ok],
               refusals=[(bad, "invalid:example")], incumbent=inc,
               probe_fn=probe_fn, static_fn=static_fn,
               rungs=((2, 1.0),), static_margin=0.20)
    assert res.pruned == {"invalid:example": 1, "static_worse": 1,
                          "over_hbm": 1}
    # only the incumbent and the static survivor were ever measured
    assert {c[0] for c in calls} == {"inc", "ok"}
    assert res.improved and res.winner.get("name") == "ok"
    assert set(res.static) == {inc.key, worse.key, heavy.key, ok.key}


def test_crashing_candidate_loses_not_the_tune():
    inc = Candidate.make("train", name="inc")
    bad = Candidate.make("train", name="bad")

    def probe_fn(cand, steps, rung):
        if cand.get("name") == "bad":
            raise MemoryError("RESOURCE_EXHAUSTED: out of memory")
        return {"score": 10.0}
    res = tune(space="train", candidates=[inc, bad], incumbent=inc,
               probe_fn=probe_fn, rungs=((2, 1.0),))
    assert res.winner.key == inc.key
    assert res.pruned == {"measured_worse": 1}
    assert "MemoryError" in res.results[bad.key]["error"]
    assert math.isinf(driver._score(res.results[bad.key]))


def test_seeded_bad_knob_rejected_by_measured_phase():
    """The acceptance-criteria seed: a statically-plausible huge comm
    bucket must be killed by its PROBE, not survive to the winner."""
    ctx = CPU_DP2
    inc = train_incumbent(ctx)
    bad = inc.replace(grad_reduce="reduce_scatter", bucket_mb=4096.0)
    good = inc.replace(remat="dots")
    assert validate_train(dict(bad.knobs), ctx) is None   # enumerable

    def probe_fn(cand, steps, rung):
        if cand.get("bucket_mb") == 4096.0:
            # what the real probe does: the 8 GiB double-buffered flat
            # bucket allocation dies -> driver scores it inf
            raise MemoryError("flat bucket allocation failed")
        return {"score": 10.0 if cand.key == inc.key else 9.0}
    res = tune(space="train", candidates=[inc, bad, good], incumbent=inc,
               probe_fn=probe_fn, rungs=((2, 1.0),))
    assert res.winner.key == good.key
    assert res.pruned.get("measured_worse") == 1
    assert math.isinf(driver._score(res.results[bad.key]))


def test_probe_counters_and_cached_resume(tmp_path):
    inc = Candidate.make("train", name="inc")
    a = Candidate.make("train", name="a")
    path = str(tmp_path / "probes.jsonl")
    probe_fn, _ = _scripted({"inc": 10.0, "a": 5.0})
    before = _counter_total("paddle_autotune_probes_total", "ctrtest")
    log = ProbeLog(path)
    res = tune(space="train", candidates=[inc, a], incumbent=inc,
               probe_fn=probe_fn, rungs=((2, 1.0),), log=log,
               phase="ctrtest")
    log.close()
    assert res.probes_executed == 2
    assert _counter_total("paddle_autotune_probes_total",
                          "ctrtest") - before == 2
    # resume over the same log: every probe replays from cache — no
    # execution, no counter motion, same winner
    probe_fn2, calls2 = _scripted({"inc": 0.0, "a": 0.0})   # unused
    log2 = ProbeLog(path)
    res2 = tune(space="train", candidates=[inc, a], incumbent=inc,
                probe_fn=probe_fn2, rungs=((2, 1.0),), log=log2,
                phase="ctrtest")
    log2.close()
    assert res2.probes_executed == 0 and not calls2
    assert _counter_total("paddle_autotune_probes_total",
                          "ctrtest") - before == 2
    assert res2.winner.key == res.winner.key
    assert res2.results[a.key]["score"] == 5.0


_KILL_SCRIPT = textwrap.dedent("""\
    import json, os, signal, sys
    sys.path.insert(0, {repo!r})
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from paddle_tpu.tuning import driver
    from paddle_tpu.tuning.space import Candidate

    SCORES = {{"inc": 10.0, "a": 5.0, "b": 6.0, "c": 7.0}}
    inc = Candidate.make("train", name="inc")
    pool = [Candidate.make("train", name=n) for n in "abc"]
    kill_after = int(os.environ.get("KILL_AFTER", "0"))
    executed = [0]

    def probe_fn(cand, steps, rung):
        executed[0] += 1
        if kill_after and executed[0] > kill_after:
            os.kill(os.getpid(), signal.SIGKILL)   # mid-probe, un-catchable
        return {{"score": SCORES[cand.get("name")]}}

    log = driver.ProbeLog(sys.argv[1])
    res = driver.tune(space="train", candidates=[inc] + pool,
                      incumbent=inc, probe_fn=probe_fn,
                      rungs=((1, 0.5), (2, 1.0)), log=log)
    log.close()
    print(json.dumps({{"executed": res.probes_executed,
                       "completed": log.completed_probes,
                       "winner": res.winner.key,
                       "pruned": res.pruned}}))
""")


def test_sigkill_mid_tune_resumes_from_probe_log(tmp_path):
    """SIGKILL mid-tune, then resume: completed probes replay from the
    JSONL without re-running, the total probe count is conserved, and
    the winner matches an uninterrupted run."""
    script = tmp_path / "tune_once.py"
    script.write_text(_KILL_SCRIPT.format(repo=str(REPO)))
    log_path = tmp_path / "probes.jsonl"
    env = dict(os.environ, JAX_PLATFORMS="cpu")

    # clean reference run (its own log): rung0 inc+3, keep ceil(3/2)=2,
    # rung1 inc+2 -> 7 probes, winner "a"
    ref = subprocess.run(
        [sys.executable, str(script), str(tmp_path / "ref.jsonl")],
        env=env, capture_output=True, text=True, timeout=120)
    assert ref.returncode == 0, ref.stderr
    clean = json.loads(ref.stdout.strip().splitlines()[-1])
    assert clean["executed"] == clean["completed"] == 7

    # killed run: dies un-catchably inside probe #4
    killed = subprocess.run(
        [sys.executable, str(script), str(log_path)],
        env=dict(env, KILL_AFTER="3"), capture_output=True, text=True,
        timeout=120)
    assert killed.returncode == -signal.SIGKILL
    lines = [json.loads(l) for l in log_path.read_text().splitlines()]
    assert len(lines) == 3 and all(l["executed"] for l in lines)

    # a torn tail line (the write the kill interrupted) must be skipped
    with open(log_path, "a") as f:
        f.write('{"kind": "probe", "space": "train", "ru')

    resumed = subprocess.run(
        [sys.executable, str(script), str(log_path)],
        env=env, capture_output=True, text=True, timeout=120)
    assert resumed.returncode == 0, resumed.stderr
    out = json.loads(resumed.stdout.strip().splitlines()[-1])
    # conservation: 3 (before the kill) + 4 (after) == the clean 7
    assert out["executed"] == 4
    assert out["completed"] == clean["completed"] == 7
    assert out["winner"] == clean["winner"]
    assert out["pruned"] == clean["pruned"]


# ---------------------------------------------------------------------------
# probe harness on real (micro) workloads
# ---------------------------------------------------------------------------

MICRO = TrainProbeGeometry(d_model=16, num_layers=1, num_heads=2,
                           d_ff=32, T=8, vocab_size=32, batch=2)


def test_run_train_probe_smoke(tmp_path):
    inc = train_incumbent(CPU1)
    res = run_train_probe(inc, MICRO, steps=2, warmup=1)
    assert res["score"] > 0 and math.isfinite(res["score"])
    assert res["steps"] == 2 and math.isfinite(res["loss"])
    # the AOT report anchors the static model — it must be present
    assert res["report"]["flops"] and res["report"]["bytes_accessed"]
    assert res["report"]["peak_hbm_bytes"]

    # monitored discipline: one JSONL record per timed step, candidate
    # key stamped as the config
    mon = tmp_path / "probe_monitor.jsonl"
    res = run_train_probe(inc.replace(remat="full"), MICRO, steps=2,
                          monitor=str(mon))
    rows = [json.loads(l) for l in mon.read_text().splitlines()
            if l.strip()]
    steps_rows = [r for r in rows if r.get("loss") is not None]
    assert len(steps_rows) == 2
    assert any(r.get("config", "").startswith("train:") for r in rows)


def test_run_serve_probe_smoke():
    ctx = SpaceContext(n_devices=jax.device_count(), vocab_size=64,
                       max_seq=32, max_batch=2, page_size=8)
    geom = ServeProbeGeometry(d_model=16, num_layers=1, num_heads=2,
                              d_ff=32, vocab_size=64, max_seq=32,
                              page_size=8, max_new_tokens=4,
                              prompt_len_max=6)
    res = run_serve_probe(serve_incumbent(ctx), geom, n_requests=2)
    assert res["failed"] == 0 and res["requests"] == 2
    assert res["score"] > 0 and math.isfinite(res["score"])
    assert res["ms_per_token"] == pytest.approx(res["score"], abs=1e-3)
    assert res["steady_state_recompiles"] == 0
    assert res["slo"]["ok"]


def test_timed_loop_disciplines():
    seen = []

    def step_fn(i):
        seen.append(i)
        return i
    t = probe_mod.timed_loop(step_fn, 3, warmup=2)
    # compile call + 2 warmup + 3 timed, indices threaded through
    assert seen == [0, 1, 2, 3, 4, 5]
    assert len(t.step_times_s) == 3 and t.steps == 3
    assert t.ms_per_step >= 0 and t.compile_s >= 0
    hooked = []
    t = probe_mod.timed_loop(step_fn, 2, per_step_sync=False,
                             after_compile=lambda: hooked.append(True))
    assert hooked == [True]
    assert t.step_times_s == [] and t.block_s > 0
    assert t.values[0] == 0 and len(t.values) == 3


# ---------------------------------------------------------------------------
# TUNED.json round trip
# ---------------------------------------------------------------------------

def _scripted_tunes():
    t_inc = train_incumbent(CPU_DP2)
    t_win = t_inc.replace(remat="dots", grad_reduce="reduce_scatter",
                          comm_dtype="bf16", bucket_mb=8.0,
                          fused_opt=True, fused_ln=True,
                          ce_vocab_chunk=64)
    scores = {t_inc.key: 10.0, t_win.key: 8.0}
    tr = tune(space="train", candidates=[t_inc, t_win], incumbent=t_inc,
              probe_fn=lambda c, s, r: {"score": scores[c.key]},
              rungs=((2, 1.0),))
    s_ctx = SpaceContext(n_devices=jax.device_count(), max_seq=32,
                         max_batch=4, page_size=8, vocab_size=64)
    s_inc = serve_incumbent(s_ctx)
    s_win = Candidate.make("serve", buckets=(8, 16), max_batch=4,
                           num_pages=16,
                           fused_decode=False, spec=2, weight_dtype="int8",
                           sharding="none", tp=1, disagg="off",
                           disagg_decode_batch=1, error_feedback=False)
    sscores = {s_inc.key: 4.0, s_win.key: 2.0}
    sr = tune(space="serve", candidates=[s_inc, s_win], incumbent=s_inc,
              probe_fn=lambda c, s, r: {"score": sscores[c.key]},
              rungs=((2, 1.0),))
    return tr, sr


def test_tuned_doc_roundtrip_and_fingerprint_gate(tmp_path):
    tr, sr = _scripted_tunes()
    doc = tuned.build_doc({"train": tr, "serve": sr},
                          hw=probe_mod.hw_fingerprint(), args="--test")
    path = str(tmp_path / "TUNED.json")
    tuned.save(path, doc)
    loaded = tuned.load(path)
    assert loaded["version"] == tuned.SCHEMA_VERSION
    assert loaded["spaces"]["train"]["improved"]
    assert loaded["spaces"]["train"]["config"]["remat"] == "dots"
    assert loaded["spaces"]["train"]["score"] == {"winner_ms": 8.0,
                                                 "incumbent_ms": 10.0}
    # per-knob provenance: value + measured delta + probe ids
    prov = loaded["spaces"]["train"]["provenance"]
    assert prov["grad_reduce"]["value"] == "reduce_scatter"
    assert prov["grad_reduce"]["delta_vs_incumbent_ms"] == -2.0
    assert prov["grad_reduce"]["probe_ids"]

    # live fingerprint matches -> doc applies
    assert tuned.load_for_device(path) is not None
    # a doc tuned on other hardware warns + falls back to defaults
    alien = dict(loaded, hw={"platform": "tpu", "device_kind": "TPU v4",
                             "n_devices": 4, "degraded": False})
    with pytest.warns(RuntimeWarning, match="fingerprint mismatch"):
        assert tuned.load_for_device(alien) is None
    # schema-version drift is refused, not half-applied
    bad = str(tmp_path / "BAD.json")
    with open(bad, "w") as f:
        json.dump(dict(loaded, version=99), f)
    with pytest.warns(RuntimeWarning, match="unusable"):
        assert tuned.load_for_device(bad) is None

    # attribution stamp: full knob vector per space + content hash
    stamp = tuned.config_stamp(loaded, path)
    assert stamp["train"]["comm_dtype"] == "bf16"
    assert stamp["serve"]["weight_dtype"] == "int8"
    assert stamp["tuned_from"] == {"path": path,
                                   "sha256": tuned.file_hash(path)}


def test_tuned_appliers_respect_caller_and_mesh(tmp_path):
    tr, sr = _scripted_tunes()
    doc = tuned.build_doc({"train": tr, "serve": sr},
                          hw=probe_mod.hw_fingerprint())

    ck = tuned.train_cfg_kwargs(doc)
    assert ck == {"remat": True, "remat_policy": "dots", "fused_ln": True,
                  "ce_vocab_chunk": 64, "ce_direct_bytes_limit": 0}

    defaults = dict(tuned.TRAIN_STEP_DEFAULTS)

    class _P:
        def __init__(self, dp, n):
            self.dp, self.n_devices = dp, n
    # dp=1 mesh: the rs/bf16 levers are meaningless there — skipped with
    # a warning, not crashed on
    with pytest.warns(RuntimeWarning):
        kw = tuned.resolve_train_step_kwargs(doc, _P(1, 1), defaults)
    assert kw["grad_reduce"] == "psum"
    assert kw["grad_allreduce_dtype"] is None
    # dp=2: the whole winner applies (rs unlocks bucket + fused_opt)
    kw = tuned.resolve_train_step_kwargs(doc, _P(2, 2), defaults)
    assert kw == {"grad_reduce": "reduce_scatter",
                  "grad_allreduce_dtype": "bf16", "bucket_mb": 8.0,
                  "error_feedback": False, "fused_opt": True}
    # explicit caller choices always beat the tuner
    mine = dict(defaults, grad_reduce="reduce_scatter", bucket_mb=0.05)
    kw = tuned.resolve_train_step_kwargs(doc, _P(2, 2), mine)
    assert kw["bucket_mb"] == 0.05 and kw["grad_reduce"] == "reduce_scatter"

    ek = tuned.engine_kwargs(doc, page_size=8)
    assert ek == {"prefill_buckets": (8, 16), "max_batch": 4,
                  "page_size": 8, "num_pages": 16,
                  "weight_dtype": "int8"}
    assert tuned.serve_lane_kwargs(doc) == {"spec": 2, "disagg": "off",
                                            "disagg_decode_batch": 1}


def test_make_train_step_accepts_tuned(tmp_path):
    """The parallelize lane end-to-end: a TUNED.json whose winner flips
    the gradient path to quantized reduce-scatter must build and run a
    real dp=2 step — same artifact into init_sharded and the step."""
    from paddle_tpu.models import gpt as G
    from paddle_tpu.parallel import parallelize as PZ

    tr, sr = _scripted_tunes()
    doc = tuned.build_doc({"train": tr, "serve": sr},
                          hw=probe_mod.hw_fingerprint())
    path = str(tmp_path / "TUNED.json")
    tuned.save(path, doc)

    cfg = G.GPT_TINY.scaled(d_model=16, num_layers=1, num_heads=2,
                            d_ff=32, max_seq_len=8, vocab_size=32,
                            **tuned.train_cfg_kwargs(doc))
    assert cfg.remat and cfg.remat_policy == "dots"
    pcfg = PZ.ParallelConfig(dp=2, pp=1, tp=1, microbatches=1)
    mesh = PZ.build_mesh(pcfg)
    with warnings.catch_warnings():
        warnings.simplefilter("error", RuntimeWarning)   # no skip-warns
        params, opt = PZ.init_sharded(jax.random.PRNGKey(0), cfg, pcfg,
                                      mesh, tuned=path)
        step = PZ.make_train_step(cfg, pcfg, mesh, lr=1e-3, tuned=path)
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, 32, (1, 4, 8), dtype=np.int32)
    labels = rng.integers(0, 32, (1, 4, 8), dtype=np.int32)
    params, opt, loss, gnorm = step(params, opt, tokens, labels)
    assert math.isfinite(float(loss)) and math.isfinite(float(gnorm))
