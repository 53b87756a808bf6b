"""ISSUE 26: the program's one tracer as the serving loop's and the train
step's own clock: compact records in a ring that counts what falls off,
open spans that are profiler annotations, one record a tick, the phases of
a prefill and of a tick as children, and the walk from a request to the
ticks it rode."""
import glob
import io
import json
import os
import subprocess
import sys
import time

import jax
import numpy as np
import pytest

from paddle_tpu import serving
from paddle_tpu.models import gpt
from paddle_tpu.observability import spans
from paddle_tpu.parallel import parallelize as PZ
from paddle_tpu.serving.server import EngineLoop

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture()
def tracer():
    tr = spans.default_tracer()
    tr.clear()
    yield tr
    spans.set_tracing_enabled(True)


@pytest.fixture(scope="module")
def paged_engine():
    cfg = gpt.GPT_TINY.scaled(num_layers=2, max_seq_len=64)
    params = gpt.init_params(jax.random.PRNGKey(0), cfg)
    eng = serving.DecodeEngine(params, cfg, serving.EngineConfig(
        max_batch=4, max_seq=32, prefill_buckets=(8, 16),
        page_size=8))
    eng.warmup()
    return eng


def _serve(engine, prompts, max_new=4):
    sched = serving.Scheduler(engine)
    reqs = [sched.submit(p, max_new_tokens=max_new) for p in prompts]
    for _ in range(64):
        sched.step()
        if all(r.finished.is_set() for r in reqs):
            break
    assert all(r.state == "done" for r in reqs)
    return sched, reqs


# ---------------------------------------------------------------------------
# the tracer
# ---------------------------------------------------------------------------

def test_ring_counts_what_falls_off():
    tr = spans.SpanTracer(ring=8)
    assert spans.default_tracer()._ring.maxlen >= 65536
    for i in range(8):
        tr.record("r", i, 1, trace=1)
    assert tr.dropped == 0 and len(tr.spans()) == 8
    for i in range(5):
        tr.record("r", 8 + i, 1, trace=1)
    assert tr.dropped == 5
    assert [s["start_ns"] for s in tr.spans()] == list(range(5, 13))


def test_records_are_compact_and_handed_out_as_the_documented_dict(tracer):
    with spans.span("outer", attrs={"k": 1}) as sp:
        sp.set_attr("late", 2)
        spans.record("timed_elsewhere", 10, 5)
    assert all(type(r) is tuple for r in tracer._ring)
    inner, outer = tracer.spans()
    assert set(outer) == {"name", "trace", "span", "parent", "start_ns",
                          "dur_ns", "tid", "thread", "attrs"}
    assert outer["attrs"] == {"k": 1, "late": 2}
    assert "attrs" not in inner and inner["parent"] == outer["span"]
    assert tracer.summary()["outer"]["count"] == 1
    assert [s["name"] for s in tracer.trace_spans(outer["trace"])] == [
        "timed_elsewhere", "outer"]


def test_a_span_of_another_trace_gives_the_thread_its_context_back(tracer):
    with spans.span("loop", trace=1234) as loop:
        with spans.span("theirs", trace=77, parent=5):
            assert spans.current_context()[0] == 77
        assert spans.current_context() == (1234, loop.span_id)
        with spans.span("child"):
            pass
    assert spans.current_context() is None
    by = {s["name"]: s for s in tracer.spans()}
    assert (by["theirs"]["trace"], by["theirs"]["parent"]) == (77, 5)
    assert by["child"]["parent"] == by["loop"]["span"]


def test_one_clock_said_once():
    assert spans.clock_ns is time.perf_counter_ns
    m, ns = time.monotonic(), spans.clock_ns()
    assert abs(spans.monotonic_to_ns(m) - ns) < 1e6
    req = serving.scheduler.Request(prompt=[1], max_new_tokens=1,
                                    deadline=0.0)
    assert req.submit_ns == spans.monotonic_to_ns(req.submitted)


def test_nothing_recorded_or_annotated_while_tracing_is_off(tracer,
                                                            monkeypatch):
    made = []
    real = spans._annotation_class()
    assert real is jax.profiler.TraceAnnotation
    monkeypatch.setattr(
        spans, "_annotation",
        lambda name, **kw: made.append((name, kw)) or real(name, **kw))
    spans.set_tracing_enabled(False)
    with spans.span("off") as sp:
        sp.set_attr("a", 1)
    assert spans.record("off", 0, 1) is None
    assert not made and not tracer.spans()
    spans.set_tracing_enabled(True)
    with spans.span("on"):
        pass
    # the annotation carries the record's id, and nothing else
    (on,) = tracer.spans()
    assert on["name"] == "on"
    assert made == [("paddle/on", {"span": on["span"]})]


def test_tracing_loads_no_jax_into_a_process_that_has_none():
    code = (
        "import importlib.util, sys\n"
        "spec = importlib.util.spec_from_file_location('spans', sys.argv[1])\n"
        "m = importlib.util.module_from_spec(spec)\n"
        "sys.modules['spans'] = m\n"
        "spec.loader.exec_module(m)\n"
        "with m.span('stub'):\n"
        "    pass\n"
        "assert m.default_tracer().spans()[0]['name'] == 'stub'\n"
        "assert 'jax' not in sys.modules, 'tracing imported jax'\n")
    path = os.path.join(REPO, "paddle_tpu", "observability", "spans.py")
    proc = subprocess.run([sys.executable, "-c", code, path],
                          capture_output=True, text=True, timeout=60)
    assert proc.returncode == 0, proc.stderr[-2000:]


def test_a_sink_hears_of_an_open_span_before_its_children_end():
    # a process SIGKILLed inside a span has flushed the children that
    # ended; their parent must be on disk too (tools/trace_assemble.py
    # counts a child without one as an orphan)
    sink = io.StringIO()
    tr = spans.SpanTracer(sink=sink)
    with tr.span("parent"):
        with tr.span("child"):
            pass
        lines = [json.loads(x) for x in sink.getvalue().splitlines()]
        child = next(x for x in lines if x["name"] == "child"
                     and not x.get("attrs"))
        announced = [x for x in lines if x["name"] == "parent"]
        assert len(announced) == 1 and announced[0]["attrs"] == {
            "open": True} and announced[0]["dur_ns"] == 0
        assert child["parent"] == announced[0]["span"]
    final = [json.loads(x) for x in sink.getvalue().splitlines()][-1]
    assert final["name"] == "parent" and "attrs" not in final
    assert final["span"] == announced[0]["span"]
    # the ring holds finished spans only
    assert [s["name"] for s in tr.spans()] == ["child", "parent"]


# ---------------------------------------------------------------------------
# the serving loop's span tree
# ---------------------------------------------------------------------------

STEP_CHILDREN = {"serve/admit", "serve/decode_tick", "serve/emit"}
# a tick the step feeds itself, then one found in flight (dispatched ahead
# by its predecessor's plan, its feed under that): under a scheduler the
# second plans, and as a rule dispatches, its successor between its tokens
# and its logits; the first's successor is planned after the step's emit
TICK_PHASES = ["decode/feed", "decode/run", "decode/fetch_logits",
               "decode/commit"]
AHEAD_PHASES = ["decode/run", "decode/plan", "decode/fetch_logits",
                "decode/commit"]
PREFILL_PHASES = ["prefill/prep", "prefill/run", "prefill/fetch_logits",
                  "prefill/publish"]
# the executable's call alone, the engine's boundary with the device: a
# grandchild, under the run of a tick the step fed, under the plan that
# dispatched a tick ahead, under a prefill's run
CALLS = {"decode/call": {"decode/run", "decode/plan"},
         "prefill/call": {"prefill/run"}}


def test_span_tree_of_a_step_on_a_paged_engine(tracer, paged_engine,
                                               monkeypatch):
    # a tick of the length a real model's has (the tiny one's is under a
    # millisecond, of which the spans' own cost is a few per cent)
    exe = paged_engine._decode_exec()

    def slow(*args):
        time.sleep(0.02)
        return exe(*args)

    monkeypatch.setattr(paged_engine, "_decode_exec", lambda: slow)
    sched, (r1, r2) = _serve(paged_engine, [[1, 2, 3, 4, 5], [6, 7, 8]],
                             max_new=5)
    ss = tracer.spans()
    by_id = {s["span"]: s for s in ss}
    loop = [s for s in ss if s["trace"] == sched.loop_trace]
    steps = [s for s in loop if s["name"] == "serve/step"]
    # every step is a root of the loop's one trace, numbered in order
    assert [s["attrs"]["step"] for s in steps] == list(range(sched.steps))
    assert all(s["parent"] is None for s in steps)
    assert {s["name"] for s in loop} == (
        {"serve/step", "decode/call"} | STEP_CHILDREN
        | set(TICK_PHASES + AHEAD_PHASES))
    for s in loop:
        if s["name"] in CALLS:
            assert by_id[s["parent"]]["name"] in CALLS[s["name"]]
            assert not [k for k in ss if k["parent"] == s["span"]]
        if s["name"] in STEP_CHILDREN:
            assert by_id[s["parent"]]["name"] == "serve/step"
        if s["name"] in TICK_PHASES:
            assert by_id[s["parent"]]["name"] == "serve/decode_tick"
    # one plan a tick: after the first step's emit, then inside each tick
    plans = [by_id[s["parent"]]["name"] for s in loop
             if s["name"] == "decode/plan"]
    assert plans == ["serve/step"] + ["serve/decode_tick"] * 3
    # the first step admitted both and ticked once
    first = steps[0]
    assert first["attrs"] == {"step": 0, "worked": True, "prefills": 2,
                              "active": 2}
    admit = next(s for s in loop if s["name"] == "serve/admit")
    assert admit["parent"] == first["span"]
    assert admit["attrs"] == {"admitted": 2}
    # exactly one serve/decode_tick a tick, naming its riders
    ticks = [s for s in loop if s["name"] == "serve/decode_tick"]
    assert len(ticks) == len({t["attrs"]["step"] for t in ticks}) == 4
    assert ticks[0]["attrs"] == {"step": 0, "batch": 2,
                                 "riders": [r1.id, r2.id],
                                 "cached_tokens": 5 + 3,
                                 "kv_path": "xla_gather", "live_pages": 2,
                                 # no layer of this model is recurrent
                                 "state_slots": 0, "state_bytes": 0,
                                 "ahead": False}
    assert [t["attrs"]["ahead"] for t in ticks] == [False, True, True, True]
    # a tick's phases, in order, and they account for the tick
    shares = []
    for t in ticks:
        kids = [s for s in ss if s["parent"] == t["span"]]
        assert [k["name"] for k in kids] == (
            AHEAD_PHASES if t["attrs"]["ahead"] else TICK_PHASES)
        assert all(k["start_ns"] >= t["start_ns"] for k in kids)
        shares.append(sum(k["dur_ns"] for k in kids) / t["dur_ns"])
    assert all(x <= 1.0 for x in shares)
    # the phases leave no part of a tick uncovered but the steps between
    # them, a few microseconds of this thread. Under six workers the
    # thread is at times descheduled just there, for longer than 1 % of a
    # 20 ms tick (the driver's run of PR 28's tree failed on the median of
    # the four): so the tick that was interrupted least is held to 1 %,
    # which load can only break by interrupting all four
    assert max(shares) > 0.99, shares
    # after each tick its tokens are handed out under serve/emit
    emits = [s for s in loop if s["name"] == "serve/emit"]
    assert [e["attrs"]["emitted"] for e in emits] == [2, 2, 2, 2]
    assert [e["attrs"]["finished"] for e in emits] == [0, 0, 0, 2]
    # a request's own trace: no record a token; its prefill is a real span
    # under its root with the four phases as children, and names its step
    for req in (r1, r2):
        fam = tracer.trace_spans(req.trace_id)
        assert sorted({s["name"] for s in fam}) == sorted(
            ["serve/request", "serve/queue_wait", "serve/prefill",
             "serve/evict", "prefill/call"] + PREFILL_PHASES)
        pre = next(s for s in fam if s["name"] == "serve/prefill")
        assert pre["parent"] == req.root_span
        assert pre["attrs"] == {"prompt_len": len(req.prompt), "step": 0,
                                "scan_tokens": 0, "delta_chunks": 0,
                                "bucket": 8,
                                "prefix_len": 0, "slot": req.slot}
        kids = [s for s in fam if s["parent"] == pre["span"]]
        assert [k["name"] for k in kids] == PREFILL_PHASES
        assert sum(k["dur_ns"] for k in kids) <= pre["dur_ns"]
        # once a prefill, a leaf of its run, named by it
        (call,) = [s for s in fam if s["name"] == "prefill/call"]
        assert by_id[call["parent"]]["name"] == "prefill/run"
        assert by_id[call["parent"]]["parent"] == pre["span"]
        assert call["attrs"] == {"exe": "prefill_b8"}
        assert by_id[call["parent"]]["attrs"]["call"] == call["span"]
        # inside the step's serve/admit in time, though on its own trace
        assert admit["start_ns"] <= pre["start_ns"] and (
            pre["start_ns"] + pre["dur_ns"]
            <= admit["start_ns"] + admit["dur_ns"])
        evict = next(s for s in fam if s["name"] == "serve/evict")
        assert evict["parent"] == req.root_span
        assert evict["attrs"]["reason"] == "done"
    # at most 12 records a tick from the loop, prefills and requests aside
    assert len(loop) / len(ticks) <= 12


def _tick_records(ss):
    """(serve/decode_tick, its decode/run) pairs in step order, and the
    decode/call records by id."""
    calls = {s["span"]: s for s in ss if s["name"] == "decode/call"}
    ticks = sorted((s for s in ss if s["name"] == "serve/decode_tick"),
                   key=lambda s: s["attrs"]["step"])
    runs = {s["parent"]: s for s in ss if s["name"] == "decode/run"}
    return [(t, runs[t["span"]]) for t in ticks], calls


def test_a_tick_remembers_the_call_that_dispatched_it(tracer, paged_engine):
    sched, _ = _serve(paged_engine, [[1, 2, 3, 4, 5], [6, 7, 8]], max_new=5)
    ss = tracer.spans()
    by_id = {s["span"]: s for s in ss}
    pairs, calls = _tick_records(ss)
    # exactly one decode/call a dispatched tick: four collected, and the
    # last tick's plan found its riders finishing and dispatched none
    assert len(calls) == len(pairs) == 4
    assert all(c["attrs"] == {"exe": "decode"} for c in calls.values())
    named = [run["attrs"]["call"] for _, run in pairs]
    assert sorted(named) == sorted(calls)
    step_of = {}
    for s in ss:
        if s["name"] == "serve/step":
            step_of[s["span"]] = s["attrs"]["step"]

    def step(rec):
        while rec["span"] not in step_of:
            rec = by_id[rec["parent"]]
        return step_of[rec["span"]]

    for tick, run in pairs:
        call = calls[run["attrs"]["call"]]
        holder = by_id[call["parent"]]
        if tick["attrs"]["ahead"]:
            # called one step earlier, under that step's plan: the
            # attribute is the link across the two steps
            assert holder["name"] == "decode/plan"
            assert step(call) == tick["attrs"]["step"] - 1
            assert call["start_ns"] + call["dur_ns"] <= run["start_ns"]
        else:
            # a tick the step fed itself: the call is inside its own run
            assert holder["span"] == run["span"]
            assert run["start_ns"] <= call["start_ns"]
        # the round trip, call's start to the tokens on the host, holds
        # the call
        assert (call["start_ns"] + call["dur_ns"]
                <= run["start_ns"] + run["dur_ns"])
    assert [t["attrs"]["ahead"] for t, _ in pairs] == [False, True, True,
                                                       True]


def test_a_dropped_ticks_call_is_named_by_no_run(tracer, paged_engine):
    sched = serving.Scheduler(paged_engine)
    req = sched.submit([1, 2, 3], max_new_tokens=8)
    sched.step()                       # prefill, a tick, the next ahead
    assert paged_engine.ahead_feed is not None
    sched.abort_all("gone")            # drops the tick in flight
    assert paged_engine.ahead_feed is None and req.state == "failed"
    ss = tracer.spans()
    calls = [s["span"] for s in ss if s["name"] == "decode/call"]
    named = [s["attrs"]["call"] for s in ss if s["name"] == "decode/run"]
    assert len(calls) == 2 and named == calls[:1]


def test_the_replayed_resume_and_the_verify_window_call_under_their_own_parent(
        tracer):
    cfg = gpt.GPT_TINY.scaled(num_layers=1, max_seq_len=64)
    params = gpt.init_params(jax.random.PRNGKey(0), cfg)
    eng = serving.DecodeEngine(params, cfg, serving.EngineConfig(
        max_batch=2, max_seq=32, prefill_buckets=(8,), page_size=8,
        verify_window=2))
    # a stream longer than the ladder: the head prefills, the tail
    # replays through the decode executable under a second serve/prefill
    slot, _, _ = eng.resume_sequence_sampled(list(range(1, 11)),
                                             serving.SamplingParams())
    eng.verify_step({slot: [3, 4]})
    ss = tracer.spans()
    by_id = {s["span"]: s for s in ss}
    got = [(s["name"], s["attrs"]["exe"]) for s in ss
           if s["name"] in CALLS]
    assert got == [("prefill/call", "prefill_b8"),
                   ("decode/call", "decode"), ("decode/call", "decode"),
                   ("decode/call", "verify_w2")]
    replayed = [s for s in ss if s["name"] == "decode/call"
                and s["attrs"]["exe"] == "decode"]
    for call in replayed:
        run = by_id[call["parent"]]
        assert run["name"] == "decode/run"
        assert run["attrs"]["call"] == call["span"]
        assert by_id[run["parent"]]["attrs"]["replayed"] == 2


def test_no_call_span_and_no_link_while_tracing_is_off(tracer, paged_engine):
    spans.set_tracing_enabled(False)
    sched, _ = _serve(paged_engine, [[1, 2, 3]], max_new=3)
    assert not tracer.spans()
    spans.set_tracing_enabled(True)
    # a tick dispatched while tracing was off has no call to name
    sched = serving.Scheduler(paged_engine)
    sched.submit([1, 2, 3], max_new_tokens=4)
    spans.set_tracing_enabled(False)
    sched.step()
    spans.set_tracing_enabled(True)
    sched.step()
    run = next(s for s in tracer.spans() if s["name"] == "decode/run")
    assert run["attrs"]["call"] is None
    for _ in range(8):
        sched.step()


def test_decode_step_ms_is_a_ticks_round_trip(tracer, paged_engine,
                                              monkeypatch):
    """``paddle_serve_decode_step_ms``: dispatch to tokens on the host,
    observed where ``decode/run`` ends: not the logits' fetch, and for a
    tick found in flight not the plan that follows."""
    from paddle_tpu.serving import metrics as smetrics

    seen = []
    real = smetrics.m_decode_ms.observe

    class _Probe:
        @staticmethod
        def observe(ms):
            # where the ring stands when the histogram hears of a tick
            seen.append((ms, [s["name"] for s in tracer.spans()][-1]))
            real(ms)

    monkeypatch.setattr(smetrics, "m_decode_ms", _Probe)
    _serve(paged_engine, [[1, 2, 3]], max_new=4)
    assert len(seen) == 3 and {last for _, last in seen} == {"decode/run"}
    pairs, calls = _tick_records(tracer.spans())
    for (ms, _), (_, run) in zip(seen, pairs):
        call = calls[run["attrs"]["call"]]
        trip = (run["start_ns"] + run["dur_ns"] - call["start_ns"]) / 1e6
        assert trip <= ms < trip + 1.0


def test_from_a_slow_request_to_the_ticks_it_rode(tracer, paged_engine):
    sched = serving.Scheduler(paged_engine)
    long = sched.submit([1, 2, 3], max_new_tokens=6)
    sched.step()
    sched.step()
    late = sched.submit([4, 5, 6, 7], max_new_tokens=2)
    for _ in range(16):
        sched.step()
    assert long.state == late.state == "done"
    roots = {s["attrs"]["request_id"]: s for s in tracer.spans()
             if s["name"] == "serve/request"
             and not s["attrs"].get("open")}
    # late was prefilled in step 2 beside the tick step 1 had dispatched
    # ahead for long alone, and rides from step 3 on
    assert (late.first_step, late.last_step) == (2, 3)
    for req, steps in ((long, [0, 1, 2, 3, 4]), (late, [3])):
        root = roots[req.id]["attrs"]
        assert (root["first_step"], root["last_step"]) == (
            req.first_step, req.last_step)
        assert (req.first_step <= steps[0]
                and steps[-1] == req.last_step)
        rode = [t for t in tracer.attr_range(
            "serve/decode_tick", "step", root["first_step"],
            root["last_step"]) if req.id in t["attrs"]["riders"]]
        assert [t["attrs"]["step"] for t in rode] == steps
        assert len(rode) == len(req.tokens) - 1
    # the tick both rode says so
    shared = tracer.attr_range("serve/decode_tick", "step", 3, 3)[0]
    assert shared["attrs"]["riders"] == [long.id, late.id]
    assert shared["attrs"]["batch"] == 2
    # a request that never ran has no steps to show
    sched2 = serving.Scheduler(paged_engine)
    gone = sched2.submit([1, 2], max_new_tokens=2)
    assert sched2.cancel(gone)
    root = [s for s in tracer.trace_spans(gone.trace_id)
            if not s["attrs"].get("open")][0]
    assert root["attrs"]["first_step"] is None
    assert root["attrs"]["last_step"] is None


def test_slo_forensic_dump_lists_the_ticks_the_worst_request_rode(
        tracer, paged_engine, tmp_path):
    from paddle_tpu.observability.slo import (DEFAULT_OBJECTIVES,
                                              ForensicDir, SLOEngine)

    _, (req, other) = _serve(paged_engine, [[1, 2, 3], [4, 5]], max_new=3)
    fdir = ForensicDir(str(tmp_path / "forensics"), keep=4)
    eng = SLOEngine(forensics=fdir, min_events=8)
    target = next(o for o in DEFAULT_OBJECTIVES
                  if o.name == "ttft_p99").target
    for i in range(20):
        eng.note_request(ttft_ms=target * 10, tpot_ms=1.0, code=200,
                         trace_id=req.trace_id, request_id=str(req.id),
                         t=1000.0 + i * 0.1)
    assert eng.evaluate(1020.0)["ok"] is False
    (name,) = fdir.files()
    with open(os.path.join(fdir.dirname, name)) as f:
        dump = json.load(f)
    assert {s["name"] for s in dump["trace_spans"]} >= {
        "serve/request", "serve/queue_wait", "serve/prefill", "serve/evict"}
    ticks = dump["ticks_ridden"]
    assert [t["attrs"]["step"] for t in ticks] == list(
        range(req.first_step, req.last_step + 1))
    assert all(t["name"] == "serve/decode_tick"
               and req.id in t["attrs"]["riders"] for t in ticks)


def test_engine_loop_parks_under_one_idle_span(tracer, paged_engine):
    sched = serving.Scheduler(paged_engine)
    loop = EngineLoop(sched, idle_sleep_s=0.001).start()
    try:
        time.sleep(0.05)                 # some fifty parks
        r = sched.submit([1, 2, 3], max_new_tokens=2)
        loop.wake()
        assert r.wait(timeout=30) and r.state == "done"
        time.sleep(0.02)
    finally:
        loop.stop()
    assert not loop.alive
    idle = [s for s in tracer.spans() if s["name"] == "serve/loop_idle"]
    # one span a stretch with nothing to do (before the request, after
    # it), not one a park: an idle server leaves the ring alone
    assert 1 <= len(idle) <= 3
    assert idle[0]["dur_ns"] >= 0.04e9
    assert all(s["trace"] == sched.loop_trace and s["parent"] is None
               and s["thread"] == "serve-engine-loop" for s in idle)
    steps = [s for s in tracer.spans() if s["name"] == "serve/step"]
    assert all(s["start_ns"] >= idle[0]["start_ns"] + idle[0]["dur_ns"]
               for s in steps)


def test_a_loop_that_can_do_nothing_leaves_one_record_a_stretch(
        tracer, paged_engine, monkeypatch):
    sched = serving.Scheduler(paged_engine)
    monkeypatch.setattr(paged_engine, "can_admit", lambda n: False)
    req = sched.submit([1, 2, 3], max_new_tokens=2)
    before = len(tracer.spans())
    occ = paged_engine.cache.occupancy
    assert [sched.step() for _ in range(1000)] == [False] * 1000
    # counted as ever, and nothing in the ring while the stretch lasts
    assert sched.steps == 1000 and sched.pending() == 1
    assert sched.occupancy_sum == pytest.approx(1000 * occ)
    assert len(tracer.spans()) == before
    monkeypatch.undo()
    for _ in range(8):
        sched.step()
    assert req.state == "done"
    steps = [s for s in tracer.spans() if s["name"] == "serve/step"]
    assert steps[0]["attrs"] == {"step": 0, "steps": 1000, "worked": False,
                                 "prefills": 0, "active": 0}
    assert steps[0]["trace"] == sched.loop_trace
    assert steps[0]["parent"] is None
    assert steps[1]["attrs"]["step"] == 1000 and steps[1]["attrs"]["worked"]
    assert steps[0]["start_ns"] + steps[0]["dur_ns"] <= steps[1]["start_ns"]
    # the ring grew by the stretch's one record and the request's own
    assert sum(s["start_ns"] < steps[1]["start_ns"]
               for s in tracer.spans()) - before <= 3
    # a queued request that is overdue is the full step's to expire
    late = sched.submit([1, 2], max_new_tokens=2, timeout_s=0.0)
    monkeypatch.setattr(paged_engine, "can_admit", lambda n: False)
    assert sched.stalled_step() is False
    sched.step()
    assert late.state == "expired"


def test_engine_loop_keeps_one_idle_span_over_steps_that_can_do_nothing(
        tracer, paged_engine, monkeypatch):
    sched = serving.Scheduler(paged_engine)
    monkeypatch.setattr(paged_engine, "can_admit", lambda n: False)
    loop = EngineLoop(sched, idle_sleep_s=0.001).start()
    try:
        req = sched.submit([1, 2, 3], max_new_tokens=2)
        loop.wake()
        time.sleep(0.1)                  # some hundred steps, each parked
        stalled = sched.steps
        before = [s["name"] for s in tracer.spans()]
        monkeypatch.undo()
        assert req.wait(timeout=30) and req.state == "done"
    finally:
        loop.stop()
    assert stalled >= 20
    assert before.count("serve/loop_idle") <= 1 and "serve/step" not in before
    ss = tracer.spans()
    idle = [s for s in ss if s["name"] == "serve/loop_idle"]
    assert len(idle) <= 3 and max(s["dur_ns"] for s in idle) >= 0.08e9
    first = min((s for s in ss if s["name"] == "serve/step"),
                key=lambda s: s["start_ns"])
    assert first["attrs"]["steps"] >= 20 and not first["attrs"]["worked"]


# ---------------------------------------------------------------------------
# the train step
# ---------------------------------------------------------------------------

class _Trainer:
    """A tiny ``make_train_step`` with its (donated) state threaded on."""

    def __init__(self):
        cfg = gpt.GPT_TINY.scaled(num_layers=1, max_seq_len=16)
        pcfg = PZ.ParallelConfig(dp=1, pp=1, tp=1)
        mesh = PZ.build_mesh(pcfg, devices=jax.devices()[:1])
        self.params, self.opt = PZ.init_sharded(
            jax.random.PRNGKey(0), cfg, pcfg, mesh)
        self.step = PZ.make_train_step(cfg, pcfg, mesh, lr=1e-3)
        self.tokens = np.zeros((1, 2, 16), np.int32)

    def run(self, n):
        for _ in range(n):
            self.params, self.opt, loss, _ = self.step(
                self.params, self.opt, self.tokens, self.tokens)
        return float(loss)


@pytest.fixture(scope="module")
def trainer():
    return _Trainer()


def test_train_step_spans(tracer, trainer):
    trainer.run(3)
    ss = tracer.spans()
    steps = [s for s in ss if s["name"] == "train/step"]
    seqs = [s["attrs"]["seq"] for s in steps]
    assert seqs == list(range(seqs[0], seqs[0] + 3))
    assert len({s["trace"] for s in steps}) == 3      # a trace a step
    compiles = [s for s in ss if s["name"] == "train/compile"]
    # the first call with a signature compiles, inside its train/step
    assert len(compiles) <= 1
    for c in compiles:
        assert c["parent"] == steps[0]["span"]
    # one record a step once compiled
    assert len([s for s in ss if s["trace"] == steps[-1]["trace"]]) == 1


# ---------------------------------------------------------------------------
# on the profiler's clock
# ---------------------------------------------------------------------------

def _host_events(trace_dir, prefix):
    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(prefix):
                    out.append((ev.name, int(ev.start_ns),
                                int(ev.duration_ns), dict(ev.stats)))
    return sorted(out, key=lambda e: e[1])


def test_a_profiler_capture_holds_the_spans_where_the_ring_has_them(
        tracer, paged_engine, trainer, tmp_path):
    trainer.run(1)                                   # compiled
    tracer.clear()
    jax.profiler.start_trace(str(tmp_path))
    try:
        _serve(paged_engine, [[1, 2, 3], [4, 5, 6, 7]], max_new=4)
        trainer.run(2)
    finally:
        jax.profiler.stop_trace()
    events = _host_events(str(tmp_path), spans.ANNOTATION_PREFIX)
    names = {e[0] for e in events}
    assert {"paddle/serve/step", "paddle/serve/decode_tick",
            "paddle/decode/run", "paddle/serve/prefill",
            "paddle/prefill/run", "paddle/serve/emit", "paddle/serve/evict",
            "paddle/train/step"} <= names, names
    ring = sorted(tracer.spans(), key=lambda s: s["start_ns"])
    # one offset puts every annotation on its record: start and length
    # agree to a millisecond
    first_tick = next(e for e in events
                      if e[0] == "paddle/serve/decode_tick")
    offset = first_tick[1] - next(
        s for s in ring if s["name"] == "serve/decode_tick")["start_ns"]
    for name in ("serve/decode_tick", "train/step", "serve/prefill"):
        got = [e for e in events if e[0] == "paddle/" + name]
        want = [s for s in ring if s["name"] == name]
        assert len(got) == len(want) >= 2
        for (_, start, dur, _), rec in zip(got, want):
            assert abs(start - offset - rec["start_ns"]) < 1e6, name
            assert abs(dur - rec["dur_ns"]) < 1e6, name
    # every annotation carries its record's id and nothing else, so the
    # attributes, the parent and the trace are one lookup away; what the
    # ring holds without an annotation was timed elsewhere
    by_id = {s["span"]: s for s in ring}
    assert all(set(stats) == {"span"} for _, _, _, stats in events)
    assert len({stats["span"] for _, _, _, stats in events}) == len(events)
    for name, _, _, stats in events:
        assert "paddle/" + by_id[stats["span"]]["name"] == name
    assert {"paddle/decode/call", "paddle/prefill/call"} <= names
    bare = {s["name"] for s in ring} - {n[len("paddle/"):] for n in names}
    assert bare == {"serve/request", "serve/queue_wait"}
