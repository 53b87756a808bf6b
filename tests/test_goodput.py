"""Goodput ledger + span tracer (ISSUE 10): exclusive-time accounting,
run windows, gang merges, cross-thread span context propagation
(prefetch/checkpoint/serving threads), the span plane in the merged
chrome trace, and the gang prom-exposition merge."""
import json
import os
import sys
import threading
import time

import numpy as np
import pytest

from paddle_tpu.observability import goodput, prom, spans, trace_merge

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))
from metrics_check import validate_prom_text  # noqa: E402


# ---------------------------------------------------------------------------
# ledger accounting
# ---------------------------------------------------------------------------

def test_ledger_exclusive_nesting_and_window():
    led = goodput.GoodputLedger()
    assert led.start_window()
    assert not led.start_window()   # reentrant open is a no-op
    with led.timer("productive_step"):
        time.sleep(0.03)
        with led.timer("compile"):
            time.sleep(0.03)
    with led.timer("input_stall"):
        time.sleep(0.01)
    rep = led.end_window()
    cats = rep["categories"]
    # the nested compile stole its wall from the enclosing step
    assert 0.025 < cats["productive_step"] < 0.055
    assert 0.025 < cats["compile"] < 0.055
    assert 0.008 < cats["input_stall"] < 0.03
    # exclusive accounting sums EXACTLY to wall (other absorbs the rest)
    assert abs(sum(cats.values()) - rep["wall_s"]) < 2e-3
    assert rep["unaccounted_fraction"] < 0.2
    assert set(cats) == set(goodput.CATEGORIES)


def test_ledger_same_category_nesting_no_double_count():
    led = goodput.GoodputLedger()
    with led.timer("productive_step"):
        with led.timer("productive_step"):
            time.sleep(0.02)
    total = led.totals()["productive_step"]
    assert 0.015 < total < 0.04   # counted once, not twice


def test_ledger_totals_include_open():
    led = goodput.GoodputLedger()
    with led.timer("compile"):
        time.sleep(0.02)
        open_view = led.totals(include_open=True)
        closed_view = led.totals()
    assert open_view["compile"] > 0.015
    assert closed_view["compile"] == 0.0


def test_ledger_attribute_and_window_other():
    led = goodput.GoodputLedger()
    led.start_window()
    time.sleep(0.02)            # uncovered -> other
    led.attribute("restart_downtime", 1.5)
    rep = led.end_window(extra={"job": "t"})
    assert rep["categories"]["other"] > 0.01
    assert rep["categories"]["restart_downtime"] == 1.5
    assert rep["job"] == "t"
    assert led.last_window is rep


def test_run_window_context_and_export(tmp_path, monkeypatch):
    monkeypatch.setenv(goodput.ENV_DIR, str(tmp_path))
    monkeypatch.setenv("PADDLE_TRAINER_ID", "3")
    led = goodput.GoodputLedger()
    with led.run_window():
        with led.timer("productive_step"):
            time.sleep(0.01)
    files = [f for f in os.listdir(tmp_path) if f.endswith(".json")]
    assert len(files) == 1 and files[0].startswith("goodput.rank3.")
    rep = json.load(open(tmp_path / files[0]))
    assert rep["rank"] == 3
    assert rep["categories"]["productive_step"] > 0
    # the per-rank prom exposition rides along for the gang merge
    proms = [f for f in os.listdir(tmp_path) if f.endswith(".prom")]
    assert len(proms) == 1


def test_merge_reports_gang_semantics():
    r = {"wall_s": 10.0, "rank": 0,
         "categories": {"productive_step": 8.0, "compile": 1.5,
                        "other": 0.5}}
    r2 = {"wall_s": 10.0, "rank": 1,
          "categories": {"productive_step": 6.0, "compile": 3.0,
                         "other": 1.0}}
    gang = goodput.merge_reports([r, r2], restart_downtime_s=2.0)
    # downtime charged once per rank: the whole gang idles in a restart
    assert gang["categories"]["restart_downtime"] == 4.0
    assert gang["wall_s"] == 24.0
    assert gang["nranks"] == 2
    total = sum(gang["categories"].values())
    assert abs(gang["gang_goodput_fraction"] - 14.0 / total) < 1e-6
    assert abs(gang["unaccounted_fraction"] - 1.5 / total) < 1e-6


def test_write_gang_report_merges_rank_files(tmp_path):
    for rank in (0, 1):
        with open(tmp_path / f"goodput.rank{rank}.100{rank}.json",
                  "w") as f:
            json.dump({"wall_s": 5.0, "rank": rank,
                       "categories": {"productive_step": 4.0,
                                      "other": 1.0}}, f)
        with open(tmp_path / f"goodput.rank{rank}.100{rank}.prom",
                  "w") as f:
            f.write("# TYPE paddle_goodput_seconds_total counter\n"
                    'paddle_goodput_seconds_total{category='
                    '"productive_step"} 4\n')
    path = goodput.write_gang_report(str(tmp_path),
                                     restart_downtime_s=1.0, nranks=2)
    gang = json.load(open(path))
    assert gang["rank_reports"] == 2
    assert gang["categories"]["productive_step"] == 8.0
    assert gang["categories"]["restart_downtime"] == 2.0
    merged = open(tmp_path / "gang_metrics.prom").read()
    validate_prom_text(merged)
    assert 'paddle_goodput_seconds_total{category="productive_step"} 8' \
        in merged


def test_write_gang_report_empty_dir(tmp_path):
    assert goodput.write_gang_report(str(tmp_path)) is None


# ---------------------------------------------------------------------------
# prom exposition merge
# ---------------------------------------------------------------------------

def test_merge_expositions_counter_sum_gauge_max_histogram_sum():
    t1 = ("# HELP a_total reqs\n# TYPE a_total counter\n"
          'a_total{code="200"} 2\n'
          "# TYPE depth gauge\ndepth 3\n"
          "# TYPE lat_ms histogram\n"
          'lat_ms_bucket{le="1"} 1\nlat_ms_bucket{le="+Inf"} 2\n'
          "lat_ms_sum 1.5\nlat_ms_count 2\n")
    t2 = ("# HELP a_total reqs\n# TYPE a_total counter\n"
          'a_total{code="200"} 5\na_total{code="500"} 1\n'
          "# TYPE depth gauge\ndepth 1\n"
          "# TYPE lat_ms histogram\n"
          'lat_ms_bucket{le="1"} 3\nlat_ms_bucket{le="+Inf"} 4\n'
          "lat_ms_sum 2.5\nlat_ms_count 4\n")
    merged = prom.merge_expositions([t1, t2])
    validate_prom_text(merged)
    assert 'a_total{code="200"} 7' in merged
    assert 'a_total{code="500"} 1' in merged
    assert "\ndepth 3" in merged            # gauge: max, not sum
    assert 'lat_ms_bucket{le="1"} 4' in merged
    assert "lat_ms_sum 4" in merged
    assert "lat_ms_count 6" in merged


# ---------------------------------------------------------------------------
# span tracer
# ---------------------------------------------------------------------------

def test_span_nesting_and_ring():
    tr = spans.SpanTracer(ring=8)
    with tr.span("outer") as o:
        with tr.span("inner"):
            pass
    ss = tr.spans()
    inner = next(s for s in ss if s["name"] == "inner")
    outer = next(s for s in ss if s["name"] == "outer")
    assert inner["trace"] == outer["trace"]
    assert inner["parent"] == outer["span"]
    assert outer["parent"] is None
    for _ in range(20):
        tr.record("fill", 0, 1)
    assert len(tr.spans()) == 8   # bounded ring


def test_span_disabled_is_noop():
    tr = spans.SpanTracer()
    spans.set_tracing_enabled(False)
    try:
        with tr.span("x") as sp:
            sp.set_attr("k", 1)
        assert tr.record("y", 0, 1) is None
        assert tr.spans() == []
    finally:
        spans.set_tracing_enabled(True)


def test_span_record_explicit_trace_keeps_parent_none():
    tr = spans.SpanTracer()
    with tr.span("ambient"):
        # an explicit trace must NOT inherit the ambient parent: this is
        # how root spans (serve/request) stay roots on a busy loop thread
        sid = tr.record("root", 0, 1, trace=77, parent=None, span_id=5)
    rec = next(s for s in tr.spans() if s["name"] == "root")
    assert rec["trace"] == 77 and rec["parent"] is None and sid == 5


def test_span_context_cross_thread_parenting():
    tr = spans.SpanTracer()
    ctx = {}
    with tr.span("submit") as sp:
        ctx["c"] = tr.current_context()

    def work():
        with tr.context(ctx["c"]):
            with tr.span("worker_side"):
                pass
        # context is restored after the block: a second span on this
        # thread must NOT leak the attached parent
        with tr.span("fresh"):
            pass

    t = threading.Thread(target=work)
    t.start()
    t.join()
    ss = tr.spans()
    submit = next(s for s in ss if s["name"] == "submit")
    worker_side = next(s for s in ss if s["name"] == "worker_side")
    fresh = next(s for s in ss if s["name"] == "fresh")
    assert worker_side["parent"] == submit["span"]
    assert worker_side["trace"] == submit["trace"]
    assert fresh["trace"] != submit["trace"] and fresh["parent"] is None


def test_span_jsonl_sink(tmp_path):
    p = tmp_path / "spans.jsonl"
    tr = spans.SpanTracer(sink=str(p))
    with tr.span("a"):
        pass
    tr.set_sink(None)
    rows = [json.loads(ln) for ln in open(p)]
    assert rows and rows[0]["name"] == "a" and rows[0]["dur_ns"] >= 0


def test_span_summary_percentiles():
    tr = spans.SpanTracer()
    for i in range(10):
        tr.record("op", 0, (i + 1) * 1_000_000)   # 1..10 ms
    roll = tr.summary()["op"]
    assert roll["count"] == 10
    assert roll["p50_ms"] == pytest.approx(6.0, abs=1.1)
    assert roll["p99_ms"] == pytest.approx(10.0, abs=0.1)
    assert roll["max_ms"] == pytest.approx(10.0, abs=0.1)


def test_trace_spans_walk():
    tr = spans.SpanTracer()
    tr.record("b", 20, 1, trace=9)
    tr.record("a", 10, 1, trace=9)
    tr.record("c", 30, 1, trace=8)
    walk = tr.trace_spans(9)
    assert [s["name"] for s in walk] == ["a", "b"]


# ---------------------------------------------------------------------------
# satellite: context propagation through the real worker threads
# ---------------------------------------------------------------------------

def test_prefetch_thread_spans_parent_to_caller():
    from paddle_tpu.reader import prefetch_to_device

    tr = spans.default_tracer()
    tr.clear()
    with tr.span("train_loop") as sp:
        root_ctx = tr.current_context()
        batches = [{"x": np.ones((2, 2), np.float32)} for _ in range(3)]
        out = list(prefetch_to_device(iter(batches), size=2))
    assert len(out) == 3
    staged = [s for s in tr.spans() if s["name"] == "input/stage_batch"]
    assert len(staged) == 3
    root = next(s for s in tr.spans() if s["name"] == "train_loop")
    for s in staged:
        assert s["trace"] == root["trace"], "orphan staging span"
        assert s["parent"] == root["span"]
        assert s["thread"] == "device_prefetch"


def test_checkpoint_async_save_thread_spans_parent(tmp_path):
    from paddle_tpu.parallel.checkpoint import ElasticCheckpointer

    tr = spans.default_tracer()
    tr.clear()
    ck = ElasticCheckpointer(str(tmp_path), use_async=True)
    ck.save(1, {"w": np.ones((4,), np.float32)})
    ck.wait()
    ck.close()
    ss = tr.spans()
    save = next(s for s in ss if s["name"] == "checkpoint/save")
    write = next(s for s in ss if s["name"] == "checkpoint/write")
    assert write["trace"] == save["trace"], "writer span orphaned"
    assert write["parent"] == save["span"]
    assert write["thread"] == "elastic-ckpt-writer"
    assert write["attrs"]["step"] == 1


# ---------------------------------------------------------------------------
# satellite: span plane in the merged chrome trace
# ---------------------------------------------------------------------------

def test_span_chrome_events_own_pid_and_rows():
    tracer_spans = [
        {"name": "a", "trace": 1, "span": 2, "parent": None,
         "start_ns": 5_000_000, "dur_ns": 1_000_000, "tid": 11,
         "thread": "MainThread"},
        {"name": "b", "trace": 1, "span": 3, "parent": 2,
         "start_ns": 6_000_000, "dur_ns": 500_000, "tid": 12,
         "thread": "worker"},
    ]
    meta, events = trace_merge.span_chrome_events(tracer_spans)
    pids = {e["pid"] for e in events}
    assert pids == {trace_merge.SPAN_PID}
    assert trace_merge.SPAN_PID != trace_merge.DEVICE_PID_BASE
    names = [m for m in meta if m["name"] == "thread_name"]
    assert len(names) == 2          # one row per recording thread
    assert any("MainThread" in m["args"]["name"] for m in names)
    b = next(e for e in events if e["name"] == "b")
    assert b["args"]["parent"] == "2"
    assert b["args"]["trace"] == "1"


def test_span_plane_pre_epoch_alignment():
    # a span opened BEFORE start_profiler is aligned to the merged-trace
    # epoch (clamped), not dropped and not drawn before the trace starts
    tracer_spans = [
        {"name": "early", "trace": 1, "span": 2, "parent": None,
         "start_ns": 1_000_000, "dur_ns": 4_000_000, "tid": 1,
         "thread": "t"},
        {"name": "ancient", "trace": 1, "span": 3, "parent": None,
         "start_ns": 0, "dur_ns": 1_000_000, "tid": 1, "thread": "t"},
    ]
    epoch_us = 3_000.0   # trace epoch at 3 ms
    _meta, events = trace_merge.span_chrome_events(tracer_spans,
                                                   epoch_us=epoch_us)
    early = next(e for e in events if e["name"] == "early")
    assert early["ts"] == epoch_us            # clamped, kept
    assert early["dur"] == pytest.approx(2_000.0)  # in-window share
    ancient = next(e for e in events if e["name"] == "ancient")
    assert ancient["ts"] == epoch_us and ancient["dur"] == 0.0


def test_merge_events_includes_span_plane():
    host = [{"name": "h", "ph": "X", "ts": 10.0, "dur": 5.0, "pid": 1,
             "tid": 1}]
    tracer_spans = [{"name": "s", "trace": 1, "span": 2, "parent": None,
                     "start_ns": 12_000, "dur_ns": 2_000, "tid": 1,
                     "thread": "t"}]
    doc = trace_merge.merge_events(host, [], tracer_spans=tracer_spans)
    ev = doc["traceEvents"]
    span_rows = [e for e in ev
                 if e.get("pid") == trace_merge.SPAN_PID
                 and e.get("ph") == "X"]
    assert len(span_rows) == 1 and span_rows[0]["name"] == "s"
    procs = [e for e in ev if e.get("name") == "process_name"
             and e.get("pid") == trace_merge.SPAN_PID]
    assert len(procs) == 1


# ---------------------------------------------------------------------------
# satellite: serving EngineLoop thread — per-request trace isolation
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny_serving():
    import jax.random as jrandom

    from paddle_tpu import serving as pserving
    from paddle_tpu.models import gpt as gpt_model

    cfg = gpt_model.GPT_TINY.scaled(num_layers=1, max_seq_len=32)
    params = gpt_model.init_params(jrandom.PRNGKey(0), cfg)
    engine = pserving.DecodeEngine(
        params, cfg, pserving.EngineConfig(max_batch=2, max_seq=16,
                                           prefill_buckets=(4, 8),
                                           page_size=4))
    engine.warmup()
    return pserving, engine, cfg


def test_serving_request_spans_isolated(tiny_serving):
    pserving, engine, cfg = tiny_serving
    tr = spans.default_tracer()
    tr.clear()
    sched = pserving.Scheduler(engine)
    r1 = sched.submit([1, 2, 3], max_new_tokens=3)
    r2 = sched.submit([4, 5], max_new_tokens=3)
    for _ in range(10):
        sched.step()
        if r1.finished.is_set() and r2.finished.is_set():
            break
    assert r1.state == "done" and r2.state == "done"
    assert r1.trace_id != r2.trace_id
    ss = tr.spans()
    for req in (r1, r2):
        fam = [s for s in ss if s["trace"] == req.trace_id]
        names = {s["name"] for s in fam}
        assert {"serve/request", "serve/queue_wait", "serve/prefill",
                "serve/evict"} <= names, names
        # a request gets no record a token: the ticks are the loop's
        assert "serve/decode_tick" not in names
        # the dur-0 open sentinel (flushed at admission for crash
        # stitchability, ISSUE 18) shares the root's span id; the final
        # record is the one without attrs.open
        root = next(s for s in fam if s["name"] == "serve/request"
                    and not (s.get("attrs") or {}).get("open"))
        assert root["span"] == req.root_span and root["parent"] is None
        # no orphans: every child parents to a span of the SAME request
        own = {s["span"] for s in fam}
        for s in fam:
            if s["parent"] is not None:
                assert s["parent"] in own, (req.id, s)
        # no leakage: nothing from the other request's trace
        assert root["attrs"]["state"] == "done"
        # from a request to the ticks it rode: the root's first_step ..
        # last_step is a range of serve/decode_tick records, one a tick,
        # each naming its riders
        first, last = (root["attrs"]["first_step"],
                       root["attrs"]["last_step"])
        assert (first, last) == (req.first_step, req.last_step)
        rode = [t for t in tr.attr_range("serve/decode_tick", "step",
                                         first, last)
                if req.id in t["attrs"]["riders"]]
        # the prefill gives the first token, every later one is a tick
        assert len(rode) == len(req.tokens) - 1
        assert [t["attrs"]["step"] for t in rode] == list(
            range(first, last + 1))
    # exactly one record a tick, on the loop's one trace, with its batch
    ticks = [s for s in ss if s["name"] == "serve/decode_tick"]
    steps = [t["attrs"]["step"] for t in ticks]
    assert len(set(steps)) == len(steps)
    assert {t["trace"] for t in ticks} == {sched.loop_trace}
    assert all(t["attrs"]["batch"] == len(t["attrs"]["riders"]) >= 1
               for t in ticks)
    # loop-thread context never sticks: after the ticks the loop thread's
    # ambient context is clean (a fresh span starts a fresh trace)
    with tr.span("after") as sp:
        pass
    after = next(s for s in tr.spans() if s["name"] == "after")
    assert after["trace"] not in (r1.trace_id, r2.trace_id)


def test_engine_loop_thread_spans_and_health_rollups(tiny_serving):
    # the REAL EngineLoop thread ticks the scheduler: request spans must
    # still land on the request's trace (recorded from the loop thread),
    # and /health must expose the percentile rollups
    pserving, engine, cfg = tiny_serving
    tr = spans.default_tracer()
    tr.clear()
    sched = pserving.Scheduler(engine)
    front = pserving.FrontDoor(scheduler=sched).start()
    try:
        r = sched.submit([1, 2, 3], max_new_tokens=2)
        front.loop.wake()
        assert r.wait(timeout=30) and r.state == "done"
        fam = [s for s in tr.spans() if s["trace"] == r.trace_id]
        names = {s["name"] for s in fam}
        assert {"serve/request", "serve/prefill"} <= names, names
        ticks = [s for s in tr.spans() if s["name"] == "serve/decode_tick"
                 and r.id in s["attrs"]["riders"]]
        assert ticks and ticks[0]["thread"] == "serve-engine-loop"
        loop_side = [s for s in fam if s["name"] == "serve/prefill"]
        assert loop_side[0]["thread"] == "serve-engine-loop"
        health = front.health()
        assert "span_rollups_ms" in health
        roll = health["span_rollups_ms"]["serve/request"]
        assert roll["count"] >= 1 and roll["p99_ms"] >= 0
    finally:
        front.stop()


# ---------------------------------------------------------------------------
# monitor rows carry the per-step goodput breakdown
# ---------------------------------------------------------------------------

def test_monitor_rows_carry_goodput_breakdown(tmp_path):
    from paddle_tpu.observability import TrainMonitor

    led = goodput.ledger()
    path = tmp_path / "mon.jsonl"
    mon = TrainMonitor(path=str(path), examples_per_step=4,
                       sample_hbm=False)
    for _ in range(2):
        with led.timer("input_stall"):
            time.sleep(0.002)
        with mon.step() as s:
            with led.timer("productive_step"):
                time.sleep(0.004)
            s.observe(loss=np.float32(1.0))
    mon.close()
    rows = [json.loads(ln) for ln in open(path)]
    assert len(rows) == 2
    for row in rows:
        assert "goodput_ms" in row
        assert row["goodput_ms"]["productive_step"] >= 3.0
    # the second row's delta includes the inter-step stall
    assert rows[1]["goodput_ms"].get("input_stall", 0) >= 1.0
