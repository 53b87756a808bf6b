"""The readers of the program's own spans: the ring inside a window (and
the refusal where the ring may have lost part of it), the ``paddle/``
annotations of a trace with planted gaps, and each reader on the rehearsal
entry, traced."""
import json
import os
import shutil
import sys
import time
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import checks, harness, program_spans as PS  # noqa: E402
from benchmark import trace_reduce as T  # noqa: E402

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)
NEW = sorted(checks.PROGRAM_SPAN_READERS)


def _reader(name):
    return harness.load_module(os.path.join(
        ROOT, "benchmark", "layer_metrics", name + ".py"))


def test_the_eight_readers_are_in_the_manifest_by_name():
    assert len(NEW) == 8
    checks.program_span_readers_are_in_the_manifest_by_name(ROOT)
    # the two cells of today, each under the metrics of its own path
    listed = {m["name"]: m["workloads"] for m in MANIFEST["per_layer"]}
    assert "serve_cgpt1p3b_closed14" in listed["serve_tick_ms"]
    assert "train_cgpt1p3b_l6_1chip" in listed["train_dispatch_ms"]


def test_a_cell_listed_under_another_paths_metric_is_refused(tmp_path):
    manifest = json.loads(json.dumps(MANIFEST))
    for sub in ("traffic", "layer_metrics"):
        shutil.copytree(os.path.join(ROOT, "benchmark", sub),
                        tmp_path / "benchmark" / sub,
                        ignore=shutil.ignore_patterns("__pycache__"))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(manifest))
    checks.program_span_readers_are_in_the_manifest_by_name(str(tmp_path))
    for m in manifest["per_layer"]:
        if m["name"] == "serve_tick_ms":
            m["workloads"].append("train_cgpt1p3b_l6_1chip")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(manifest))
    with pytest.raises(AssertionError):
        checks.program_span_readers_are_in_the_manifest_by_name(
            str(tmp_path))
    # and one of the eight gone from the list altogether
    manifest = json.loads(json.dumps(MANIFEST))
    manifest["per_layer"] = [m for m in manifest["per_layer"]
                             if m["name"] != "serve_host_gap_ms"]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(manifest))
    with pytest.raises(AssertionError):
        checks.program_span_readers_are_in_the_manifest_by_name(
            str(tmp_path))


# ---------------------------------------------------------------------------
# the ring inside a window
# ---------------------------------------------------------------------------

def _spans_module(ring):
    from paddle_tpu.observability import spans

    tracer = spans.SpanTracer(ring=ring)
    return types.SimpleNamespace(
        default_tracer=lambda: tracer,
        monotonic_to_ns=lambda t: int(t * 1e9) + 500), tracer


def test_window_records_keeps_what_lies_inside_the_window():
    mod, tracer = _spans_module(16)
    for start in (0, 900, 1500, 2600, 2800, 3100):       # ns; length 300
        tracer.record("tick", start, 300, trace=1)
    tracer.record("other", 1600, 10, trace=1, attrs={"step": 3})
    # the window is time.monotonic() seconds; the tracer's helper turns it
    # into the ring's units (here half a microsecond further on)
    got = PS.window_records(mod, (1e-6, 2.5e-6))          # 1500..3000 ns
    assert [r["start_ns"] for r in got["tick"]] == [1500, 2600]
    assert got["other"][0]["attrs"] == {"step": 3}
    assert PS.ms(got["tick"]) == [0.0003, 0.0003]
    assert PS.by_step(got["other"]) == {3: got["other"]}
    assert PS.window_records(mod, None) is None


def test_window_records_refuses_a_window_the_ring_has_lost_part_of():
    mod, tracer = _spans_module(4)
    for i in range(6):                    # ends at 1100, 2100 .. 6100
        tracer.record("tick", i * 1000 + 1000, 100, trace=1)
    assert tracer.dropped == 2            # the ring holds 3000..6000
    # the oldest record left ended before the window: nothing of the
    # window fell off, whatever fell off before it
    got = PS.window_records(mod, (3.5e-6, 7e-6))
    assert [r["start_ns"] for r in got["tick"]] == [4000, 5000, 6000]
    # a window that reaches back to the oldest record left: refused
    assert PS.window_records(mod, (2e-6, 7e-6)) is None
    assert PS.window_records(mod, (0.0, 7e-6)) is None


def test_a_program_whose_tracer_cannot_be_read_gives_nothing():
    mod, tracer = _spans_module(4)
    tracer.record("tick", 10, 1, trace=1)
    older = types.SimpleNamespace(
        default_tracer=lambda: types.SimpleNamespace(
            spans=tracer.spans))          # no ``dropped``, no clock helper
    assert PS.window_records(older, (0.0, 1.0)) is None
    run = types.SimpleNamespace(window=(0.0, 1.0), profile=None,
                                trace_dir="/nonexistent")
    run._program_spans = (None, None)
    for name in NEW:
        assert _reader(name).read(run) is None


# ---------------------------------------------------------------------------
# a trace with planted gaps
# ---------------------------------------------------------------------------

def _planted(fetch2=1860):
    """Two ticks on one device, in microseconds. Tick 1: step 0..1000,
    tick 100..900 with feed 100..150, run 150..820, fetch 820..880, commit
    880..900, then emit 905..950; the device is busy 150..800. Tick 2 the
    same 1000 later, but its device program ends at 1700, its run at 1710,
    and the host dawdles under serve/decode_tick alone until the fetch
    begins at ``fetch2``."""
    us = 1000
    ops = [("fusion.1 fusion", 150 * us, 650 * us),
           ("fusion.1 fusion", 1150 * us, 550 * us)]
    spans = []
    for base, run_end, fetch in ((0, 820, 820), (1000, 1710, fetch2)):
        spans += [("serve/step", base, 1000),
                  ("serve/decode_tick", base + 100, 800),
                  ("decode/feed", base + 100, 50),
                  ("decode/run", base + 150, run_end - base - 150),
                  ("decode/fetch_logits", fetch, base + 880 - fetch),
                  ("decode/commit", base + 880, 20),
                  ("serve/emit", base + 905, 45)]
    return T.Profile({"/device:TPU:0": ops}, {"/device:TPU:0": []},
                     sorted(((n, s * us, d * us) for n, s, d in spans),
                            key=lambda e: e[1]))


def test_host_gap_is_a_ticks_length_less_the_device_time_inside_it():
    p = _planted()
    assert PS.host_gaps_ms(p, "serve/decode_tick") == pytest.approx(
        [0.800 - 0.650, 0.800 - 0.550])
    assert PS.host_gaps_ms(p, "serve/step") == pytest.approx(
        [1.000 - 0.650, 1.000 - 0.550])
    assert PS.host_gaps_ms(p, "no/such") == []


def test_idle_is_divided_over_the_innermost_spans_that_cover_it():
    p = _planted()
    # gap 0..150: serve/step 0..100, decode/feed 100..150. Gap 800..1150:
    # the end of decode/run 20, the fetch 60, the commit 20, serve/step 5,
    # serve/emit 45, serve/step 50 + 100 into the next step, its feed 50.
    # Gap 1700..2000: decode/run 10, serve/decode_tick alone 150 (between
    # the run's end and the fetch), fetch 20, commit 20, serve/step 5,
    # emit 45, serve/step 50.
    want = {"serve/step": 310e-6, "serve/decode_tick": 150e-6,
            "decode/feed": 100e-6, "serve/emit": 90e-6,
            "decode/fetch_logits": 80e-6, "decode/commit": 40e-6,
            "decode/run": 30e-6}
    assert dict(T.idle_by_span(p)) == pytest.approx(want)
    assert [n for n, _ in T.idle_by_span(p)] == list(want)   # most first
    assert PS.idle_line(p).startswith(
        "idle by program span: serve/step 0.000310, serve/decode_tick "
        "0.000150, decode/feed 0.000100")
    assert PS.unattributed_idle_share(p) == pytest.approx(
        100.0 * (310 + 150) / 800)
    # the ledger's breakdown is the same division
    assert T.breakdown(p)["idle_gaps"] == [
        [n, pytest.approx(v)] for n, v in want.items()]
    # with the host's work after the tick under a leaf span (the fetch
    # begins where the run ends), that idle has a name
    q = _planted(fetch2=1710)
    got = dict(T.idle_by_span(q))
    assert "serve/decode_tick" not in got
    assert got["decode/fetch_logits"] == pytest.approx(230e-6)
    assert PS.unattributed_idle_share(q) == pytest.approx(100.0 * 310 / 800)
    assert PS.is_leaf("prefill/run") and PS.is_leaf("serve/loop_idle")
    assert not PS.is_leaf("serve/decode_tick")
    assert not PS.is_leaf(T.OUTSIDE)


def test_the_two_trace_readers_on_a_run_with_a_planted_trace(capsys):
    planted = _planted()
    run = types.SimpleNamespace(window=None, profile=planted)
    assert _reader("serve_host_gap_ms").read(run) == pytest.approx(
        (0.150 + 0.250) / 2)
    assert _reader("serve_idle_unattributed").read(run) == pytest.approx(
        57.5)
    # the line the ledger's notes can carry, once a run
    out = capsys.readouterr().out
    assert out.count("[bench] idle by program span: serve/step") == 1
    # no device plane (a rehearsal on the CPU), no span, or spans of
    # another path alone: nothing is reported
    for profile in (T.Profile({}, {}, planted.spans),
                    T.Profile(planted.devices, planted.modules, [])):
        bare = types.SimpleNamespace(window=None, profile=profile)
        assert _reader("serve_host_gap_ms").read(bare) is None
        assert _reader("serve_idle_unattributed").read(bare) is None
    train = types.SimpleNamespace(window=None, profile=T.Profile(
        planted.devices, planted.modules, [("train_step", 0, 2000000)]))
    assert _reader("serve_host_gap_ms").read(train) is None


def test_decode_step_roofline_reads_the_programs_tick_records():
    """``cached_tokens`` comes from the program's ``serve/decode_tick``
    records inside the traced window, no wrapper's span."""
    family = types.SimpleNamespace(
        bytes_per_decode_step=lambda config, lengths, weight_bytes:
        1000 * weight_bytes + 10 * sum(lengths))
    cell = types.SimpleNamespace(
        family=family,
        config={"serving": {"engine": {"weight_dtype": "bf16"}}})

    def tick(start_ns, cached):
        return {"name": "serve/decode_tick", "start_ns": start_ns,
                "dur_ns": 100, "attrs": {"cached_tokens": cached}}

    from paddle_tpu.observability import spans

    at = spans.monotonic_to_ns
    ring = {"serve/decode_tick": [tick(at(1.0), 100), tick(at(2.0), 300),
                                  tick(at(3.0) - 50, 500)]}
    run = types.SimpleNamespace(
        cell=cell, window=(0.0, 4.0), trace_window=(1.5, 3.0),
        peaks={"hbm_bytes_per_s": 1e9},
        profile=T.Profile({}, {"/device:TPU:0": [
            ("jit__decode_fn_paged(1)", 0, 10000),
            ("jit__prefill_fn(2)", 0, 99999)]}, []))
    run._program_spans = (ring, None)
    # one tick inside the traced window: 2000 + 3000 bytes at 1 GB/s is
    # 5 us; the program took 10 us
    assert _reader("decode_step_roofline").read(run) == pytest.approx(50.0)
    run.trace_window = (0.5, 2.5)                 # two ticks: mean 4000
    assert _reader("decode_step_roofline").read(run) == pytest.approx(40.0)
    run.trace_window = (5.0, 6.0)                 # none
    assert _reader("decode_step_roofline").read(run) is None
    run._program_spans = (None, None)             # a ring that lost records
    run.trace_window = (0.5, 2.5)
    assert _reader("decode_step_roofline").read(run) is None


def test_load_xplane_keeps_the_programs_spans_of_a_real_capture(tmp_path):
    import jax

    from paddle_tpu.observability import spans

    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("bench/loss_fetch"):
            with jax.profiler.TraceAnnotation("somebody/elses"):
                with spans.span("serve/step"):
                    with spans.span("decode/run"):
                        time.sleep(0.002)
    finally:
        jax.profiler.stop_trace()
    found = sorted((tmp_path / "plugins" / "profile").glob("*/*.xplane.pb"))
    got = T.load_xplane(str(found[-1])).spans
    assert [n for n, _, _ in got] == ["loss_fetch", "serve/step",
                                      "decode/run"]
    _, (_, s0, d0), (_, s1, d1) = got
    assert s0 <= s1 and s1 + d1 <= s0 + d0 and d1 >= 2e6
    # and the innermost of them owns the stretch it covers
    line = T.innermost_timeline(got)
    assert [n for _, _, n in line] == ["loss_fetch", "serve/step",
                                       "decode/run", "serve/step",
                                       "loss_fetch"]


# ---------------------------------------------------------------------------
# each reader on the rehearsal entry, traced
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("cell", [w["name"] for w in MANIFEST["workloads"]])
def test_rehearsal_traced_reports_the_program_span_readers(cell):
    got = checks.traced_rehearsal_reports_the_program_span_readers(ROOT,
                                                                   cell)
    # today's two cells, by name, report what they did when the eight
    # arrived; a later cell is held to the lists it put itself on
    pinned = {"serve_cgpt1p3b_closed14": {
        "serve_queue_wait_ms", "serve_sched_self_ms", "serve_tick_ms",
        "serve_logits_fetch_ms", "serve_prefill_share"},
        "train_cgpt1p3b_l6_1chip": {"train_dispatch_ms", "train_step_ms"}}
    assert pinned.get(cell, set()) <= set(got)
