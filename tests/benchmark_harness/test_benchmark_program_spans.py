"""The readers of the program's own spans: the ring inside a window (and
the refusal where the ring may have lost part of it), the ``paddle/``
annotations of a trace with planted gaps, and each reader on the rehearsal
entry, traced."""
import io
import json
import os
import sys
import time
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import harness, program_spans as PS  # noqa: E402
from benchmark import trace_reduce as T  # noqa: E402

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)
NEW = ["serve_queue_wait_ms", "serve_sched_self_ms", "serve_tick_ms",
       "serve_logits_fetch_ms", "serve_prefill_share", "serve_host_gap_ms",
       "serve_idle_unattributed", "train_dispatch_ms"]


def _reader(name):
    return harness.load_module(os.path.join(
        ROOT, "benchmark", "layer_metrics", name + ".py"))


def test_the_eight_readers_are_the_manifests_last_eight():
    assert [m["name"] for m in MANIFEST["per_layer"][-8:]] == NEW
    cells = {w["name"]: w for w in MANIFEST["workloads"]}
    for m in MANIFEST["per_layer"][-8:]:
        (cell,) = m["workloads"]
        assert cells[cell]["traffic"].startswith(
            "train" if m["name"].startswith("train") else "closed")
        assert m["source"] == ("device_trace" if m["name"] in (
            "serve_host_gap_ms", "serve_idle_unattributed")
            else "program_span")


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
    and the host dawdles under serve/decode_tick alone (the benchmark's
    wrappers, say) until the fetch begins at ``fetch2``."""
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


def test_idle_goes_to_the_innermost_program_span_at_the_gaps_middle():
    p = _planted()
    # gaps: 0..150 (middle 75: serve/step alone), 800..1150 (middle 975:
    # serve/step, past serve/emit's end at 950), 1700..2000 (middle 1850:
    # serve/decode_tick alone, between decode/run's end and the fetch)
    assert dict(PS.idle_by_span(p)) == pytest.approx(
        {"serve/step": 500e-6, "serve/decode_tick": 300e-6})
    assert PS.idle_line(p) == ("idle by program span: serve/step "
                               "0.000500, serve/decode_tick 0.000300")
    assert PS.unattributed_idle_share(p) == pytest.approx(100.0)
    # with the host's work after the tick under a leaf span (the fetch
    # begins where the run ends), that idle has a name
    q = _planted(fetch2=1710)
    assert dict(PS.idle_by_span(q)) == pytest.approx(
        {"serve/step": 500e-6, "decode/fetch_logits": 300e-6})
    assert PS.unattributed_idle_share(q) == pytest.approx(100.0 * 500 / 800)
    assert PS.is_leaf("prefill/run") and PS.is_leaf("serve/loop_idle")
    assert not PS.is_leaf("serve/decode_tick")
    assert not PS.is_leaf(T.OUTSIDE)


def test_the_two_trace_readers_on_a_run_with_a_planted_trace(
        tmp_path, monkeypatch, capsys):
    planted = _planted()
    trace_dir = tmp_path / "trace"
    (trace_dir / "plugins" / "profile" / "t0").mkdir(parents=True)
    (trace_dir / "plugins" / "profile" / "t0" / "h.xplane.pb").write_bytes(
        b"")
    monkeypatch.setattr(PS, "annotations", lambda path: planted.spans)
    run = types.SimpleNamespace(
        window=None, trace_dir=str(trace_dir),
        profile=T.Profile(planted.devices, planted.modules, []))
    assert _reader("serve_host_gap_ms").read(run) == pytest.approx(
        (0.150 + 0.250) / 2)
    assert _reader("serve_idle_unattributed").read(run) == pytest.approx(
        100.0)
    # the line the ledger's notes can carry, once a run
    out = capsys.readouterr().out
    assert out.count("[bench] idle by program span: serve/step") == 1
    # no device plane (a rehearsal on the CPU), or no annotation of the
    # program's: nothing is reported
    for profile, spans in ((T.Profile({}, {}, []), planted.spans),
                           (run.profile, [])):
        monkeypatch.setattr(PS, "annotations", lambda path, s=spans: s)
        bare = types.SimpleNamespace(window=None, profile=profile,
                                     trace_dir=str(trace_dir))
        assert _reader("serve_host_gap_ms").read(bare) is None
        assert _reader("serve_idle_unattributed").read(bare) is None


def test_annotations_keeps_the_programs_spans_of_a_real_capture(tmp_path):
    import jax

    from paddle_tpu.observability import spans

    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("bench/not_ours"):
            with spans.span("serve/step"):
                with spans.span("decode/run"):
                    time.sleep(0.002)
    finally:
        jax.profiler.stop_trace()
    found = sorted((tmp_path / "plugins" / "profile").glob("*/*.xplane.pb"))
    got = PS.annotations(str(found[-1]))
    assert [n for n, _, _ in got] == ["serve/step", "decode/run"]
    (_, s0, d0), (_, s1, d1) = got
    assert s0 <= s1 and s1 + d1 <= s0 + d0 and d1 >= 2e6


# ---------------------------------------------------------------------------
# each reader on the rehearsal entry, traced
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("cell", [w["name"] for w in MANIFEST["workloads"]])
def test_rehearsal_traced_reports_the_program_span_readers(cell):
    out = io.StringIO()
    result = harness.run_cell(ROOT, cell, 2 ** 31 + 11, 1.0, 1,
                              rehearsal=True, out=out)
    got = result["metrics"]
    want = [m["name"] for m in MANIFEST["per_layer"][-8:]
            if cell in m["workloads"] and m["source"] == "program_span"]
    assert want and all(got[name]["value"] > 0 or name ==
                        "serve_prefill_share" for name in want), got
    # the CPU has no device plane: the two readers of the trace report
    # nothing there
    assert "serve_host_gap_ms" not in got
    assert "serve_idle_unattributed" not in got
    if "serve_tick_ms" in got:
        # the program's tick and its scheduler's own time make up the
        # benchmark's span round the same step
        inner = (got["serve_tick_ms"]["value"]
                 + got["serve_sched_self_ms"]["value"])
        assert inner == pytest.approx(got["decode_tick_ms"]["value"],
                                      rel=0.5)
        assert got["serve_queue_wait_ms"]["value"] == pytest.approx(
            got["sched_queue_ms"]["value"], rel=0.5)
        assert 0 < got["serve_prefill_share"]["value"] < 100
        assert got["serve_logits_fetch_ms"]["value"] < \
            got["serve_tick_ms"]["value"]
    else:
        assert got["train_dispatch_ms"]["value"] <= \
            got["train_step_ms"]["value"] * 1.5
