"""The join of the program's ring with the profiler's trace
(``benchmark/span_join.py``) on a planted ring and a planted profile: the
clock constant, a span the session did not see, a tick's three quantities
by hand, the skew that cannot move them, what is counted and left out; and
the four readers on each serving cell's traced rehearsal."""
import json
import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import checks, harness, span_join as SJ  # noqa: E402
from benchmark import trace_reduce as T  # noqa: E402

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)
NEW = {"serve_call_overhead_ms": "device_trace",
       "serve_wakeup_ms": "device_trace",
       "serve_call_ms": "program_span",
       "serve_span_join_share": "program_span"}
SERVING = [w["name"] for w in MANIFEST["workloads"]
           if w["name"].startswith("serve_")]
US = 1000
K = 7_000_000_123           # profiler's host clock - ring clock, ns
PLANE = "/device:TPU:0"


def _reader(name):
    return harness.load_module(os.path.join(
        ROOT, "benchmark", "layer_metrics", name + ".py"))


def _planted(with_calls=True):
    """A prefill and three ticks in microseconds of the ring's clock, the
    profiler's host clock ``K`` further on, the device's events on that.

    Step 0 (open when the session starts at 60, so never annotated):
    a 512-rung prefill called at 350 for 700, its program 1200..7700, its
    run over at 8500; then tick 0, which the step feeds: called at 10250
    for 600 inside its run, program 10900..18900, run over at 20000; the
    plan after the emit calls tick 1 at 21300 for 500, program
    21900..30000. Step 1 collects it (run over at 30900) and calls tick 2
    at 31100 for 500, program 31750..39650. Step 2 collects that (run
    over at 40450) and calls tick 3 at 40600, program 41700..44700, which
    is dropped: no run names its call. A last call at 46100 has no
    program (the session ended), and a program at -5000 has no call.
    """
    ids = iter(range(100, 10_000))
    records, by_name = [], {}

    def rec(name, start, end, parent=None, trace=1, **attrs):
        r = {"name": name, "trace": trace, "span": next(ids),
             "parent": parent, "start_ns": start * US,
             "dur_ns": (end - start) * US, "tid": 1, "thread": "loop"}
        if attrs:
            r["attrs"] = attrs
        records.append(r)
        by_name.setdefault(name, []).append(r)
        return r["span"]

    def call(name, start, end, parent, exe):
        if with_calls:
            return rec(name, start, end, parent, exe=exe)

    def run(name, start, end, parent, called):
        attrs = {"sampler": "greedy"}
        if with_calls:
            attrs["call"] = called
        return rec(name, start, end, parent, **attrs)

    s0 = rec("serve/step", 0, 22000, step=0, worked=True, prefills=1)
    rec("serve/queue_wait", 10, 100, trace=9)
    pf = rec("serve/prefill", 100, 9000, trace=9, step=0, bucket=512)
    pr = run("prefill/run", 300, 8500, pf, None)
    pc = call("prefill/call", 350, 1050, pr, "prefill_b512")
    d0 = rec("serve/decode_tick", 10100, 21000, s0, step=0, ahead=False)
    r0 = run("decode/run", 10200, 20000, d0, None)
    c0 = call("decode/call", 10250, 10850, r0, "decode")
    pl0 = rec("decode/plan", 21200, 21900, s0)
    c1 = call("decode/call", 21300, 21800, pl0, "decode")
    s1 = rec("serve/step", 22000, 34000, step=1, worked=True, prefills=0)
    d1 = rec("serve/decode_tick", 22100, 33000, s1, step=1, ahead=True)
    run("decode/run", 22150, 30900, d1, c1)
    pl1 = rec("decode/plan", 30950, 31700, d1)
    c2 = call("decode/call", 31100, 31600, pl1, "decode")
    s2 = rec("serve/step", 34000, 46000, step=2, worked=True, prefills=0)
    d2 = rec("serve/decode_tick", 34100, 45000, s2, step=2, ahead=True)
    run("decode/run", 34150, 40450, d2, c2)
    pl2 = rec("decode/plan", 40500, 41200, d2)
    call("decode/call", 40600, 41100, pl2, "decode")
    s3 = rec("serve/step", 46000, 47000, step=3, worked=True, prefills=0)
    call("decode/call", 46100, 46600, s3, "decode")
    if with_calls:
        for r, called in ((pr, pc), (r0, c0)):
            next(x for x in records if x["span"] == r)["attrs"][
                "call"] = called
    # every span()-made record the session saw, a nanosecond or two out
    annotations = [
        (r["name"], r["start_ns"] + K + i % 3 - 1, r["dur_ns"], r["span"])
        for i, r in enumerate(records)
        if r["name"] != "serve/queue_wait" and r["start_ns"] >= 60 * US]
    programs = [("jit__prefill_fn_paged(1)", -5000, 1000),
                ("jit__prefill_fn_paged(3)", 1200, 6500),
                ("jit__decode_fn_paged(2)", 10900, 8000),
                ("jit__decode_fn_paged(2)", 21900, 8100),
                ("jit__decode_fn_paged(2)", 31750, 7900),
                ("jit__decode_fn_paged(2)", 41700, 3000),
                ("jit_convert_element_type(5)", 45000, 10)]
    modules = [(n, s * US + K, d * US) for n, s, d in programs]
    return types.SimpleNamespace(
        records=records, annotations=annotations, modules=modules,
        window=(60 * US, 47000 * US), by_name=by_name)


def _join(p, shift_ns=0):
    modules = [(n, s + shift_ns, d) for n, s, d in p.modules]
    return SJ.Join(p.records, p.annotations, modules, p.window)


def _profile(p):
    ops = [("fusion.1 fusion", s, d) for n, s, d in p.modules]
    return T.Profile({PLANE: ops}, {PLANE: p.modules},
                     [a[:3] for a in p.annotations])


# ---------------------------------------------------------------------------
# record <-> annotation
# ---------------------------------------------------------------------------

def test_the_clock_constant_comes_from_the_joined_pairs():
    p = _planted()
    j = _join(p)
    assert j.offset_ns == K and j.offset_spread_ns == 2
    assert len(j.found) == len(p.annotations) == len(p.records) - 2
    # every annotated record inside the window found its annotation
    assert j.share == 100.0
    # one that lost its id does not, and the constant stands
    lost = [(n, s, d, None if n == "decode/plan" else i)
            for n, s, d, i in p.annotations]
    j = SJ.Join(p.records, lost, p.modules, p.window)
    assert j.offset_ns == K
    assert j.share == pytest.approx(100.0 * (len(j.annotated) - 3)
                                    / len(j.annotated))
    # a program whose annotations carry no id: no constant, nothing joined
    bare = [(n, s, d, None) for n, s, d, _ in p.annotations]
    j = SJ.Join(p.records, bare, p.modules, p.window)
    assert j.offset_ns is None and j.share == 0.0 and not j.pairs
    assert SJ.lines(j) == []


def test_a_span_open_when_the_session_began_is_placed_by_the_constant():
    p = _planted()
    j = _join(p)
    step0, wait = p.by_name["serve/step"][0], p.by_name["serve/queue_wait"][0]
    assert {r["span"] for r in j.unannotated} == {step0["span"],
                                                  wait["span"]}
    assert j.placed(step0) == (K, 22000 * US + K)
    assert j.placed(wait) == (10 * US + K, 100 * US + K)
    # neither counts against the gauge: one is not inside the window, the
    # other was timed elsewhere and is no annotation's name
    assert all(r["span"] not in (step0["span"], wait["span"])
               for r in j.annotated)
    assert "without an annotation: serve/queue_wait 1, serve/step 1" in (
        SJ.lines(j)[0])


def test_load_annotations_reads_the_span_stat_of_a_real_capture(tmp_path):
    import jax

    from paddle_tpu.observability import spans

    tracer = spans.default_tracer()
    tracer.clear()
    with spans.span("serve/step"):                 # open when it starts
        jax.profiler.start_trace(str(tmp_path))
        try:
            with jax.profiler.TraceAnnotation("bench/loss_fetch"):
                with spans.span("decode/run"):
                    with spans.span("decode/call", attrs={"exe": "decode"}):
                        pass
            spans.record("serve/queue_wait", spans.clock_ns(), 5)
        finally:
            jax.profiler.stop_trace()
    found = sorted((tmp_path / "plugins" / "profile").glob("*/*.xplane.pb"))
    got = SJ.load_annotations(str(found[-1]))
    ring = {r["name"]: r for r in tracer.spans()}
    assert [(n, i) for n, _, _, i in got] == [
        ("decode/run", ring["decode/run"]["span"]),
        ("decode/call", ring["decode/call"]["span"])]
    j = SJ.Join(tracer.spans(), got, [], (0, 2 ** 62))
    assert j.share == 100.0 and j.offset_spread_ns < 1e6
    assert {r["name"] for r in j.unannotated} == {"serve/step",
                                                  "serve/queue_wait"}
    lo, hi = j.placed(ring["serve/step"])
    assert lo <= got[0][1] and got[0][1] + got[0][2] <= hi


# ---------------------------------------------------------------------------
# call <-> program <-> run
# ---------------------------------------------------------------------------

def test_three_ticks_and_a_prefill_by_hand():
    j = _join(_planted())
    assert [p.exe for p in j.pairs] == ["prefill_b512"] + ["decode"] * 4
    t0, t1, t2 = j.ticks()
    assert [(t.round_trip_ns, t.program_ns, t.call_ns, t.overhead_ns,
             t.beyond_call_ns) for t in (t0, t1, t2)] == [
        (9750 * US, 8000 * US, 600 * US, 1750 * US, 1150 * US),
        (9600 * US, 8100 * US, 500 * US, 1500 * US, 1000 * US),
        (9350 * US, 7900 * US, 500 * US, 1450 * US, 950 * US)]
    # the first was fed by its own step, the others dispatched ahead
    assert j.ticks(ahead=False) == [t0] and j.ticks(ahead=True) == [t1, t2]
    (pre,) = [p for p in j.joined if p.exe == "prefill_b512"]
    assert (pre.round_trip_ns, pre.program_ns, pre.call_ns, pre.overhead_ns,
            pre.beyond_call_ns) == (8150 * US, 6500 * US, 700 * US,
                                    1650 * US, 950 * US)
    # lag + program + wake is the round trip, whatever the timeline
    for p in j.joined:
        assert p.lag_ns + p.program_ns + p.wake_ns == p.round_trip_ns
    assert [(p.lag_ns, p.wake_ns) for p in j.joined] == [
        (850 * US, 800 * US), (650 * US, 1100 * US), (600 * US, 900 * US),
        (650 * US, 800 * US)]


def test_what_does_not_pair_is_counted_and_left_out():
    p = _planted()
    j = _join(p)
    # the dropped tick ran on the device: paired, and no run names it
    assert len(j.pairs) - len(j.joined) == 1
    assert j.pairs[-1].run is None and j.pairs[-1].program_ns == 3000 * US
    # the call after the session's last program; the program before any
    # call; a program of another name is nobody's
    assert [c["start_ns"] for c in j.lone_calls] == [46100 * US]
    assert [m[1] for m in j.lone_programs] == [-5000 * US + K]
    assert not j.refused
    line = SJ.lines(j)[1]
    assert line == (
        "calls and programs: 5 of 6 calls paired with their program, 1 "
        "calls inside the window with no program, 1 programs with no call, "
        "0 pairs refused, 1 calls that no run names, 0 queued behind "
        "another's program or collected behind it (left out of the medians)")
    # a call with no program in the middle: its successor's program is not
    # taken for its own
    p.modules = [m for m in p.modules if m[1] != 21900 * US + K]
    j = _join(p)
    assert [c["start_ns"] for c in j.lone_calls] == [21300 * US,
                                                     46100 * US]
    assert [t.program_ns for t in j.ticks()] == [8000 * US, 7900 * US]


@pytest.mark.parametrize("shift_us", [0, 800, -800])
def test_a_prefill_called_while_a_tick_is_in_flight_queues_behind_it(
        shift_us):
    """A request that arrives after a tick was dispatched ahead is
    prefilled by the next step before that step collects the tick: two
    calls 1.9 ms apart, the second inside 2 ms of the first's program, and
    the programs one behind the other. They pair in order."""
    ids = iter(range(1, 100))

    def rec(name, start, end, parent=None, **attrs):
        return {"name": name, "trace": 1, "span": next(ids),
                "parent": parent, "start_ns": start * US,
                "dur_ns": (end - start) * US, "attrs": attrs}

    plan = rec("decode/plan", 800, 1550)
    d = rec("decode/call", 1000, 1500, plan["span"], exe="decode")
    pr = rec("prefill/run", 2850, 16500)
    p = rec("prefill/call", 2900, 3500, pr["span"], exe="prefill_b256")
    pr["attrs"]["call"] = p["span"]
    tick = rec("serve/decode_tick", 16600, 18000, ahead=True)
    run = rec("decode/run", 16700, 16800, tick["span"], call=d["span"])
    records = [plan, d, pr, p, tick, run]
    annotations = [(r["name"], r["start_ns"] + K, r["dur_ns"], r["span"])
                   for r in records]
    modules = [("jit__decode_fn_paged(2)", (1300 + shift_us) * US + K,
                8000 * US),
               ("jit__prefill_fn_paged(3)", (9300 + shift_us) * US + K,
                6500 * US)]
    j = SJ.Join(records, annotations, modules, (0, 20000 * US))
    assert not (j.lone_calls or j.lone_programs or j.refused)
    assert [(q.exe, q.program_ns) for q in j.joined] == [
        ("decode", 8000 * US), ("prefill_b256", 6500 * US)]
    # the tick's round trip holds the prefill it was collected behind, the
    # prefill's its wait for the tick: true of both, typical of neither,
    # so they are counted and kept out of the medians; the skew's bounds
    # hold for them as for any
    tick_pair, prefill_pair = j.joined
    assert tick_pair.overhead_ns == (16800 - 1000 - 8000) * US
    assert prefill_pair.overhead_ns == (16500 - 2900 - 6500) * US
    assert tick_pair.queued and prefill_pair.queued
    assert j.alone == [] and j.ticks() == []
    assert "2 queued behind another's program" in SJ.lines(j)[1]
    assert j.skew_interval_ns() == ((shift_us - 700) * US,
                                    (300 + shift_us) * US)


def test_a_program_longer_than_its_round_trip_is_refused():
    p = _planted()
    p.modules = [(n, s, 9700 * US if s == 21900 * US + K else d)
                 for n, s, d in p.modules]
    j = _join(p)
    assert [(c["start_ns"], m[2]) for c, m in j.refused] == [
        (21300 * US, 9700 * US)]
    assert [t.program_ns for t in j.ticks()] == [8000 * US, 7900 * US]
    # and one of the wrong kind for its call
    p = _planted()
    p.modules = [("jit__prefill_fn_paged(3)" if s == 31750 * US + K else n,
                  s, d) for n, s, d in p.modules]
    j = _join(p)
    assert [c["start_ns"] for c, _ in j.refused] == [31100 * US]
    assert "1 pairs refused" in SJ.lines(j)[1]


# ---------------------------------------------------------------------------
# the skew
# ---------------------------------------------------------------------------

def _run_with(join, profile=None):
    run = types.SimpleNamespace(window=None, profile=profile)
    run._span_join = (join,)
    return run


@pytest.mark.parametrize("shift_us", [800, -800])
def test_a_skewed_device_timeline_moves_neither_metric(shift_us):
    p = _planted()
    straight, skewed = _join(p), _join(p, shift_us * US)
    for name, want in (("serve_call_overhead_ms", 1.5),
                       ("serve_wakeup_ms", 0.975)):
        a = _reader(name).read(_run_with(straight))
        b = _reader(name).read(_run_with(skewed))
        assert a == b == want, name          # to the nanosecond
    assert [(t.overhead_ns, t.beyond_call_ns) for t in skewed.ticks()] == [
        (t.overhead_ns, t.beyond_call_ns) for t in straight.ticks()]
    # what the shared timeline says does move, and the interval with it
    assert straight.skew_interval_ns() == (-800 * US, 600 * US)
    assert skewed.skew_interval_ns() == ((-800 + shift_us) * US,
                                         (600 + shift_us) * US)
    assert [p_.lag_ns - q.lag_ns for p_, q in
            zip(skewed.joined, straight.joined)] == [shift_us * US] * 4


def test_the_printed_lines_of_a_traced_run():
    p = _planted()
    join, profile = _join(p), _profile(p)
    lines = SJ.lines(join, profile)
    assert len(lines) == 5
    assert lines[2] == (
        "skew interval: the device's events lie [-0.8000, 0.6000] ms too "
        "late on the trace's timeline (width 1.4000, 4 programs); medians "
        "as observed lag 0.6500 wake 0.8500 ms, at the ends lag 1.4500 "
        "wake 0.0500 ms and lag 0.0500 wake 1.4500 ms")
    # idle_gaps' division of the gaps at the programs' edges: the wait
    # after a program against the plan and the call before the next
    assert lines[3].startswith(
        "idle under decode/run : decode/plan + decode/call, s: observed "
        "0.002900 : 0.003250, at -0.8000 ms ")
    for skew in (-800 * US, 600 * US):
        moved = T.Profile(
            {PLANE: [(n, s - skew, d) for n, s, d in profile.devices[PLANE]]},
            {}, profile.spans)
        idle = dict(T.idle_by_span(moved))
        assert SJ._division(profile, skew) == pytest.approx(
            (idle["decode/run"], idle["decode/plan"] + idle["decode/call"]))
    # taken 0.6 ms earlier the three collected ticks' waits are that much
    # longer, and the first's program begins inside its call
    assert SJ._division(profile, 600 * US)[0] == pytest.approx(
        0.0029 + 3 * 0.0006 - 0.00005)
    assert lines[4] == (
        "device programs by calling span: decode n=3 program_ms=8.0000 "
        "call_ms=0.5000 overhead_ms=1.5000 beyond_call_ms=1.0000; "
        "prefill_b512 n=1 program_ms=6.5000 call_ms=0.7000 "
        "overhead_ms=1.6500 beyond_call_ms=0.9500")


# ---------------------------------------------------------------------------
# the readers
# ---------------------------------------------------------------------------

def test_the_four_readers_are_in_the_manifest_with_the_serving_cells():
    by_name = {m["name"]: m for m in MANIFEST["per_layer"]}
    assert [m["name"] for m in MANIFEST["per_layer"]][-4:] == list(NEW)
    for name, source in NEW.items():
        entry = by_name[name]
        assert entry["source"] == source and entry["workloads"] == SERVING
        assert entry["moves"] == "serve_tokens_per_s"
        assert _reader(name).META["source"] == source


def test_a_ring_without_the_call_spans_reports_nothing():
    """The parent's program: the same spans less ``decode/call``,
    ``prefill/call`` and the ``call`` attribute."""
    p = _planted(with_calls=False)
    j = _join(p)
    assert j.offset_ns == K and not j.pairs and j.ticks() == []
    assert j.skew_interval_ns() is None
    run = _run_with(j, _profile(p))
    run._program_spans = ({n: rs for n, rs in p.by_name.items()}, None)
    assert _reader("serve_call_overhead_ms").read(run) is None
    assert _reader("serve_wakeup_ms").read(run) is None
    assert _reader("serve_call_ms").read(run) is None
    assert _reader("serve_span_join_share").read(run) == 100.0
    # no join at all (no trace, a ring that lost part of the window, no
    # id on any annotation): nothing from three of them
    run = _run_with(None)
    run._program_spans = (None, None)
    for name in NEW:
        assert _reader(name).read(run) is None
    # and the call's own length from the ring alone
    p = _planted()
    run._program_spans = (p.by_name, None)
    assert _reader("serve_call_ms").read(run) == 0.5


def test_the_ring_is_refused_where_it_lost_part_of_the_traced_window():
    from paddle_tpu.observability import spans

    tracer = spans.SpanTracer(ring=4)
    mod = types.SimpleNamespace(default_tracer=lambda: tracer,
                                monotonic_to_ns=lambda t: int(t * 1e9))
    for i in range(6):                    # 1000..1100, 2000..2100, ...
        tracer.record("tick", i * 1000 + 1000, 100, trace=1)
    assert tracer.dropped == 2            # the ring holds 3000..6000
    got, window = SJ.ring_records(mod, (3.5e-6, 5.05e-6))
    # what overlaps the window, its edge too (5000..5100)
    assert [r["start_ns"] for r in got] == [4000, 5000]
    assert window == (3500, 5050)
    assert SJ.ring_records(mod, (2e-6, 7e-6)) is None
    assert SJ.ring_records(mod, None) is None
    run = types.SimpleNamespace(trace_window=None, trace_dir="/nonexistent",
                                profile=None)
    assert SJ.read(run) is None and SJ.read(run) is None


@pytest.mark.parametrize("cell", SERVING)
def test_rehearsal_traced_reports_the_join_and_the_call(cell):
    assert len(SERVING) == 4
    got = checks.traced_rehearsal_reports_the_program_span_readers(ROOT,
                                                                   cell)
    assert got["serve_call_ms"]["value"] > 0
    assert got["serve_span_join_share"]["value"] > 99.0
    # the CPU has no device plane: nothing to pair a call with
    assert "serve_call_overhead_ms" not in got
    assert "serve_wakeup_ms" not in got
