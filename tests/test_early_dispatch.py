"""Early dispatch (docs/serving.md "The tick's anatomy"): under a scheduler
the engine dispatches tick n+1 as soon as tick n's sampled tokens are on the
host, before it fetches tick n's logits, unless the next step could decide
something that tick would pre-empt. Same executable, same inputs, same
order: every token and every logit is what synchronous calls give.

Each family's rehearsal engine (GPT block, hybrid of Mamba and attention
layers, latent cache with sparse experts) is built once and shared; a test
leaves it with every slot free and nothing in flight.
"""
import threading
import time

import numpy as np
import pytest

import jax

from paddle_tpu import serving
from paddle_tpu.models import gpt as G
from paddle_tpu.models import jamba as J
from paddle_tpu.models import kimi_k2 as K
from paddle_tpu.observability import metrics as om
from paddle_tpu.observability import spans
from paddle_tpu.serving.server import EngineLoop

FAMILIES = {
    "gpt": (G, G.GPT_TINY.scaled(num_layers=2, max_seq_len=64)),
    "hybrid": (J, J.JAMBA_TINY),
    "latent": (K, K.KIMI_K2_TINY.scaled(experts_held=4, first_expert=4)),
}
# 13 allocatable pages of 8 rows for 4 slots of up to 64: short requests
# never meet the pool's end, four long ones do (the dry-pool test)
ENGINE = dict(max_batch=4, max_seq=64, page_size=8, num_pages=14,
              prefill_buckets=(8, 16), prefix_cache=False)
PROMPTS = [[1, 2, 3, 4, 5], [6, 7, 8], [9, 10, 11, 12, 13, 14, 15],
           [16, 17], [18, 19, 20, 21], [22, 23, 24, 25, 26, 27]]


def _build(family, **kw):
    mod, cfg = FAMILIES[family]
    params = mod.init_params(jax.random.PRNGKey(0), cfg)
    eng = serving.DecodeEngine(params, cfg,
                               serving.EngineConfig(**dict(ENGINE, **kw)))
    eng.warmup()
    return eng


_ENGINES = {}


@pytest.fixture(params=list(FAMILIES))
def engine(request):
    if request.param not in _ENGINES:
        _ENGINES[request.param] = _build(request.param)
    eng = _ENGINES[request.param]
    yield eng
    assert eng.ahead_feed is None and not eng.cache.live_slots()
    assert eng.next_tick is None and eng.poisoned is None


@pytest.fixture()
def tracer():
    tr = spans.default_tracer()
    tr.clear()
    return tr


def _counter(outcome):
    fam = om.default_registry().snapshot().get(
        "paddle_serve_early_dispatch_total", {})
    return sum(s["value"] for s in fam.get("series", [])
               if s["labels"] == (outcome,))


class Calls:
    """The engine's entries as a scheduler drove them, call by call, with
    what each returned: ``("start", prompt) -> (slot, logits, token)``,
    ``("decode", feed) -> {slot: (token, logits)}``, ``("free", slot)``."""

    def __init__(self, engine, monkeypatch):
        self.log = []
        start, decode, free = (engine.start_sequence_sampled,
                               engine.decode_step_sampled,
                               engine.free_sequence)

        def started(tokens, params):
            out = start(tokens, params)
            self.log.append(("start", list(tokens), out))
            return out

        def decoded(feed, params):
            out = decode(feed, params)
            self.log.append(("decode", dict(feed), out))
            return out

        def freed(slot):
            self.log.append(("free", slot, None))
            return free(slot)

        monkeypatch.setattr(engine, "start_sequence_sampled", started)
        monkeypatch.setattr(engine, "decode_step_sampled", decoded)
        monkeypatch.setattr(engine, "free_sequence", freed)

    def replay(self, engine):
        """The same calls, made directly and one after another: nothing is
        in flight when the next is made. Yields (recorded, direct)."""
        for kind, arg, out in self.log:
            if kind == "start":
                yield out, engine.start_sequence_sampled(
                    arg, serving.sampling.GREEDY)
            elif kind == "decode":
                assert engine.ahead_feed is None
                yield out, engine.decode_step_sampled(arg, None)
            else:
                engine.free_sequence(arg)


def _outcomes(sched, monkeypatch):
    """[(step, outcome)] of every question the engine asks ``sched``."""
    asked = []
    inner = sched._next_tick

    def spy(sampled):
        outcome, plan = inner(sampled)
        asked.append((sched.steps, outcome))
        return outcome, plan

    monkeypatch.setattr(sched, "_next_tick", spy)
    return asked


def _run(sched, reqs, steps=200):
    for _ in range(steps):
        if all(r.finished.is_set() for r in reqs):
            return
        sched.step()
    raise AssertionError([r.state for r in reqs])


def test_a_scheduler_run_hands_out_what_direct_synchronous_calls_give(
        engine, monkeypatch):
    calls = Calls(engine, monkeypatch)
    sched = serving.Scheduler(engine)
    asked = _outcomes(sched, monkeypatch)
    reqs = [sched.submit(p, max_new_tokens=5 + i)
            for i, p in enumerate(PROMPTS[:2])]
    for i in range(200):
        if i in (2, 3, 6):          # arrivals beside ticks in flight
            reqs.append(sched.submit(PROMPTS[len(reqs)],
                                     max_new_tokens=4 + i))
        sched.step()
        if i > 6 and all(r.finished.is_set() for r in reqs):
            break
    assert [r.state for r in reqs] == ["done"] * 5
    assert [o for _, o in asked].count("ahead") >= 8
    monkeypatch.undo()                   # the engine's own entries again
    compared = 0
    for recorded, direct in calls.replay(engine):
        if isinstance(recorded, dict):
            assert recorded.keys() == direct.keys()
            for slot in recorded:
                assert recorded[slot][0] == direct[slot][0]
                np.testing.assert_array_equal(recorded[slot][1],
                                              direct[slot][1])
                compared += 1
        else:
            assert recorded[0] == direct[0] and recorded[2] == direct[2]
            np.testing.assert_array_equal(recorded[1], direct[1])
    assert compared == sum(len(r.tokens) - 1 for r in reqs)
    # request by request: its first token from its prefill, then one a tick
    owner, streams = {}, {}
    for kind, arg, out in calls.log:
        if kind == "start":
            owner[out[0]] = tuple(arg)
            streams[tuple(arg)] = [out[2]]
        elif kind == "decode":
            for slot, (tok, _) in out.items():
                streams[owner[slot]].append(tok)
    for r in reqs:
        assert streams[tuple(r.prompt)] == r.tokens


def test_plain_ticks_run_ahead_and_an_admission_is_never_behind_one(
        engine, tracer, monkeypatch):
    sched = serving.Scheduler(engine)
    asked = _outcomes(sched, monkeypatch)
    before = {k: _counter(k) for k in ("ahead", "held_admission",
                                       "held_idle")}
    # four riders fill the slots; two more wait for a slot
    reqs = [sched.submit(p, max_new_tokens=4 + 2 * i)
            for i, p in enumerate(PROMPTS)]
    _run(sched, reqs)
    by_step = dict(asked)
    ticks = [s for s in tracer.spans() if s["name"] == "serve/decode_tick"]
    assert [t["attrs"]["step"] for t in ticks] == list(range(len(ticks)))
    assert len(asked) == len(ticks)             # one question a tick
    prefills = {s["attrs"]["step"] for s in tracer.spans()
                if s["name"] == "serve/prefill"}
    assert prefills == {0, 3, 5}
    for step in sorted(prefills - {0}):
        # a slot was freed by that tick's stop with a request waiting
        assert by_step[step - 1] == "held_admission"
        assert not ticks[step]["attrs"]["ahead"]
    # a queue that cannot be admitted (no slot) holds nothing back
    assert by_step[0] == by_step[1] == "ahead"
    assert asked[-1][1] == "held_idle"          # no rider continues
    held = [o for _, o in asked if o != "ahead"]
    assert held == ["held_admission", "held_admission", "held_idle"]
    for t in ticks[1:]:
        assert t["attrs"]["ahead"] == (
            by_step[t["attrs"]["step"] - 1] == "ahead")
    got = {k: _counter(k) - v for k, v in before.items()}
    assert got == {"ahead": len(asked) - 3, "held_admission": 2,
                   "held_idle": 1}
    assert sched.early_dispatch == got
    assert sched.early_dispatch_share() == got["ahead"] / len(asked)
    # no prefill starts while a tick dispatched after its request's submit
    # is in flight (dispatch: inside the decode/plan of the tick before;
    # collected: the end of its own decode/run)
    ss = tracer.spans()
    plans = [s for s in ss if s["name"] == "decode/plan"]
    assert len(plans) == len(ticks)             # one a tick, in order
    for t in (t for t in ticks if t["attrs"]["ahead"]):
        plan = plans[t["attrs"]["step"] - 1]
        run = next(s for s in ss if s["name"] == "decode/run"
                   and s["parent"] == t["span"])
        for r in reqs:
            pre = next(s for s in tracer.trace_spans(r.trace_id)
                       if s["name"] == "serve/prefill")
            inside = (plan["start_ns"] < pre["start_ns"]
                      < run["start_ns"] + run["dur_ns"])
            assert not (inside and r.submit_ns < plan["start_ns"])


def test_an_arrival_beside_a_tick_in_flight_rides_from_the_next(
        engine, tracer):
    sched = serving.Scheduler(engine)
    first = sched.submit(PROMPTS[0], max_new_tokens=8)
    sched.step()
    sched.step()
    assert engine.ahead_feed == {first.slot: first.tokens[-1]}
    late = sched.submit(PROMPTS[1], max_new_tokens=3)
    sched.step()             # prefills late beside the tick in flight
    assert late.state == "active" and len(late.tokens) == 1
    assert engine.ahead_feed == {first.slot: first.tokens[-1],
                                 late.slot: late.tokens[-1]}
    _run(sched, [first, late])
    ticks = {t["attrs"]["step"]: t["attrs"] for t in tracer.spans()
             if t["name"] == "serve/decode_tick"}
    assert ticks[2]["riders"] == [first.id] and ticks[2]["ahead"]
    assert ticks[3]["riders"] == [first.id, late.id] and ticks[3]["ahead"]
    assert (late.first_step, late.last_step) == (2, 4)


def test_a_rider_expired_in_flight_has_its_lane_dropped_and_its_slot_reused(
        engine):
    want = {}
    for p in (PROMPTS[0], PROMPTS[2]):
        sched = serving.Scheduler(engine)
        r = sched.submit(p, max_new_tokens=6)
        _run(sched, [r])
        want[tuple(p)] = r.tokens
    sched = serving.Scheduler(engine)
    stays = sched.submit(PROMPTS[0], max_new_tokens=6)
    goes = sched.submit(PROMPTS[1], max_new_tokens=6)
    sched.step()
    sched.step()
    slot = goes.slot
    assert set(engine.ahead_feed) == {stays.slot, slot}
    dropped = _counter("dropped_lanes")
    goes.deadline = time.monotonic() - 1.0      # between dispatch and collection
    sched.step()
    assert goes.state == "expired" and len(goes.tokens) == 3
    assert _counter("dropped_lanes") == dropped + 1
    assert not engine.cache.is_live(slot)
    assert len(stays.tokens) == 4               # its lane was collected
    # the slot is taken by the next request while a tick is in flight, and
    # serves it from a state of its own
    again = sched.submit(PROMPTS[2], max_new_tokens=6)
    sched.step()
    assert again.slot == slot
    _run(sched, [stays, again])
    assert stays.tokens == want[tuple(PROMPTS[0])]
    assert again.tokens == want[tuple(PROMPTS[2])]


def test_a_dry_pool_holds_the_dispatch_back_and_preempts_as_before(
        engine, monkeypatch):
    def serve(plain):
        sched = serving.Scheduler(engine)
        sched._plain = plain          # False: every tick the step's own
        asked = _outcomes(sched, monkeypatch)
        reqs = [sched.submit(p, max_new_tokens=40) for p in PROMPTS[:4]]
        _run(sched, reqs, steps=400)
        return sched, reqs, [o for _, o in asked]

    sync, sync_reqs, none = serve(False)
    assert not none and sync.preemptions > 0
    assert sync.early_dispatch == {"held_engine": sync.steps}
    sched, reqs, asked = serve(True)
    assert "held_capacity" in asked and asked.count("ahead") > 20
    assert sched.preemptions == sync.preemptions
    assert [r.tokens for r in reqs] == [r.tokens for r in sync_reqs]
    assert engine.steady_state_recompiles == 0


@pytest.mark.parametrize("how", ["abort_all", "drain", "loop_stop"])
def test_nothing_stays_in_flight_and_no_waiter_hangs(engine, how):
    sched = serving.Scheduler(engine)
    reqs = [sched.submit(p, max_new_tokens=6) for p in PROMPTS[:3]]
    if how == "loop_stop":
        loop = EngineLoop(sched, idle_sleep_s=0.001).start()
        while not all(len(r.tokens) >= 2 for r in reqs):
            time.sleep(0.001)
        loop.stop()
        assert not loop.alive and loop.faults == 0
        assert engine.ahead_feed is None        # collected, not lost:
        n = [len(r.tokens) for r in reqs]
        assert sched.settle() is False
        sched.drain(timeout_s=30)               # the rest, step by step
        assert all(len(r.tokens) == 6 >= k for r, k in zip(reqs, n))
    else:
        sched.step()
        sched.step()
        assert set(engine.ahead_feed) == {r.slot for r in reqs}
        if how == "abort_all":
            assert sched.abort_all("test") == 3
            assert [r.state for r in reqs] == ["failed"] * 3
        else:
            assert sched.drain(timeout_s=30)
            assert [len(r.tokens) for r in reqs] == [6] * 3
            # draining, the scheduler dispatched nothing more ahead
            assert sched.early_dispatch.get("ahead") == 2
    assert all(r.finished.is_set() for r in reqs)
    assert engine.ahead_feed is None


def test_free_with_a_tick_in_flight_leaves_no_buffer():
    """What the benchmark's ``ServeProgram.free`` does."""
    eng = _build("gpt")
    sched = serving.Scheduler(eng)
    loop = EngineLoop(sched, idle_sleep_s=0.001).start()
    req = sched.submit(PROMPTS[0], max_new_tokens=50)
    while len(req.tokens) < 3:
        time.sleep(0.001)
    loop.stop()
    assert eng._ahead is None and eng.next_tick is None
    eng.qparams = eng.cache.k = eng.cache.v = None
    eng._exec.clear()
    assert not any(isinstance(v, jax.Array) for v in vars(eng).values())


def test_a_failing_tick_in_flight_poisons_the_engine(engine, monkeypatch):
    class Lost:
        def __array__(self, *a, **kw):
            raise RuntimeError("device lost")

    exe, calls = engine._decode_exec(), []

    def failing(*args):
        out = exe(*args)
        calls.append(1)
        if len(calls) == 2:         # the first tick dispatched ahead
            return (out[0], out[1], Lost(), *out[3:])
        return out

    monkeypatch.setattr(engine, "_decode_exec", lambda: failing)
    sched = serving.Scheduler(engine)
    reqs = [sched.submit(p, max_new_tokens=6) for p in PROMPTS[:2]]
    sched.step()                    # dispatches the failing tick, ahead
    assert engine.poisoned is None and len(calls) == 2
    with pytest.raises(RuntimeError, match="device lost"):
        sched.step()
    # its caches were the manager's already: no later call can be trusted,
    # with or without donation
    assert "decode failed" in engine.poisoned
    with pytest.raises(RuntimeError, match="poisoned"):
        engine.decode_step_sampled({0: 1}, None)
    loop = EngineLoop(sched)
    assert loop._check_poisoned() and sched.refusing
    assert all(r.state == "failed" and r.finished.is_set() for r in reqs)
    # (the failure was the test's: the caches are sound, the engine goes
    # back to the others)
    engine.poisoned = None


def test_one_tick_record_a_decode_call_and_no_recompile(engine, tracer,
                                                        monkeypatch):
    exe, calls = engine._decode_exec(), []

    def counted(*args):
        calls.append(time.perf_counter_ns())
        return exe(*args)

    monkeypatch.setattr(engine, "_decode_exec", lambda: counted)
    compiles = engine.compiles
    sched = serving.Scheduler(engine)
    reqs = [sched.submit(p, max_new_tokens=3 + i)
            for i, p in enumerate(PROMPTS)]
    _run(sched, reqs)
    ticks = [s for s in tracer.spans() if s["name"] == "serve/decode_tick"]
    assert len(ticks) == len(calls) == sched.steps
    assert sum(t["attrs"]["ahead"] for t in ticks) == \
        sched.early_dispatch["ahead"]
    # a record describes the tick whose tokens it collects: its riders'
    # cached tokens are those the call wrote behind
    for t in ticks:
        assert t["attrs"]["batch"] == len(t["attrs"]["riders"])
        kids = [s["name"] for s in tracer.spans()
                if s["parent"] == t["span"]]
        # a tick found in flight plans its successor before it fetches its
        # logits; one the step fed itself hands its tokens out first
        assert kids == (["decode/run", "decode/plan", "decode/fetch_logits",
                         "decode/commit"] if t["attrs"]["ahead"] else
                        ["decode/feed", "decode/run", "decode/fetch_logits",
                         "decode/commit"])
    assert engine.compiles == compiles
    assert engine.steady_state_recompiles == 0


def test_a_step_s_own_tick_hands_its_tokens_out_before_the_next_dispatch(
        engine, tracer, monkeypatch):
    """The tick of a prefill step has its riders' longest gap behind it:
    its successor is planned after the emit, not before the logits."""
    exe, calls = engine._decode_exec(), []

    def stamped(*args):
        calls.append(spans.clock_ns())
        return exe(*args)

    monkeypatch.setattr(engine, "_decode_exec", lambda: stamped)
    sched = serving.Scheduler(engine)
    reqs = [sched.submit(p, max_new_tokens=4) for p in PROMPTS[:2]]
    sched.step()
    late = sched.submit(PROMPTS[2], max_new_tokens=4)
    _run(sched, reqs + [late])
    ss = tracer.spans()
    ticks = [s for s in ss if s["name"] == "serve/decode_tick"]
    for t in ticks[:-1]:
        step = t["parent"]
        emit = next(s for s in ss if s["name"] == "serve/emit"
                    and s["parent"] == step)
        dispatched = calls[t["attrs"]["step"] + 1]
        if t["attrs"]["ahead"]:
            assert t["start_ns"] < dispatched < emit["start_ns"]
        else:
            plan = next(s for s in ss if s["name"] == "decode/plan"
                        and s["parent"] == step)
            assert emit["start_ns"] + emit["dur_ns"] <= plan["start_ns"] \
                < dispatched < plan["start_ns"] + plan["dur_ns"]
    assert [t["attrs"]["ahead"] for t in ticks[:2]] == [False, True]


def test_an_engine_driven_directly_never_runs_ahead(engine, tracer):
    slot, _, tok = engine.start_sequence_sampled(PROMPTS[0],
                                                 serving.sampling.GREEDY)
    for _ in range(3):
        tok = engine.decode_step_sampled({slot: tok}, None)[slot][0]
        assert engine.ahead_feed is None
    engine.free_sequence(slot)
    assert "decode/plan" not in {s["name"] for s in tracer.spans()}


def test_the_speculative_wrapper_is_held():
    cfg = FAMILIES["gpt"][1]
    params = G.init_params(jax.random.PRNGKey(0), cfg)
    kw = dict(ENGINE, verify_window=3)
    target = serving.DecodeEngine(params, cfg, serving.EngineConfig(**kw))
    draft = serving.DecodeEngine(params, cfg, serving.EngineConfig(**kw))
    for eng in (serving.SpecDecodeEngine(target, draft), target):
        sched = serving.Scheduler(eng)
        req = sched.submit(PROMPTS[0], max_new_tokens=6)
        _run(sched, [req])
        assert set(sched.early_dispatch) == {"held_engine"}
        assert target.ahead_feed is None and draft.ahead_feed is None


def test_waiters_wake_when_the_loop_faults_with_a_tick_in_flight(engine,
                                                                 monkeypatch):
    sched = serving.Scheduler(engine)
    loop = EngineLoop(sched, idle_sleep_s=0.001)
    reqs = [sched.submit(p, max_new_tokens=30) for p in PROMPTS[:2]]
    sched.step()
    sched.step()
    assert engine.ahead_feed is not None
    monkeypatch.setattr(sched, "_emit", lambda *a: 1 / 0)
    loop.start()
    woke = threading.Event()
    threading.Thread(target=lambda: (reqs[0].wait(20), woke.set()),
                     daemon=True).start()
    assert woke.wait(30) and loop.faults >= 1
    loop.stop()
    assert [r.state for r in reqs] == ["failed"] * 2
    assert engine.ahead_feed is None
