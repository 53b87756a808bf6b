"""An engine call hands its executable ONE host array (serving/engine.py,
"the feed"; docs/serving.md "The tick's anatomy"): the decode tick's, the
prefill rung's and the verify window's host arguments are packed into one
int32 array that the program cuts apart. What the program sees is what it
saw as eight or nine arrays, bit for bit: the construction this replaced is
kept here as the oracle.

Each family's tiny engine (GPT block, hybrid of Mamba and attention layers,
delta-rule hybrid) is built once and shared; a test leaves it with every
slot free.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu import serving
from paddle_tpu.models import gpt as G
from paddle_tpu.models import jamba as J
from paddle_tpu.models import olmo_hybrid as O
from paddle_tpu.serving import engine as E
from paddle_tpu.serving import sampling as samp
from paddle_tpu.serving.server import EngineLoop

from serving_helpers import greedy_reference

SP = serving.SamplingParams
FAMILIES = {
    "gpt": (G, G.GPT_TINY.scaled(num_layers=2, max_seq_len=64)),
    "hybrid": (J, J.JAMBA_TINY),
    "delta": (O, O.OLMO_HYBRID_TINY),
}
ENGINE = dict(max_batch=4, max_seq=64, page_size=8,
              prefill_buckets=(8, 16), prefix_cache=False)
# the cases of the round trip: no request samples; temperature and nucleus
# with seeds at and above 2^31 (a float's bits and a seed's sign bit have
# to cross unharmed)
KNOBS = {
    "greedy": lambda slot: None,
    "sampled": lambda slot: SP(temperature=0.7, top_k=3 * slot,
                               top_p=0.95, seed=2 ** 31 + 5 * (slot - 2)),
}
_ENGINES = {}


def _engine(family, **kw):
    key = (family, tuple(sorted(kw.items())))
    if key not in _ENGINES:
        mod, cfg = FAMILIES[family]
        eng = serving.DecodeEngine(
            mod.init_params(jax.random.PRNGKey(0), cfg), cfg,
            serving.EngineConfig(**dict(ENGINE, **kw)))
        eng.warmup()
        _ENGINES[key] = eng
    return _ENGINES[key]


@pytest.fixture(params=list(FAMILIES))
def engine(request):
    eng = _engine(request.param)
    yield eng
    assert eng.ahead_feed is None and not eng.cache.live_slots()


def _bits(a):
    a = np.asarray(a)
    assert a.dtype.itemsize == 4, a.dtype
    return a.view(np.int32)


def _same(got, want):
    """Dtype for dtype, element for element, bit for bit."""
    got, want = np.asarray(got), np.asarray(want)
    assert got.dtype == want.dtype and got.shape == want.shape, (
        got.dtype, want.dtype, got.shape, want.shape)
    np.testing.assert_array_equal(_bits(got), _bits(want))


# ---------------------------------------------------------------------------
# the oracle: the host arrays as the engine built them before the feed
# ---------------------------------------------------------------------------

def _old_batch_arrays(params_by_slot, max_batch):
    temps = np.zeros((max_batch,), np.float32)
    top_ks = np.zeros((max_batch,), np.int32)
    top_ps = np.ones((max_batch,), np.float32)
    seeds = np.zeros((max_batch,), np.int32)
    for slot, sp in params_by_slot.items():
        temps[slot] = sp.temperature
        top_ks[slot] = sp.top_k
        top_ps[slot] = sp.top_p
        seeds[slot] = np.int32(np.uint32(sp.seed))
    return temps, top_ks, top_ps, seeds


def _old_masked_tables(eng, active_slots):
    tables = eng.cache._tables.copy()
    active = set(active_slots)
    for s in range(eng.ecfg.max_batch):
        if s not in active:
            tables[s, :] = 0
    return tables


def _old_tick_args(eng, slot_tokens, params_by_slot):
    """tokens, positions, tables, actives and the sampler's four, from
    the cache as ``_tick_args`` left it (the riders' next pages mapped)."""
    B = eng.ecfg.max_batch
    tokens = np.zeros((B,), np.int32)
    positions = np.zeros((B,), np.int32)
    for slot, tok in slot_tokens.items():
        tokens[slot] = tok
        positions[slot] = eng.cache.length(slot)
    actives = np.zeros((B,), np.int32)
    actives[list(slot_tokens)] = 1
    return (tokens, positions, _old_masked_tables(eng, slot_tokens), actives,
            *_old_batch_arrays(params_by_slot or {}, B))


def _riders_off_the_low_slots(eng):
    """Slots 2 and 3 live with 5 and 8 rows (the second's next row opens
    its second page), slots 0 and 1 free again."""
    prompts = ([1, 2, 3], [4, 5], [6, 7, 8, 9, 10], list(range(11, 19)))
    slots = [eng.start_sequence_sampled(p, serving.GREEDY)[0]
             for p in prompts]
    assert slots == [0, 1, 2, 3]
    eng.free_sequence(0)
    eng.free_sequence(1)
    assert eng.cache.length(3) == eng.ecfg.page_size
    return {2: 17, 3: 250}


_cut_slot = jax.jit(E.cut_slot_feed, static_argnums=1)
_cut_rung = jax.jit(E.cut_rung_feed, static_argnums=1)


@pytest.mark.parametrize("knobs", sorted(KNOBS))
def test_the_ticks_feed_cuts_into_the_eight_arrays_it_replaced(knobs):
    eng = _engine("gpt")
    riders = _riders_off_the_low_slots(eng)
    params = {s: KNOBS[knobs](s) for s in riders}
    params = None if knobs == "greedy" else params
    mapped = eng.cache._slots[3].mapped
    feed, sampler = eng._tick_args(riders, params)
    assert eng.cache._slots[3].mapped == mapped + 1     # row 8: a new page
    assert sampler == {"greedy": "greedy", "sampled": "filtered"}[knobs]
    assert feed.dtype == np.int32 and feed.shape == (
        4, 7 + eng.cache.max_pages_per_slot)
    tokens, positions, tables, actives, sp = _cut_slot(feed, eng.table_width)
    want = _old_tick_args(eng, riders, params)
    for got, old in zip((tokens[:, 0], positions, tables, actives, *sp),
                        want):
        _same(got, old)
    # the riders' rows are live and the others' all zero: a dead lane
    # writes the scratch page
    tables = np.asarray(tables)
    assert tables[2, 0] and tables[3, :2].all() and not tables[:2].any()
    if knobs == "sampled":
        assert np.asarray(sp[3])[3] == np.int32(np.uint32(2 ** 31 + 5)) < 0
        assert np.asarray(sp[0])[2] == np.float32(0.7)
    for slot in riders:
        eng.free_sequence(slot)


@pytest.mark.parametrize("knobs", sorted(KNOBS))
def test_the_rungs_feed_cuts_into_the_nine_arrays_it_replaced(
        knobs, monkeypatch):
    """A prompt behind a cached prefix of one page (``prefix_len`` 8): the
    feed the prefill call hands over, cut as the program cuts it."""
    eng = _engine("gpt", prefix_cache=True)
    first = list(range(40, 52))
    s0, _l, _t = eng.start_sequence_sampled(first, serving.GREEDY)
    eng.free_sequence(s0)
    s_low, _l, _t = eng.start_sequence_sampled([7, 7, 7], serving.GREEDY)
    handed = []
    call = eng._call
    monkeypatch.setattr(eng, "_call",
                        lambda exe, feed: (handed.append(feed),
                                           call(exe, feed))[1])
    params = KNOBS[knobs](3) or serving.GREEDY
    prompt = first[:8] + [90, 91, 92, 93, 94]
    slot, _logits, _tok = eng.start_sequence_sampled(prompt, params)
    (feed,) = handed
    assert slot == 1 and eng.cache.prefix_len(slot) == 8
    M = eng.cache.max_pages_per_slot
    assert feed.dtype == np.int32 and feed.shape == (8 + 7 + M,)
    tokens, length, prefix_len, table_row, got_slot, sp = _cut_rung(feed, M)
    padded = np.zeros((1, 8), np.int32)
    padded[0, :5] = prompt[8:]
    want = (padded, np.int32(5), np.int32(8), eng.cache.table_row(slot),
            np.int32(slot), np.float32(params.temperature),
            np.int32(params.top_k), np.float32(params.top_p),
            np.int32(np.uint32(params.seed)))
    for got, old in zip((tokens, length, prefix_len, table_row, got_slot,
                         *sp), want):
        _same(got, old)
    eng.free_sequence(slot)
    eng.free_sequence(s_low)


def test_the_verify_windows_feed_cuts_into_the_seven_arrays_it_replaced(
        monkeypatch):
    eng = _engine("gpt", verify_window=3)
    riders = _riders_off_the_low_slots(eng)
    windows = {2: [17, 18, 19], 3: [250, 251, 252]}
    params = {s: KNOBS["sampled"](s) for s in riders}
    handed = []
    exe = eng._verify_exec()
    monkeypatch.setitem(eng._exec, "verify_w3",
                        lambda *args: (handed.append(args), exe(*args))[1])
    eng.verify_step(windows, params)
    (_qparams, _caches, feed), = handed
    tokens, starts, tables, _actives, sp = _cut_slot(feed, eng.table_width)
    want_tokens = np.zeros((4, 3), np.int32)
    want_starts = np.zeros((4,), np.int32)
    for slot, win in windows.items():
        want_tokens[slot] = win
        want_starts[slot] = eng.cache.length(slot)
    for got, old in zip((tokens, starts, tables, *sp), (
            want_tokens, want_starts, _old_masked_tables(eng, windows),
            *_old_batch_arrays(params, 4))):
        _same(got, old)
    for slot in riders:
        eng.free_sequence(slot)


def test_batch_arrays_vectors_are_the_blocks_columns():
    """``samp.batch_arrays`` into a feed's block: the four vectors it
    returns ARE the block's columns (what ``_note_sampler`` reads is what
    the program will), and without a block they are what they were."""
    params = {1: SP(temperature=1.7, seed=2 ** 31 + 5),
              2: SP(temperature=0.9, top_k=4, top_p=0.6, seed=3)}
    feed = np.full((4, 9), -1, np.int32)
    got = samp.batch_arrays(params, 4, out=feed[:, 3:7])
    for col, (vec, old) in enumerate(zip(got, _old_batch_arrays(params, 4))):
        _same(vec, old)
        _same(feed[:, 3 + col], _bits(old))
        assert np.shares_memory(vec, feed)
    assert (feed[:, :3] == -1).all() and (feed[:, 7:] == -1).all()
    for vec, old in zip(samp.batch_arrays(params, 4),
                        _old_batch_arrays(params, 4)):
        _same(vec, old)
    assert samp.path_name(*got[:3]) == "filtered"


# ---------------------------------------------------------------------------
# every call hands over one host array
# ---------------------------------------------------------------------------

def _record_what_executables_are_handed(eng, monkeypatch):
    """Wrap every compiled program of ``eng``: [(program, arguments behind
    the weights that are host arrays)] a call."""
    handed = []

    def recorder(name, exe):
        def call(*args):
            handed.append((name, [a for a in jax.tree_util.tree_leaves(
                args[1:]) if not isinstance(a, jax.Array)]))
            return exe(*args)
        return call

    for name, exe in list(eng._exec.items()):
        monkeypatch.setitem(eng._exec, name, recorder(name, exe))
    return handed


def test_a_decode_and_a_prefill_call_hand_over_one_host_array(
        engine, monkeypatch):
    handed = _record_what_executables_are_handed(engine, monkeypatch)
    slot, _logits, tok = engine.start_sequence_sampled(
        [5, 6, 7, 8, 9], SP(temperature=0.8, seed=1))
    engine.decode_step_sampled({slot: tok}, None)
    engine.dispatch_ahead({slot: 3}, None)
    engine.drop_ahead()
    engine.free_sequence(slot)
    assert [name for name, _ in handed] == ["prefill_b8", "decode", "decode"]
    for name, host in handed:
        assert len(host) == 1, (name, [np.shape(a) for a in host])
        assert host[0].dtype == np.int32


def test_a_verify_call_hands_over_one_host_array(monkeypatch):
    eng = _engine("gpt", verify_window=3)
    handed = _record_what_executables_are_handed(eng, monkeypatch)
    slot, _logits, _tok = eng.start_sequence_sampled([5, 6, 7],
                                                     serving.GREEDY)
    eng.verify_step({slot: [1, 2, 3]}, None)
    eng.free_sequence(slot)
    assert [(name, len(host)) for name, host in handed] == [
        ("prefill_b8", 1), ("verify_w3", 1)]


def test_a_tensor_parallel_call_hands_over_one_host_array(monkeypatch):
    eng = _engine("gpt", sharding="tp", tp=2)
    handed = _record_what_executables_are_handed(eng, monkeypatch)
    slot, _logits, tok = eng.start_sequence_sampled([5, 6, 7],
                                                    serving.GREEDY)
    eng.decode_step_sampled({slot: tok}, None)
    eng.free_sequence(slot)
    assert [(name, len(host)) for name, host in handed] == [
        ("prefill_b8", 1), ("decode", 1)]


# ---------------------------------------------------------------------------
# streams through Scheduler + EngineLoop
# ---------------------------------------------------------------------------

PROMPTS = [[1, 2, 3, 4, 5], [6, 7, 8], [9, 10, 11, 12, 13, 14, 15],
           [16, 17], [18, 19, 20, 21], [22, 23, 24, 25, 26, 27]]


def _serve(engine, params):
    """Six requests through a scheduler under a loop, four slots: ticks
    run ahead, requests queue, join and leave. -> requests, the logits
    every token was sampled from (recorded at the engine's entries)."""
    rows = {}
    start, decode = (engine.start_sequence_sampled,
                     engine.decode_step_sampled)

    def started(tokens, p):
        slot, logits, tok = start(tokens, p)
        rows[slot] = [(len(tokens) - 1, logits, tok)]
        rows[tuple(tokens)] = rows[slot]
        return slot, logits, tok

    def decoded(feed, p):
        positions = {s: engine.cache.length(s) for s in feed}
        out = decode(feed, p)
        for slot, (tok, logits) in out.items():
            rows[slot].append((positions[slot], logits, tok))
        return out

    engine.start_sequence_sampled, engine.decode_step_sampled = (
        started, decoded)
    sched = serving.Scheduler(engine)
    loop = EngineLoop(sched).start()
    try:
        reqs = [sched.submit(p, max_new_tokens=6 + i, sampling=params(i))
                for i, p in enumerate(PROMPTS)]
        for r in reqs:
            assert r.finished.wait(120)
    finally:
        loop.stop()
        del engine.start_sequence_sampled, engine.decode_step_sampled
    assert [r.state for r in reqs] == ["done"] * len(reqs)
    assert sched.early_dispatch.get("ahead", 0) > 0
    return reqs, rows


def test_a_greedy_stream_is_the_references(engine):
    reqs, _rows = _serve(engine, lambda i: None)
    for r in reqs:
        assert r.tokens == greedy_reference(engine, r.prompt,
                                            len(r.tokens))


def _oracle_token(logits, temp, top_k, top_p, seed, position):
    # tests/test_sampling_paths.py's oracle: the sampler PR 30 replaced
    logits = jnp.asarray(logits, jnp.float32)
    key = jnp.stack([jnp.uint32(position), jnp.asarray(seed).astype(
        jnp.uint32)])
    sampled = jax.random.categorical(
        key, samp._masked_logits(logits, temp, top_k, top_p))
    return int(jnp.where(temp <= 0.0, jnp.argmax(logits), sampled))


def test_a_sampled_stream_draws_what_the_host_asked_for(engine):
    """Every token of six sampled requests (temperature, top-k, nucleus,
    seeds above 2^31) is the draw of its own parameters, position and seed
    from the logits it was sampled from: the knobs cross the feed whole."""
    knobs = [SP(temperature=0.7 + 0.1 * i, top_k=(0, 5, 0)[i % 3],
                top_p=(1.0, 1.0, 0.95)[i % 3], seed=2 ** 31 - 2 + i)
             for i in range(len(PROMPTS))]
    reqs, rows = _serve(engine, lambda i: knobs[i])
    differs = 0
    for r, sp in zip(reqs, knobs):
        stream = rows[tuple(r.prompt)]
        assert [tok for _pos, _logits, tok in stream][:len(r.tokens)] == \
            r.tokens
        for position, logits, tok in stream[:len(r.tokens)]:
            assert tok == _oracle_token(
                logits, np.float32(sp.temperature), np.int32(sp.top_k),
                np.float32(sp.top_p), np.int32(np.uint32(sp.seed)),
                position)
            differs += tok != int(np.argmax(logits))
    assert differs > 5          # the streams are sampled, not the argmax


# ---------------------------------------------------------------------------
# the beat: EngineLoop's second thread
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("pending", [False, True],
                         ids=["an_idle_loop_sends_none",
                              "a_loop_with_requests_beats"])
def test_the_loop_beats_while_requests_are_pending(monkeypatch, pending):
    """One tiny host-to-device transfer every ``BEAT_S`` while the
    scheduler holds requests (PERF.md section 6, PR 38: what keeps the
    host's side of the transfers awake under one feed array a tick), none
    from an idle loop, and the thread ends with the loop."""
    import time

    eng = _engine("gpt")
    sent = []
    put = jax.device_put

    def device_put(x, *a, **kw):
        if isinstance(x, np.ndarray) and x.nbytes == 64:
            sent.append(time.monotonic())
        return put(x, *a, **kw)

    monkeypatch.setattr(jax, "device_put", device_put)
    sched = serving.Scheduler(eng)
    loop = EngineLoop(sched).start()
    try:
        if pending:
            reqs = [sched.submit(p, max_new_tokens=40) for p in PROMPTS[:3]]
            for r in reqs:
                assert r.finished.wait(120)
            assert [r.state for r in reqs] == ["done"] * 3
            time.sleep(10 * loop.BEAT_S)
            n = len(sent)
            assert n > 0 and loop.beats == n
            # a beat every BEAT_S at the soonest
            assert min(np.diff(sent), default=1.0) > 0.5 * loop.BEAT_S
        time.sleep(25 * loop.BEAT_S)
        assert len(sent) == (n if pending else 0)     # idle: not one more
    finally:
        loop.stop()
    assert not loop._beat_thread.is_alive() and not loop.alive
