"""ISSUE 13 serving-stack coverage: paged KV allocator + prefix cache,
tensor-parallel engines over the sharding layer, in-executable sampling,
draft-model speculative decoding, and the scheduler's head-of-line /
preemption behaviors. All CPU-sized: GPT_TINY-scale engines, the 8-device
CPU mesh from conftest for the tp lanes.
"""
import numpy as np
import pytest

import jax

from paddle_tpu import serving
from paddle_tpu.models import gpt
from paddle_tpu.observability import metrics as om
from paddle_tpu.serving import metrics as sm
from paddle_tpu.serving import sampling as samp
from paddle_tpu.serving.paged_kv import (PagedKVCache, PagePoolFullError,
                                         PrefixCache)

from serving_helpers import greedy_engine as _greedy
from serving_helpers import greedy_knobs as _greedy_knobs
from serving_helpers import greedy_reference as _reference
from serving_helpers import pack_rung_feed as _pack_rung_feed
from serving_helpers import pack_slot_feed as _pack_slot_feed


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


@pytest.fixture(scope="module")
def paged_eng(tiny_model):
    eng = make_engine(tiny_model)
    eng.warmup()
    return eng


def _recompile_total():
    snap = om.default_registry().snapshot()
    return sum(s["value"] for s in
               snap.get("paddle_recompiles_total", {}).get("series", []))


# ---------------------------------------------------------------------------
# paged allocator
# ---------------------------------------------------------------------------

def test_paged_pool_alloc_free_refcount():
    c = PagedKVCache(num_layers=1, max_slots=2, max_seq=16, num_heads=1,
                     head_dim=2, page_size=4, num_pages=6)
    assert c.free_page_count() == 5          # page 0 is scratch
    s0 = c.alloc(length=6)                   # 2 pages
    assert c.free_page_count() == 3
    row = c.table_row(s0)
    assert (row[:2] > 0).all() and (row[2:] == 0).all()
    # growth maps the next page exactly at the boundary
    assert c.ensure_capacity(s0, 9)
    assert c.table_row(s0)[2] > 0 and c.free_page_count() == 2
    s1 = c.alloc(length=8)                   # the last 2 pages
    assert c.free_page_count() == 0
    assert not c.ensure_capacity(s1, 9)      # pool dry -> False, no map
    with pytest.raises(PagePoolFullError):
        PagedKVCache(num_layers=1, max_slots=3, max_seq=16, num_heads=1,
                     head_dim=2, page_size=4, num_pages=2).alloc(length=8)
    c.free(s0)
    assert c.free_page_count() == 3
    c.free(s1)
    assert c.free_page_count() == 5          # every page came back
    assert c.pool_occupancy() == 0.0


def test_paged_shared_prefix_refcounts():
    c = PagedKVCache(num_layers=1, max_slots=3, max_seq=16, num_heads=1,
                     head_dim=2, page_size=4, num_pages=8)
    s0 = c.alloc(length=8)
    shared = [int(p) for p in c.table_row(s0)[:2]]
    # second slot attaches the same 2 pages + 1 own page
    s1 = c.alloc(length=10, prefix_pages=shared)
    assert [int(p) for p in c.table_row(s1)[:2]] == shared
    assert c.prefix_len(s1) == 8
    c.free(s0)                               # shared pages still ref'd
    assert all(c._ref[p] == 1 for p in shared)
    assert c.free_page_count() == 4
    c.free(s1)
    assert c.free_page_count() == 7


def test_prefix_cache_lookup_insert_reclaim():
    pool = PagedKVCache(num_layers=1, max_slots=2, max_seq=16,
                        num_heads=1, head_dim=2, page_size=4, num_pages=8)
    cache = PrefixCache(pool)
    toks = list(range(10))
    s = pool.alloc(length=10)
    row = pool.table_row(s)
    assert cache.insert(toks, row) == 2       # 2 full pages -> 2 entries
    # longest page-aligned prefix that leaves >=1 suffix token
    plen, pages = cache.lookup(toks)
    assert plen == 8 and list(pages) == [int(p) for p in row[:2]]
    assert cache.lookup(toks[:5])[0] == 4
    assert cache.lookup([99] * 10) == (0, ())
    pool.free(s)     # cache refs keep the 2 published pages live; the
    assert pool.free_page_count() == 5        # partial 3rd page frees
    freed = cache.reclaim(10)                 # pressure: drop everything
    assert freed == 2 and pool.free_page_count() == 7
    assert len(cache) == 0
    assert cache.lookup(toks)[0] == 0         # entries really gone


def test_can_admit_counts_what_the_prefix_cache_would_give_back():
    """Pages that only the prefix cache still holds are free for
    admission: ``_take_pages`` reclaims them on demand, so ``can_admit``
    has to count them (it once asked the bound method ``reclaim`` for an
    attribute of the cache and read 0). 1 free page + 15 reclaimable
    admits a prompt of 16 pages; pages a live slot shares do not count."""
    pool = PagedKVCache(num_layers=1, max_slots=3, max_seq=64,
                        num_heads=1, head_dim=2, page_size=4,
                        num_pages=17)                # 16 usable
    cache = PrefixCache(pool)
    pool.prefix_cache = cache
    toks = list(range(61))
    s = pool.alloc(length=61)                        # all 16 pages
    assert cache.insert(toks, pool.table_row(s)) == 15
    assert not pool.can_admit(4)                     # every page is held
    pool.free(s)                                     # the partial page
    assert pool.free_page_count() == 1 and cache.reclaimable() == 15
    assert pool.can_admit(64) and pool.can_admit(4)
    assert not pool.can_admit(65)                     # 17 pages: never
    # a slot attached to 2 of the cached pages pins them
    shared = [int(p) for p in cache.lookup(toks[:9])[1]]
    s2 = pool.alloc(length=9, prefix_pages=shared)   # + the free page
    assert pool.free_page_count() == 0 and cache.reclaimable() == 13
    assert pool.can_admit(52) and not pool.can_admit(53)
    # and admission really gets what can_admit promised
    s3 = pool.alloc(length=52)
    assert cache.reclaimable() == 0 and pool.free_page_count() == 0
    pool.free(s2)
    pool.free(s3)
    # without a prefix cache the free list alone counts
    bare = PagedKVCache(num_layers=1, max_slots=2, max_seq=16, num_heads=1,
                        head_dim=2, page_size=4, num_pages=4)
    bare.alloc(length=8)
    assert bare.can_admit(4) and not bare.can_admit(8)


def test_alloc_reclaims_from_the_prefix_cache_but_not_what_it_attaches():
    """Pool pressure inside ``alloc``: the pool asks its prefix cache to
    give pages back, and the prefix pages the new slot attaches are
    pinned first, so the reclaim that drops their entries cannot free
    (and recycle) them."""
    pool = PagedKVCache(num_layers=1, max_slots=2, max_seq=32, num_heads=1,
                        head_dim=2, page_size=4, num_pages=9)   # 8 usable
    cache = PrefixCache(pool)
    pool.prefix_cache = cache
    toks = list(range(16))
    s = pool.alloc(length=16)
    assert cache.insert(toks, pool.table_row(s)) == 4
    pool.free(s)
    assert pool.free_page_count() == 4 and cache.reclaimable() == 4
    plen, shared = cache.lookup(toks[:9])
    assert plen == 8
    assert pool.can_admit(32, prefix_len=plen)       # 6 own pages of 4 + 4
    s2 = pool.alloc(length=32, prefix_pages=shared)  # has to reclaim 2
    row = pool.table_row(s2)
    assert [int(p) for p in row[:2]] == [int(p) for p in shared]
    assert len(set(int(p) for p in row)) == 8 and 0 not in row
    # the entry the lookup freshened outlived the reclaim (LRU went
    # first): its two pages are the cache's and the slot's, the rest the
    # slot's alone
    assert [int(pool._ref[int(p)]) for p in row] == [2, 2, 1, 1, 1, 1, 1, 1]
    assert len(cache) == 1 and pool.free_page_count() == 0
    pool.free(s2)
    assert pool.free_page_count() == 6 and cache.reclaimable() == 2


def test_default_engine_drains_a_queue_of_fresh_prompts(tiny_model):
    """PERF.md section 7's reproduction, now a passing test: 4 slots, 16
    usable pages, the default prefix cache over the whole pool
    (``prefix_cache_pages`` 0), eight fresh prompts of three full pages.
    Finished requests leave their pages with the prefix cache, so the
    free list alone never again covers a prompt (the parent stopped at
    three queued requests, 1 free page, 15 reclaimable, for good); the
    queued ones are admitted all the same, by reclaiming."""
    cfg, _ = tiny_model
    eng = make_engine(tiny_model, page_size=4, num_pages=17)
    assert eng.prefix is not None and eng.ecfg.prefix_cache_pages == 0
    eng.warmup()
    sched = serving.Scheduler(eng, serving.SchedulerConfig(
        default_timeout_s=120.0))
    rng = np.random.RandomState(41)
    prompts = [rng.randint(0, cfg.vocab_size, size=12).tolist()
               for _ in range(8)]
    reqs = [sched.submit(p, max_new_tokens=4) for p in prompts]
    reclaimed_for = 0
    for _ in range(60):
        if not sched.pending():
            break
        free = eng.cache.free_page_count()
        queued = [r for r in reqs if r.state == "queued"]
        sched.step()
        admitted = [r for r in queued if r.state != "queued"]
        if len(admitted) * 3 > free:
            reclaimed_for += len(admitted)   # the free list did not cover
    assert [r.state for r in reqs] == ["done"] * 8, \
        [(r.state, r.error) for r in reqs]
    assert reclaimed_for >= 3
    assert sched.preemptions == 0
    for p, r in zip(prompts, reqs):
        assert r.tokens == _reference(eng, p, 4)


# ---------------------------------------------------------------------------
# engine parity (the acceptance bar: at f32 the engine's greedy tokens are
# those of the cache-free full forward, ``reference_logits``)
# ---------------------------------------------------------------------------

def test_paged_tokens_match_reference(tiny_model, paged_eng):
    cfg, _ = tiny_model
    rng = np.random.RandomState(7)
    for plen in (3, 9, 15):
        prompt = rng.randint(0, cfg.vocab_size, size=plen).tolist()
        assert _greedy(paged_eng, prompt, 8) == \
            _reference(paged_eng, prompt, 8)


def test_paged_interleaved_slots_isolated(tiny_model, paged_eng):
    cfg, _ = tiny_model
    rng = np.random.RandomState(8)
    p_a = rng.randint(0, cfg.vocab_size, size=5).tolist()
    p_b = rng.randint(0, cfg.vocab_size, size=11).tolist()
    sa, la = paged_eng.start_sequence(p_a)
    sb, lb = paged_eng.start_sequence(p_b)
    ta, tb = [int(np.argmax(la))], [int(np.argmax(lb))]
    for _ in range(5):
        out = paged_eng.decode_step({sa: ta[-1], sb: tb[-1]})
        ta.append(int(np.argmax(out[sa])))
        tb.append(int(np.argmax(out[sb])))
    paged_eng.free_sequence(sa)
    paged_eng.free_sequence(sb)
    assert ta == _reference(paged_eng, p_a, 6)
    assert tb == _reference(paged_eng, p_b, 6)


def test_prefix_cache_prefills_once(tiny_model, paged_eng):
    """The headline satellite: a repeated system prompt attaches its
    cached pages and prefills only the suffix — with identical logits,
    and every page refcount unwinding cleanly."""
    cfg, _ = tiny_model
    eng = paged_eng
    prompt = list(range(40, 52))              # 12 tokens -> 1 full page
    tok0 = sm.m_prefill_tokens._unlabeled().value
    s1, l1 = eng.start_sequence(prompt)
    d1 = sm.m_prefill_tokens._unlabeled().value - tok0
    s2, l2 = eng.start_sequence(prompt)
    d2 = sm.m_prefill_tokens._unlabeled().value - tok0 - d1
    assert d1 == 12 and d2 == 4, (d1, d2)
    assert eng.prefix.hits >= 1
    np.testing.assert_allclose(l1, l2, rtol=1e-5, atol=1e-5)
    # decode continues correctly off the shared prefix
    t1, t2 = int(np.argmax(l1)), int(np.argmax(l2))
    o = eng.decode_step({s1: t1, s2: t2})
    assert int(np.argmax(o[s1])) == int(np.argmax(o[s2]))
    # and matches the reference exactly
    ref = _reference(eng, prompt, 2)
    assert [t1, int(np.argmax(o[s1]))] == ref
    eng.free_sequence(s1)
    eng.free_sequence(s2)
    # slots gone; only the prefix cache still holds its published page
    eng.prefix.clear()
    assert eng.cache.free_page_count() == eng.cache.num_pages - 1


@pytest.mark.slow
def test_prefix_cache_off_still_correct(tiny_model):
    """(slow: own engine warmup; the prefix-cache-ON paths are the
    tier-1-gated ones.)"""
    eng = make_engine(tiny_model, prefix_cache=False)
    eng.warmup()
    assert eng.prefix is None
    prompt = list(range(30, 42))
    assert _greedy(eng, prompt, 5) == _reference(eng, prompt, 5)


# ---------------------------------------------------------------------------
# scheduler: head-of-line bypass + page-pool preemption
# ---------------------------------------------------------------------------

def test_scheduler_hol_bypass_and_starvation_bound(tiny_model):
    """One long prompt at the head must not stall fitting short prompts
    behind it — and the bypass count is bounded (one engine, two
    scheduler configs: the engine warmup is the expensive part)."""
    cfg, params = tiny_model
    # pool: 5 usable pages of 8 rows; the long prompt needs 2+ and the
    # engine admits shorts while the long one cannot fit
    eng = serving.DecodeEngine(params, cfg, serving.EngineConfig(
        max_batch=2, max_seq=32, prefill_buckets=(8, 16),
        page_size=8, num_pages=6, prefix_cache=False))
    eng.warmup()
    sched = serving.Scheduler(eng, serving.SchedulerConfig(
        hol_starvation_limit=100))
    # occupy 4 pages with two active shorts that keep decoding
    a = sched.submit([1, 2, 3], max_new_tokens=24)
    b = sched.submit([4, 5, 6], max_new_tokens=24)
    sched.step()
    assert a.state == "active" and b.state == "active"
    long_req = sched.submit(list(range(1, 16)), max_new_tokens=2)  # 2 pages
    shorts = [sched.submit([9, 9], max_new_tokens=2) for _ in range(3)]
    hol0 = sm.m_hol_admits._unlabeled().value
    while sched.pending():
        sched.step()
    everyone = [a, b, long_req] + shorts
    assert all(r.state == "done" for r in everyone)
    # some non-fitting head was bypassed by fitting requests behind it
    # (under pool pressure the preempted resume is usually the head) —
    # and nobody starved: every request completed
    assert sm.m_hol_admits._unlabeled().value > hol0
    assert max(r.hol_skips for r in everyone) >= 1

    # --- starvation bound: with limit=1, a pinned head blocks later
    # fitting requests instead of being bypassed forever
    sched = serving.Scheduler(eng, serving.SchedulerConfig(
        hol_starvation_limit=1))
    blocker = sched.submit([1, 1, 1], max_new_tokens=60, timeout_s=60)
    blocker2 = sched.submit([2, 2, 2], max_new_tokens=60, timeout_s=60)
    sched.step()                               # both active: 2+2 pages
    long_req = sched.submit(list(range(1, 16)), max_new_tokens=1)
    s1 = sched.submit([5, 5], max_new_tokens=1)
    s2 = sched.submit([6, 6], max_new_tokens=1)
    sched.step()
    sched.step()
    # limit=1: at most one short got past the long head, the next is
    # pinned behind it even though it would fit
    assert long_req.hol_skips <= 1
    admitted_shorts = sum(r.state in ("active", "done") for r in (s1, s2))
    assert admitted_shorts <= 1
    assert blocker.state == "active" and blocker2.state == "active"


def test_scheduler_page_pool_preemption_recompute(tiny_model):
    """Pool dry mid-generation: the youngest request is requeued
    (recompute) and both requests still produce exactly the greedy
    reference stream."""
    cfg, params = tiny_model
    eng = serving.DecodeEngine(params, cfg, serving.EngineConfig(
        max_batch=2, max_seq=32, prefill_buckets=(8,),
        page_size=4, num_pages=7, prefix_cache=False))
    eng.warmup()
    sched = serving.Scheduler(eng, serving.SchedulerConfig(
        default_timeout_s=120.0))
    # two prompts of 3 tokens (1 page each) that generate 13+ tokens
    # (4 pages each at the end) — 8 pages needed, 6 usable -> preempt
    pa, pb = [11, 12, 13], [21, 22, 23]
    ra = sched.submit(pa, max_new_tokens=12)
    rb = sched.submit(pb, max_new_tokens=12)
    while sched.pending():
        sched.step()
    assert ra.state == "done" and rb.state == "done"
    assert sched.preemptions >= 1
    assert ra.tokens == _reference(eng, pa, 12)
    assert rb.tokens == _reference(eng, pb, 12)


def test_partial_feed_does_not_clobber_live_slots(tiny_model, paged_eng):
    """Regression: a LIVE slot excluded from a decode call rides as a
    masked lane — its write must land on the scratch page (its table row
    is zeroed for the call), not in its own first page. The spec draft's
    catch-up rounds feed exactly such partial batches."""
    cfg, _ = tiny_model
    eng = paged_eng
    rng = np.random.RandomState(23)
    pa = rng.randint(0, cfg.vocab_size, size=4).tolist()
    pb = rng.randint(0, cfg.vocab_size, size=6).tolist()
    sa, la = eng.start_sequence(pa)
    sb, lb = eng.start_sequence(pb)
    ta = [int(np.argmax(la))]
    for _ in range(4):                      # b sits live but unfed
        ta.append(int(np.argmax(eng.decode_step({sa: ta[-1]})[sa])))
    tb = [int(np.argmax(lb))]
    for _ in range(4):
        tb.append(int(np.argmax(eng.decode_step({sb: tb[-1]})[sb])))
    eng.free_sequence(sa)
    eng.free_sequence(sb)
    assert ta == _reference(eng, pa, 5)
    assert tb == _reference(eng, pb, 5)     # row 0 survived the idle ride


# ---------------------------------------------------------------------------
# sampling
# ---------------------------------------------------------------------------

def test_sampling_greedy_lane_is_exact(tiny_model, paged_eng):
    """temperature=0 through the sampled API == host argmax (the whole
    pre-sampling engine behavior)."""
    prompt = [3, 1, 4]
    slot, logits, tok = paged_eng.start_sequence_sampled(
        prompt, serving.GREEDY)
    assert tok == int(np.argmax(logits))
    out = paged_eng.decode_step_sampled({slot: tok}, None)
    tok2, lg2 = out[slot]
    assert tok2 == int(np.argmax(lg2))
    paged_eng.free_sequence(slot)


def test_sampling_topk1_and_determinism(tiny_model, paged_eng):
    prompt = [8, 6, 7]
    sp_k1 = serving.SamplingParams(temperature=1.0, top_k=1, seed=5)
    slot, logits, tok = paged_eng.start_sequence_sampled(prompt, sp_k1)
    assert tok == int(np.argmax(logits))      # top_k=1 collapses to greedy
    paged_eng.free_sequence(slot)

    sp = serving.SamplingParams(temperature=1.2, top_k=5, top_p=0.9,
                                seed=123)

    def run():
        slot, _l, t = paged_eng.start_sequence_sampled(prompt, sp)
        toks = [t]
        for _ in range(6):
            out = paged_eng.decode_step_sampled({slot: toks[-1]}, {slot: sp})
            toks.append(out[slot][0])
        paged_eng.free_sequence(slot)
        return toks

    first = run()
    assert first == run()                      # same seed -> same stream
    sp2 = serving.SamplingParams(temperature=1.2, top_k=5, top_p=0.9,
                                 seed=124)
    slot, _l, t = paged_eng.start_sequence_sampled(prompt, sp2)
    paged_eng.free_sequence(slot)               # different seed compiles 0


def test_sampling_respects_topk_support(tiny_model, paged_eng):
    sp = serving.SamplingParams(temperature=1.5, top_k=3, seed=77)
    slot, logits, tok = paged_eng.start_sequence_sampled([2, 7, 1], sp)
    support = set(np.argsort(logits)[-3:].tolist())
    assert tok in support
    toks = [tok]
    for _ in range(8):
        out = paged_eng.decode_step_sampled({slot: toks[-1]}, {slot: sp})
        t2, lg = out[slot]
        assert t2 in set(np.argsort(lg)[-3:].tolist())
        toks.append(t2)
    paged_eng.free_sequence(slot)


def test_adjusted_probs_np_matches_support():
    rng = np.random.RandomState(0)
    logits = rng.randn(32).astype(np.float32)
    sp = samp.SamplingParams(temperature=0.7, top_k=4, top_p=0.8, seed=0)
    p = samp.adjusted_probs_np(logits, sp)
    assert abs(p.sum() - 1.0) < 1e-9
    assert (p > 0).sum() <= 4                  # top-k bound
    # greedy: one-hot argmax
    g = samp.adjusted_probs_np(logits, samp.GREEDY)
    assert g[np.argmax(logits)] == 1.0 and g.sum() == 1.0


def test_mixed_sampling_zero_recompiles(tiny_model, paged_eng):
    """Different per-request knobs sharing one decode batch never
    change a shape."""
    cfg, _ = tiny_model
    sched = serving.Scheduler(paged_eng)
    before = _recompile_total()
    rng = np.random.RandomState(3)
    sps = [serving.GREEDY,
           serving.SamplingParams(temperature=0.8, seed=1),
           serving.SamplingParams(temperature=1.1, top_k=4, seed=2),
           serving.SamplingParams(temperature=0.9, top_p=0.7, seed=3)]
    reqs = [sched.submit(
        rng.randint(0, cfg.vocab_size, size=int(rng.randint(2, 14)))
        .tolist(), max_new_tokens=5, sampling=sps[i % 4])
        for i in range(8)]
    while sched.pending():
        sched.step()
    assert all(r.state == "done" for r in reqs)
    assert _recompile_total() - before == 0
    assert paged_eng.steady_state_recompiles == 0


# ---------------------------------------------------------------------------
# tensor-parallel engine (needs the conftest 8-device CPU mesh)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tp_eng(tiny_model):
    eng = make_engine(tiny_model, sharding="tp", tp=2)
    eng.warmup()
    return eng


def test_tp2_logits_match_single_chip(tiny_model, paged_eng, tp_eng):
    cfg, _ = tiny_model
    rng = np.random.RandomState(9)
    prompt = rng.randint(0, cfg.vocab_size, size=7).tolist()
    st, lt = tp_eng.start_sequence(prompt)
    sr, lr = paged_eng.start_sequence(prompt)
    np.testing.assert_allclose(lt, lr, rtol=1e-4, atol=1e-4)
    a, b = int(np.argmax(lt)), int(np.argmax(lr))
    for _ in range(6):
        oa = tp_eng.decode_step({st: a})
        ob = paged_eng.decode_step({sr: b})
        np.testing.assert_allclose(oa[st], ob[sr], rtol=1e-4, atol=1e-4)
        a, b = int(np.argmax(oa[st])), int(np.argmax(ob[sr]))
        assert a == b
    tp_eng.free_sequence(st)
    paged_eng.free_sequence(sr)


def test_tp2_zero_recompile_steady_state(tiny_model, tp_eng):
    cfg, _ = tiny_model
    compiles = tp_eng.compiles
    sched = serving.Scheduler(tp_eng)
    before = _recompile_total()
    rng = np.random.RandomState(11)
    reqs = [sched.submit(
        rng.randint(0, cfg.vocab_size, size=int(rng.randint(1, 16)))
        .tolist(), max_new_tokens=int(rng.randint(1, 5)))
        for _ in range(8)]
    while sched.pending():
        sched.step()
    assert all(r.state == "done" for r in reqs)
    assert _recompile_total() - before == 0
    assert tp_eng.compiles == compiles
    assert tp_eng.steady_state_recompiles == 0


def test_tp_rejects_int8_and_bad_sizes(tiny_model):
    cfg, params = tiny_model
    with pytest.raises(ValueError, match="int8"):
        serving.DecodeEngine(params, cfg, serving.EngineConfig(
            max_seq=32, sharding="tp", tp=2, weight_dtype="int8"))
    with pytest.raises(ValueError, match="divide"):
        serving.DecodeEngine(params, cfg, serving.EngineConfig(
            max_seq=32, sharding="tp", tp=3))


# ---------------------------------------------------------------------------
# safety rails on the new executables (ISSUE 13 satellite)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("layout_kw", [
    {"prefill_buckets": (8,)},
    {"sharding": "tp", "tp": 2, "prefill_buckets": (8,)},
], ids=["one_chip", "tp2"])
def test_poisoned_after_donation_failure_new_paths(tiny_model, layout_kw):
    """The donation-poisoning guard covers the one-chip and the tp
    executables."""
    eng = make_engine(tiny_model, **layout_kw)
    eng.warmup()

    def raiser(*a, **k):
        raise RuntimeError("device OOM")

    eng._donate = True              # simulate the TPU donation contract
    eng._exec["prefill_b8"] = raiser
    with pytest.raises(RuntimeError, match="device OOM"):
        eng.start_sequence([1, 2, 3])
    assert eng.poisoned is not None
    with pytest.raises(RuntimeError, match="poisoned"):
        eng.start_sequence([1, 2, 3])
    with pytest.raises(RuntimeError, match="poisoned"):
        eng.decode_step({0: 1})


@pytest.mark.parametrize("layout_kw", [
    {"prefill_buckets": (8,)},
    {"sharding": "tp", "tp": 2, "prefill_buckets": (8,)},
], ids=["one_chip", "tp2"])
def test_recompile_negative_control_new_paths(tiny_model, layout_kw):
    """A same-name rebuild under a drifted signature must tick the
    explainer + the engine's steady-state counter, on one chip and
    under the tp mesh."""
    eng = make_engine(tiny_model, **layout_kw)
    eng._prefill_exec(8)
    eng._warm = True
    before = _recompile_total()
    fn, example = eng._prefill_program(16)
    eng._compile("prefill_b8", fn, example, donate_argnums=(1,))
    assert _recompile_total() - before == 1
    assert eng.steady_state_recompiles == 1


# ---------------------------------------------------------------------------
# speculative decoding
# ---------------------------------------------------------------------------

def make_spec(tiny_model, k=3, draft_layers=1, same_params=False, **kw):
    cfg, params = tiny_model
    kw.setdefault("max_batch", 4)
    kw.setdefault("max_seq", 32)
    kw.setdefault("prefill_buckets", (8, 16))
    kw.setdefault("page_size", 8)
    target = serving.DecodeEngine(params, cfg, serving.EngineConfig(
        verify_window=k + 1, **kw))
    if same_params:
        dcfg, dparams = cfg, params
    else:
        dcfg = cfg.scaled(num_layers=draft_layers)
        dparams = gpt.init_params(jax.random.PRNGKey(42), dcfg)
    draft = serving.DecodeEngine(dparams, dcfg,
                                 serving.EngineConfig(**kw))
    return serving.SpecDecodeEngine(target, draft)


@pytest.fixture(scope="module")
def spec_eng(tiny_model):
    """Shared k=2, 1-layer-draft spec engine (warmup compiles are the
    expensive part — the greedy/interleaved/scheduler tests all ride
    this one; single-rung ladder, prompts <= 8)."""
    spec = make_spec(tiny_model, k=2, prefill_buckets=(8,))
    spec.warmup()
    return spec


@pytest.fixture(scope="module")
def spec_self_eng(tiny_model):
    """Shared draft==target spec engine (acceptance must be exactly 1)."""
    spec = make_spec(tiny_model, k=2, same_params=True,
                     prefill_buckets=(8,))
    spec.warmup()
    return spec


def test_spec_greedy_exact(tiny_model, spec_eng):
    cfg, _ = tiny_model
    spec = spec_eng
    rng = np.random.RandomState(13)
    for plen in (3, 8):
        prompt = rng.randint(0, cfg.vocab_size, size=plen).tolist()
        want = _reference(spec.target, prompt, 12)
        slot, _l, tok = spec.start_sequence_sampled(prompt, serving.GREEDY)
        got = [tok]
        while len(got) < 12:
            out = spec.generate_step({slot: got[-1]},
                                     {slot: serving.GREEDY})
            got.extend(out[slot])
        spec.free_sequence(slot)
        assert got[:12] == want
    assert spec.stats.windows > 0 and spec.stats.proposed > 0


def test_spec_self_draft_accepts_everything(tiny_model, spec_self_eng):
    """draft == target: every proposal must be accepted (acceptance rate
    exactly 1.0) and each window emits k+1 tokens."""
    spec = spec_self_eng
    slot, _l, tok = spec.start_sequence_sampled([5, 3, 1], serving.GREEDY)
    got = [tok]
    for _ in range(4):
        out = spec.generate_step({slot: got[-1]}, {slot: serving.GREEDY})
        assert len(out[slot]) == 3            # k accepted + bonus
        got.extend(out[slot])
    spec.free_sequence(slot)
    assert spec.stats.acceptance_rate == 1.0
    assert spec.stats.tokens_per_window == 3.0


def test_spec_interleaved_slots(tiny_model, spec_eng):
    cfg, _ = tiny_model
    spec = spec_eng
    rng = np.random.RandomState(17)
    p_a = rng.randint(0, cfg.vocab_size, size=4).tolist()
    p_b = rng.randint(0, cfg.vocab_size, size=8).tolist()
    sa, _la, ta0 = spec.start_sequence_sampled(p_a, serving.GREEDY)
    sb, _lb, tb0 = spec.start_sequence_sampled(p_b, serving.GREEDY)
    ta, tb = [ta0], [tb0]
    for _ in range(4):
        out = spec.generate_step({sa: ta[-1], sb: tb[-1]},
                                 {sa: serving.GREEDY, sb: serving.GREEDY})
        ta.extend(out[sa])
        tb.extend(out[sb])
    spec.free_sequence(sa)
    spec.free_sequence(sb)
    n = min(len(ta), len(tb), 8)
    assert ta[:n] == _reference(spec.target, p_a, n)
    assert tb[:n] == _reference(spec.target, p_b, n)


def test_spec_sampled_rejection_math(tiny_model, spec_self_eng):
    """Sampled spec with draft == target: p_t == p_d, so min(1, ratio)
    is 1 — everything accepted and the stream equals the draft's (and
    therefore the target's) sampled distribution."""
    spec = spec_self_eng
    acc0, prop0 = spec.stats.accepted, spec.stats.proposed
    sp = serving.SamplingParams(temperature=0.9, top_k=8, seed=31)
    slot, _l, tok = spec.start_sequence_sampled([6, 2, 8], sp)
    got = [tok]
    for _ in range(3):
        out = spec.generate_step({slot: got[-1]}, {slot: sp})
        got.extend(out[slot])
    spec.free_sequence(slot)
    assert spec.stats.accepted - acc0 == spec.stats.proposed - prop0 > 0


def test_spec_scheduler_end_to_end(tiny_model, spec_eng):
    """Spec engine behind the full scheduler: requests complete, emitted
    streams equal the target-only greedy reference, zero recompiles."""
    cfg, _ = tiny_model
    spec = spec_eng
    sched = serving.Scheduler(spec)
    before = _recompile_total()
    rng = np.random.RandomState(19)
    prompts = [rng.randint(0, cfg.vocab_size,
                           size=int(rng.randint(2, 9))).tolist()
               for _ in range(5)]
    reqs = [sched.submit(p, max_new_tokens=7) for p in prompts]
    while sched.pending():
        sched.step()
    assert all(r.state == "done" for r in reqs)
    for p, r in zip(prompts, reqs):
        assert r.tokens == _reference(spec.target, p, len(r.tokens))
        assert len(r.tokens) == 7
    assert _recompile_total() - before == 0
    assert spec.steady_state_recompiles == 0
    # acceptance telemetry moved
    snap = om.default_registry().snapshot()
    hist = snap["paddle_serve_spec_accepted_tokens"]["series"][0]
    assert hist["count"] >= spec.stats.windows > 0


# ---------------------------------------------------------------------------
# ISSUE 27: the KV pools are the layer loop's carry, and the decode tick has
# two lowerings of one algorithm (Pallas through the page table / gather)
# ---------------------------------------------------------------------------

def _xs_ys_layers(body, x, kp, vp, blocks):
    """The parent's data flow for the same per-layer arithmetic: each
    layer's pool is sliced out as the scan's ``xs`` and re-stacked as its
    ``ys``."""
    import jax.numpy as jnp

    def step(h, xs):
        layer_p, kp_l, vp_l = xs
        h, kp_l, vp_l = body(h, layer_p, jnp.int32(0), kp_l[None],
                             vp_l[None])
        return h, (kp_l[0], vp_l[0])

    x, (kp, vp) = jax.lax.scan(step, x, (blocks, kp, vp))
    return x, kp, vp


def _seeded_pools(eng, seed):
    rng = np.random.default_rng(seed)
    shape = eng.cache.k.shape
    return (jax.numpy.asarray(rng.standard_normal(shape), eng.cache.dtype),
            jax.numpy.asarray(rng.standard_normal(shape), eng.cache.dtype))


def _paged_program(eng, program, rng):
    """(fn, args after the pools) of one paged program over a cache in
    which slots 0 and 2 are live (7 and 16 rows: mid-page, page edge),
    slot 1 is a dead lane."""
    B, M = eng.ecfg.max_batch, eng.cache.max_pages_per_slot
    V = eng.cfg.vocab_size
    tables = np.zeros((B, M), np.int32)
    tables[0] = 1 + np.arange(M)
    tables[2] = 1 + M + np.arange(M)
    if program == "decode":
        return eng._decode_fn_paged, (_pack_slot_feed(
            rng.integers(0, V, (B,)), [7, 0, 16, 0], tables, [1, 0, 1, 0],
            *_greedy_knobs(B)),)
    if program == "prefill":
        # a 8-token suffix behind a cached 8-token prefix, 5 valid
        return eng._prefill_fn_paged, (_pack_rung_feed(
            rng.integers(0, V, (1, 8)), 5, 8, tables[2], 2, 0.0, 0, 1.0,
            0),)
    return eng._verify_fn_paged, (_pack_slot_feed(
        rng.integers(0, V, (B, eng.ecfg.verify_window)), [7, 0, 14, 0],
        tables, [1, 0, 1, 0], *_greedy_knobs(B)),)


@pytest.mark.parametrize("program", ["decode", "prefill", "verify"])
def test_carried_pools_match_xs_ys_scan(tiny_model, monkeypatch, program):
    """Part 1 changes where the pools live in the layer loop, not a
    number: each paged program over the carried pools returns the pools,
    logits and tokens that the scan over per-layer slices returned."""
    from paddle_tpu.models import gpt_serving

    eng = make_engine(tiny_model, verify_window=3)
    assert eng.kv_path == "xla_gather"
    fn, args = _paged_program(eng, program, np.random.default_rng(5))
    kp, vp = _seeded_pools(eng, 6)

    def run():
        # every paged program takes the pools as one argument and hands
        # them back as one
        pools, logits, toks = jax.jit(fn)(eng.qparams, (kp, vp), *args)
        return (*pools, logits, toks)

    new = run()
    monkeypatch.setattr(gpt_serving, "layers_over_pools", _xs_ys_layers)
    old = run()
    for got, want in zip(new, old):
        np.testing.assert_array_equal(np.asarray(got, np.float32),
                                      np.asarray(want, np.float32))
    # the program wrote the rows it was given and no others
    changed = np.flatnonzero(
        (np.asarray(new[0], np.float32)           # [L, P, page, nh * hd]
         != np.asarray(kp, np.float32)).any(axis=(0, 2, 3)))
    M = eng.cache.max_pages_per_slot
    allowed = {"decode": {0, 1 + 0, 1 + M + 2},
               "prefill": {1 + M + 1},
               "verify": {0, 1 + 0, 1 + 1, 1 + M + 1, 1 + M + 2}}[program]
    assert set(changed.tolist()) <= allowed, changed


def test_serve_bench_engine_parity_lane_holds_the_reference(tiny_model):
    """tools/serve_bench.py's acceptance lane, whose oracle was a second
    cached engine: the engine's greedy tokens against ``reference_logits``
    and the tp=2 engine against the one-chip one."""
    import importlib.util
    import os

    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "serve_bench.py")
    spec = importlib.util.spec_from_file_location("serve_bench", path)
    sb = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(sb)
    cfg, params = tiny_model
    out = sb.engine_parity_lane(
        params, cfg, dict(max_batch=2, max_seq=32, prefill_buckets=(8,),
                          page_size=sb.PAGE_SIZE), seed=3, n_tokens=8)
    assert out["tokens"] == 8 and out["tokens_match_reference"] is True
    assert out["tp2_tokens_match"] is True
    assert out["tp2_max_logit_diff"] < 1e-4


@pytest.mark.parametrize("kv_path", ["xla_gather", "pallas_paged"])
def test_dead_lane_leaves_every_page_but_scratch(tiny_model, kv_path):
    """The paged heir of the masked-lane regression: a lane that does
    not ride (all-zero table row, ``actives`` 0) writes the scratch page
    and nothing else, in both lowerings of the tick; a live lane beside
    it writes its one row."""
    eng = make_engine(tiny_model)
    eng.kv_path = kv_path                    # before anything compiles
    B, M = eng.ecfg.max_batch, eng.cache.max_pages_per_slot
    ps, V = eng.ecfg.page_size, eng.cfg.vocab_size
    rng = np.random.default_rng(11)
    tables = np.zeros((B, M), np.int32)
    tables[2] = 1 + M + np.arange(M)         # the one rider
    tokens = rng.integers(0, V, (B,))
    feed = _pack_slot_feed(tokens, [0, 0, 11, 0], tables, [0, 0, 1, 0],
                           *_greedy_knobs(B))
    kp, vp = _seeded_pools(eng, 12)
    (kp2, vp2), _logits, _toks = jax.jit(eng._decode_fn_paged)(
        eng.qparams, (kp, vp), feed)
    for before, after in ((kp, kp2), (vp, vp2)):
        before = np.asarray(before, np.float32)
        after = np.asarray(after, np.float32)
        differs = before != after                # [L, P, page, nh * hd]
        pages = np.flatnonzero(differs.any(axis=(0, 2, 3)))
        assert set(pages.tolist()) == {0, 1 + M + 11 // ps}
        # on the rider's page, row 11 % page_size alone
        rows = np.flatnonzero(
            differs[:, 1 + M + 11 // ps].any(axis=(0, 2)))
        assert rows.tolist() == [11 % ps]
    # all lanes dead: the scratch page alone
    dead = _pack_slot_feed(tokens, np.zeros((B,)), np.zeros((B, M)),
                           np.zeros((B,)), *_greedy_knobs(B))
    (kp3, _vp3), _l, _t = jax.jit(eng._decode_fn_paged)(
        eng.qparams, (kp, vp), dead)
    np.testing.assert_array_equal(np.asarray(kp3, np.float32)[:, 1:],
                                  np.asarray(kp, np.float32)[:, 1:])


def test_kernel_and_gather_ticks_agree_over_a_run(tiny_model):
    """The two lowerings of the paged decode tick (the Pallas kernel, in
    interpret mode here, and gather + masked softmax) through a
    multi-tick, multi-slot engine run: the same greedy tokens, logits and
    pools to float rounding. The test steers the engine's choice (the CPU
    lane would take the gather), as tests/test_chip_compile.py steers the
    backend question."""
    gather = make_engine(tiny_model)
    kernel = make_engine(tiny_model)
    kernel.kv_path = "pallas_paged"          # before anything compiles
    assert gather.kv_path == "xla_gather"
    rng = np.random.RandomState(3)
    prompts = [rng.randint(0, gather.cfg.vocab_size, size=n).tolist()
               for n in (1, 8, 13)]          # 1 token; a page edge; ragged
    runs = []
    for eng in (gather, kernel):
        slots, toks = [], {}
        for p in prompts:
            slot, logits = eng.start_sequence(p)
            slots.append(slot)
            toks[slot] = int(np.argmax(logits))
        trail = []
        for tick in range(10):
            feed = dict(toks)
            if tick == 4:                    # one slot sits a tick out
                feed.pop(slots[1])
            out = eng.decode_step(feed)
            for slot, logits in out.items():
                toks[slot] = int(np.argmax(logits))
                trail.append((tick, slot, logits))
        runs.append((trail, np.asarray(eng.cache.k, np.float32),
                     np.asarray(eng.cache.v, np.float32)))
    (t_g, k_g, v_g), (t_k, k_k, v_k) = runs
    assert len(t_g) == len(t_k) == 29
    for (tick, slot, lg), (_, slot_k, lk) in zip(t_g, t_k):
        assert slot == slot_k
        assert int(np.argmax(lg)) == int(np.argmax(lk)), (tick, slot)
        np.testing.assert_allclose(lk, lg, atol=2e-5, rtol=2e-5)
    # page 0 is the scratch page: dead lanes write it, nothing reads it
    np.testing.assert_allclose(k_k[:, 1:], k_g[:, 1:], atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(v_k[:, 1:], v_g[:, 1:], atol=2e-5, rtol=2e-5)


def test_tick_record_names_kv_path_and_live_pages(tiny_model):
    """serve/decode_tick says how the tick read the cache and how many
    pages its riders hold; /health says the engine's path."""
    from paddle_tpu.observability import spans
    from paddle_tpu.serving.server import FrontDoor

    eng = make_engine(tiny_model)
    eng.warmup()
    sched = serving.Scheduler(eng, serving.SchedulerConfig())
    tracer = spans.default_tracer()
    before = len(tracer.spans())
    reqs = [sched.submit([1, 2, 3], max_new_tokens=3),
            sched.submit(list(range(1, 10)), max_new_tokens=3)]
    for _ in range(8):
        sched.step()
    assert all(r.state == "done" for r in reqs)
    ticks = [s for s in tracer.spans()[before:]
             if s["name"] == "serve/decode_tick"]
    assert ticks
    assert {t["attrs"]["kv_path"] for t in ticks} == {"xla_gather"}
    # 3 and 9 prompt tokens (+ the first generated): 1 page and 2 pages
    assert ticks[0]["attrs"]["live_pages"] == 3
    front = FrontDoor(scheduler=sched, port=0)
    try:
        assert front.health()["kv_path"] == "xla_gather"
    finally:
        front.httpd.server_close()
