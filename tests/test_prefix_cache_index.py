"""ISSUE 40: the prefix cache's index costs a prompt's pages, not their
square. The class as it stood before (whole-prefix keys, whole-prefix
tuples in every entry, a walk over every entry for the held pages) is
frozen here as the plain reference; the chained, counted one has to answer
every call as it does, and leave the pool as it does. Two tests count
instead of timing: the bytes a prefill feeds the hash, and the entries an
insert touches in a large cache."""
import hashlib
from collections import OrderedDict

import numpy as np
import pytest

import jax

from paddle_tpu import serving
from paddle_tpu.models import gpt
from paddle_tpu.observability import spans
from paddle_tpu.serving import paged_kv
from paddle_tpu.serving.paged_kv import (CacheFullError, PagedKVCache,
                                         PagePoolFullError, PrefixCache)


class ReferencePrefixCache:
    """``PrefixCache`` as of PR 39, less its metric counts: the semantics
    the index is held to."""

    def __init__(self, pool, capacity_pages=0):
        self.pool = pool
        self.capacity_pages = int(capacity_pages) or pool.num_pages
        self._entries = OrderedDict()   # key -> (tokens tuple, pages tuple)
        self.hits = 0
        self.misses = 0

    @staticmethod
    def _key(tokens):
        return hashlib.sha1(np.asarray(tokens, np.int64).tobytes()).digest()

    def _held_pages(self):
        held = set()
        for _, pages in self._entries.values():
            held.update(pages)
        return held

    def held_page_count(self):
        return len(self._held_pages())

    def reclaimable(self):
        return sum(1 for p in self._held_pages() if self.pool._ref[p] == 1)

    def has(self, tokens):
        key = self._key(tuple(int(t) for t in tokens))
        ent = self._entries.get(key)
        return ent is not None and ent[0] == tuple(int(t) for t in tokens)

    def lookup(self, tokens):
        ps = self.pool.page_size
        max_j = (len(tokens) - 1) // ps
        for j in range(max_j, 0, -1):
            prefix = tuple(int(t) for t in tokens[:j * ps])
            key = self._key(prefix)
            ent = self._entries.get(key)
            if ent is not None and ent[0] == prefix:
                self._entries.move_to_end(key)
                self.hits += 1
                return j * ps, ent[1]
        self.misses += 1
        return 0, ()

    def adopt_nested(self, tokens, pages):
        ps = self.pool.page_size
        pages = tuple(int(p) for p in pages)
        if len(tokens) < len(pages) * ps:
            raise ValueError("adopted pages cover more than the tokens")
        registered = 0
        for j in range(1, len(pages) + 1):
            prefix = tuple(int(t) for t in tokens[:j * ps])
            key = self._key(prefix)
            if key in self._entries:
                continue
            self._entries[key] = (prefix, pages[:j])
            registered += 1
        self._evict_over_capacity()
        return registered

    def insert(self, tokens, table_row):
        ps = self.pool.page_size
        full = len(tokens) // ps
        added = 0
        newly_held = []
        held = self._held_pages()
        for j in range(1, full + 1):
            prefix = tuple(int(t) for t in tokens[:j * ps])
            key = self._key(prefix)
            if key in self._entries:
                self._entries.move_to_end(key)
                continue
            pages = tuple(int(p) for p in table_row[:j])
            if any(p == 0 for p in pages):
                break
            self._entries[key] = (prefix, pages)
            added += 1
            for p in pages:
                if p not in held:
                    held.add(p)
                    newly_held.append(p)
        if newly_held:
            self.pool.ref_pages(newly_held)
        self._evict_over_capacity()
        return added

    def _drop_entry(self, key):
        _tokens, pages = self._entries.pop(key)
        still_held = self._held_pages()
        self.pool.deref_pages([p for p in pages if p not in still_held])

    def _evict_over_capacity(self):
        while (self._entries
               and self.held_page_count() > self.capacity_pages):
            self._drop_entry(next(iter(self._entries)))

    def reclaim(self, n_pages):
        freed0 = self.pool.free_page_count()
        while (self._entries
               and self.pool.free_page_count() - freed0 < n_pages):
            self._drop_entry(next(iter(self._entries)))
        return self.pool.free_page_count() - freed0

    def clear(self):
        while self._entries:
            self._drop_entry(next(iter(self._entries)))

    def __len__(self):
        return len(self._entries)


PAGE = 4
MAX_SEQ = 96
SLOTS = 6
CAPACITIES = (4, 8, 16, 32, 64, 128)


def _pool_and_cache(cls, num_pages, capacity):
    pool = PagedKVCache(num_layers=1, max_slots=SLOTS, max_seq=MAX_SEQ,
                        num_heads=1, head_dim=1, page_size=PAGE,
                        num_pages=num_pages)
    pool.prefix_cache = cls(pool, capacity)
    return pool, pool.prefix_cache


def _outcome(fn):
    """What a call returned, or the pool's refusal it raised."""
    try:
        out = fn()
    except (PagePoolFullError, CacheFullError) as e:
        return type(e).__name__
    if isinstance(out, np.ndarray):
        return out.tolist()
    if isinstance(out, tuple):
        return tuple(tuple(x) if isinstance(x, tuple) else x for x in out)
    return out


class _Pair:
    """The reference and the index over two pools of one geometry, driven
    by the same calls; every call's outcomes and the state after it are
    compared."""

    def __init__(self, num_pages, capacity):
        self.sides = [_pool_and_cache(ReferencePrefixCache, num_pages,
                                      capacity),
                      _pool_and_cache(PrefixCache, num_pages, capacity)]

    def both(self, fn):
        want, got = (_outcome(lambda: fn(pool, cache))
                     for pool, cache in self.sides)
        assert got == want, (got, want)
        self.check()
        return want

    def check(self):
        (rp, rc), (p, c) = self.sides
        assert (c.hits, c.misses, len(c)) == (rc.hits, rc.misses, len(rc))
        assert c.held_page_count() == rc.held_page_count()
        assert c.reclaimable() == rc.reclaimable()
        assert p._ref.tolist() == rp._ref.tolist()
        assert p._free_pages == rp._free_pages
        assert p._free_slots == rp._free_slots
        assert p._tables.tolist() == rp._tables.tolist()


def _prefill(pool, cache, tokens):
    """The engine's use of the cache around one prefill."""
    prefix_len, pages = cache.lookup(tokens)
    slot = pool.alloc(length=len(tokens), prefix_pages=pages)
    added = cache.insert(tokens, pool.table_row(slot))
    return prefix_len, tuple(pages), slot, added


def _adopt(pool, cache, tokens):
    """serving/kv_transfer.py's and prefix_store.py's use: claim, adopt."""
    n = len(tokens) // pool.page_size
    if cache.has(tokens) or pool.free_page_count() <= n:
        return None
    return cache.adopt_nested(tokens, pool.claim_pages(n))


@pytest.mark.parametrize("seed", range(24))
def test_index_answers_and_leaves_the_pool_as_the_reference_does(seed):
    rng = np.random.default_rng(seed)
    capacity = CAPACITIES[seed % len(CAPACITIES)]
    # a pool the live slots alone can run dry (6 x 24 pages asked of it),
    # so alloc and ensure_capacity lean on reclaim
    num_pages = int(rng.integers(40, 110))
    pair = _Pair(num_pages, capacity)
    stems = [rng.integers(0, 50, int(rng.integers(2, 15)) * PAGE).tolist()
             for _ in range(4)]
    live = []

    def prompt():
        stem = stems[int(rng.integers(len(stems)))]
        cut = int(rng.integers(0, len(stem) + 1))
        if rng.random() < 0.5:
            cut -= cut % PAGE
        n = cut + int(rng.integers(1, MAX_SEQ - 8 - cut))
        if rng.random() < 0.4:                 # page-aligned whole
            n -= n % PAGE
            if n <= cut:
                n += PAGE
        return stem[:cut] + rng.integers(50, 60, n - cut).tolist()

    for _ in range(220):
        op = rng.random()
        if op < 0.38:
            tokens = prompt()
            out = pair.both(lambda p, c: _prefill(p, c, tokens))
            if not isinstance(out, str):
                live.append(out[2])
        elif op < 0.58 and live:
            slot = live.pop(int(rng.integers(len(live))))
            pair.both(lambda p, c: p.free(slot))
        elif op < 0.68 and live:
            slot = live[int(rng.integers(len(live)))]
            upto = min(MAX_SEQ, pair.sides[0][0].length(slot)
                       + int(rng.integers(1, 4 * PAGE)))
            if pair.both(lambda p, c: p.ensure_capacity(slot, upto)):
                pair.both(lambda p, c: p.set_length(slot, upto))
        elif op < 0.76:
            tokens = prompt()
            pair.both(lambda p, c: c.lookup(tokens))
        elif op < 0.84:
            tokens = prompt()
            tokens = tokens[:len(tokens) - len(tokens) % PAGE
                            if rng.random() < 0.8 else len(tokens)]
            pair.both(lambda p, c: c.has(tokens))
        elif op < 0.92:
            tokens = prompt()
            tokens = tokens[:len(tokens) - len(tokens) % PAGE]
            if tokens:
                pair.both(lambda p, c: _adopt(p, c, tokens))
        elif op < 0.95:
            n = int(rng.integers(1, 90))
            pair.both(lambda p, c: p.can_admit(n))
        elif op < 0.99:
            n = int(rng.integers(1, 12))
            pair.both(lambda p, c: c.reclaim(n))
        else:
            pair.both(lambda p, c: c.clear())
    for slot in live:
        pair.both(lambda p, c: p.free(slot))
    pair.both(lambda p, c: c.clear())
    pool = pair.sides[1][0]
    assert pool.free_page_count() == num_pages - 1
    assert not pair.sides[1][1]._held


def test_a_long_entry_is_a_hit_after_its_short_one_was_evicted():
    """LRU drops a prompt's first page's entry first, and where that entry
    maps a page of its own the eviction can stop there: the probe runs from
    the longest boundary down and does not stop at the gap."""
    pair = _Pair(num_pages=40, capacity=5)
    a = list(range(4 * PAGE))

    def unshared(pool, cache, tokens):
        # a prefill whose hit the engine's _trim_prefix gave up
        cache.lookup(tokens)
        slot = pool.alloc(length=len(tokens))
        return slot, cache.insert(tokens, pool.table_row(slot))

    out = pair.both(lambda p, c: _prefill(p, c, a[:PAGE] + [7]))
    pair.both(lambda p, c: p.free(out[2]))
    slot, added = pair.both(lambda p, c: unshared(p, c, a + [7]))
    assert added == 3
    pair.both(lambda p, c: p.free(slot))
    # a sixth page over a capacity of five: the one-page entry goes, and
    # its page with it
    out = pair.both(lambda p, c: _prefill(p, c, [90] * PAGE + [7]))
    pair.both(lambda p, c: p.free(out[2]))
    cache = pair.sides[1][1]
    assert len(cache) == 4 and cache.held_page_count() == 5
    assert pair.both(lambda p, c: (c.has(a[:PAGE]), c.has(a))) == (
        False, True)
    hit = pair.both(lambda p, c: c.lookup(a + [9]))
    assert hit[0] == 4 * PAGE and len(hit[1]) == 4


class _CountingHash:
    """``hashlib`` as paged_kv sees it, counting what it is fed."""

    def __init__(self):
        self.calls = 0
        self.bytes = 0

    def sha256(self, data=b""):
        self.calls += 1
        self.bytes += len(data)
        return hashlib.sha256(data)


def test_a_prefill_feeds_the_hash_its_prompt_not_its_square(monkeypatch):
    counted = _CountingHash()
    monkeypatch.setattr(paged_kv, "hashlib", counted)
    pool = PagedKVCache(num_layers=1, max_slots=2, max_seq=2048,
                        num_heads=1, head_dim=1, page_size=16,
                        num_pages=300)
    cache = pool.prefix_cache = PrefixCache(pool, 128)
    tokens = np.random.default_rng(0).integers(0, 50257, 1536).tolist()
    # as engine.py:start_sequence_sampled: the lookup's keys publish
    keys = cache.page_keys(tokens)
    assert cache.lookup(tokens, keys) == (0, ())
    slot = pool.alloc(length=len(tokens))
    assert cache.insert(tokens, pool.table_row(slot), keys) == 96
    assert counted.calls == 96
    assert counted.bytes <= 2 * 1536 * 8
    # and without the keys handed over, twice that and no more
    assert cache.lookup(tokens + [1]) == (1536, tuple(
        pool.table_row(slot)[:96].tolist()))
    assert cache.insert(tokens, pool.table_row(slot)) == 0
    assert counted.calls == 3 * 96


class _Touched(OrderedDict):
    """An ``OrderedDict`` that notes the keys asked of it, and any walk."""

    def __init__(self, *args):
        self.keys_touched = set()
        self.walks = 0
        super().__init__(*args)
        self.keys_touched.clear()
        self.walks = 0

    def _note(self, key):
        self.keys_touched.add(key)
        return key

    def __getitem__(self, key):
        return super().__getitem__(self._note(key))

    def __setitem__(self, key, value):
        super().__setitem__(self._note(key), value)

    def __delitem__(self, key):
        super().__delitem__(self._note(key))

    def __contains__(self, key):
        return super().__contains__(self._note(key))

    def get(self, key, default=None):
        return super().get(self._note(key), default)

    def pop(self, key, *default):
        return super().pop(self._note(key), *default)

    def move_to_end(self, key, last=True):
        super().move_to_end(self._note(key), last)

    def popitem(self, last=True):
        key, value = super().popitem(last)
        self._note(key)
        return key, value

    def __iter__(self):
        self.walks += 1
        return super().__iter__()

    def values(self):
        self.walks += 1
        return super().values()

    def items(self):
        self.walks += 1
        return super().items()

    def keys(self):
        self.walks += 1
        return super().keys()


def test_an_insert_touches_its_own_chain_and_what_it_evicts():
    ps = 16
    pool = PagedKVCache(num_layers=1, max_slots=2, max_seq=1024,
                        num_heads=1, head_dim=1, page_size=ps,
                        num_pages=2200)
    cache = pool.prefix_cache = PrefixCache(pool, 2010)
    rng = np.random.default_rng(1)
    while len(cache) < 2000:
        old = rng.integers(0, 50257, 40 * ps).tolist()
        slot = pool.alloc(length=len(old))
        cache.insert(old, pool.table_row(slot))
        pool.free(slot)
    assert len(cache) == cache.held_page_count() == 2000
    before = list(cache._entries)
    cache._entries = _Touched(cache._entries)
    tokens = rng.integers(0, 50257, 24 * ps + 3).tolist()
    keys = cache.page_keys(tokens)
    assert cache.lookup(tokens, keys) == (0, ())
    slot = pool.alloc(length=len(tokens))
    evicted = cache.evicted
    assert cache.insert(tokens, pool.table_row(slot), keys) == 24
    # 2024 pages held over a capacity of 2010: the oldest 40-page chain
    # lets go of its pages with its last entry
    assert cache.evicted - evicted == 40
    assert cache.held_page_count() == 2000 + 24 - 40
    assert cache._entries.walks == 0
    assert cache._entries.keys_touched == set(keys) | set(before[:40])
    assert cache.reclaimable() == cache.held_page_count() - 24
    assert cache._entries.walks == 0


def test_the_engine_hashes_a_prompt_once_and_its_spans_say_so(monkeypatch):
    cfg = gpt.GPT_TINY.scaled(num_layers=1, max_seq_len=64)
    params = gpt.init_params(jax.random.PRNGKey(0), cfg)
    eng = serving.DecodeEngine(params, cfg, serving.EngineConfig(
        max_batch=2, max_seq=32, prefill_buckets=(8, 24), page_size=8,
        prefix_cache_pages=3))
    counted = _CountingHash()
    monkeypatch.setattr(paged_kv, "hashlib", counted)
    tracer = spans.default_tracer()

    def prefill(tokens):
        tracer.clear()
        slot, _logits = eng.start_sequence(tokens)
        eng.cache.free(slot)
        by_name = {s["name"]: s.get("attrs") for s in tracer.spans()}
        return (by_name["serve/prefill"]["prefix_len"],
                by_name["prefill/prep"], by_name["prefill/publish"])

    first = list(range(1, 18))                  # two pages and a token
    assert prefill(first) == (0, {"prefix_pages_hashed": 2},
                              {"prefix_added": 2, "prefix_evicted": 0})
    assert (counted.calls, counted.bytes) == (2, 8 * 8 + (8 * 8 + 32))
    # a hit on both pages; the third page is new and fills the capacity
    assert prefill(first[:16] + [40] * 8) == (
        16, {"prefix_pages_hashed": 3},
        {"prefix_added": 1, "prefix_evicted": 0})
    # a fourth page is one too many: the first chain's two entries go and
    # free nothing (the second chain maps their pages), then the third
    assert prefill([50] * 9) == (0, {"prefix_pages_hashed": 1},
                                 {"prefix_added": 1, "prefix_evicted": 3})
    assert counted.calls == 2 + 3 + 1
