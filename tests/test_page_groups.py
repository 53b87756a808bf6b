"""``PagedKVCache`` with several page groups (docs/serving.md "Window and
global layers"): a window group's ring and its bound, admission and release
over both groups, the pages a rider gives back while it decodes; and a
manager of one group as it was."""
import numpy as np
import pytest

import jax.numpy as jnp

from paddle_tpu.serving import metrics as smetrics
from paddle_tpu.serving.paged_kv import (CacheFullError, PagedKVCache,
                                         PagePoolFullError, PrefixCache,
                                         table_width)

ROWS = ((16,),) * 2


def _cache(window=8, page=4, full_pages=0, window_pages=0, slots=3,
           max_seq=64):
    return PagedKVCache(
        0, slots, max_seq, dtype=jnp.float32, page_size=page,
        num_pages=full_pages, groups=[
            {"name": "full", "layers": 1, "rows": ROWS, "window": None},
            {"name": "window", "layers": 3, "rows": ROWS, "window": window,
             "num_pages": window_pages}])


@pytest.mark.parametrize("window, page, max_seq, width", [
    (None, 16, 1024, 64), (4096, 64, 17408, 65), (4096, 16, 17408, 257),
    (8, 4, 64, 3), (12, 8, 64, 3), (10, 4, 64, 4), (100, 4, 64, 16)])
def test_table_width_is_the_ring_a_window_touches(window, page, max_seq,
                                                  width):
    assert table_width(window, max_seq, page) == width
    if window is not None and width < max_seq // page:
        # a window ending anywhere lies on at most ``width`` pages
        for end in range(window, window + 3 * page):
            first, last = (end - window + 1) // page, end // page
            assert last - first + 1 <= width
        # and never more than the window and two pages of tokens
        assert width * page <= window + 2 * page


def test_geometry_of_two_groups():
    c = _cache()
    assert [a.shape for a in c.arrays()] == [
        (1, 49, 4, 16), (1, 49, 4, 16), (3, 10, 4, 16), (3, 10, 4, 16)]
    assert c.table_widths == (16, 3)
    assert c.nbytes == 2 * (49 + 3 * 10) * 4 * 16 * 4
    assert not c.keys_and_values           # page I/O is for one group
    assert c.group("window").window == 8 and c.group("nope") is None
    assert c.table_row(0).shape == (19,)
    assert c.table_rows(np.array([0, 2])).shape == (2, 19)
    arrays = c.arrays()
    c.set_arrays(tuple(a + 1 for a in arrays))
    assert float(c.groups[1].pools[1][0, 0, 0, 0]) == 1.0


@pytest.mark.parametrize("length, full, window", [
    (1, 1, 1), (4, 1, 1), (5, 2, 2), (12, 3, 3), (13, 4, 3), (40, 10, 3)])
def test_alloc_maps_what_each_group_still_holds(length, full, window):
    c = _cache()
    slot = c.alloc(length)
    assert c.pages_held(slot) == {"full": full, "window": window}
    row = c.table_row(slot)
    assert np.count_nonzero(row[:16]) == full
    # the ring: logical page j at entry j % 3, the last three alone
    n = -(-length // 4)
    ring = row[16:]
    for j in range(max(0, n - 3), n):
        assert ring[j % 3] != 0
    assert np.count_nonzero(ring) == window
    assert c.length(slot) == length
    c.free(slot)
    assert [g.held_pages() for g in c.groups] == [0, 0]
    assert not c.table_row(slot).any()


def test_a_rider_gives_back_the_pages_that_leave_its_window():
    """Decoding from 6 to 41 tokens over pages of 4 with a window of 8: the
    full group grows a page every four tokens, the window group turns its
    ring and never holds more than 3 pages (12 = 8 + 4 tokens)."""
    c = _cache()
    slot = c.alloc(6)
    released0 = smetrics.m_window_released.value
    seen = set()
    for n in range(6, 41):
        assert c.ensure_capacity(slot, n + 1)
        c.set_length(slot, n + 1)
        held = c.pages_held(slot)
        assert held["full"] == -(-(n + 1) // 4)
        assert held["window"] == min(held["full"], 3)
        assert held["window"] * 4 <= 8 + 2 * 4
        ring = c.groups[1].tables[slot]
        # the page the newest row lies on is at its ring entry
        assert ring[(n // 4) % 3] != 0
        seen.update(int(p) for p in ring if p)
    g = c.group("window")
    assert g.released == 11 - 3            # pages 0..7 left the window
    assert smetrics.m_window_released.value - released0 == 8
    assert len(seen) <= g.num_pages - 1
    assert c.live_rows([slot]) == {"full": 41, "window": 8}
    assert c.live_rows([slot], extra=1) == {"full": 42, "window": 8}
    assert c.held_over_one_table() == pytest.approx(
        (11 * 1 + 3 * 3) / (11 * 4))
    c.free(slot)
    assert g.held_pages() == 0 and len(g.free_pages) == g.num_pages - 1
    assert np.all(g.ref[1:] == 0)
    assert c.held_over_one_table() is None


def test_admission_counts_every_group():
    # the full group is the short one: 6 pages
    c = _cache(full_pages=7)
    assert c.can_admit(24) and not c.can_admit(25)
    a = c.alloc(16)                          # 4 pages of 6
    assert c.can_admit(8) and not c.can_admit(9)
    with pytest.raises(PagePoolFullError, match="need 3 free"):
        c.alloc(12)
    assert c.pages_held(1) == {"full": 0, "window": 0}   # nothing leaked
    assert c.free_slot_count() == 2
    # the window group is the short one: 4 pages, a ring takes 3
    c = _cache(window_pages=5)
    a = c.alloc(40)
    assert c.pages_held(a)["window"] == 3
    assert c.can_admit(4) and not c.can_admit(5)
    with pytest.raises(PagePoolFullError):
        c.alloc(8)
    assert c.groups[0].held_pages() == 10    # the full group gave it back
    b = c.alloc(3)
    # b cannot grow into a second page until a leaves: the scheduler's cue
    assert c.ensure_capacity(b, 4) and not c.ensure_capacity(b, 5)
    assert c.pages_held(b) == {"full": 1, "window": 1}
    # a turns its ring in place all the same: a page back, a page taken
    assert c.ensure_capacity(a, 45)
    c.free(a)
    assert c.ensure_capacity(b, 5)
    c.alloc(1), c.alloc(1)
    with pytest.raises(CacheFullError):
        c.alloc(1)


def test_occupancy_and_fragmentation_count_every_group():
    c = _cache()
    assert c.pool_occupancy() == 0.0
    slot = c.alloc(40)
    assert c.pool_occupancy() == pytest.approx((10 + 3) / (48 + 9))
    assert c.fragmentation() == 0.0          # whole pages in both
    c.ensure_capacity(slot, 42)
    c.set_length(slot, 42)
    # 11 and 3 pages for 42 and 42 - 8 x 4 = 10 rows: two rows of a page
    assert c.fragmentation() == pytest.approx(1 - (42 + 10) / (14 * 4))
    gauge = smetrics.m_kv_pages
    assert gauge.labels("full").value == 11
    assert gauge.labels("window").value == 3


def test_shared_prefix_pages_are_refused_over_several_groups():
    c = _cache()
    with pytest.raises(ValueError, match="one group"):
        c.alloc(8, prefix_pages=[1])
    for call in (lambda: c.read_pages([1]),
                 lambda: c.write_pages([1], None, None),
                 lambda: c.adopt_slot(4, [1])):
        with pytest.raises(ValueError, match="key and value pair"):
            call()


def test_a_manager_of_one_group_is_what_it_was():
    """The four older descriptions state one group: the names, the table
    and the page plumbing they had, and the prefix cache over them."""
    c = PagedKVCache(2, 2, 32, 2, 8, page_size=8, num_pages=6)
    assert len(c.groups) == 1 and c.groups[0].window is None
    assert (c.num_layers, c.num_pages, c.rows) == (2, 6, ((2, 8),) * 2)
    assert c.k.shape == (2, 6, 8, 2, 8) and c.keys_and_values
    assert c.table_widths == (4,) and c.pools is c.groups[0].pools
    slot = c.alloc(9)
    assert c.table_row(slot).tolist() == [1, 2, 0, 0]
    assert c._tables is c.groups[0].tables and c._ref[1] == 1
    assert c._free_pages == [3, 4, 5] and c.free_page_count() == 3
    assert c.held_over_one_table() == 1.0
    assert c.live_rows([slot]) == {"full": 9}
    prefix = PrefixCache(c)
    c.prefix_cache = prefix
    tokens = list(range(16))
    c.ensure_capacity(slot, 16)
    prefix.insert(tokens, c.table_row(slot))
    c.free(slot)
    assert prefix.reclaimable() == 2 and c.can_admit(32)
    hit, pages = prefix.lookup(tokens + [1])
    assert hit == 16 and c.alloc(17, prefix_pages=pages) == 0
    assert c.pool_occupancy() == pytest.approx(3 / 5)
    latent = PagedKVCache(3, 2, 32, page_size=8, rows=((128,),))
    assert [a.shape for a in latent.arrays()] == [(3, 9, 8, 128)]
    assert not latent.keys_and_values
