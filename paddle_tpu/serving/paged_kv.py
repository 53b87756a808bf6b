"""Block-granular paged KV cache + token-hash prefix cache (ISSUE 13,
docs/serving.md).

A private ``[max_seq]`` run of rows for every slot would hold 8 slots x
1024 positions of HBM even when seven of them hold 12-token chats. This
module is the engine's one cache, a **page pool**: one preallocated
``[L, num_pages, page_size, *row]`` K/V pair (a token's row: ``nh * hd``
flat lanes as the models here ask, ``(nh, hd)`` by default and under a
mesh), fixed-size pages handed
out from a host-side free list, and a per-slot **page table**
(``[max_pages_per_slot]`` int32 of physical page ids) that rides into the
decode/prefill executables as a plain device array — so long-context
and short-chat traffic share HBM at page granularity and no shape ever
changes (the zero-recompile contract is untouched).

Layout rules:

- **page 0 is the scratch page** — reserved, never allocated, never
  read. Unmapped page-table entries point at it, so bucket-padding rows
  written past a slot's allocation land harmlessly there instead of
  needing dynamic shapes.
- A slot's pages are mapped in logical order; positions ``< length`` are
  always backed by real pages (``ensure_capacity`` maps the next page at
  the token boundary *before* the decode step that writes into it).
- **Sharing is append-safe by construction**: shared pages are full,
  page-aligned prompt-prefix pages; every write a slot ever performs
  lands at positions ``>= prefix_len``, i.e. in pages it owns alone —
  no copy-on-write machinery needed.

The **prefix cache** keys page-aligned token prefixes by a chained
content hash, a page a link (:class:`PrefixCache` says what the key is
and what each call costs): after a
prompt prefill, its full pages are published under every page-boundary
prefix; a later prompt sharing the prefix attaches those pages by
refcount and prefills only its suffix through the continuation-prefill
executable. A shared system prompt therefore prefills ONCE per engine,
metered by ``paddle_serve_prefix_cache_total{hit|miss}``. Entries are
LRU; pool pressure reclaims cache-held pages before any allocation
fails.

**Recurrent state** (hybrid models, docs/serving.md "Hybrid models"): a
model whose layers are mostly recurrent mixers gives the manager a
``state`` geometry, the shapes of a slot's two state rows as the model
states them: ``{"layers": Lr, "conv": shape, "ssm": shape}``. A second
kind of cache then lives beside the pages: a fixed-size row per slot and
recurrent layer, ``conv [Lr, slots, *conv]`` in the cache's dtype (the
last inputs of the layer's causal conv) and ``ssm [Lr, slots, *ssm]``
float32 (the recurrence's state: Mamba's ``[d_state, d_inner]`` scan
state, or a delta rule's matrix a head), allocated once, carried through
the compiled programs in place like the pools. A slot's row is born with
the slot (the prefill
program writes it from an empty history, never from what the row held), is
advanced by the ticks the slot rides, and is dead at ``free``; the pool is
then built for the attention layers alone. The one object answers
``can_admit``, ``alloc``, ``free``, ``length`` and ``nbytes`` for both.

**Latent rows** (docs/serving.md "Latent attention"): a model whose
attention caches one compressed row a token, shared by all heads, asks for
``rows=((width,),)``: ONE pool ``[L, num_pages, page_size, width]`` in place
of the key and value pair. Pages, tables, refcounts and the allocator are
the same; what a page holds is the model's business. The page-content I/O
(``read_pages``/``write_pages``/``adopt_slot``: prefix store, KV hand-off)
is written for the pair and refuses a manager with another set of pools.

**Page groups** (docs/serving.md "Window and global layers"): a model whose
layers do not all keep the same rows asks for several *groups*, each
``{"name", "layers", "rows", "window"}``: a pool set, a free list, the
refcounts and one table a slot of its own (:class:`_PageGroup`), all under
this one manager. A group with a ``window`` of ``W`` tokens serves layers
that attend the last ``W`` positions only: its table is a **ring** of
``ceil(W / page) + 1`` entries (logical page ``j`` at entry ``j % ring``),
the page that left the window is given back to the group's free list when
the page that takes its entry is mapped (``ensure_capacity`` at a page
boundary, while the rider decodes), and a prompt longer than the window
takes pages for its last ``ring`` logical pages alone. Such a slot never
holds more than ``W + 2`` pages of tokens in that group. A slot's logical
pages are mapped in every group at once; ``can_admit``, ``alloc``,
``free``, ``ensure_capacity``, ``nbytes`` and the page metrics count every
group. The first group is the one the prefix cache and the page-content
I/O see; a manager with more than one refuses both.
"""
from __future__ import annotations

import dataclasses
import hashlib
from collections import OrderedDict
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from . import metrics as smetrics

__all__ = ["PagedKVCache", "PrefixCache", "PagePoolFullError",
           "CacheFullError", "TRANSFER_PAGE_BUCKET"]

# Gather/scatter width bucket for the host transfer path
# (:meth:`PagedKVCache.read_pages` / ``write_pages``). Page groups are
# padded up to a multiple of this with the scratch page so every
# ≤-bucket group reuses ONE compiled gather and ONE compiled scatter —
# without it each distinct group size costs a ~100ms XLA compile the
# first time it appears, which lands squarely on the KV-handoff TTFT
# path. KV handoffs chunk at DEFAULT_CHUNK_PAGES == this width, so the
# steady state is exactly one shape.
TRANSFER_PAGE_BUCKET = 4


# K and V move in ONE device call each way — on CPU the per-op dispatch
# overhead (~1ms) dominates these small transfers, so halving the call
# count roughly halves export/adopt latency on the handoff path.
@jax.jit
def _gather_pages_exec(k, v, idx):
    return k[:, idx], v[:, idx]


@jax.jit
def _scatter_pages_exec(k, v, idx, k_pages, v_pages):
    return k.at[:, idx].set(k_pages), v.at[:, idx].set(v_pages)


def table_width(window: Optional[int], max_seq: int, page_size: int) -> int:
    """Entries of a slot's table row in a group: every logical page of
    ``max_seq``, or the ring of a window group, ``ceil(window / page) + 1``
    (a window of ``W`` positions ending anywhere touches at most that many
    pages). What the engine sizes the feed with, with no cache built."""
    pages = max_seq // page_size
    if window is None:
        return pages
    return min(pages, -(-int(window) // page_size) + 1)


class _PageGroup:
    """One page-backed cache of the manager: the pools of the layers that
    keep the same rows over the same span, with a free list, refcounts and a
    table a slot of its own. ``window`` None: a slot's table names every
    logical page; else the table is a ring (module docstring)."""

    def __init__(self, name: str, layers: int,
                 rows: Sequence[Tuple[int, ...]], window: Optional[int],
                 num_pages: int, max_slots: int, max_seq: int,
                 page_size: int, dtype: Any):
        self.name, self.layers = str(name), int(layers)
        self.window = None if window is None else int(window)
        self.rows = tuple(tuple(int(n) for n in r) for r in rows)
        self.width = table_width(window, max_seq, page_size)
        # default pool = every slot at its fullest (+1 scratch page)
        self.num_pages = int(num_pages) or max_slots * self.width + 1
        if self.num_pages < 2:
            raise ValueError("num_pages must be >= 2 (page 0 is scratch)")
        self.pools = [jnp.zeros((self.layers, self.num_pages, page_size) + r,
                                dtype) for r in self.rows]
        self.tables = np.zeros((max_slots, self.width), np.int32)
        self.ref = np.zeros((self.num_pages,), np.int64)
        self.ref[0] = 1                              # scratch: pinned
        self.free_pages: List[int] = list(range(1, self.num_pages))
        self.released = 0      # pages given back while their slot lived

    def first_held(self, n_pages: int) -> int:
        """The first logical page a slot of ``n_pages`` logical pages
        still holds."""
        return 0 if self.window is None else max(0, n_pages - self.width)

    def entry(self, j: int) -> int:
        return j if self.window is None else j % self.width

    def held_pages(self) -> int:
        return self.num_pages - 1 - len(self.free_pages)


class CacheFullError(RuntimeError):
    """All slots are occupied (the scheduler should queue, not crash)."""


class PagePoolFullError(RuntimeError):
    """No free page available (after prefix-cache reclaim) — the
    scheduler should defer admission or preempt, not crash."""


@dataclasses.dataclass
class _SlotState:
    live: bool = False
    length: int = 0          # valid prefix length (tokens written)
    prefix_len: int = 0      # leading tokens backed by shared pages
    mapped: int = 0          # logical pages currently mapped
    generation: int = 0      # bumped on every alloc — reuse visible to tests


class PagedKVCache:
    """Page-pool allocator + the two pooled cache arrays.

    The device arrays are pure values (``k``/``v`` swapped wholesale per
    call: donated in, fresh handle out); what this class owns is the HOST
    truth the scheduler plans against: which slots are live, how long
    each slot's valid prefix is (``alloc`` / ``free`` / ``length`` /
    ``headroom`` / ``lengths_vector``), the per-slot page tables,
    page-budget queries, and refcounts shared with the prefix cache.
    Slot state never reaches the compiled functions, so join/evict at
    token boundaries is a host-side bookkeeping edit, not a recompile."""

    def __init__(self, num_layers: int, max_slots: int, max_seq: int,
                 num_heads: int = 0, head_dim: int = 0,
                 dtype: Any = jnp.float32,
                 page_size: int = 8, num_pages: int = 0,
                 state: Optional[Dict[str, Any]] = None,
                 rows: Optional[Sequence[Tuple[int, ...]]] = None,
                 groups: Optional[Sequence[Dict[str, Any]]] = None):
        if max_slots < 1 or max_seq < 1:
            raise ValueError("max_slots and max_seq must be >= 1")
        if page_size < 1 or max_seq % page_size:
            raise ValueError(
                f"page_size {page_size} must divide max_seq {max_seq}")
        self.max_slots = int(max_slots)
        self.max_seq = int(max_seq)
        if rows is not None and len(rows[0]) == 2:
            num_heads, head_dim = rows[0]
        self.num_heads = int(num_heads)
        self.head_dim = int(head_dim)
        self.dtype = dtype
        self.page_size = int(page_size)
        self.max_pages_per_slot = self.max_seq // self.page_size
        # what a token's row holds in each pool: keys and values of every
        # head unless the model says otherwise (``rows``); one group over
        # all the layers unless it names several (``groups``: each
        # {"name", "layers", "rows", "window"[, "num_pages"]}; the
        # manager's ``num_pages`` sizes the first)
        if groups is None:
            groups = [{"name": "full", "layers": num_layers, "window": None,
                       "rows": rows or ((self.num_heads, self.head_dim),) * 2}]
        self.groups = [
            _PageGroup(g["name"], g["layers"], g["rows"], g.get("window"),
                       g.get("num_pages", 0) or (0 if i else num_pages),
                       self.max_slots, self.max_seq, self.page_size, dtype)
            for i, g in enumerate(groups)]
        # per-slot recurrent state: {"layers", "conv": shape, "ssm":
        # shape} from the model (a slot's two state rows as the model
        # states them), None for an attention-only model
        self.conv = self.ssm = None
        self.state_bytes_per_slot = 0
        self.state_resets = 0
        if state is not None:
            lead = (int(state["layers"]), self.max_slots)
            self.conv = jnp.zeros(
                lead + tuple(int(n) for n in state["conv"]), dtype)
            self.ssm = jnp.zeros(
                lead + tuple(int(n) for n in state["ssm"]), jnp.float32)
            self.state_bytes_per_slot = (
                self.conv.nbytes + self.ssm.nbytes) // self.max_slots
        self._slots = [_SlotState() for _ in range(self.max_slots)]
        self._free_slots: List[int] = list(range(self.max_slots))
        # the PrefixCache over this pool, set by the engine: pool
        # pressure reclaims the pages only it still holds
        self.prefix_cache: Optional["PrefixCache"] = None

    # -- geometry ----------------------------------------------------------
    # The first group is the one every mechanism built before the groups
    # sees (prefix cache, page-content I/O, the verify program): its
    # pools, table, refcounts and free list under the names they had.
    @property
    def pools(self):
        return self.groups[0].pools

    @pools.setter
    def pools(self, value):
        self.groups[0].pools = value

    num_layers = property(lambda self: self.groups[0].layers)
    num_pages = property(lambda self: self.groups[0].num_pages)
    rows = property(lambda self: self.groups[0].rows)
    _tables = property(lambda self: self.groups[0].tables)
    _ref = property(lambda self: self.groups[0].ref)
    _free_pages = property(lambda self: self.groups[0].free_pages)

    @property
    def keys_and_values(self) -> bool:
        """Whether the pools are the key and value pair the page-content
        I/O (and the engine's verify program) is written for."""
        return len(self.groups) == 1 and len(self.pools) == 2

    def group(self, name: str) -> Optional[_PageGroup]:
        return next((g for g in self.groups if g.name == name), None)

    @property
    def k(self):
        return self.pools[0]

    @k.setter
    def k(self, value):
        self.pools[0] = value

    @property
    def v(self):
        return self.pools[1]

    @v.setter
    def v(self, value):
        self.pools[1] = value

    @property
    def nbytes(self) -> int:
        """Every group's pools and the recurrent state together."""
        return (sum(int(p.size) for g in self.groups for p in g.pools)
                * jnp.dtype(self.dtype).itemsize
                + self.state_bytes_per_slot * self.max_slots)

    @property
    def recurrent(self) -> bool:
        return self.state_bytes_per_slot > 0

    def arrays(self) -> tuple:
        """What the compiled programs carry: the pools (``(k, v)``, or a
        latent model's one), group after group, and the two state arrays
        behind them where there are any."""
        pools = tuple(p for g in self.groups for p in g.pools)
        if self.recurrent:
            return (*pools, self.conv, self.ssm)
        return pools

    def set_arrays(self, arrays) -> None:
        n = 0
        for g in self.groups:
            g.pools = list(arrays[n:n + len(g.pools)])
            n += len(g.pools)
        if self.recurrent:
            self.conv, self.ssm = arrays[n], arrays[n + 1]

    def live_state_bytes(self) -> int:
        return self.state_bytes_per_slot * (
            self.max_slots - len(self._free_slots))

    def _note_state(self, born: bool = False) -> None:
        if not self.recurrent:
            return
        if born:
            self.state_resets += 1
            smetrics.m_state_resets.inc()
        smetrics.m_state_bytes.set(self.live_state_bytes())

    def pages_for(self, n_tokens: int) -> int:
        """Pages needed to back ``n_tokens`` cache rows."""
        return -(-int(n_tokens) // self.page_size)

    # -- page plumbing -----------------------------------------------------
    def free_page_count(self) -> int:
        return len(self._free_pages)

    def _available(self, g: _PageGroup) -> int:
        """Pages ``_take_pages`` could hand out of ``g`` right now: its
        free list, and for the first group what only the prefix cache
        still holds (``_take_pages`` reclaims it)."""
        avail = len(g.free_pages)
        if g is self.groups[0] and self.prefix_cache is not None:
            avail += self.prefix_cache.reclaimable()
        return avail

    def _take_pages(self, n: int, g: Optional[_PageGroup] = None
                    ) -> List[int]:
        g = g or self.groups[0]
        free = g.free_pages
        if (n > len(free) and g is self.groups[0]
                and self.prefix_cache is not None):
            self.prefix_cache.reclaim(n - len(free))
        if n > len(free):
            raise PagePoolFullError(
                f"need {n} free page(s), have {len(free)} "
                f"of {g.num_pages}"
                + (f" in group {g.name!r}" if len(self.groups) > 1 else ""))
        out = [free.pop(0) for _ in range(n)]
        for p in out:
            assert g.ref[p] == 0, f"free page {p} had refs"
            g.ref[p] = 1
        return out

    def ref_pages(self, pages: Sequence[int]) -> None:
        for p in pages:
            assert p != 0 and self._ref[p] > 0, f"ref on dead page {p}"
            self._ref[p] += 1

    def deref_pages(self, pages: Sequence[int],
                    g: Optional[_PageGroup] = None) -> None:
        g = g or self.groups[0]
        for p in pages:
            if p == 0:
                continue
            assert g.ref[p] > 0, f"double free of page {p}"
            g.ref[p] -= 1
            if g.ref[p] == 0:
                g.free_pages.append(p)
        g.free_pages.sort()
        self._note_pool_metrics()

    def _map_pages(self, slot: int, first: int, upto: int) -> None:
        """Map the logical pages ``[first, upto)`` of ``slot`` in every
        group, all or none (:class:`PagePoolFullError` where a group
        cannot). A window group maps those it will still hold (the last
        ``ring``), each into its ring entry, and gives back the page that
        entry held: it left the window when this one began."""
        plans = []
        for g in self.groups:
            lo = max(first, g.first_held(upto))
            row = g.tables[slot]
            # a ring entry's old page goes back before the new one is
            # taken, so a full group can still turn
            leaving = ([int(row[g.entry(j)]) for j in range(lo, upto)
                        if row[g.entry(j)]] if g.window is not None else [])
            if upto - lo > self._available(g) + len(leaving):
                raise PagePoolFullError(
                    f"need {upto - lo} free page(s), have "
                    f"{len(g.free_pages)} of {g.num_pages}"
                    + (f" in group {g.name!r}"
                       if len(self.groups) > 1 else ""))
            plans.append((g, lo, leaving))
        for g, lo, leaving in plans:
            row = g.tables[slot]
            if leaving:
                self.deref_pages(leaving, g)
                g.released += len(leaving)
                smetrics.m_window_released.inc(len(leaving))
            pages = self._take_pages(upto - lo, g)
            for j, page in zip(range(lo, upto), pages):
                row[g.entry(j)] = page

    # -- slot bookkeeping --------------------------------------------------
    def can_admit(self, prompt_len: int, prefix_len: int = 0) -> bool:
        """Would a prompt of ``prompt_len`` (with ``prefix_len`` tokens
        already cache-backed) fit right now, in every group? Pages that
        only the prefix cache still holds count as free: ``_take_pages``
        reclaims them."""
        if not self._free_slots:
            return False
        n, n_prefix = self.pages_for(prompt_len), prefix_len // self.page_size
        return all(n - max(n_prefix, g.first_held(n)) <= self._available(g)
                   for g in self.groups)

    def alloc(self, length: int = 0,
              prefix_pages: Sequence[int] = ()) -> int:
        """Claim a slot; attach ``prefix_pages`` (shared, refcounted) and
        map fresh pages so every position ``< length`` is backed (in a
        window group: every position the window still reaches).

        Raises :class:`CacheFullError` when no slot is free and
        :class:`PagePoolFullError` when a pool is dry (the slot is NOT
        claimed in that case)."""
        if not self._free_slots:
            raise CacheFullError(
                f"all {self.max_slots} decode slots are live")
        if length > self.max_seq:
            raise ValueError(
                f"sequence length {length} exceeds max_seq {self.max_seq}")
        n_prefix = len(prefix_pages)
        if n_prefix * self.page_size > length:
            raise ValueError("prefix pages cover more than the sequence")
        if n_prefix and len(self.groups) > 1:
            raise ValueError("shared prefix pages belong to one group; "
                             f"this manager has {len(self.groups)}")
        # pin the shared prefix FIRST: _take_pages may trigger the
        # prefix cache's reclaim, which must not be able to free (and
        # recycle) the very pages this slot is about to attach
        self.ref_pages(prefix_pages)
        slot = self._free_slots[0]
        for g in self.groups:
            g.tables[slot][:] = 0
        self._tables[slot][:n_prefix] = prefix_pages
        n = self.pages_for(length)
        try:
            self._map_pages(slot, n_prefix, n)
        except PagePoolFullError:
            # (pages are taken only once every group has enough, so this
            # undoes a reclaim that fell short of what it promised)
            for g in self.groups:
                own = g.tables[slot][n_prefix if g is self.groups[0] else 0:]
                self.deref_pages([int(p) for p in own if p], g)
                g.tables[slot][:] = 0
            self.deref_pages(prefix_pages)
            raise
        self._free_slots.pop(0)
        st = self._slots[slot]
        st.live = True
        st.length = int(length)
        st.prefix_len = n_prefix * self.page_size
        st.mapped = n
        st.generation += 1
        self._note_pool_metrics()
        self._note_state(born=True)
        return slot

    def ensure_capacity(self, slot: int, upto_len: int) -> bool:
        """Map pages so positions ``< upto_len`` are write-backed, in
        every group (a window group gives back the page that left the
        window). Returns False (mapping nothing) when a pool cannot cover
        it — the scheduler's cue to preempt."""
        st = self._slots[slot]
        if not st.live:
            raise ValueError(f"slot {slot} is not live")
        if upto_len > self.max_seq:
            return False
        n = self.pages_for(upto_len)
        if n <= st.mapped:
            return True
        try:
            self._map_pages(slot, st.mapped, n)
        except PagePoolFullError:
            return False
        st.mapped = n
        self._note_pool_metrics()
        return True

    def free(self, slot: int) -> None:
        st = self._slots[slot]
        if not st.live:
            raise ValueError(f"slot {slot} is not live")
        for g in self.groups:
            row = g.tables[slot]
            self.deref_pages([int(p) for p in row if p], g)
            row[:] = 0
        st.live = False
        st.length = 0
        st.prefix_len = 0
        st.mapped = 0
        self._free_slots.append(slot)
        self._free_slots.sort()
        self._note_state()

    def set_length(self, slot: int, length: int) -> None:
        st = self._slots[slot]
        if length > self.max_seq:
            raise ValueError(
                f"slot {slot}: length {length} exceeds max_seq "
                f"{self.max_seq}")
        if self.pages_for(length) > st.mapped:
            raise ValueError(
                f"slot {slot}: length {length} beyond mapped pages "
                f"({st.mapped} x {self.page_size})")
        st.length = int(length)

    def length(self, slot: int) -> int:
        return self._slots[slot].length

    def prefix_len(self, slot: int) -> int:
        return self._slots[slot].prefix_len

    def generation(self, slot: int) -> int:
        return self._slots[slot].generation

    def is_live(self, slot: int) -> bool:
        return self._slots[slot].live

    def live_slots(self) -> List[int]:
        return [i for i, s in enumerate(self._slots) if s.live]

    def free_slot_count(self) -> int:
        return len(self._free_slots)

    @property
    def occupancy(self) -> float:
        return (self.max_slots - len(self._free_slots)) / self.max_slots

    def lengths_vector(self) -> np.ndarray:
        return np.array([s.length if s.live else 0 for s in self._slots],
                        np.int32)

    def headroom(self, slot: int) -> int:
        return self.max_seq - self._slots[slot].length

    # -- page content I/O (serving/prefix_store.py warm restart) -----------
    def claim_pages(self, n: int) -> List[int]:
        """Take ``n`` pages off the free list with ONE reference each —
        the prefix cache's reference when the pages are adopted as a
        restored cache entry. Raises :class:`PagePoolFullError` (after
        the prefix cache's reclaim) when the pool cannot cover it."""
        return self._take_pages(int(n))

    def _wire_row(self) -> Tuple[int, ...]:
        """A token's row of one layer as page contents travel: ``(nh,
        hd)`` where the manager knows the heads, else the pool's own."""
        row = self.rows[0]
        if self.num_heads * self.head_dim == int(np.prod(row)):
            return (self.num_heads, self.head_dim)
        return row

    def _keys_and_values_only(self, what: str) -> None:
        if not self.keys_and_values:
            raise ValueError(
                f"{what}: page contents move as a key and value pair; this "
                f"manager's pools hold rows of {self.rows} (latent rows "
                "have no prefix store and no hand-off)")

    def read_pages(self, pages: Sequence[int]
                   ) -> Tuple[np.ndarray, np.ndarray]:
        """Host copies of the K/V contents of ``pages``:
        ``([L, n, page_size, nh, hd] k, same v)`` — what the prefix
        store persists at publish time."""
        self._keys_and_values_only("read_pages")
        idx = np.asarray(list(pages), np.int32)
        n = idx.size
        pad = -n % TRANSFER_PAGE_BUCKET
        if pad:
            # pad the gather with scratch-page reads so every group in a
            # bucket shares one compiled shape (zero-recompile contract)
            idx = np.concatenate([idx, np.zeros(pad, np.int32)])
        k, v = _gather_pages_exec(self.k, self.v, idx)
        # on the wire a row is (nh, hd) whatever the pool's rows are (flat
        # lanes here, the head axis under a mesh): a reshape on the host
        wire = k.shape[:3] + self._wire_row()
        k = np.asarray(k).reshape(wire)
        v = np.asarray(v).reshape(wire)
        return (k[:, :n], v[:, :n]) if pad else (k, v)

    def write_pages(self, pages: Sequence[int], k_pages: np.ndarray,
                    v_pages: np.ndarray) -> None:
        """Write restored K/V contents into ``pages`` (boot-time only:
        the arrays are replaced wholesale, which is exactly how the
        engine treats them between executable calls)."""
        self._keys_and_values_only("write_pages")
        idx = np.asarray(list(pages), np.int32)
        n = idx.size
        pad = -n % TRANSFER_PAGE_BUCKET
        k_pages = np.asarray(k_pages)
        v_pages = np.asarray(v_pages)
        k_pages, v_pages = (a.reshape(a.shape[:3] + r)
                            for a, r in zip((k_pages, v_pages), self.rows))
        if pad:
            # pad the scatter with writes to the scratch page (whose
            # contents are garbage by contract) so every group in a
            # bucket shares one compiled shape
            idx = np.concatenate([idx, np.zeros(pad, np.int32)])
            zeros = np.zeros(
                k_pages.shape[:1] + (pad,) + k_pages.shape[2:],
                k_pages.dtype)
            k_pages = np.concatenate([k_pages, zeros], axis=1)
            v_pages = np.concatenate([v_pages, zeros], axis=1)
        self.k, self.v = _scatter_pages_exec(
            self.k, self.v, idx,
            jnp.asarray(k_pages, self.dtype),
            jnp.asarray(v_pages, self.dtype))

    def adopt_slot(self, length: int, pages: Sequence[int]) -> int:
        """Bind already-claimed, already-written ``pages`` to a fresh
        slot with ``length`` valid positions — the receiving half of a
        KV handoff (serving/kv_transfer.py). The pages must carry the
        single reference :meth:`claim_pages` gave them; that reference
        becomes the slot's, so :meth:`free` returns them to the pool.
        Raises :class:`CacheFullError` when no slot is free (the caller
        still owns the pages and must deref them)."""
        pages = [int(p) for p in pages]
        self._keys_and_values_only("adopt_slot")
        if self.recurrent:
            raise ValueError(
                "adopt_slot: pages carry keys and values only; a slot's "
                "recurrent state has no hand-off (kv_transfer)")
        if length > self.max_seq:
            raise ValueError(
                f"sequence length {length} exceeds max_seq {self.max_seq}")
        if len(pages) != self.pages_for(length):
            raise ValueError(
                f"adopting {len(pages)} page(s) for length {length}; "
                f"need {self.pages_for(length)}")
        for p in pages:
            if p == 0 or self._ref[p] <= 0:
                raise ValueError(f"adopting unclaimed page {p}")
        if not self._free_slots:
            raise CacheFullError(
                f"all {self.max_slots} decode slots are live")
        slot = self._free_slots.pop(0)
        st = self._slots[slot]
        st.live = True
        st.length = int(length)
        st.prefix_len = 0
        st.mapped = len(pages)
        st.generation += 1
        row = self._tables[slot]
        row[:] = 0
        row[:len(pages)] = pages
        self._note_pool_metrics()
        return slot

    # -- executable feeds --------------------------------------------------
    def table_row(self, slot: int) -> np.ndarray:
        """[max_pages_per_slot] int32 page table for one slot (copy); a
        manager of several groups hands their rows side by side, group
        after group (``table_widths``)."""
        if len(self.groups) == 1:
            return self._tables[slot].copy()
        return np.concatenate([g.tables[slot] for g in self.groups])

    def table_rows(self, slots) -> np.ndarray:
        """[len(slots), max_pages_per_slot] int32 page tables of ``slots``
        (copy): the riders' rows of the decode feed."""
        if len(self.groups) == 1:
            return self._tables[slots]
        return np.concatenate([g.tables[slots] for g in self.groups], axis=1)

    @property
    def table_widths(self) -> Tuple[int, ...]:
        return tuple(g.width for g in self.groups)

    # -- what the groups hold ------------------------------------------------
    def live_rows(self, slots, extra: int = 0) -> Dict[str, int]:
        """{group: cache rows a layer that ``slots`` have live in it}, each
        slot's length plus ``extra`` (the row a tick is about to write), a
        window group's clipped to its window: what a decode tick over those
        riders reads, a layer of the group."""
        out = {}
        for g in self.groups:
            lens = [self._slots[s].length + extra for s in slots]
            out[g.name] = int(sum(
                n if g.window is None else min(n, g.window) for n in lens))
        return out

    def pages_held(self, slot: int) -> Dict[str, int]:
        """{group: pages ``slot`` holds in it}."""
        return {g.name: int(np.count_nonzero(g.tables[slot]))
                for g in self.groups}

    def held_over_one_table(self) -> Optional[float]:
        """Pages x layers the live slots hold over what ONE table for all
        the layers would make them hold (every layer every mapped page);
        None with no slot live. 1.0 for a manager of one group."""
        live = [i for i, s in enumerate(self._slots) if s.live]
        one = sum(self._slots[i].mapped for i in live) * sum(
            g.layers for g in self.groups)
        if not one:
            return None
        return sum(int(np.count_nonzero(g.tables[live])) * g.layers
                   for g in self.groups) / one

    # -- pool metrics ------------------------------------------------------
    def pool_occupancy(self) -> float:
        """Allocated pages / allocatable pages (scratch excluded), every
        group's together."""
        total = sum(g.num_pages - 1 for g in self.groups)
        return sum(g.held_pages() for g in self.groups) / total

    def fragmentation(self) -> float:
        """Internal waste: 1 - used_rows / allocated_rows (0 when every
        allocated page is full of valid tokens; pages are fixed-size so
        there is no external fragmentation)."""
        mapped = sum(s.mapped for s in self._slots if s.live)
        cache_held = int(np.sum(self._ref[1:] > 0)) - sum(
            s.mapped for s in self._slots if s.live)
        # cache-held shared pages are full by construction; count them in
        allocated_rows = (mapped + max(cache_held, 0)) * self.page_size
        used_rows = sum(s.length for s in self._slots if s.live) + \
            max(cache_held, 0) * self.page_size
        # a further group's slots hold their last pages alone: rows from
        # their first held page up to their length, of the pages held
        for g in self.groups[1:]:
            for st in self._slots:
                if st.live:
                    first = g.first_held(st.mapped)
                    allocated_rows += (st.mapped - first) * self.page_size
                    used_rows += st.length - first * self.page_size
        if allocated_rows <= 0:
            return 0.0
        return 1.0 - used_rows / allocated_rows

    def _note_pool_metrics(self) -> None:
        smetrics.m_page_occupancy.set(self.pool_occupancy())
        smetrics.m_page_fragmentation.set(self.fragmentation())
        if len(self.groups) > 1:
            for g in self.groups:
                smetrics.m_kv_pages.labels(g.name).set(g.held_pages())


class _Chain:
    """The pages one publication mapped, shared by the entries it made:
    entry ``j`` maps ``pages[:j]``, so together they hold
    ``pages[:top]``, ``top`` the longest of them still in the cache."""

    __slots__ = ("pages", "live", "top")

    def __init__(self, pages: Tuple[int, ...]):
        self.pages = pages
        self.live: set = set()
        self.top = 0


class PrefixCache:
    """Token-hash keyed, refcounted, LRU prefix cache over a page pool.

    Entries are page-aligned prompt prefixes; the cache holds ONE ref on
    every page of every entry (slots using the pages hold their own).
    ``capacity_pages`` bounds distinct cache-held pages; LRU entries are
    dropped on overflow and under pool pressure (:meth:`reclaim`: the
    engine sets this cache as the pool's ``prefix_cache``).

    **The key** of the prefix that ends with page ``j`` is chained:
    ``key_j = SHA-256(key_{j-1} || page j's tokens as int64)``,
    ``key_0`` empty, so one pass over a prompt (:meth:`page_keys`) names
    every one of its page boundaries. The digest IS the prefix: no entry
    keeps tokens to compare, and a collision of two 256-bit digests is
    not handled (it would hand one prompt another's pages), as in the
    common serving stacks.

    **An entry** is ``(chain, j)``: the :class:`_Chain` of the
    publication that made it, whose one page tuple all its entries
    share, and how many of those pages the entry maps. ``_held`` counts,
    a page, the chains that hold it; the cache's pool reference on a
    page lives while that count does.

    **What a call costs**, in pages ``P`` of the prompt it is given,
    whatever the cache holds: :meth:`page_keys` ``P`` hashes of a page;
    :meth:`lookup`, :meth:`has`, :meth:`insert`, :meth:`adopt_nested`
    the keys (unless handed them) and ``P`` probes; dropping an entry
    O(1) and the pages its chain lets go of, each once a chain;
    :meth:`held_page_count` O(1); :meth:`reclaimable` the held pages
    (at most ``capacity_pages``)."""

    def __init__(self, pool: PagedKVCache, capacity_pages: int = 0):
        self.pool = pool
        self.capacity_pages = int(capacity_pages) or pool.num_pages
        # insertion/use-ordered: key -> (chain, pages of it mapped)
        self._entries: "OrderedDict[bytes, Tuple[_Chain, int]]" = \
            OrderedDict()
        self._held: Dict[int, int] = {}     # page -> chains holding it
        self.hits = 0
        self.misses = 0
        self.evicted = 0                    # entries dropped, ever

    def page_keys(self, tokens: Sequence[int]) -> List[bytes]:
        """The key of every page-boundary prefix of ``tokens``, shortest
        first: ``keys[j - 1]`` names ``tokens[:j * page_size]``. What
        :meth:`lookup` and :meth:`insert` take as ``keys``, so that a
        prefill hashes its prompt once."""
        ps = self.pool.page_size
        full = len(tokens) // ps
        buf = np.asarray(tokens[:full * ps], np.int64).tobytes()
        step = 8 * ps
        keys, key = [], b""
        for i in range(0, len(buf), step):
            key = hashlib.sha256(key + buf[i:i + step]).digest()
            keys.append(key)
        return keys

    def held_page_count(self) -> int:
        return len(self._held)

    def reclaimable(self) -> int:
        """Pages that a full reclaim could hand back to the pool (those
        only the cache still holds)."""
        if not self._held:
            return 0
        return int(np.count_nonzero(self.pool._ref[list(self._held)] == 1))

    def has(self, tokens: Sequence[int]) -> bool:
        """Exact-entry probe WITHOUT metric counts or LRU freshening —
        the disagg prefix-index's "is it already local?" check."""
        if not len(tokens) or len(tokens) % self.pool.page_size:
            return False
        return self.page_keys(tokens)[-1] in self._entries

    def lookup(self, tokens: Sequence[int],
               keys: Optional[List[bytes]] = None
               ) -> Tuple[int, Tuple[int, ...]]:
        """Longest cached page-aligned prefix of ``tokens`` that still
        leaves at least one suffix token to prefill. Returns
        ``(prefix_len, pages)`` — (0, ()) on miss. Counts the
        hit/miss metric and freshens LRU order on hit. Probes from the
        longest boundary down: LRU drops a prompt's short entries before
        its long ones, and a long one is a hit without them."""
        if keys is None:
            keys = self.page_keys(tokens)
        ps = self.pool.page_size
        for j in range((len(tokens) - 1) // ps, 0, -1):
            key = keys[j - 1]
            ent = self._entries.get(key)
            if ent is not None:
                self._entries.move_to_end(key)
                self.hits += 1
                smetrics.m_prefix_cache.labels("hit").inc()
                return j * ps, ent[0].pages[:j]
        self.misses += 1
        smetrics.m_prefix_cache.labels("miss").inc()
        return 0, ()

    def adopt_nested(self, tokens: Sequence[int],
                     pages: Sequence[int]) -> int:
        """Register a RESTORED page-aligned prefix (warm restart,
        serving/prefix_store.py): ``pages`` already hold their single
        cache reference (:meth:`PagedKVCache.claim_pages`) and their
        contents are already written into the pool. Mirrors
        :meth:`insert`'s nested publication — every page-boundary prefix
        of ``tokens`` becomes an entry sharing the same pages. Returns
        how many entries were registered (existing keys are skipped)."""
        ps = self.pool.page_size
        pages = tuple(int(p) for p in pages)
        if len(tokens) < len(pages) * ps:
            raise ValueError("adopted pages cover more than the tokens")
        registered, _fresh = self._publish(
            self.page_keys(tokens[:len(pages) * ps]), pages, touch=False)
        self._evict_over_capacity()
        return registered

    def insert(self, tokens: Sequence[int], table_row: np.ndarray,
               keys: Optional[List[bytes]] = None) -> int:
        """Publish every page-boundary prefix of ``tokens`` whose pages
        are in ``table_row`` (the slot's mapping after prefill). Returns
        how many NEW entries were added. New pages get one cache ref."""
        if keys is None:
            keys = self.page_keys(tokens)
        added, fresh = self._publish(
            keys, tuple(int(p) for p in table_row[:len(keys)]), touch=True)
        if fresh:
            self.pool.ref_pages(fresh)
        self._evict_over_capacity()
        return added

    def _publish(self, keys: List[bytes], pages: Tuple[int, ...],
                 touch: bool) -> Tuple[int, List[int]]:
        """One chain over ``pages`` and an entry of it under every key
        the cache lacks (a key it has is freshened if ``touch``), up to
        the first unmapped page. Returns how many entries were made and
        the pages no chain held before."""
        chain = _Chain(pages)
        mapped = pages.index(0) if 0 in pages else len(pages)
        for j, key in enumerate(keys, 1):
            if key in self._entries:
                if touch:
                    self._entries.move_to_end(key)
                continue
            if j > mapped:
                break                      # unmapped — nothing cacheable
            self._entries[key] = (chain, j)
            chain.live.add(j)
            chain.top = j
        fresh = []
        for p in pages[:chain.top]:
            n = self._held.get(p, 0)
            if not n:
                fresh.append(p)
            self._held[p] = n + 1
        return len(chain.live), fresh

    def _drop_oldest(self) -> None:
        """Drop the least recently used entry; the pages its chain held
        for it alone go back to the pool unless another chain holds
        them."""
        _key, (chain, j) = self._entries.popitem(last=False)
        self.evicted += 1
        chain.live.remove(j)
        if j < chain.top:
            return                         # a longer entry holds them all
        top = j - 1
        while top and top not in chain.live:
            top -= 1
        chain.top = top
        gone = []
        for p in chain.pages[top:j]:
            n = self._held[p] - 1
            if n:
                self._held[p] = n
            else:
                del self._held[p]
                gone.append(p)
        if gone:
            self.pool.deref_pages(gone)

    def _evict_over_capacity(self) -> None:
        while self._entries and len(self._held) > self.capacity_pages:
            self._drop_oldest()

    def reclaim(self, n_pages: int) -> int:
        """Pool-pressure hook: drop LRU entries until ``n_pages`` pages
        returned to the free list (or the cache is empty). Returns pages
        actually freed."""
        freed0 = self.pool.free_page_count()
        while (self._entries
               and self.pool.free_page_count() - freed0 < n_pages):
            self._drop_oldest()
        return self.pool.free_page_count() - freed0

    def clear(self) -> None:
        while self._entries:
            self._drop_oldest()

    def __len__(self) -> int:
        return len(self._entries)
