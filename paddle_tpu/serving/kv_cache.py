"""Preallocated slot-major KV cache for the serving engine.

One slab per projection: ``[num_layers, max_slots, max_seq, nh, hd]``,
allocated ONCE at engine startup and threaded through every prefill/decode
executable with buffer donation — steady-state serving never allocates,
never frees, and never changes a shape (the zero-recompile contract,
docs/serving.md).

The device arrays are pure values (jax); what this class owns is the HOST
truth the scheduler plans against: which slots are live, how long each
slot's valid prefix is, and a per-slot generation counter so tests can
prove a freed slot's storage really is reused. Slot state never reaches
the compiled functions — they see only ``positions``/``lengths`` vectors,
so join/evict at token boundaries is a host-side bookkeeping edit, not a
recompile.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, List, Optional

import numpy as np

import jax
import jax.numpy as jnp

__all__ = ["KVCache", "CacheFullError", "TRANSFER_ROW_BUCKET"]

# Row-window width bucket for the transfer path (read_rows/write_rows).
# Windows are widened to a multiple of this (clamped to max_seq) so a
# KV handoff compiles ONE slice/update shape instead of one per chunk
# remainder; kv_transfer chunks at this same width.
TRANSFER_ROW_BUCKET = 64


# Transfer-path row I/O compiled ONCE per row-window size: slot and
# start are traced scalars, so a KV handoff touching every slot at many
# offsets reuses a single executable instead of compiling a fresh
# gather/scatter for each (slot, start) pair (~100ms apiece). Callers
# guarantee start + n <= max_seq — dynamic_slice would silently clamp
# (and shift) an out-of-range window, so the host wrappers assert it.
@functools.partial(jax.jit, static_argnames=("n",))
def _read_rows_exec(k, v, slot, start, *, n):
    sizes = (k.shape[0], 1, n, k.shape[3], k.shape[4])
    zero = jnp.int32(0)
    starts = (zero, slot, start, zero, zero)
    return (jax.lax.dynamic_slice(k, starts, sizes)[:, 0],
            jax.lax.dynamic_slice(v, starts, sizes)[:, 0])


@jax.jit
def _write_rows_exec(k, v, slot, start, k_rows, v_rows):
    zero = jnp.int32(0)
    starts = (zero, slot, start, zero, zero)
    return (jax.lax.dynamic_update_slice(k, k_rows[:, None], starts),
            jax.lax.dynamic_update_slice(v, v_rows[:, None], starts))


class CacheFullError(RuntimeError):
    """All slots are occupied (the scheduler should queue, not crash)."""


@dataclasses.dataclass
class _SlotState:
    live: bool = False
    length: int = 0          # valid prefix length (tokens written)
    generation: int = 0      # bumped on every alloc — reuse visible to tests


class KVCache:
    """Slot allocator + the two cache slabs.

    ``k``/``v`` are replaced wholesale by the engine after every
    prefill/decode call (donated in, fresh handle out). ``max_seq`` bounds
    prompt+generation per slot; ``max_slots`` is the static decode batch.
    """

    def __init__(self, num_layers: int, max_slots: int, max_seq: int,
                 num_heads: int, head_dim: int, dtype: Any = jnp.float32):
        if max_slots < 1 or max_seq < 1:
            raise ValueError("max_slots and max_seq must be >= 1")
        self.num_layers = int(num_layers)
        self.max_slots = int(max_slots)
        self.max_seq = int(max_seq)
        self.num_heads = int(num_heads)
        self.head_dim = int(head_dim)
        self.dtype = dtype
        shape = (num_layers, max_slots, max_seq, num_heads, head_dim)
        self.k = jnp.zeros(shape, dtype)
        self.v = jnp.zeros(shape, dtype)
        self._slots = [_SlotState() for _ in range(max_slots)]
        self._free: List[int] = list(range(max_slots))

    # -- geometry ----------------------------------------------------------
    @property
    def nbytes(self) -> int:
        return int(self.k.size + self.v.size) * jnp.dtype(self.dtype).itemsize

    state_bytes_per_slot = 0         # a slab holds keys and values only

    def set_arrays(self, arrays) -> None:
        """Commit what a compiled program handed back (the engine's one
        way to swap the cache arrays, as on :class:`PagedKVCache`)."""
        self.k, self.v = arrays

    # -- slot bookkeeping --------------------------------------------------
    def alloc(self, length: int = 0) -> int:
        """Claim a free slot (lowest index first — deterministic tests);
        raises :class:`CacheFullError` when none is free."""
        if not self._free:
            raise CacheFullError(
                f"all {self.max_slots} KV-cache slots are live")
        if length > self.max_seq:
            raise ValueError(
                f"sequence length {length} exceeds max_seq {self.max_seq}")
        slot = self._free.pop(0)
        st = self._slots[slot]
        st.live = True
        st.length = int(length)
        st.generation += 1
        return slot

    def free(self, slot: int) -> None:
        st = self._slots[slot]
        if not st.live:
            raise ValueError(f"slot {slot} is not live")
        st.live = False
        st.length = 0
        self._free.append(slot)
        self._free.sort()

    def set_length(self, slot: int, length: int) -> None:
        if length > self.max_seq:
            raise ValueError(
                f"slot {slot}: length {length} exceeds max_seq "
                f"{self.max_seq}")
        self._slots[slot].length = int(length)

    def length(self, slot: int) -> int:
        return self._slots[slot].length

    def generation(self, slot: int) -> int:
        return self._slots[slot].generation

    def is_live(self, slot: int) -> bool:
        return self._slots[slot].live

    def live_slots(self) -> List[int]:
        return [i for i, s in enumerate(self._slots) if s.live]

    def free_slot_count(self) -> int:
        return len(self._free)

    @property
    def occupancy(self) -> float:
        return (self.max_slots - len(self._free)) / self.max_slots

    def lengths_vector(self) -> np.ndarray:
        """[max_slots] int32 of valid prefix lengths (0 for dead slots) —
        the host-side source of the decode step's positions feed."""
        return np.array([s.length if s.live else 0 for s in self._slots],
                        np.int32)

    # -- row content I/O (serving/kv_transfer.py handoff) ------------------
    def read_rows(self, slot: int, start: int, n: int):
        """Host copies of ``n`` cache rows of ``slot`` beginning at
        position ``start``: ``([L, n, nh, hd] k, same v)`` — the slab
        analogue of :meth:`PagedKVCache.read_pages`, chunk-sized so a KV
        handoff never materializes a whole slot at once."""
        if start + n > self.max_seq:
            raise ValueError(
                f"read_rows window [{start}, {start + n}) exceeds "
                f"max_seq {self.max_seq}")
        s2, bn, off = self._row_window(start, n)
        k, v = _read_rows_exec(self.k, self.v, jnp.int32(slot),
                               jnp.int32(s2), n=bn)
        k = np.asarray(k)
        v = np.asarray(v)
        return k[:, off:off + n], v[:, off:off + n]

    def _row_window(self, start: int, n: int):
        """Widen [start, start+n) to a bucket-multiple window inside
        [0, max_seq): returns (window_start, window_len, offset of the
        requested rows within the window)."""
        bucket = min(TRANSFER_ROW_BUCKET, self.max_seq)
        bn = min(-(-int(n) // bucket) * bucket, self.max_seq)
        s2 = min(int(start), self.max_seq - bn)
        return s2, bn, int(start) - s2

    def write_rows(self, slot: int, start: int, k_rows: np.ndarray,
                   v_rows: np.ndarray) -> None:
        """Write transferred K/V rows into ``slot`` at ``start`` (host
        path between executable calls — the arrays are replaced
        wholesale, same as the engine does after every step)."""
        k_rows = np.asarray(k_rows)
        v_rows = np.asarray(v_rows)
        n = int(k_rows.shape[1])
        if start + n > self.max_seq:
            raise ValueError(
                f"write_rows window [{start}, {start + n}) exceeds "
                f"max_seq {self.max_seq}")
        s2, bn, off = self._row_window(start, n)
        if bn != n or off:
            # read-modify-write the widened window so the update keeps
            # one compiled shape without clobbering neighbor rows
            cur_k, cur_v = _read_rows_exec(
                self.k, self.v, jnp.int32(slot), jnp.int32(s2), n=bn)
            cur_k = np.array(cur_k)
            cur_v = np.array(cur_v)
            cur_k[:, off:off + n] = k_rows
            cur_v[:, off:off + n] = v_rows
            k_rows, v_rows = cur_k, cur_v
        self.k, self.v = _write_rows_exec(
            self.k, self.v, jnp.int32(slot), jnp.int32(s2),
            jnp.asarray(k_rows, self.dtype),
            jnp.asarray(v_rows, self.dtype))

    def headroom(self, slot: int) -> int:
        """Tokens this slot can still grow by before hitting max_seq."""
        return self.max_seq - self._slots[slot].length
