"""Decode-path attention over a preallocated KV cache (serving engine).

Training attention (models/gpt.py ``_causal_attention`` / the Pallas flash
kernel) scores a whole ``[B, T]`` block against itself. Serving decode is a
different shape class: ONE new token per sequence attends over everything
the cache already holds, so the kernel is a ``[B, nh, hd] x [B, S, nh, hd]``
row-score + masked online softmax — O(S) memory, no ``[T, T]`` square, and
every shape static so the decode executable compiles exactly once
(docs/serving.md).

The helpers here are pure jnp on purpose: the shapes are MXU-trivial
(one q row per head), so XLA's fusion is already near roofline on TPU and
the same code path is CPU-testable. A Pallas variant only pays once decode
batches are large enough for the HBM round-trip between the score and the
weighted sum to show up in the step attribution — the KERNEL_NOTES
decision-table bar every kernel in this repo has to clear first.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

__all__ = ["decode_attention", "prefill_attention", "paged_gather",
           "paged_cache_update", "paged_page_write",
           "paged_prefill_attention", "window_attention",
           "latent_decode_attention", "band_prefill_attention",
           "sliding_decode_attention"]


def decode_attention(q, k_cache, v_cache, lengths,
                     sm_scale: Optional[float] = None):
    """One-token attention over the cache.

    q:        [B, nh, hd]     — the current token's query
    k_cache:  [B, S, nh, hd]  — cached keys (only [:lengths[b]] valid)
    v_cache:  [B, S, nh, hd]
    lengths:  [B] int32       — valid prefix length per slot, INCLUDING the
                                current token (callers run
                                :func:`paged_cache_update` first and
                                hand in the :func:`paged_gather` view)

    Returns [B, nh, hd]. Scores are computed in f32 regardless of the
    cache dtype (softmax stability at bf16 caches), positions >= length are
    masked to -inf, and empty slots (length 0 — inactive batch lanes in the
    continuous-batching decode step) produce zeros instead of NaNs.

    Grouped-query heads: a cache of ``kvh < nh`` heads (``kvh`` divides
    ``nh``) is read once and shared by each group of ``nh / kvh`` queries.
    """
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    if k_cache.shape[2] != q.shape[1]:
        B, nh, hd = q.shape
        kvh = k_cache.shape[2]
        out = _grouped_attention(
            q.reshape(B, 1, kvh, nh // kvh, hd), k_cache, v_cache,
            (jnp.arange(k_cache.shape[1])[None, None, :]
             < lengths[:, None, None])[:, None, None], sm_scale)
        return out.reshape(B, nh, hd)
    S = k_cache.shape[1]
    scores = jnp.einsum("bnh,bsnh->bns", q.astype(jnp.float32),
                        k_cache.astype(jnp.float32)) * sm_scale
    valid = jnp.arange(S)[None, None, :] < lengths[:, None, None]
    scores = jnp.where(valid, scores, -jnp.inf)
    # max over an all-masked row is -inf; pin it to 0 so exp() is finite
    m = jnp.max(scores, axis=-1, keepdims=True)
    m = jnp.where(jnp.isfinite(m), m, 0.0)
    e = jnp.where(valid, jnp.exp(scores - m), 0.0)
    denom = jnp.sum(e, axis=-1, keepdims=True)
    probs = e / jnp.maximum(denom, 1e-30)
    out = jnp.einsum("bns,bsnh->bnh", probs,
                     v_cache.astype(jnp.float32))
    return out.astype(q.dtype)


def latent_decode_attention(q_lat, rows, lengths, rank: int,
                            sm_scale: float):
    """One-token absorbed latent attention over a gathered view: the
    lowering of ``pallas_kernels.mla_paged_decode_attention`` off the TPU,
    and its parity reference.

    q_lat ``[B, H, rank + rope]``; rows ``[B, S, rank + rope]`` (one shared
    row a token: ``[c_kv | k_rope]``, only ``[:lengths[b]]`` valid, the
    current token included); returns ``[B, H, rank]``: the probabilities
    times the rows' first ``rank`` values. Float32 inside; an empty slot
    gives zeros. In the terms of :func:`_grouped_attention`: one key/value
    head serving a group of ``H`` queries, its value a slice of its key."""
    valid = jnp.arange(rows.shape[1])[None, :] < lengths[:, None]
    shared = rows[:, :, None]                            # [B, S, 1, W]
    out = _grouped_attention(q_lat[:, None, None], shared,
                             shared[..., :rank],
                             valid[:, None, None, None], sm_scale)
    return out[:, 0, 0]


def _grouped_attention(q, k, v, mask, sm_scale):
    """q [B, T, kvh, g, hd] against k, v [B, S, kvh, hd] under ``mask``
    (broadcastable to [B, kvh, g, T, S]) -> [B, T, kvh, g, hd]: each
    key/value head serves its group of ``g`` query heads, float32
    inside, all-masked rows give zeros."""
    scores = jnp.einsum("btkgh,bskh->bkgts", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * sm_scale
    scores = jnp.where(mask, scores, -jnp.inf)
    m = jnp.max(scores, axis=-1, keepdims=True)
    m = jnp.where(jnp.isfinite(m), m, 0.0)
    e = jnp.where(mask, jnp.exp(scores - m), 0.0)
    probs = e / jnp.maximum(jnp.sum(e, axis=-1, keepdims=True), 1e-30)
    out = jnp.einsum("bkgts,bskh->btkgh", probs, v.astype(jnp.float32))
    return out.astype(q.dtype)


_BAND_QUERY_BLOCK = 256


def band_prefill_attention(q, k, v, window: Optional[int] = None,
                           sm_scale: Optional[float] = None):
    """Causal self-attention of a rung inside a band, grouped heads: q
    ``[T, nh, hd]``, k, v ``[T, kvh, hd]`` -> ``[T, nh, hd]``; query ``i``
    sees keys ``i - window < j <= i`` (``window`` None: every ``j <= i``).
    A block of queries at a time (``[kvh, g, block, T]`` float32 scores,
    never ``[nh, T, T]``). The lowering of
    ``pallas_kernels.band_flash_attention`` off the TPU, and its parity
    reference."""
    T, nh, hd = q.shape
    kvh = k.shape[1]
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(hd)
    blk = _BAND_QUERY_BLOCK if T % _BAND_QUERY_BLOCK == 0 else T
    keys = jnp.arange(T)[None, :]

    def one(i):
        rows = i * blk + jnp.arange(blk)[:, None]
        seen = keys <= rows
        if window is not None:
            seen &= rows - keys < window
        qb = jax.lax.dynamic_slice_in_dim(q, i * blk, blk, 0)
        return _grouped_attention(
            qb.reshape(1, blk, kvh, nh // kvh, hd), k[None], v[None],
            seen[None, None, None], sm_scale)[0]

    out = jax.lax.map(one, jnp.arange(T // blk))
    return out.reshape(T, nh, hd)


def sliding_decode_attention(q, k_rows, v_rows, positions, kv_heads: int,
                             page: int, window: Optional[int] = None,
                             ring: bool = False,
                             sm_scale: Optional[float] = None):
    """One-token grouped-query attention over a gathered view of a page
    group, with a lower bound: the lowering of
    ``pallas_kernels.gqa_paged_decode_attention`` off the TPU, and its
    parity reference.

    q ``[B, H, hd]``; k_rows/v_rows ``[B, M * page, kv_heads * hd]``
    (:func:`paged_gather` of the slot's table: every logical page in
    order, or with ``ring`` a ring of ``M`` entries, logical page ``j`` at
    entry ``j % M``), this tick's row already written; positions ``[B]``:
    the row attends ``(position - window, position]`` (``window`` None:
    ``[0, position]``). Returns ``[B, H, hd]``."""
    B, H, hd = q.shape
    S = k_rows.shape[1]
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(hd)
    at = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
    if ring:
        # the row a ring entry holds now: the newest logical page at it
        M = S // page
        cur = (positions // page)[:, None]
        entry = at // page
        at = (cur - (cur - entry) % M) * page + at % page
    seen = (at >= 0) & (at <= positions[:, None])
    if window is not None:
        seen &= at > positions[:, None] - window
    out = _grouped_attention(
        q.reshape(B, 1, kv_heads, H // kv_heads, hd),
        k_rows.reshape(B, S, kv_heads, hd),
        v_rows.reshape(B, S, kv_heads, hd),
        seen[:, None, None, None], sm_scale)
    return out.reshape(B, H, hd)


def paged_gather(pool, tables, layer=None, heads=None):
    """Materialize per-slot contiguous cache views from a paged pool.

    pool:   [P, page, nh, hd]  (one layer's K or V page pool), or with
            ``layer`` the engine's whole [L, P, page, nh, hd] pool: the
            gather then indexes (layer, page) at once and no layer is
            sliced out of the pool first. A pool of flat rows
            ``[.., page, nh * hd]`` (what the page-table kernels read)
            comes back as heads with ``heads=(nh, hd)``: a reshape of the
            gathered temporary, never of the pool
    tables: [B, M] int32       (physical page per logical page per slot;
                                unmapped entries point at the reserved
                                scratch page — positions there are always
                                masked by the caller's lengths)
    layer:  int32 scalar       (traced: the layer loop's variable)

    Returns [B, M*page, nh, hd] — the slot-major layout every attention
    helper here already consumes, so the paged variants are gather +
    the existing masked-softmax kernels (one fused gather under XLA).
    On a TPU the decode tick does not come here:
    ``pallas_kernels.paged_decode_attention`` reads the live pages
    through the table in-kernel (docs/kernels.md); this materializing
    path is the lowering off-TPU and under a mesh, and the parity
    reference."""
    B, M = tables.shape
    g = pool[tables] if layer is None else pool[layer, tables]
    return g.reshape((B, M * g.shape[2])
                     + (g.shape[3:] if heads is None else tuple(heads)))


def _never_one_index(new, *index):
    """XLA rewrites a scatter of ONE index as a dynamic-update-slice and
    then lays the whole operand out to suit the update: on the TPU a
    transposed copy of both carried KV pools every layer (850 ms for a
    16-token prefill at 1.3B; my chip run, PR 27). Writing the one row or
    page twice keeps it a scatter, in place."""
    if new.shape[0] != 1:
        return (new,) + index
    return tuple(jnp.concatenate([a, a]) for a in (new,) + index)


def paged_cache_update(pool, new, phys_pages, rows, layer=None):
    """Write one new row per sequence into the page pool.

    pool:       [P, page, nh, hd], or [L, P, page, nh, hd] with ``layer``
    new:        [B, nh, hd]
    phys_pages: [B] int32   (physical page per slot — scratch for dead lanes)
    rows:       [B] int32   (row within the page)

    Batch scatter with fixed shapes — donation (or a loop's carry) makes
    it an in-place HBM write of B rows. Colliding indices only occur on
    the scratch page, which is never read back."""
    new, phys_pages, rows = _never_one_index(new.astype(pool.dtype),
                                             phys_pages, rows)
    if layer is None:
        return pool.at[phys_pages, rows].set(new)
    return pool.at[layer, phys_pages, rows].set(new)


def paged_page_write(pool, pages_data, phys_pages, layer=None):
    """Write whole pages into the pool (the prefill path).

    pool:       [P, page, nh, hd], or [L, P, page, nh, hd] with ``layer``
    pages_data: [n, page, nh, hd]  (suffix K/V reshaped to page granularity)
    phys_pages: [n] int32
    """
    pages_data, phys_pages = _never_one_index(
        pages_data.astype(pool.dtype), phys_pages)
    if layer is None:
        return pool.at[phys_pages].set(pages_data)
    return pool.at[layer, phys_pages].set(pages_data)


def paged_prefill_attention(q, k_all, v_all, prefix_len,
                            sm_scale: Optional[float] = None):
    """Suffix prefill over a gathered paged view (prefix-cache capable).

    q:          [1, T, nh, hd]  — suffix queries at global positions
                                  ``prefix_len + i``
    k_all/v_all:[1, S, nh, hd]  — the slot's full gathered view (cached
                                  prefix rows + this call's suffix rows
                                  already scattered in), or the view of
                                  a pool of flat rows, [1, S, nh * hd]
    prefix_len: scalar int32    — tokens already cached ahead of the
                                  suffix (page-aligned by the allocator)

    Query i may attend key j iff ``j <= prefix_len + i`` — plain causal
    attention when prefix_len == 0, continuation prefill otherwise. Same
    f32 contraction order as :func:`decode_attention` so a decode replay
    of the same positions agrees to float rounding."""
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    (B, T, nh, hd), S = q.shape, k_all.shape[1]
    flat = k_all.ndim == 3
    if flat:
        # a view of flat rows [B, S, nh * hd]: transposed, its major axis
        # splits into heads for nothing (``[nh * hd, S]`` is ``[nh, hd,
        # S]`` tile for tile), one pass over the view as the head rows'
        # heads-major copy was; ``[S, nh, hd]`` would be re-tiled first and
        # then laid out heads-major, two passes, and the heads' lane slices
        # stacked are 2 x 16 small programs a layer (described compile, PR
        # 42)
        k_all, v_all = (jnp.swapaxes(x, 1, 2).reshape(B, nh, hd, S)
                        for x in (k_all, v_all))
    scores = jnp.einsum("bqnh,bnhk->bnqk" if flat else "bqnh,bknh->bnqk",
                        q.astype(jnp.float32),
                        k_all.astype(jnp.float32)) * sm_scale
    mask = (jnp.arange(S)[None, :]
            <= prefix_len + jnp.arange(T)[:, None])[None, None]
    scores = jnp.where(mask, scores, -jnp.inf)
    m = jnp.max(scores, axis=-1, keepdims=True)
    m = jnp.where(jnp.isfinite(m), m, 0.0)
    e = jnp.where(mask, jnp.exp(scores - m), 0.0)
    probs = e / jnp.maximum(jnp.sum(e, axis=-1, keepdims=True), 1e-30)
    out = jnp.einsum("bnqk,bnhk->bqnh" if flat else "bnqk,bknh->bqnh",
                     probs, v_all.astype(jnp.float32))
    return out.astype(q.dtype)


def window_attention(q, k_cache, v_cache, starts,
                     sm_scale: Optional[float] = None):
    """W-query attention over the cache (speculative-verify window: the
    window of speculative decoding, NOT a sliding window of keys; those
    paths are :func:`band_prefill_attention` and
    :func:`sliding_decode_attention`).

    q:        [B, W, nh, hd]  — window queries; query w sits at global
                               position ``starts[b] + w``
    k_cache:  [B, S, nh, hd]  — cache with the window rows already written
    starts:   [B] int32

    Query w attends keys ``j <= starts + w`` (causal across the window,
    full visibility of the prefix). W=1 is exactly
    :func:`decode_attention` with ``lengths = starts + 1``."""
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    W, S = q.shape[1], k_cache.shape[1]
    scores = jnp.einsum("bwnh,bsnh->bnws", q.astype(jnp.float32),
                        k_cache.astype(jnp.float32)) * sm_scale
    mask = (jnp.arange(S)[None, None, :]
            <= starts[:, None, None] + jnp.arange(W)[None, :, None])
    mask = mask[:, None]                   # [B, 1, W, S]
    scores = jnp.where(mask, scores, -jnp.inf)
    m = jnp.max(scores, axis=-1, keepdims=True)
    m = jnp.where(jnp.isfinite(m), m, 0.0)
    e = jnp.where(mask, jnp.exp(scores - m), 0.0)
    probs = e / jnp.maximum(jnp.sum(e, axis=-1, keepdims=True), 1e-30)
    out = jnp.einsum("bnws,bsnh->bwnh", probs,
                     v_cache.astype(jnp.float32))
    return out.astype(q.dtype)


def prefill_attention(q, k, v, sm_scale: Optional[float] = None):
    """Causal self-attention for the prefill pass: [B, T, nh, hd] all
    around (k and v may hold ``kvh < nh`` heads: grouped-query). Numerically
    the same contraction order as decode_attention so
    prefill logits and a later decode replay of the same positions agree
    to float rounding (the parity bar tests/test_serving_engine.py holds
    the engine to)."""
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    T = q.shape[1]
    if k.shape[2] != q.shape[2]:
        B, _, nh, hd = q.shape
        kvh = k.shape[2]
        out = _grouped_attention(
            q.reshape(B, T, kvh, nh // kvh, hd), k, v,
            jnp.tril(jnp.ones((T, T), jnp.bool_)), sm_scale)
        return out.reshape(B, T, nh, hd)
    scores = jnp.einsum("bqnh,bknh->bnqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * sm_scale
    mask = jnp.tril(jnp.ones((T, T), jnp.bool_))[None, None]
    scores = jnp.where(mask, scores, -jnp.inf)
    m = jnp.max(scores, axis=-1, keepdims=True)
    e = jnp.where(mask, jnp.exp(scores - m), 0.0)
    probs = e / jnp.maximum(jnp.sum(e, axis=-1, keepdims=True), 1e-30)
    out = jnp.einsum("bnqk,bknh->bqnh", probs, v.astype(jnp.float32))
    return out.astype(q.dtype)
