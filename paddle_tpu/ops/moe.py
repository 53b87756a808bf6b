"""Sparse experts for one chip of an expert-parallel group.

A chip that holds ``G`` consecutive experts of a layer's ``E`` routes every
token over all ``E`` (the router is whole on every chip) and computes the
part of ``sum_k w_k E_k(x)`` that falls on the experts it holds; the other
chips' parts arrive by an exchange this module does not contain.

* :func:`route`: sigmoid scores in float32, the ``top_k`` largest of
  ``score + bias`` chosen, weighted by the scores *without* the bias,
  normalised to sum to one and scaled (``noaux_tc`` with one group).
* :func:`expert_share`: the (token, choice) pairs that fall on held experts
  are sorted by expert, the rows gathered, one grouped product a projection
  over the ragged groups, and the weighted rows summed back per token. **No
  capacity, no dropped token**: the row buffer is sized for the worst case.
  Because that worst case (every choice of every token on this chip) is
  thirty times the expected load, the gather and the combine run on a
  buffer of ``T`` rows when the held pairs fit it and on the full ``k T``
  otherwise (a ``lax.cond``, both shapes static).
* :func:`grouped_matmul`: ``lhs[rows of group g] @ rhs[g]``. On the TPU a
  Pallas kernel named ``moe_grouped_matmul`` that visits only the row
  tiles holding rows of a held expert, reading each visited expert's
  weight tile once a visit; elsewhere ``jax.lax.ragged_dot``. The kernel
  follows ``jax.experimental.pallas.ops.tpu.megablox.gmm`` (The JAX
  Authors, Apache-2.0): its group metadata is used as shipped, and its
  kernel body is adapted to take the stacked weights of all layers with
  the layer index as a prefetched scalar (so no layer's experts are ever
  sliced out into a copy) and to leave rows of no held group unvisited.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import pallas_kernels as _pk

__all__ = ["route", "expert_share", "grouped_matmul", "ROW_TILE"]

ROW_TILE = 128                   # rows of the sorted buffer a visit covers
_TILE_BYTES = 4 << 20            # a weight tile in VMEM (two are in flight)


def route(x, w_g, bias, top_k: int, scale: float):
    """x ``[T, D]`` -> ``(experts [T, top_k] int32, weights [T, top_k]
    float32)`` over all ``E = w_g.shape[1]`` experts. The product, the
    sigmoid and the choice are float32, as the source computes them."""
    s = jax.nn.sigmoid(jnp.dot(
        x.astype(jnp.float32), w_g.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))
    _, experts = jax.lax.top_k(s + bias.astype(jnp.float32), top_k)
    w = jnp.take_along_axis(s, experts, axis=1)
    w = w / jnp.sum(w, axis=1, keepdims=True) * scale
    return experts.astype(jnp.int32), w


# ---------------------------------------------------------------------------
# the grouped product
# ---------------------------------------------------------------------------

def _tile(dim: int, cap: int) -> int:
    """The largest multiple of 128 that divides ``dim`` and is at most
    ``cap``; ``dim`` itself where it is no multiple of 128 (a block equal
    to the array's own dimension is always taken)."""
    if dim % 128:
        return dim
    best = 128
    for t in range(128, min(dim, cap) + 1, 128):
        if dim % t == 0:
            best = t
    return best


def _gmm_kernel(offsets_ref, gids_ref, mids_ref, layer_ref, lhs_ref,
                rhs_ref, out_ref, acc_ref, *, tm, tiles_k):
    del layer_ref                        # used by the index maps alone
    visit, k_i = pl.program_id(1), pl.program_id(2)

    @pl.when(k_i == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(lhs_ref[...], rhs_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(k_i == tiles_k - 1)
    def _store():
        # rows of this tile that belong to the visit's group; the others
        # keep what an earlier visit of the same tile stored
        g = gids_ref[visit]
        row = mids_ref[visit] * tm + jax.lax.broadcasted_iota(
            jnp.int32, acc_ref.shape, 0)
        mine = (row >= offsets_ref[g]) & (row < offsets_ref[g + 1])
        out_ref[...] = jnp.where(
            mine, acc_ref[...], out_ref[...].astype(jnp.float32)
        ).astype(out_ref.dtype)


def _gmm_pallas(lhs, rhs, group_sizes, layer):
    from jax.experimental.pallas.ops.tpu.megablox.gmm import \
        make_group_metadata

    m, k = lhs.shape
    groups, n = rhs.shape[1], rhs.shape[3]
    tm = ROW_TILE if m % ROW_TILE == 0 else m
    tn = _tile(n, 1024)
    tk = _tile(k, max(128, _TILE_BYTES // (tn * rhs.dtype.itemsize)))
    tiles_k, tiles_n = k // tk, n // tn
    # one more group takes the rows of no held expert: it lies outside
    # the groups asked for, so none of its tiles is visited
    rest = m - jnp.sum(group_sizes)
    (offsets, gids, mids), visits = make_group_metadata(
        group_sizes=jnp.concatenate(
            [group_sizes.astype(jnp.int32), rest[None].astype(jnp.int32)]),
        m=m, tm=tm, start_group=jnp.int32(0), num_nonzero_groups=groups,
        visit_empty_groups=False)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(tiles_n, visits, tiles_k),
        in_specs=[
            pl.BlockSpec((tm, tk), lambda n_i, v, k_i, off, gid, mid, lay:
                         (mid[v], k_i)),
            pl.BlockSpec((None, None, tk, tn),
                         lambda n_i, v, k_i, off, gid, mid, lay:
                         (lay[0], gid[v], k_i, n_i))],
        out_specs=pl.BlockSpec(
            (tm, tn), lambda n_i, v, k_i, off, gid, mid, lay:
            (mid[v], n_i)),
        scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)])
    _pk._count_launch("moe_grouped_matmul")
    return pl.pallas_call(
        functools.partial(_gmm_kernel, tm=tm, tiles_k=tiles_k),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), lhs.dtype),
        compiler_params=_pk._CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=48 << 20),
        interpret=_pk._interpret(),
        name="moe_grouped_matmul",
    )(offsets, gids, mids, jnp.reshape(layer, (1,)).astype(jnp.int32),
      lhs, rhs)


def grouped_matmul(lhs, rhs, group_sizes, layer=None,
                   use_pallas: Optional[bool] = None):
    """``out[rows of group g] = lhs[rows of group g] @ rhs[g]``.

    lhs ``[M, K]``, its rows sorted by group: group 0's first, then group
    1's, ...; rhs ``[G, K, N]``, or ``[L, G, K, N]`` with ``layer`` (an
    int32 scalar, traced: the layer loop's variable) so that the kernel
    indexes the stacked weights where they lie; group_sizes ``[G]`` int32
    with a sum of at most ``M``. Rows past the sum belong to no group: the
    kernel does not visit their tiles and what ``out`` holds there is
    undefined (the XLA lowering leaves zeros). Returns ``[M, N]`` in
    ``lhs``'s dtype, float32 sums."""
    if use_pallas is None:
        use_pallas = _pk._on_tpu()
    if rhs.ndim == 3:
        rhs, layer = rhs[None], 0
    if use_pallas:
        return _gmm_pallas(lhs, rhs, group_sizes, jnp.asarray(layer))
    w = jax.lax.dynamic_index_in_dim(rhs, layer, 0, keepdims=False)
    return jax.lax.ragged_dot(
        lhs, w.astype(lhs.dtype), group_sizes.astype(jnp.int32),
        preferred_element_type=jnp.float32).astype(lhs.dtype)


# ---------------------------------------------------------------------------
# the chip's share of an expert layer
# ---------------------------------------------------------------------------

def _round_up(n: int, to: int) -> int:
    return -(-n // to) * to


def expert_share(x, valid, experts, weights, w_gate_up, w_down, *,
                 first_expert: int, layer=None,
                 use_pallas: Optional[bool] = None,
                 small_rows: Optional[int] = None
                 ) -> Tuple[jax.Array, jax.Array]:
    """The held experts' part of ``sum_k w_k E_k(x)``.

    x ``[T, D]``; valid ``[T]`` bool (a rung's padding and a tick's idle
    lanes take no part and are not counted); experts/weights ``[T, k]``
    from :func:`route`; w_gate_up ``[G, D, 2F]`` (gate then up) and w_down
    ``[G, F, D]`` of the held experts ``[first_expert, first_expert + G)``,
    or both with a leading layer axis and ``layer``; ``small_rows``: the
    rows of the small buffer, ``T`` unless the caller expects more held
    pairs than tokens (a chip that holds an eighth of the experts at eight
    a token expects ``T``). Returns ``(y [T, D] in
    x's dtype, report [G + 1] int32)``: the tokens on each held expert, and
    last the held pairs that reached no expert (always 0)."""
    T, k = experts.shape
    G, F = w_down.shape[-3], w_down.shape[-2]
    local = experts - first_expert
    held = (local >= 0) & (local < G) & valid[:, None]
    key = jnp.where(held, local, G).reshape(-1)              # [T k]
    counts = jnp.bincount(key, length=G + 1)[:G].astype(jnp.int32)
    order = jnp.argsort(key, stable=True)
    flat_w = weights.reshape(-1)

    def run(rows):
        """The share through a sorted buffer of ``rows`` rows."""
        pair = order[:rows]
        token = pair // k
        mine = key[pair] < G
        w = jnp.where(mine, flat_w[pair], 0.0)
        h = grouped_matmul(x[token], w_gate_up, counts, layer, use_pallas)
        a = (jax.nn.silu(h[:, :F].astype(jnp.float32))
             * h[:, F:].astype(jnp.float32)).astype(x.dtype)
        o = grouped_matmul(a, w_down, counts, layer, use_pallas)
        # rows of no held pair hold nothing defined: a select, not a product
        o = jnp.where(mine[:, None], o.astype(jnp.float32) * w[:, None], 0.0)
        # summed back per token on the MXU: a scatter-add of rows is
        # serial on the TPU, a 0/1 matrix is not
        back = (token[None, :] == jnp.arange(T)[:, None]) & mine[None, :]
        y = jnp.dot(back.astype(x.dtype), o.astype(x.dtype),
                    preferred_element_type=jnp.float32).astype(x.dtype)
        # held pairs the buffer did not take: none, by the choice of
        # ``rows`` below; counted so that it is seen and not assumed
        dropped = jnp.sum(counts) - jnp.sum(mine.astype(jnp.int32))
        return y, jnp.concatenate([counts, dropped[None]])

    # the buffer is whole row tiles, or one tile of everything
    full = T * k
    tile = ROW_TILE if full % ROW_TILE == 0 else full
    small = min(full, _round_up(max(small_rows or T, tile), tile))
    if small == full:
        return run(full)
    return jax.lax.cond(jnp.sum(counts) <= small,
                        lambda: run(small), lambda: run(full))
