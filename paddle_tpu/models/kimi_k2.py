"""Kimi-K2 text decoder (``model_type: kimi_k2``, the DeepSeek-V3 block):
latent attention (MLA) over a latent paged cache, sigmoid-routed experts
with a shared expert, yarn rotary positions. Serving path.

Pre-norm residual block, every norm an RMSNorm with a gain, no bias::

    h += Attn(norm_in(h));  h += FFN(norm_ff(h))

The first ``first_k_dense_replace`` layers' FFN is a gated MLP of width
``intermediate_size``; every later layer is an expert layer: ``y = sum_k w_k
E_k(u) + Shared(u)`` with the router of ``ops/moe.py`` over all
``n_routed_experts_published`` experts and ``num_experts_per_tok`` a token.

**Latent attention.** ``c_q = norm(u W_qa)``, ``q = c_q W_qb`` -> heads x
(``q_nope``, ``q_rope``); ``[c_kv | k_rope] = u W_kva``, ``c_kv =
norm(c_kv)``; ``k_rope`` is one row shared by all heads; rotary on ``q_rope``
and ``k_rope`` alone. What a token leaves in the cache is ``[c_kv | k_rope]``
(``kv_lora_rank + qk_rope_head_dim`` values a layer) and nothing else. A
rung (prefill, and :func:`forward`) expands ``[k_nope | v] = c_kv W_kvb``
and attends causally; a tick attends *absorbed*: ``q_nope`` is taken through
``W_kvb``'s key half once (``W_uk``), scored against the cached rows as they
lie, and the weighted sum of ``c_kv`` goes through the value half
(``W_uv``).

**One chip's share.** ``experts_held`` experts from ``first_expert`` on are
this chip's; it routes over all published experts and adds up its own
experts' part and the shared expert (``ops/moe.py:expert_share``). With
``experts_held == n_routed_experts_published`` that is the whole layer.
``vocab_size`` rows of embedding and head are held (a slice of the
published table from row 0).

The stored tree keeps the published orientation (matrices ``[in, out]``,
rotary pairs interleaved); :meth:`KimiK2Serving.hold` re-lays what the
programs contract: the rope columns of ``W_qb``/``W_kva`` permuted to halves
(``ops/rope.py``), ``W_kvb`` split into ``W_uk [H, rank, nope]`` and ``W_uv
[H, rank, v]``. Training is not built.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional

import numpy as np

import jax
import jax.numpy as jnp

from ..ops import moe as _moe
from ..ops import pallas_kernels as _pk
from ..ops import rope as _rope
from ..ops.decode_attention import (latent_decode_attention,
                                    paged_cache_update, paged_gather,
                                    paged_page_write)
from .blocks import (gated_mlp, hold_leaves, layer_at, rms_logits,
                     rms_norm)

__all__ = ["KimiK2Config", "KIMI_K2_TINY", "leaf_shapes", "init_params",
           "forward", "KimiK2Serving"]


@dataclasses.dataclass(frozen=True)
class KimiK2Config:
    """The keys of the published ``config.json`` that shape the program,
    and what one chip of an expert-parallel group holds of it."""
    vocab_size: int = 163840             # rows held, from row 0
    hidden_size: int = 7168
    intermediate_size: int = 18432
    moe_intermediate_size: int = 2048
    num_hidden_layers: int = 61
    first_k_dense_replace: int = 1
    num_attention_heads: int = 64
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    n_routed_experts_published: int = 384    # the router's width
    experts_held: int = 384
    first_expert: int = 0
    num_experts_per_tok: int = 8
    n_shared_experts: int = 1
    routed_scaling_factor: float = 2.827
    rms_norm_eps: float = 1e-5
    rope_theta: float = 50000.0
    rope_scaling: Optional[Any] = None   # the published yarn group (a dict)
    dtype: Any = jnp.bfloat16            # compute dtype

    @property
    def num_expert_layers(self) -> int:
        return self.num_hidden_layers - self.first_k_dense_replace

    @property
    def latent_width(self) -> int:
        """Values a token leaves in the cache, a layer."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def cache_width(self) -> int:
        """Width of a cache row as it is stored: ``latent_width`` rounded
        up to whole lanes (576 -> 640). The TPU's tiled layout stores a
        576-wide row in 640 anyway (Mosaic sees the ``[.., 576]`` bfloat16
        pool as ``[.., 640]``) and refuses a page copy that is not whole
        tiles; said here, the spare lanes are the program's: they hold
        zeros, a query's spare lanes hold zeros, and the score is one
        aligned product over the whole row."""
        return -(-self.latent_width // 128) * 128

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def shared_width(self) -> int:
        return self.moe_intermediate_size * self.n_shared_experts

    @property
    def softmax_scale(self) -> float:
        return _rope.yarn_softmax_scale(self.qk_head_dim, self.rope_scaling)

    def inv_freq(self) -> np.ndarray:
        return _rope.yarn_inv_freq(self.qk_rope_head_dim, self.rope_theta,
                                   self.rope_scaling)

    def scaled(self, **kw) -> "KimiK2Config":
        return dataclasses.replace(self, **kw)

    def serving_description(self) -> "KimiK2Serving":
        """What ``DecodeEngine`` builds its programs from
        (``serving/model.py``)."""
        return KimiK2Serving(self)


_TINY_YARN = {"type": "yarn", "factor": 4.0, "beta_fast": 32.0,
              "beta_slow": 1.0, "mscale": 1.0, "mscale_all_dim": 1.0,
              "original_max_position_embeddings": 16}
KIMI_K2_TINY = KimiK2Config(
    vocab_size=256, hidden_size=64, intermediate_size=128,
    moe_intermediate_size=32, num_hidden_layers=3, first_k_dense_replace=1,
    num_attention_heads=4, q_lora_rank=24, kv_lora_rank=16,
    qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=8,
    n_routed_experts_published=16, experts_held=16, first_expert=0,
    num_experts_per_tok=4, routed_scaling_factor=2.5, rope_theta=10000.0,
    rope_scaling=_TINY_YARN, dtype=jnp.float32)

# leaves held in float32 whatever the weights' type: gains, and the
# router, whose product, sigmoid and choice the source computes in float32
F32_LEAVES = ("norm_in", "norm_ff", "q_norm", "kv_norm", "final_norm",
              "router", "router_bias")


def leaf_shapes(cfg: KimiK2Config) -> Dict[str, Any]:
    """The stored parameter tree as shapes (published orientation; an
    expert's gate and up projections side by side on the output axis, the
    layout the grouped product contracts: 5 GB that are never re-laid)."""
    D, H = cfg.hidden_size, cfg.num_attention_heads
    L, Ld, Le = (cfg.num_hidden_layers, cfg.first_k_dense_replace,
                 cfg.num_expert_layers)
    Rq, Rkv = cfg.q_lora_rank, cfg.kv_lora_rank
    F, Fs, Fd = (cfg.moe_intermediate_size, cfg.shared_width,
                 cfg.intermediate_size)
    E, G = cfg.n_routed_experts_published, cfg.experts_held
    return {
        "embed": (cfg.vocab_size, D), "final_norm": (D,),
        "head": (D, cfg.vocab_size),
        "attn": {"norm_in": (L, D), "w_qa": (L, D, Rq), "q_norm": (L, Rq),
                 "w_qb": (L, Rq, H * cfg.qk_head_dim),
                 "w_kva": (L, D, cfg.latent_width), "kv_norm": (L, Rkv),
                 "w_kvb": (L, Rkv,
                           H * (cfg.qk_nope_head_dim + cfg.v_head_dim)),
                 "w_o": (L, H * cfg.v_head_dim, D), "norm_ff": (L, D)},
        "dense": {"gate": (Ld, D, Fd), "up": (Ld, D, Fd),
                  "down": (Ld, Fd, D)},
        "moe": {"router": (Le, D, E), "router_bias": (Le, E),
                "shared_gate": (Le, D, Fs), "shared_up": (Le, D, Fs),
                "shared_down": (Le, Fs, D),
                "w_gate_up": (Le, G, D, 2 * F), "w_down": (Le, G, F, D)}}


def init_params(key, cfg: KimiK2Config) -> Dict[str, Any]:
    """Float32 parameters, a leaf from its own key (path by path):
    projections N(0, 0.02), out-projections (``w_o``, ``down``) scaled by
    ``1 / sqrt(2 L)``, gains 1, and the router's selection bias small and
    non-zero, so that selection and weighting differ."""
    std = 0.02
    resid = std / math.sqrt(2 * cfg.num_hidden_layers)
    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        leaf_shapes(cfg), is_leaf=lambda s: isinstance(s, tuple))
    keys = jax.random.split(key, len(leaves))

    def draw(path, shape, k):
        name = path[-1].key
        if name in ("norm_in", "norm_ff", "q_norm", "kv_norm", "final_norm"):
            return jnp.ones(shape, jnp.float32)
        z = jax.random.normal(k, shape, jnp.float32)
        if name == "router_bias":
            return z * 0.02
        if name in ("w_o", "down", "shared_down", "w_down"):
            return z * resid
        return z * std

    return jax.tree_util.tree_unflatten(
        treedef, [draw(p, s, k) for (p, s), k in zip(leaves, keys)])


# ---------------------------------------------------------------------------
# the pieces of a layer (on the HELD tree: KimiK2Serving.hold)
# ---------------------------------------------------------------------------

def _latent_projections(u, p, positions, cfg):
    """u ``[N, D]`` (normed) at ``positions [N]`` -> ``(q_nope [N, H,
    nope], q_rope [N, H, rope] rotated, c_kv [N, rank] normed, k_rope [N,
    rope] rotated)``."""
    dt, eps = cfg.dtype, cfg.rms_norm_eps
    H, dn, Rkv = (cfg.num_attention_heads, cfg.qk_nope_head_dim,
                  cfg.kv_lora_rank)
    N = u.shape[0]
    cq = rms_norm(jnp.dot(u, p["w_qa"].astype(dt)), p["q_norm"], eps)
    q_nope = jnp.dot(cq, p["w_qn"].astype(dt)).reshape(N, H, dn)
    q_rope = jnp.dot(cq, p["w_qr"].astype(dt)).reshape(N, H, -1)
    kva = jnp.dot(u, p["w_kva"].astype(dt))
    ckv = rms_norm(kva[:, :Rkv], p["kv_norm"], eps)
    cos, sin = _rope.angles(positions, cfg.inv_freq())
    return (q_nope, _rope.rotate(q_rope, cos[:, None], sin[:, None]),
            ckv, _rope.rotate(kva[:, Rkv:], cos, sin))


_QUERY_BLOCK = 256


def _causal_expanded(q_nope, q_rope, k_nope, k_rope, v, scale):
    """Causal attention of a rung in the expanded form, a block of queries
    at a time (``[H, block, T]`` float32 scores, never ``[H, T, T]``).
    q_nope/k_nope ``[T, H, nope]``, q_rope ``[T, H, rope]``, k_rope ``[T,
    rope]`` (one row for all heads), v ``[T, H, dv]`` -> ``[T, H, dv]``.
    Products take the operands as they come, sums and softmax float32."""
    T = q_nope.shape[0]
    blk = _QUERY_BLOCK if T % _QUERY_BLOCK == 0 else T
    f32 = jnp.float32

    def one(i):
        qn = jax.lax.dynamic_slice_in_dim(q_nope, i * blk, blk, 0)
        qr = jax.lax.dynamic_slice_in_dim(q_rope, i * blk, blk, 0)
        s = (jnp.einsum("qhd,khd->hqk", qn, k_nope,
                        preferred_element_type=f32)
             + jnp.einsum("qhd,kd->hqk", qr, k_rope,
                          preferred_element_type=f32)) * scale
        mask = (jnp.arange(T)[None, :]
                <= i * blk + jnp.arange(blk)[:, None])[None]
        s = jnp.where(mask, s, -jnp.inf)
        e = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
        p = (e / jnp.sum(e, axis=-1, keepdims=True)).astype(v.dtype)
        return jnp.einsum("hqk,khd->qhd", p, v,
                          preferred_element_type=f32).astype(v.dtype)

    out = jax.lax.map(one, jnp.arange(T // blk))
    return out.reshape((T,) + out.shape[2:])


def _cache_rows(x, cfg):
    """``[.., latent_width]`` -> ``[.., cache_width]``, zeros behind."""
    pad = cfg.cache_width - cfg.latent_width
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])


def _attn_sequence(x, p, positions, cfg):
    """Latent attention over a whole (padded) sequence x ``[T, D]``.
    Returns ``(out [T, D], cache rows [T, cache_width])``."""
    dt = cfg.dtype
    u = rms_norm(x, p["norm_in"], cfg.rms_norm_eps)
    q_nope, q_rope, ckv, k_rope = _latent_projections(u, p, positions, cfg)
    k_nope = jnp.einsum("tc,hcd->thd", ckv, p["w_uk"].astype(dt))
    v = jnp.einsum("tc,hcd->thd", ckv, p["w_uv"].astype(dt))
    att = _causal_expanded(q_nope, q_rope, k_nope, k_rope, v,
                           cfg.softmax_scale)
    out = jnp.dot(att.reshape(x.shape[0], -1), p["w_o"].astype(dt))
    return out, _cache_rows(jnp.concatenate([ckv, k_rope], axis=-1), cfg)


def _ffn(h, valid, held, l, cfg, use_pallas=None):
    """The feed-forward half of layer ``l`` (static for a dense layer,
    traced for an expert layer ``l = first_k_dense_replace + m``). Returns
    ``(h + ffn, report [G + 1] int32)``: tokens on each held expert, and
    the held pairs that reached no expert (0: nothing is dropped)."""
    dt = cfg.dtype
    G = cfg.experts_held
    u = rms_norm(h, layer_at(held["attn"]["norm_ff"], l), cfg.rms_norm_eps)
    if isinstance(l, int) and l < cfg.first_k_dense_replace:
        d = layer_at(held["dense"], l)
        return (h + gated_mlp(u, d["gate"], d["up"], d["down"], dt),
                jnp.zeros((G + 1,), jnp.int32))
    m = l - cfg.first_k_dense_replace
    e = held["moe"]
    experts, w = _moe.route(
        u, layer_at(e["router"], m), layer_at(e["router_bias"], m),
        cfg.num_experts_per_tok, cfg.routed_scaling_factor)
    y, report = _moe.expert_share(
        u, valid, experts, w, e["w_gate_up"], e["w_down"],
        first_expert=cfg.first_expert, layer=m, use_pallas=use_pallas)
    shared = gated_mlp(u, layer_at(e["shared_gate"], m),
                       layer_at(e["shared_up"], m),
                       layer_at(e["shared_down"], m), dt)
    return h + y + shared, report


def _over_layers(cfg, x, carry, layer):
    """``layer(x, l, carry) -> (x, carry, report)`` over the layers in
    order: the dense ones unrolled, the expert layers one ``fori_loop``
    with ``carry`` (the pool) carried in place. Returns ``(x, carry,
    reports [expert layers, G + 1])``."""
    Ld, Le = cfg.first_k_dense_replace, cfg.num_expert_layers
    for l in range(Ld):
        x, carry, _ = layer(x, l, carry)
    reports = jnp.zeros((Le, cfg.experts_held + 1), jnp.int32)

    def body(m, c):
        x, carry, reports = c
        x, carry, r = layer(x, Ld + m, carry)
        return x, carry, jax.lax.dynamic_update_index_in_dim(
            reports, r, m, 0)

    return jax.lax.fori_loop(0, Le, body, (x, carry, reports))


def _sequence(held, x, length, cfg, pool=None, write_rows=None,
              use_pallas=None):
    """x ``[T, D]`` (embedded tokens from position 0) through the layers;
    positions ``>= length`` are padding (they take no part in the experts'
    counts). ``write_rows(pool, rows, l)`` stores a layer's cache rows.
    Returns ``(hidden [T, D], pool, reports)``."""
    positions = jnp.arange(x.shape[0])
    valid = positions < length

    def layer(h, l, pool):
        out, rows = _attn_sequence(h, layer_at(held["attn"], l), positions,
                                   cfg)
        if write_rows is not None:
            pool = write_rows(pool, rows, l)
        h, report = _ffn(h + out, valid, held, l, cfg, use_pallas)
        return h, pool, report

    return _over_layers(cfg, x, pool, layer)


def _logits(held, h, cfg):
    return rms_logits(h, held["final_norm"], held["head"], cfg.rms_norm_eps,
                      cfg.dtype)


def hold(params, cfg: KimiK2Config, weight_dtype: str = "f32"):
    """The serving storage of a stored tree: matrices in ``weight_dtype``,
    :data:`F32_LEAVES` float32, and the attention's small projections
    re-laid for the programs: ``w_qb [L, Rq, H (nope + rope)]`` as ``w_qn
    [L, Rq, H nope]`` and ``w_qr [L, Rq, H rope]``; the rope columns of
    ``w_qr`` (each head's) and ``w_kva`` from interleaved pairs to halves;
    ``w_kvb [L, rank, H (nope + v)]`` as ``w_uk [L, H, rank, nope]`` and
    ``w_uv [L, H, rank, v]``, the absorbed halves. Every expert leaf is
    stored in the layout the grouped product contracts and is held as it
    is: no tick and no rung copies a weight."""
    H, dn, dr, dv = (cfg.num_attention_heads, cfg.qk_nope_head_dim,
                     cfg.qk_rope_head_dim, cfg.v_head_dim)
    Rkv = cfg.kv_lora_rank
    halves = _rope.halves_from_interleaved(dr)
    a = dict(params["attn"])
    L = a["w_qb"].shape[0]
    # each head's columns of w_qb are [nope | rope]: held apart, flat, so
    # that neither product's result is cut inside a head (a cut at 128 of
    # 192 has XLA re-lay the whole weight on every tick)
    w_qb = a.pop("w_qb").reshape(L, -1, H, dn + dr)
    a["w_qn"] = w_qb[..., :dn].reshape(L, -1, H * dn)
    a["w_qr"] = w_qb[..., dn + halves].reshape(L, -1, H * dr)
    a["w_kva"] = a["w_kva"][..., np.concatenate([np.arange(Rkv),
                                                 Rkv + halves])]
    kvb = a.pop("w_kvb").reshape(L, Rkv, H, dn + dv)
    a["w_uk"] = jnp.transpose(kvb[..., :dn], (0, 2, 1, 3))
    a["w_uv"] = jnp.transpose(kvb[..., dn:], (0, 2, 1, 3))
    return hold_leaves({**params, "attn": a}, weight_dtype, F32_LEAVES)


def forward(params, tokens, cfg: KimiK2Config):
    """tokens ``[T]`` -> logits ``[T, V]`` float32: the sequence form with
    nothing cached, on the stored tree (the engine's parity surface)."""
    held = hold(params, cfg, "f32")
    x = held["embed"][tokens].astype(cfg.dtype)
    x, _, _ = _sequence(held, x, jnp.int32(tokens.shape[0]), cfg,
                        use_pallas=False)
    return _logits(held, x, cfg)


# ---------------------------------------------------------------------------
# what the serving engine asks of a model (serving/model.py)
# ---------------------------------------------------------------------------

class KimiK2Serving:
    """The model description ``DecodeEngine`` builds its paged prefill and
    decode programs from. The cache is ONE pool ``[L, pages, page,
    cache_width]`` of latent rows (``kv_lora_rank + qk_rope_head_dim``
    values and zeros up to whole lanes); both programs hand
    back, behind ``(x, caches)``, the experts' report ``[expert layers, G
    + 1]`` int32 (``ops/moe.py``)."""
    recurrent = False
    state_geometry = None
    latent = True                    # pages hold latent rows, not K and V
    paged_kernel = True
    max_positions = None             # rotary: no table bounds max_seq

    def __init__(self, cfg: KimiK2Config):
        self.cfg = cfg
        self.vocab_size = cfg.vocab_size
        self.cache_pools = {"layers": cfg.num_hidden_layers,
                            "rows": ((cfg.cache_width,),)}

    def kernel_takes_pages(self, page_size: int, cache_dtype) -> bool:
        return _pk.mla_decode_tiles(page_size, cache_dtype)

    def hold(self, params, weight_dtype: str, chunk: int, sharded=False):
        """(int8 and a ``sharded`` engine are refused where the engine is
        built.)"""
        return hold(params, self.cfg, weight_dtype)

    def embed(self, qparams, tokens, positions):
        return qparams["embed"][tokens].astype(self.cfg.dtype)

    def logits(self, qparams, h, fused=False):
        return _logits(qparams, h, self.cfg)

    def forward(self, params, tokens):
        return forward(params, tokens[0], self.cfg)[None]

    def prefill_layers(self, qparams, x, caches, ctx):
        """x ``[1, T, D]`` from position 0 (this model is never given a
        prefix); ctx: ``length``, ``table_row``, ``page_size``. The rung
        attends expanded and writes its latent rows into the slot's pages."""
        T, ps = x.shape[1], ctx.page_size
        pages = ctx.table_row[:T // ps]

        def write_rows(pool, rows, l):
            return paged_page_write(
                pool, rows.reshape(T // ps, ps, rows.shape[-1]), pages, l)

        h, pool, reports = _sequence(qparams, x[0], ctx.length, self.cfg,
                                     caches[0], write_rows)
        return h[None], (pool,), reports

    def decode_layers(self, qparams, x, caches, ctx):
        """x ``[B, D]``; ctx: ``positions``, ``tables`` (zeroed for lanes
        that do not ride), ``actives``, ``page_size``, ``kv_path``. The
        tick attends absorbed through the page table: the Pallas kernel
        over the live pages, or gather + masked softmax."""
        cfg = self.cfg
        dt, Rkv = cfg.dtype, cfg.kv_lora_rank
        ps, tables, positions = ctx.page_size, ctx.tables, ctx.positions
        valid = ctx.actives != 0
        scale = cfg.softmax_scale
        # fused_decode off the TPU drives both kernels in interpret mode
        kernels = True if ctx.kv_path == "pallas_paged" else None

        def attend(q_lat, rows, pool, l):
            if ctx.kv_path == "pallas_paged":
                return _pk.mla_paged_decode_attention(
                    q_lat, pool, rows, tables, positions, l, Rkv, scale)
            phys = jnp.take_along_axis(
                tables, (positions // ps)[:, None], axis=1)[:, 0]
            pool = paged_cache_update(pool, rows, phys, positions % ps, l)
            return latent_decode_attention(
                q_lat, paged_gather(pool, tables, l), positions + 1, Rkv,
                scale), pool

        def layer(h, l, pool):
            p = layer_at(qparams["attn"], l)
            u = rms_norm(h, p["norm_in"], cfg.rms_norm_eps)
            q_nope, q_rope, ckv, k_rope = _latent_projections(
                u, p, positions, cfg)
            q_abs = jnp.einsum("bhd,hcd->bhc", q_nope, p["w_uk"].astype(dt))
            o_lat, pool = attend(
                _cache_rows(jnp.concatenate([q_abs, q_rope], axis=-1), cfg),
                _cache_rows(jnp.concatenate([ckv, k_rope], axis=-1), cfg),
                pool, l)
            att = jnp.einsum("bhc,hcd->bhd", o_lat, p["w_uv"].astype(dt))
            out = jnp.dot(att.reshape(h.shape[0], -1), p["w_o"].astype(dt))
            h, report = _ffn(h + out, valid, qparams, l, cfg, kernels)
            return h, pool, report

        x, pool, reports = _over_layers(cfg, x, caches[0], layer)
        return x, (pool,), reports
