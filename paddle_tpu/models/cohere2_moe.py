"""Command A+ text decoder (``model_type: cohere2_moe``): a parallel
attention + experts block, sliding-window and global layers mixed,
grouped-query heads, sigmoid-routed experts with shared experts. Serving
path.

One norm a layer (Cohere's LayerNorm: the mean taken out, a gain, no bias)
and both halves added to the residual side by side::

    u = LN(h);   h += Attn(u) + FFN(u)

**Attention.** ``q = u W_q`` as ``H`` heads of ``head_dim``, ``k = u W_k``,
``v = u W_v`` as ``KVH`` heads, query head ``i`` served by key/value head
``i // (H / KVH)``; no bias, no QK-norm, scale ``head_dim ** -0.5``. The
layer's kind comes from ``layer_types``, as data: a ``sliding_attention``
layer rotates q and k over the whole head (interleaved pairs in the source,
``rope_theta``) and query ``i`` sees keys ``i - sliding_window < j <= i``; a
``full_attention`` layer has no position at all and sees every ``j <= i``.

**Experts.** ``FFN(u) = sum_{k in top} w_k E_k(u) + (1 / S) sum_j S_j(u)``:
the router of ``ops/moe.py`` (sigmoid scores in float32, the
``num_experts_per_tok`` largest, weights normalised to one, no bias, scale
1) over all ``num_experts_published`` experts, and ``S =
num_shared_experts`` shared experts whose outputs are averaged
(``shared_expert_combination_strategy: average``); every expert is
``W_down(silu(W_gate u) * W_up u)`` of width ``intermediate_size``.

**One chip's share** (as ``models/kimi_k2.py``): ``experts_held`` experts
from ``first_expert`` on are this chip's; it routes over all published
experts and adds up its own experts' part and the shared experts. The head
is the embedding transposed; ``vocab_size`` rows of it are held, from row 0.

**The cache** is two page groups under one manager (``serving/paged_kv.py``):
the full layers' keys and values in one, the sliding layers' in another whose
slot tables are rings bounded by the window. A token's row is ``KVH *
head_dim`` values flat in the lanes.

The stored tree keeps the published orientation (matrices ``[in, out]``,
rotary pairs interleaved, the shared experts one by one);
:meth:`Cohere2MoeServing.hold` re-lays what the programs contract. Training
is not built.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from ..ops import moe as _moe
from ..ops import pallas_kernels as _pk
from ..ops import rope as _rope
from ..ops.decode_attention import (band_prefill_attention,
                                    paged_cache_update, paged_gather,
                                    paged_page_write,
                                    sliding_decode_attention)
from .blocks import hold_leaves, over_ffn_chunks

__all__ = ["Cohere2MoeConfig", "COHERE2_MOE_TINY", "leaf_shapes",
           "init_params", "hold", "forward", "Cohere2MoeServing"]

SLIDING, FULL = "sliding_attention", "full_attention"


@dataclasses.dataclass(frozen=True)
class Cohere2MoeConfig:
    """The keys of the published ``config.json`` that shape the program,
    and what one chip of an expert-parallel group holds of it."""
    vocab_size: int = 262144             # rows held, from row 0
    hidden_size: int = 4096
    intermediate_size: int = 4096        # an expert's width
    num_hidden_layers: int = 32
    layer_types: Tuple[str, ...] = (SLIDING, SLIDING, SLIDING, FULL) * 8
    num_attention_heads: int = 128
    num_key_value_heads: int = 8
    head_dim: int = 128
    sliding_window: int = 4096
    num_experts_published: int = 128     # the router's width
    experts_held: int = 128
    first_expert: int = 0
    num_experts_per_tok: int = 8
    num_shared_experts: int = 4
    layer_norm_eps: float = 1e-5
    rope_theta: float = 50000.0
    logit_scale: float = 1.0
    dtype: Any = jnp.bfloat16            # compute dtype

    def __post_init__(self):
        if len(self.layer_types) != self.num_hidden_layers:
            raise ValueError(
                f"layer_types names {len(self.layer_types)} layers, "
                f"num_hidden_layers is {self.num_hidden_layers}")
        odd = set(self.layer_types) - {SLIDING, FULL}
        if odd:
            raise ValueError(f"layer_types: unknown kinds {sorted(odd)}")

    @property
    def kv_width(self) -> int:
        """Values of a token's keys (or values) a layer: the cache row."""
        return self.num_key_value_heads * self.head_dim

    @property
    def q_width(self) -> int:
        return self.num_attention_heads * self.head_dim

    @property
    def shared_width(self) -> int:
        return self.intermediate_size * self.num_shared_experts

    def layers_of(self, kind: str) -> Tuple[int, ...]:
        return tuple(i for i, k in enumerate(self.layer_types) if k == kind)

    def inv_freq(self) -> np.ndarray:
        return _rope.yarn_inv_freq(self.head_dim, self.rope_theta)

    def scaled(self, **kw) -> "Cohere2MoeConfig":
        return dataclasses.replace(self, **kw)

    def serving_description(self) -> "Cohere2MoeServing":
        """What ``DecodeEngine`` builds its programs from
        (``serving/model.py``)."""
        return Cohere2MoeServing(self)


COHERE2_MOE_TINY = Cohere2MoeConfig(
    vocab_size=256, hidden_size=64, intermediate_size=32,
    num_hidden_layers=4, layer_types=(SLIDING, SLIDING, SLIDING, FULL),
    num_attention_heads=8, num_key_value_heads=2, head_dim=8,
    sliding_window=8, num_experts_published=16, experts_held=16,
    first_expert=0, num_experts_per_tok=4, num_shared_experts=2,
    rope_theta=10000.0, dtype=jnp.float32)

# leaves held in float32 whatever the weights' type: gains, and the router,
# whose product, sigmoid and choice the source computes in float32
F32_LEAVES = ("norm", "final_norm", "router")


def leaf_shapes(cfg: Cohere2MoeConfig) -> Dict[str, Any]:
    """The stored parameter tree as shapes (published orientation; an
    expert's gate and up projections side by side on the output axis, the
    layout the grouped product contracts)."""
    D, L, F = cfg.hidden_size, cfg.num_hidden_layers, cfg.intermediate_size
    S, E, G = (cfg.num_shared_experts, cfg.num_experts_published,
               cfg.experts_held)
    return {
        "embed": (cfg.vocab_size, D), "final_norm": (D,),
        "layers": {"norm": (L, D), "w_q": (L, D, cfg.q_width),
                   "w_k": (L, D, cfg.kv_width), "w_v": (L, D, cfg.kv_width),
                   "w_o": (L, cfg.q_width, D), "router": (L, D, E),
                   "shared_gate": (L, S, D, F), "shared_up": (L, S, D, F),
                   "shared_down": (L, S, F, D),
                   "w_gate_up": (L, G, D, 2 * F), "w_down": (L, G, F, D)}}


def init_params(key, cfg: Cohere2MoeConfig) -> Dict[str, Any]:
    """Float32 parameters, a leaf from its own key: projections N(0, 0.02),
    out-projections (``w_o``, every ``down``) scaled by ``1 / sqrt(2 L)``,
    gains 1."""
    std = 0.02
    resid = std / math.sqrt(2 * cfg.num_hidden_layers)
    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        leaf_shapes(cfg), is_leaf=lambda s: isinstance(s, tuple))
    keys = jax.random.split(key, len(leaves))

    def draw(path, shape, k):
        name = path[-1].key
        if name in ("norm", "final_norm"):
            return jnp.ones(shape, jnp.float32)
        z = jax.random.normal(k, shape, jnp.float32)
        if name in ("w_o", "shared_down", "w_down"):
            return z * resid
        return z * std

    return jax.tree_util.tree_unflatten(
        treedef, [draw(p, s, k) for (p, s), k in zip(leaves, keys)])


# ---------------------------------------------------------------------------
# the pieces of a layer (on the HELD tree: hold)
# ---------------------------------------------------------------------------

def layer_norm(x, gain, eps):
    """Cohere's LayerNorm: mean and variance over the last axis in float32,
    a gain and no bias; ``x``'s dtype out."""
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    xc = xf - mean
    var = jnp.mean(jnp.square(xc), axis=-1, keepdims=True)
    return (xc * jax.lax.rsqrt(var + eps) * gain).astype(x.dtype)


def _at(stacked, l: int):
    """Layer ``l`` (static) of stacked leaves."""
    return jax.tree_util.tree_map(lambda a: a[l], stacked)


def _qkv(u, p, positions, rotary: bool, cfg):
    """u ``[N, D]`` (normed) at ``positions [N]`` -> ``(q [N, H hd], k [N,
    KVH hd], v [N, KVH hd])`` flat, q and k rotated where the layer has
    positions. One product, cut BEFORE anything is reshaped to heads (a
    reshape straight after the product is folded back into it by XLA, which
    then re-lays the weight on every call: PERF.md section 6, PR 32)."""
    dt = cfg.dtype
    H, KVH, hd = (cfg.num_attention_heads, cfg.num_key_value_heads,
                  cfg.head_dim)
    flat = jnp.dot(u, p["w_qkv"].astype(dt))
    q, k, v = (flat[:, :cfg.q_width],
               flat[:, cfg.q_width:cfg.q_width + cfg.kv_width],
               flat[:, cfg.q_width + cfg.kv_width:])
    if rotary:
        N = u.shape[0]
        cos, sin = _rope.angles(positions, cfg.inv_freq())
        q = _rope.rotate(q.reshape(N, H, hd), cos[:, None],
                         sin[:, None]).reshape(N, -1)
        k = _rope.rotate(k.reshape(N, KVH, hd), cos[:, None],
                         sin[:, None]).reshape(N, -1)
    return q, k, v


def _ffn_rows(u, valid, stacked, l: int, cfg, use_pallas):
    """``(FFN(u) [N, D], report [G + 1] int32)`` of the rows ``u``. The
    layer's leaves are cut out of the stacked ones HERE, inside the loop
    over a rung's chunks: cut outside it they are the loop's operands, and
    each is copied out of its stack (0.4 GB a layer)."""
    dt = cfg.dtype
    N = u.shape[0]
    p = _at({k: stacked[k] for k in (
        "router", "shared_gate_up", "shared_down")}, l)
    experts, w = _moe.route(u, p["router"], jnp.zeros(
        (cfg.num_experts_published,), jnp.float32),
        cfg.num_experts_per_tok, 1.0)
    # held pairs expected: N k G / E, half as much again before the share
    # falls back to its full-size buffer
    expected = N * cfg.num_experts_per_tok * cfg.experts_held \
        / cfg.num_experts_published
    y, report = _moe.expert_share(
        u, valid, experts, w, stacked["w_gate_up"], stacked["w_down"],
        first_expert=cfg.first_expert, layer=l, use_pallas=use_pallas,
        small_rows=max(N, math.ceil(1.5 * expected)))
    Fs = cfg.shared_width
    gu = jnp.dot(u, p["shared_gate_up"].astype(dt))
    a = (jax.nn.silu(gu[:, :Fs].astype(jnp.float32))
         * gu[:, Fs:].astype(jnp.float32)).astype(dt)
    shared = jnp.dot(a, p["shared_down"].astype(dt),
                     preferred_element_type=jnp.float32)
    return y + (shared / cfg.num_shared_experts).astype(dt), report


def _ffn(u, valid, held, l: int, cfg, use_pallas=None):
    """The feed-forward half of layer ``l`` on ``u [T, D]``, a rung's
    chunk of tokens at a time (``blocks.over_ffn_chunks``). Returns ``(ffn
    [T, D], report [G + 1] int32)``."""
    stacked = held["layers"]
    return over_ffn_chunks(
        lambda rows, ok: _ffn_rows(rows, ok, stacked, l, cfg, use_pallas),
        u, valid, cfg.experts_held)


def _sequence(held, x, length, cfg, write_rows=None, use_pallas=None,
              flash=None):
    """x ``[T, D]`` (embedded tokens from position 0) through the layers;
    positions ``>= length`` are padding (they take no part in the experts'
    counts). ``write_rows(k, v, l)`` stores layer ``l``'s cache rows ``[T,
    KVH hd]``. Returns ``(hidden [T, D], reports [L, G + 1])``."""
    dt = cfg.dtype
    T = x.shape[0]
    H, KVH, hd = (cfg.num_attention_heads, cfg.num_key_value_heads,
                  cfg.head_dim)
    positions = jnp.arange(T)
    valid = positions < length
    if flash is None:
        flash = _pk._on_tpu()
    reports = []
    for l, kind in enumerate(cfg.layer_types):
        p = _at({k: held["layers"][k] for k in ("norm", "w_qkv", "w_o")}, l)
        u = layer_norm(x, p["norm"], cfg.layer_norm_eps)
        q, k, v = _qkv(u, p, positions, kind == SLIDING, cfg)
        if write_rows is not None:
            write_rows(k, v, l)
        window = cfg.sliding_window if kind == SLIDING else None
        if flash:
            att = _pk.band_flash_attention(q[None], k[None], v[None], H,
                                           KVH, window=window)[0]
        else:
            att = band_prefill_attention(
                q.reshape(T, H, hd), k.reshape(T, KVH, hd),
                v.reshape(T, KVH, hd), window).reshape(T, -1)
        ffn, report = _ffn(u, valid, held, l, cfg, use_pallas)
        x = x + jnp.dot(att, p["w_o"].astype(dt)) + ffn
        reports.append(report)
    return x, jnp.stack(reports)


def _logits(held, h, cfg):
    """Final norm, then the embedding transposed (the head is tied)."""
    h = layer_norm(h, held["final_norm"], cfg.layer_norm_eps)
    out = jnp.einsum("...d,vd->...v", h, held["embed"].astype(cfg.dtype),
                     preferred_element_type=jnp.float32)
    return out * cfg.logit_scale if cfg.logit_scale != 1.0 else out


def hold(params, cfg: Cohere2MoeConfig, weight_dtype: str = "f32"):
    """The serving storage of a stored tree: matrices in ``weight_dtype``,
    :data:`F32_LEAVES` float32, and three leaves re-laid for the programs:
    ``w_q | w_k | w_v`` side by side as ``w_qkv [L, D, (H + 2 KVH) hd]``,
    one flat product; each head's columns of its q and k parts from
    interleaved rotary pairs to halves (``ops/rope.py``; the same
    permutation on both sides of every score, so a layer without positions
    is untouched by it); the ``S`` shared experts as ONE gated MLP of width
    ``S F``: ``shared_gate_up [L, D, 2 S F]`` (every gate, then every up)
    and ``shared_down [L, S F, D]``, whose output the layer divides by
    ``S``. The routed experts are stored as the grouped product contracts
    them and are held as they are."""
    H, KVH, hd = (cfg.num_attention_heads, cfg.num_key_value_heads,
                  cfg.head_dim)
    halves = _rope.halves_from_interleaved(hd)
    a = dict(params["layers"])
    L = a["w_q"].shape[0]

    def to_halves(w, heads):
        return w.reshape(L, -1, heads, hd)[..., halves].reshape(w.shape)

    a["w_qkv"] = jnp.concatenate(
        [to_halves(a.pop("w_q"), H), to_halves(a.pop("w_k"), KVH),
         a.pop("w_v")], axis=-1)

    def side_by_side(w):                     # [L, S, D, F] -> [L, D, S F]
        return jnp.moveaxis(w, 1, 2).reshape(L, w.shape[2], -1)

    a["shared_gate_up"] = jnp.concatenate(
        [side_by_side(a.pop("shared_gate")),
         side_by_side(a.pop("shared_up"))], axis=-1)
    down = a.pop("shared_down")              # [L, S, F, D] -> [L, S F, D]
    a["shared_down"] = down.reshape(L, -1, down.shape[-1])
    return hold_leaves({**params, "layers": a}, weight_dtype, F32_LEAVES)


def forward(params, tokens, cfg: Cohere2MoeConfig):
    """tokens ``[T]`` -> logits ``[T, V]`` float32: the sequence form with
    nothing cached, on the stored tree (the engine's parity surface)."""
    held = hold(params, cfg, "f32")
    x = held["embed"][tokens].astype(cfg.dtype)
    x, _ = _sequence(held, x, jnp.int32(tokens.shape[0]), cfg,
                     use_pallas=False, flash=False)
    return _logits(held, x, cfg)


# ---------------------------------------------------------------------------
# what the serving engine asks of a model (serving/model.py)
# ---------------------------------------------------------------------------

class Cohere2MoeServing:
    """The model description ``DecodeEngine`` builds its paged prefill and
    decode programs from. The cache is two page groups: ``full`` (the
    ``full_attention`` layers' keys and values, a slot's table naming every
    page) and ``window`` (the ``sliding_attention`` layers', a ring bounded
    by ``sliding_window``), each a pool pair ``[layers of the kind, pages,
    page, KVH hd]``; a model with one kind of layer alone has one group.
    Both programs hand back, behind ``(x, caches)``, the experts' report
    ``[L, G + 1]`` int32 (``ops/moe.py``)."""
    recurrent = False
    state_geometry = None
    paged_kernel = True
    max_positions = None             # rotary or none: no table bounds max_seq

    def __init__(self, cfg: Cohere2MoeConfig):
        self.cfg = cfg
        self.vocab_size = cfg.vocab_size
        rows = ((cfg.kv_width,),) * 2
        kinds = [(name, kind, window) for name, kind, window in (
            ("full", FULL, None), ("window", SLIDING, cfg.sliding_window))
            if cfg.layers_of(kind)]
        # a group: (its name, the kind of its layers, layer -> its index in
        # the group's pools)
        self.groups = [(name, kind, {l: i for i, l in
                                     enumerate(cfg.layers_of(kind))})
                       for name, kind, _ in kinds]
        self.cache_pools = {"groups": [
            {"name": name, "layers": len(cfg.layers_of(kind)), "rows": rows,
             "window": window} for name, kind, window in kinds]}

    def kernel_takes_pages(self, page_size: int, cache_dtype) -> bool:
        """Grouped heads in whole sublane tiles
        (``pallas_kernels.paged_decode_kernel``), and a page of whole
        sublane tiles of the cache's dtype (a chunk of pages is read as one
        matrix)."""
        c = self.cfg
        return (_pk.paged_decode_kernel(
            c.num_attention_heads, c.num_key_value_heads, c.head_dim)
            == "gqa_paged_decode_attention"
            and page_size % (32 // jnp.dtype(cache_dtype).itemsize) == 0)

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

    def _group_of(self, l: int, widths):
        """Layer ``l`` -> (index of its group, index in the group's pools,
        start of the group's columns in a table row)."""
        at = 0
        for g, (_name, _kind, index) in enumerate(self.groups):
            if l in index:
                return g, index[l], at
            at += widths[g]
        raise KeyError(l)

    def prefill_layers(self, qparams, x, caches, ctx):
        """x ``[1, T, D]`` from position 0 (this model is never given a
        prefix); ctx: ``length``, ``table_row`` (the groups' rows side by
        side, ``table_widths``), ``page_size``. The rung attends its own
        keys inside the band and writes its rows into the slot's pages: a
        full layer every page of the rung, a sliding layer the pages its
        ring still holds (the prompt's last ``ring``)."""
        cfg = self.cfg
        T, ps = x.shape[1], ctx.page_size
        n = T // ps
        caches = list(caches)
        last = (ctx.length - 1) // ps        # the prompt's last logical page

        def write_rows(k, v, l):
            g, li, at = self._group_of(l, ctx.table_widths)
            M = ctx.table_widths[g]
            phys = jax.lax.dynamic_slice_in_dim(ctx.table_row, at, M)
            if cfg.layer_types[l] != SLIDING or n <= M:
                # logical page j lies at entry j: the ring has not turned
                # (entries past the prompt's pages are 0, the scratch page)
                take, phys = None, phys[:n]
            else:
                # entry e holds the newest logical page congruent to it;
                # entries the prompt has not reached write the scratch page
                j = last - (last - jnp.arange(M)) % M
                take, phys = jnp.maximum(j, 0), jnp.where(j >= 0, phys, 0)
            for i, rows in ((2 * g, k), (2 * g + 1, v)):
                pages = rows.reshape(n, ps, rows.shape[-1])
                caches[i] = paged_page_write(
                    caches[i], pages if take is None else pages[take],
                    phys, li)

        h, reports = _sequence(qparams, x[0], ctx.length, cfg, write_rows)
        return h[None], tuple(caches), reports

    def decode_layers(self, qparams, x, caches, ctx):
        """x ``[B, D]``; ctx: ``positions``, ``tables`` (the groups' rows
        side by side, zeroed for lanes that do not ride), ``actives``,
        ``page_size``, ``kv_path``. Each layer writes this tick's row and
        attends through its group's table: the Pallas kernel over the live
        pages inside the layer's span, or gather + masked softmax."""
        cfg = self.cfg
        dt = cfg.dtype
        ps, positions = ctx.page_size, ctx.positions
        H, KVH = cfg.num_attention_heads, cfg.num_key_value_heads
        valid = ctx.actives != 0
        B = x.shape[0]
        caches = list(caches)
        # fused_decode off the TPU drives the kernels in interpret mode
        kernels = True if ctx.kv_path == "pallas_paged" else None
        reports = []
        for l, kind in enumerate(cfg.layer_types):
            g, li, at = self._group_of(l, ctx.table_widths)
            M = ctx.table_widths[g]
            tables = ctx.tables[:, at:at + M]
            ring = kind == SLIDING
            window = cfg.sliding_window if ring else None
            p = _at({k: qparams["layers"][k] for k in
                     ("norm", "w_qkv", "w_o")}, l)
            u = layer_norm(x, p["norm"], cfg.layer_norm_eps)
            q, k, v = _qkv(u, p, positions, ring, cfg)
            q = q.reshape(B, H, cfg.head_dim)
            kp, vp = caches[2 * g], caches[2 * g + 1]
            if ctx.kv_path == "pallas_paged":
                att, kp, vp = _pk.gqa_paged_decode_attention(
                    q, kp, vp, k, v, tables, positions, li, KVH,
                    window=window, ring=ring)
            else:
                logical = positions // ps
                phys = jnp.take_along_axis(
                    tables, (logical % M if ring else logical)[:, None],
                    axis=1)[:, 0]
                kp = paged_cache_update(kp, k, phys, positions % ps, li)
                vp = paged_cache_update(vp, v, phys, positions % ps, li)
                att = sliding_decode_attention(
                    q, paged_gather(kp, tables, li),
                    paged_gather(vp, tables, li), positions, KVH, ps,
                    window=window, ring=ring)
            caches[2 * g], caches[2 * g + 1] = kp, vp
            ffn, report = _ffn(u, valid, qparams, l, cfg, kernels)
            x = x + jnp.dot(att.reshape(B, -1), p["w_o"].astype(dt)) + ffn
            reports.append(report)
        return x, tuple(caches), jnp.stack(reports)
