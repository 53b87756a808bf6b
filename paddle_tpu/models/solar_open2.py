"""Solar-Open2 text decoder (``model_type: solar_open2``): Kimi Delta
Attention layers (a delta rule with a gate a channel) and gated grouped-query
attention layers in a published pattern, sigmoid-routed experts with one
shared expert in every layer. Serving path.

The pattern is data: ``gqa_layers`` lists the attention layers, every other
layer is a KDA layer (the source: layers 0, 4, ..., 44 of 48). Every layer is
pre-norm (RMSNorm with a gain)::

    h = x + Mixer(norm1 x);   out = h + MoE(norm2 h)

after the last layer ``final_norm`` and an untied head. No bias, no position
of any kind (``use_rope: false``).

**KDA** (arXiv:2510.26692; ``H`` heads, keys and values of ``head_dim``), with
``u = norm1 x``::

    q~, k~, v~ = silu(conv4(u W_q)), silu(conv4(u W_k)), silu(conv4(u W_v))
    q = l2norm(q~) / sqrt(dk);  k = l2norm(k~)                 a head
    g = -exp(A_log[h]) softplus((u W_fa) W_fb + dt_bias)       [H, dk]
    alpha = exp(g);  beta = 2 sigmoid(u W_beta)                 [H]
    S[t] = (I - beta k k^T) Diag(alpha) S[t-1] + beta k v^T;  o = S[t]^T q
    out = (rmsnorm(o; gain over dv) * sigmoid((u W_ga) W_gb)) W_o

(``ops/gated_delta.py``: :func:`kda_chunked` over a padded sequence,
:func:`kda_update` one token a rider; the 2 in ``beta`` is
``kda_allow_neg_eigval``, the two-step gate projections
``kda_use_full_proj: false``). **GQA**: ``H`` query heads over ``KVH``
key/value heads of ``head_dim``, causal softmax at ``head_dim ** -0.5``, and
an output gate a value (``use_gqa_gate``): ``(Attn(u) * sigmoid(u W_gate))
W_o``. **Experts**: the router of ``ops/moe.py`` (sigmoid scores in float32,
the ``num_experts_per_tok`` largest of ``score + bias`` chosen, weights from
the scores without it, normalised, scaled by ``routed_scaling_factor``) over
all ``n_routed_experts_published`` experts, plus ``n_shared_experts`` shared
experts as one gated MLP; every expert ``W_down(silu(W_gate u) * W_up u)`` of
``moe_intermediate_size``.

**One chip's share** (as ``models/kimi_k2.py``): ``experts_held`` experts
from ``first_expert`` on are this chip's; it routes over all published
experts and adds up its own experts' part and the shared expert.
``vocab_size`` rows of embedding and head are held, from row 0.

What a sequence carries between calls: keys and values of the GQA layers in
the paged pool (a token's ``KVH * head_dim`` values flat in the lanes), and
for each KDA layer the matrix state (``H x dk x dv`` float32, ``ops/
gated_delta.py:fold_state``) and the last ``conv - 1`` rows of the conv's
input. The first description that is ``recurrent`` AND hands out the experts'
report. The stored tree keeps the published orientation, a dict a layer;
:meth:`SolarOpen2Serving.hold` lays side by side what one product contracts.
Training is not built.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from ..ops import gated_delta as _gd
from ..ops import moe as _moe
from ..ops import pallas_kernels as _pk
from ..ops.decode_attention import (band_prefill_attention,
                                    paged_cache_update, paged_gather,
                                    paged_page_write,
                                    sliding_decode_attention)
from .blocks import hold_leaves, over_ffn_chunks, rms_logits, rms_norm

__all__ = ["SolarOpen2Config", "SOLAR_OPEN2_TINY", "leaf_shapes",
           "init_params", "hold", "forward", "SolarOpen2Serving"]

KDA, GQA = "kda", "gqa"
KDA_CHUNK = 64


@dataclasses.dataclass(frozen=True)
class SolarOpen2Config:
    """The keys of the published ``config.json`` that shape the program
    (``linear_attn_config`` flat: ``linear_num_heads``, ``linear_head_dim``,
    ``short_conv_kernel_size``), and what one chip of an expert-parallel
    group holds of it."""
    vocab_size: int = 196608             # rows held, from row 0
    hidden_size: int = 4096
    num_hidden_layers: int = 48
    gqa_layers: Tuple[int, ...] = tuple(range(0, 48, 4))
    num_attention_heads: int = 64
    num_key_value_heads: int = 8
    head_dim: int = 128
    linear_num_heads: int = 64
    linear_head_dim: int = 128           # keys and values alike
    short_conv_kernel_size: int = 4
    kda_use_full_proj: bool = False
    kda_allow_neg_eigval: bool = True
    use_gqa_gate: bool = True
    use_rope: bool = False
    first_k_dense_replace: int = 0
    moe_intermediate_size: int = 1280
    n_routed_experts_published: int = 320    # the router's width
    experts_held: int = 320
    first_expert: int = 0
    num_experts_per_tok: int = 8
    n_shared_experts: int = 1
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    rms_norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16            # compute dtype

    def __post_init__(self):
        bad = [l for l in self.gqa_layers
               if not 0 <= l < self.num_hidden_layers]
        if bad or len(set(self.gqa_layers)) != len(self.gqa_layers):
            raise ValueError(f"gqa_layers {self.gqa_layers}: distinct "
                             f"layers of {self.num_hidden_layers}")
        for key, built in (("use_rope", False), ("kda_use_full_proj", False),
                           ("use_gqa_gate", True),
                           ("first_k_dense_replace", 0),
                           ("norm_topk_prob", True)):
            if getattr(self, key) != built:
                raise ValueError(f"{key}={getattr(self, key)!r}: only "
                                 f"{built!r} is built")

    @property
    def layer_types(self) -> Tuple[str, ...]:
        """Every layer's kind, from ``gqa_layers`` (never from a period)."""
        gqa = set(self.gqa_layers)
        return tuple(GQA if l in gqa else KDA
                     for l in range(self.num_hidden_layers))

    @property
    def num_kda_layers(self) -> int:
        return self.num_hidden_layers - len(self.gqa_layers)

    @property
    def kv_width(self) -> int:
        """Values of a token's keys (or values) a GQA layer: the cache row."""
        return self.num_key_value_heads * self.head_dim

    @property
    def q_width(self) -> int:
        return self.num_attention_heads * self.head_dim

    @property
    def kda_width(self) -> int:
        """Channels of a KDA layer's q (or k, or v) projection."""
        return self.linear_num_heads * self.linear_head_dim

    @property
    def gate_rank(self) -> int:
        """The inner width of the two-step gate projections: a head's."""
        return self.linear_head_dim

    @property
    def shared_width(self) -> int:
        return self.moe_intermediate_size * self.n_shared_experts

    def scaled(self, **kw) -> "SolarOpen2Config":
        return dataclasses.replace(self, **kw)

    def serving_description(self) -> "SolarOpen2Serving":
        """What ``DecodeEngine`` builds its programs from
        (``serving/model.py``)."""
        return SolarOpen2Serving(self)


SOLAR_OPEN2_TINY = SolarOpen2Config(
    vocab_size=256, hidden_size=64, num_hidden_layers=4, gqa_layers=(0,),
    num_attention_heads=8, num_key_value_heads=2, head_dim=8,
    linear_num_heads=4, linear_head_dim=16, moe_intermediate_size=32,
    n_routed_experts_published=16, experts_held=16, first_expert=0,
    num_experts_per_tok=4, dtype=jnp.float32)

# leaves held in float32 whatever the weights' type: gains, the conv's taps,
# the decay's constants, and the router, whose product, sigmoid and choice
# the source computes in float32
F32_LEAVES = ("norm1", "norm2", "final_norm", "o_norm", "conv_w", "conv_q",
              "conv_k", "conv_v", "A_log", "dt_bias", "router",
              "router_bias")


def leaf_shapes(cfg: SolarOpen2Config) -> Dict[str, Any]:
    """The stored parameter tree as shapes: published orientation
    (matrices ``[in, out]``, a conv ``[taps, channels]`` with tap ``taps -
    1`` on the current token), a dict a layer; an expert's gate and up
    projections side by side on the output axis, the layout the grouped
    product contracts."""
    D, F, Fs = cfg.hidden_size, cfg.moe_intermediate_size, cfg.shared_width
    E, G = cfg.n_routed_experts_published, cfg.experts_held
    H, Ck, r = cfg.linear_num_heads, cfg.kda_width, cfg.gate_rank
    K = cfg.short_conv_kernel_size
    ffn = {"norm2": (D,), "router": (D, E), "router_bias": (E,),
           "shared_gate": (D, Fs), "shared_up": (D, Fs),
           "shared_down": (Fs, D), "w_gate_up": (G, D, 2 * F),
           "w_down": (G, F, D)}
    kda = {"norm1": (D,), "w_q": (D, Ck), "w_k": (D, Ck), "w_v": (D, Ck),
           "conv_q": (K, Ck), "conv_k": (K, Ck), "conv_v": (K, Ck),
           "w_fa": (D, r), "w_fb": (r, Ck), "dt_bias": (Ck,),
           "A_log": (H,), "w_beta": (D, H), "w_ga": (D, r),
           "w_gb": (r, Ck), "o_norm": (cfg.linear_head_dim,),
           "w_o": (Ck, D), **ffn}
    gqa = {"norm1": (D,), "w_q": (D, cfg.q_width), "w_k": (D, cfg.kv_width),
           "w_v": (D, cfg.kv_width), "w_gate": (D, cfg.q_width),
           "w_o": (cfg.q_width, D), **ffn}
    return {"embed": (cfg.vocab_size, D), "final_norm": (D,),
            "lm_head": (D, cfg.vocab_size),
            "layers": [dict(gqa if kind == GQA else kda)
                       for kind in cfg.layer_types]}


def init_params(key, cfg: SolarOpen2Config) -> Dict[str, Any]:
    """Float32 parameters, a leaf from its own ``(key, layer, leaf)``:
    projections N(0, 0.02), out-projections (``w_o``, every ``down``) scaled
    by ``1 / sqrt(2 L)``, gains 1, the router's selection bias N(0, 0.002),
    the conv's taps uniform in +-1/sqrt(taps), and the delta rule's init
    for the decay (``A`` uniform in [1, 16], logged, a head; ``dt_bias``
    the inverse softplus of a step log-uniform in [1e-3, 1e-1], a
    channel)."""
    std = 0.02
    resid = std / math.sqrt(2 * cfg.num_hidden_layers)
    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        leaf_shapes(cfg), is_leaf=lambda s: isinstance(s, tuple))

    def draw(path, shape, k):
        name = path[-1].key
        if "norm" in name:
            return jnp.ones(shape, jnp.float32)
        if name == "A_log":
            return jnp.log(jax.random.uniform(k, shape, jnp.float32,
                                              1.0, 16.0))
        if name == "dt_bias":
            u = jax.random.uniform(k, shape, jnp.float32)
            dt = jnp.exp(u * (math.log(1e-1) - math.log(1e-3))
                         + math.log(1e-3))
            return dt + jnp.log(-jnp.expm1(-dt))
        if name.startswith("conv_"):
            bound = 1.0 / math.sqrt(shape[0])
            return jax.random.uniform(k, shape, jnp.float32, -bound, bound)
        z = jax.random.normal(k, shape, jnp.float32)
        if name == "router_bias":
            return z * 0.002
        if name in ("w_o", "shared_down", "w_down"):
            return z * resid
        return z * std

    names = sorted({path[-1].key for path, _ in leaves})

    def key_of(path):
        layer = next((step.idx for step in path if hasattr(step, "idx")),
                     cfg.num_hidden_layers)
        return jax.random.fold_in(jax.random.fold_in(key, layer),
                                  names.index(path[-1].key))

    return jax.tree_util.tree_unflatten(
        treedef, [draw(p, s, key_of(p)) for p, s in leaves])


# ---------------------------------------------------------------------------
# the pieces of a layer (on the HELD tree: hold)
# ---------------------------------------------------------------------------

def hold(params, cfg: SolarOpen2Config, weight_dtype: str = "f32"):
    """The serving storage of a stored tree: matrices in ``weight_dtype``,
    :data:`F32_LEAVES` float32, and what one product contracts laid side by
    side: a KDA layer's ``w_q | w_k | w_v`` as ``w_qkv [D, 3 H dk]`` with
    the three convs' taps as ``conv_w [taps, 3 H dk]`` (the order the
    conv's state keeps), and its three narrow projections of ``u``, ``w_fa |
    w_ga | w_beta``, as ``w_low [D, 2 r + H]``; a GQA layer's ``w_q | w_k |
    w_v | w_gate`` as ``w_qkvg [D, (2 H + 2 KVH) hd]``; the shared expert's
    gate and up as ``shared_gate_up [D, 2 Fs]``. The routed experts are
    stored as the grouped product contracts them and held as they are."""
    def beside(a, *names):
        return jnp.concatenate([a.pop(n) for n in names], axis=-1)

    def layer(p, kind):
        a = dict(p)
        if kind == KDA:
            a["w_qkv"] = beside(a, "w_q", "w_k", "w_v")
            a["conv_w"] = beside(a, "conv_q", "conv_k", "conv_v")
            a["w_low"] = beside(a, "w_fa", "w_ga", "w_beta")
        else:
            a["w_qkvg"] = beside(a, "w_q", "w_k", "w_v", "w_gate")
        a["shared_gate_up"] = beside(a, "shared_gate", "shared_up")
        return a

    tree = {**params, "layers": [layer(p, kind) for p, kind in
                                 zip(params["layers"], cfg.layer_types)]}
    return hold_leaves(tree, weight_dtype, F32_LEAVES)


def _kda_inputs(conv, u, p, cfg):
    """conv ``[..., 3 H dk]`` float32 (the conv's output before its silu),
    u ``[..., D]`` (normed) -> (q, k, v ``[..., H, dk]`` as the model's
    dtype, alpha_log ``[..., H, dk]`` and beta ``[..., H]`` float32, the
    output gate ``[..., H dv]`` float32). Every product's result is used
    flat before anything is reshaped to heads (a reshape straight after a
    product is folded back into it by XLA, which then re-lays the weight:
    PERF.md section 6, PR 32)."""
    f32 = jnp.float32
    dt = cfg.dtype
    H, dk, r = cfg.linear_num_heads, cfg.linear_head_dim, cfg.gate_rank
    Ck = cfg.kda_width
    lead = conv.shape[:-1]
    qkv = jax.nn.silu(conv)

    def l2(t):
        t = t.reshape(lead + (H, dk))
        return t * jax.lax.rsqrt(jnp.sum(t * t, axis=-1, keepdims=True)
                                 + 1e-6)

    q = l2(qkv[..., :Ck]) * (dk ** -0.5)
    k = l2(qkv[..., Ck:2 * Ck])
    v = qkv[..., 2 * Ck:].reshape(lead + (H, dk))
    low = jnp.dot(u, p["w_low"].astype(dt), preferred_element_type=f32)
    fa, ga, b = low[..., :r], low[..., r:2 * r], low[..., 2 * r:]
    z = jnp.dot(fa.astype(dt), p["w_fb"].astype(dt),
                preferred_element_type=f32) + p["dt_bias"].astype(f32)
    alpha_log = -jnp.exp(p["A_log"].astype(f32))[:, None] \
        * jax.nn.softplus(z).reshape(lead + (H, dk))
    beta = jax.nn.sigmoid(b) * (2.0 if cfg.kda_allow_neg_eigval else 1.0)
    gate = jax.nn.sigmoid(jnp.dot(ga.astype(dt), p["w_gb"].astype(dt),
                                  preferred_element_type=f32))
    return (q.astype(dt), k.astype(dt), v.astype(dt), alpha_log, beta, gate)


def _kda_out(o, gate, p, cfg):
    """o ``[..., H, dv]``: the norm a head, the gate on the flat axis as its
    product came out, ``W_o``."""
    dt = cfg.dtype
    o = rms_norm(o, p["o_norm"], cfg.rms_norm_eps)
    o = o.reshape(o.shape[:-2] + (-1,)).astype(jnp.float32) * gate
    return jnp.dot(o.astype(dt), p["w_o"].astype(dt))


def kda_sequence(u, p, length, cfg, use_pallas=None):
    """The KDA mixer over a padded sequence. u ``[T, D]`` (normed), p one
    layer's held leaves, length a traced scalar. Returns ``(out [T, D],
    conv state [(taps - 1) * 3 H dk], St [H, dk, dv] float32)``: the states
    after position ``length - 1``, from an empty history."""
    f32 = jnp.float32
    K, Cc = cfg.short_conv_kernel_size, 3 * cfg.kda_width
    T = u.shape[0]
    qkv = jnp.dot(u, p["w_qkv"].astype(cfg.dtype))
    padded = jnp.concatenate([jnp.zeros((K - 1, Cc), qkv.dtype), qkv])
    # row length - (K - 1) + j of qkv is row length + j of ``padded``
    conv_state = jax.lax.dynamic_slice(padded, (length, 0), (K - 1, Cc))
    w = p["conv_w"].astype(f32)
    conv = sum(w[j][None, :] * padded[j:j + T].astype(f32)
               for j in range(K))
    q, k, v, alpha_log, beta, gate = _kda_inputs(conv, u, p, cfg)
    o, St = _gd.kda_chunked(q, k, v, alpha_log, beta, length,
                            chunk=KDA_CHUNK, use_pallas=use_pallas)
    return _kda_out(o, gate, p, cfg), conv_state.reshape(-1), St


def kda_step(u, p, conv_state, S, layer, slots, cfg, use_pallas=None):
    """The KDA mixer for one token a slot. u ``[B, D]`` (normed),
    conv_state ``[B, (taps - 1) * 3 H dk]``, S every KDA layer's stored
    state ``[Lk, slots, ...]`` float32 with ``layer`` the one to advance,
    slots ``[B]`` (negative: the lane does not ride). Returns ``(out [B,
    D], conv_state, S)``; a lane that does not ride gets both states back
    unchanged, and its row of S is neither read nor written."""
    f32 = jnp.float32
    K, Cc = cfg.short_conv_kernel_size, 3 * cfg.kda_width
    new = jnp.dot(u, p["w_qkv"].astype(cfg.dtype))
    window = jnp.concatenate([conv_state.astype(new.dtype), new], axis=1)
    w = p["conv_w"].astype(f32)
    conv = sum(w[j][None, :] * window[:, j * Cc:(j + 1) * Cc].astype(f32)
               for j in range(K))
    q, k, v, alpha_log, beta, gate = _kda_inputs(conv, u, p, cfg)
    o, S = _gd.kda_update(S, q, k, v, jnp.exp(alpha_log), beta, slots,
                          layer=layer, use_pallas=use_pallas)
    conv_state = jnp.where((slots >= 0)[:, None],
                           window[:, Cc:].astype(conv_state.dtype),
                           conv_state)
    return _kda_out(o, gate, p, cfg), conv_state, S


def _qkvg(u, p, cfg):
    """u ``[N, D]`` (normed) -> ``(q [N, H hd], k, v [N, KVH hd], gate [N,
    H hd] float32)`` flat: one product, cut before anything is reshaped."""
    flat = jnp.dot(u, p["w_qkvg"].astype(cfg.dtype))
    qw, kw = cfg.q_width, cfg.kv_width
    gate = jax.nn.sigmoid(flat[:, qw + 2 * kw:].astype(jnp.float32))
    return (flat[:, :qw], flat[:, qw:qw + kw], flat[:, qw + kw:qw + 2 * kw],
            gate)


def _gqa_out(att, gate, p, cfg):
    """att ``[N, H hd]``: the gate a value, ``W_o``."""
    dt = cfg.dtype
    return jnp.dot((att.astype(jnp.float32) * gate).astype(dt),
                   p["w_o"].astype(dt))


def _ffn_rows(u, valid, p, cfg, use_pallas):
    """``(MoE(u) [N, D], report [G + 1] int32)`` of the rows ``u``."""
    dt = cfg.dtype
    N = u.shape[0]
    experts, w = _moe.route(u, p["router"], p["router_bias"],
                            cfg.num_experts_per_tok,
                            cfg.routed_scaling_factor)
    # held pairs expected: N k G / E, half as much again before the share
    # falls back to its full-size buffer
    expected = N * cfg.num_experts_per_tok * cfg.experts_held \
        / cfg.n_routed_experts_published
    y, report = _moe.expert_share(
        u, valid, experts, w, p["w_gate_up"], p["w_down"],
        first_expert=cfg.first_expert, use_pallas=use_pallas,
        small_rows=max(N, math.ceil(1.5 * expected)))
    Fs = cfg.shared_width
    gu = jnp.dot(u, p["shared_gate_up"].astype(dt))
    a = (jax.nn.silu(gu[:, :Fs].astype(jnp.float32))
         * gu[:, Fs:].astype(jnp.float32)).astype(dt)
    return y + jnp.dot(a, p["shared_down"].astype(dt)), report


def _ffn(u, valid, p, cfg, use_pallas=None):
    """The experts' half of a layer on ``u [T, D]`` (normed), a rung's
    chunk of tokens at a time (``blocks.over_ffn_chunks``). Returns ``(ffn
    [T, D], report [G + 1] int32)``."""
    return over_ffn_chunks(
        lambda rows, ok: _ffn_rows(rows, ok, p, cfg, use_pallas),
        u, valid, cfg.experts_held)


def _sequence(held, x, length, cfg, write_rows=None, write_state=None,
              use_pallas=None, flash=None):
    """x ``[T, D]`` (embedded tokens from position 0) through the layers;
    positions ``>= length`` are padding. ``write_rows(k, v, a)`` stores GQA
    layer ``a``'s cache rows ``[T, KVH hd]``, ``write_state(conv, St, m)``
    KDA layer ``m``'s states after ``length - 1``. Returns ``(hidden [T,
    D], reports [L, G + 1])``."""
    T = x.shape[0]
    H, KVH, hd = (cfg.num_attention_heads, cfg.num_key_value_heads,
                  cfg.head_dim)
    valid = jnp.arange(T) < length
    if flash is None:
        flash = _pk._on_tpu()
    reports = []
    m = a = 0
    for p, kind in zip(held["layers"], cfg.layer_types):
        u = rms_norm(x, p["norm1"], cfg.rms_norm_eps)
        if kind == KDA:
            out, conv, St = kda_sequence(u, p, length, cfg, use_pallas)
            if write_state is not None:
                write_state(conv, St, m)
            m += 1
        else:
            q, k, v, gate = _qkvg(u, p, cfg)
            if write_rows is not None:
                write_rows(k, v, a)
            a += 1
            if flash:
                att = _pk.band_flash_attention(q[None], k[None], v[None], H,
                                               KVH)[0]
            else:
                att = band_prefill_attention(
                    q.reshape(T, H, hd), k.reshape(T, KVH, hd),
                    v.reshape(T, KVH, hd)).reshape(T, -1)
            out = _gqa_out(att, gate, p, cfg)
        h = x + out
        ffn, report = _ffn(rms_norm(h, p["norm2"], cfg.rms_norm_eps), valid,
                           p, cfg, use_pallas)
        x = h + ffn
        reports.append(report)
    return x, jnp.stack(reports)


def _logits(held, h, cfg):
    return rms_logits(h, held["final_norm"], held["lm_head"],
                      cfg.rms_norm_eps, cfg.dtype)


def forward(params, tokens, cfg: SolarOpen2Config):
    """tokens ``[T]`` -> logits ``[T, V]`` float32: the sequence forms with
    nothing cached, on the stored tree (the engine's parity surface)."""
    T = tokens.shape[0]
    pad = (-T) % KDA_CHUNK if T > KDA_CHUNK else 0
    tokens = jnp.pad(tokens, (0, pad))      # causal: padding changes nothing
    held = hold(params, cfg, "f32")
    x = held["embed"][tokens].astype(cfg.dtype)
    x, _ = _sequence(held, x, jnp.int32(T), cfg, use_pallas=False,
                     flash=False)
    return _logits(held, x[:T], cfg)


# ---------------------------------------------------------------------------
# what the serving engine asks of a model (serving/model.py)
# ---------------------------------------------------------------------------

class SolarOpen2Serving:
    """The model description ``DecodeEngine`` builds its paged prefill and
    decode programs from. The caches are ``(k pool, v pool, conv, ssm)``:
    pools ``[Lg, pages, page, KVH * hd]`` for the GQA layers alone, ``conv
    [Lk, slots, (taps - 1) * 3 H dk]`` in the cache's dtype and ``ssm [Lk,
    slots, H, dk, dv]`` float32 (a head of 128 values is whole lane tiles:
    ``fold_state`` folds none). Both programs hand back, behind ``(x,
    caches)``, the experts' report ``[L, G + 1]`` int32 (``ops/moe.py``)."""
    recurrent = True
    paged_kernel = True
    max_positions = None             # no positional table bounds max_seq

    def __init__(self, cfg: SolarOpen2Config):
        self.cfg = cfg
        self.vocab_size = cfg.vocab_size
        H, d = cfg.linear_num_heads, cfg.linear_head_dim
        f = _gd.state_fold(H, d)
        self.cache_pools = {"layers": len(cfg.gqa_layers),
                            "rows": ((cfg.kv_width,),) * 2}
        self.state_geometry = {
            "layers": cfg.num_kda_layers,
            "conv": ((cfg.short_conv_kernel_size - 1) * 3 * cfg.kda_width,),
            "ssm": (H // f, d, f * d)}

    def kernel_takes_pages(self, page_size: int, cache_dtype) -> bool:
        """Grouped heads in whole sublane tiles
        (``pallas_kernels.paged_decode_kernel``), and a page of whole
        sublane tiles of the cache's dtype."""
        c = self.cfg
        return (_pk.paged_decode_kernel(
            c.num_attention_heads, c.num_key_value_heads, c.head_dim)
            == "gqa_paged_decode_attention"
            and page_size % (32 // jnp.dtype(cache_dtype).itemsize) == 0)

    def delta_chunks(self, tokens: int) -> int:
        """Chunks a prompt of ``tokens`` costs every KDA layer."""
        return _gd.delta_chunks(tokens, KDA_CHUNK)

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
        """x ``[1, T, D]`` from position 0 (a recurrent model is never
        given a prefix); ctx: ``length``, ``table_row``, ``slot``,
        ``page_size``. Keys and values of the rung go into the slot's
        pages; the slot's state rows are overwritten with the states after
        ``length - 1``: a slot's state is born from nothing, never from
        what the rows held."""
        T, ps = x.shape[1], ctx.page_size
        pages = ctx.table_row[:T // ps]
        caches = list(caches)

        def write_rows(k, v, a):
            for i, rows in ((0, k), (1, v)):
                caches[i] = paged_page_write(
                    caches[i], rows.reshape(T // ps, ps, rows.shape[-1]),
                    pages, a)

        def write_state(conv, St, m):
            caches[2] = jax.lax.dynamic_update_slice(
                caches[2], conv.astype(caches[2].dtype)[None, None],
                (m, ctx.slot, 0))
            caches[3] = jax.lax.dynamic_update_slice(
                caches[3], _gd.fold_state(St)[None, None],
                (m, ctx.slot, 0, 0, 0))

        h, reports = _sequence(qparams, x[0], ctx.length, self.cfg,
                               write_rows, write_state)
        return h[None], tuple(caches), reports

    def decode_layers(self, qparams, x, caches, ctx):
        """x ``[B, D]``; ctx: ``positions``, ``tables`` (zeroed for lanes
        that do not ride), ``actives``, ``page_size``, ``kv_path``. A lane
        is a slot; the KDA layers advance the riders' state rows alone
        (``kda_update``), the GQA layers write this tick's row and read the
        riders' live pages through the page table (``kv_path``
        ``pallas_paged``) or gather them."""
        cfg = self.cfg
        ps, positions, tables = ctx.page_size, ctx.positions, ctx.tables
        H, KVH = cfg.num_attention_heads, cfg.num_key_value_heads
        B = x.shape[0]
        valid = ctx.actives != 0
        slots = jnp.where(valid, jnp.arange(B, dtype=jnp.int32), -1)
        kp, vp, conv, ssm = caches
        # fused_decode off the TPU drives the kernels in interpret mode
        kernels = True if ctx.kv_path == "pallas_paged" else None
        reports = []
        m = a = 0
        for p, kind in zip(qparams["layers"], cfg.layer_types):
            u = rms_norm(x, p["norm1"], cfg.rms_norm_eps)
            if kind == KDA:
                out, c, ssm = kda_step(u, p, conv[m], ssm, m, slots, cfg,
                                       kernels)
                conv = conv.at[m].set(c)
                m += 1
            else:
                q, k, v, gate = _qkvg(u, p, cfg)
                q = q.reshape(B, H, cfg.head_dim)
                if ctx.kv_path == "pallas_paged":
                    att, kp, vp = _pk.gqa_paged_decode_attention(
                        q, kp, vp, k, v, tables, positions, a, KVH)
                else:
                    phys = jnp.take_along_axis(
                        tables, (positions // ps)[:, None], axis=1)[:, 0]
                    kp = paged_cache_update(kp, k, phys, positions % ps, a)
                    vp = paged_cache_update(vp, v, phys, positions % ps, a)
                    att = sliding_decode_attention(
                        q, paged_gather(kp, tables, a),
                        paged_gather(vp, tables, a), positions, KVH, ps)
                a += 1
                out = _gqa_out(att.reshape(B, -1), gate, p, cfg)
            h = x + out
            ffn, report = _ffn(rms_norm(h, p["norm2"], cfg.rms_norm_eps),
                               valid, p, cfg, kernels)
            x = h + ffn
            reports.append(report)
        return x, (kp, vp, conv, ssm), jnp.stack(reports)
