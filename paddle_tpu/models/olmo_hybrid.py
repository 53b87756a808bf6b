"""Olmo-Hybrid: a decoder of gated delta-rule linear-attention layers and
full multi-head attention layers in a published pattern (serving path).

The pattern is data (``layer_types``: three ``linear_attention`` then one
``full_attention``, over and over). Every layer is the OLMo 2/3 block: the
mixer and the MLP read the residual stream as it is and their OUTPUTS are
normed (RMSNorm with a gain) before they are added::

    h = x + norm_mix(mixer(x));   out = h + norm_ff(mlp(h))
    mlp(u) = down(silu(gate(u)) * up(u))

after the last layer ``final_norm`` and an untied head. No bias anywhere,
no positional embedding of any kind (the source publishes ``rope_theta:
null``).

The linear mixer is Gated DeltaNet (arXiv:2412.06464), ``H`` heads with a
key width ``dk`` and a value width ``dv``::

    [q~ | k~ | v~] = silu(causal_depthwise_conv(x W_qkv))      width 4
    q = l2norm(q~) / sqrt(dk);  k = l2norm(k~)                 per head
    beta = 2 sigmoid(x W_b);  alpha = exp(-exp(A_log) softplus(x W_a + dt_bias))
    S[t] = alpha S[t-1] (I - beta k k^T) + beta v k^T;  o = S[t] q
    out = (rmsnorm(o; gain over dv) * silu(x W_g)) W_o

(``ops/gated_delta.py``; the 2 in ``beta`` is ``linear_allow_neg_eigval``).
The full layers: ``q = rmsnorm(x W_q)``, ``k = rmsnorm(x W_k)`` over the
whole projection before the heads are cut (QK-norm), ``v = x W_v``, causal
softmax over equal heads, ``W_o``.

Every mixer has the two forms the serving engine runs (``serving/
engine.py``): a whole padded sequence with a ``length`` (prefill: the
chunked delta rule and the flash kernel), and one token for a batch of
slots (the decode tick: the riders' state rows alone are read and
written). What a sequence carries between calls: keys and values of the
full layers in the paged pool, and for each linear layer the matrix state
(``H x dk x dv`` float32, stored with heads folded into whole lane tiles:
``ops/gated_delta.py:fold_state``) and the last ``conv - 1`` rows of the
conv's input. Linear layers (with their MLPs) are stacked on a leading
axis and run as one loop a run of consecutive linear layers; the full
layers are a list. Training is not built: the delta rule has no backward
pass here.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp

from ..ops import gated_delta as _gd
from ..ops import pallas_kernels as _pk
from ..ops.decode_attention import (decode_attention, paged_cache_update,
                                    paged_gather, paged_page_write,
                                    prefill_attention)
from .blocks import (gated_mlp, hold_leaves, layer_at, mlp_shapes,
                     rms_logits, rms_norm)

__all__ = ["OlmoHybridConfig", "OLMO_HYBRID_TINY", "init_params", "forward",
           "OlmoHybridServing"]

_PERIOD = ("linear_attention",) * 3 + ("full_attention",)
DELTA_CHUNK = 64


@dataclasses.dataclass(frozen=True)
class OlmoHybridConfig:
    """The keys of the published ``config.json`` (``model_type:
    olmo_hybrid``) that shape the program."""
    vocab_size: int = 100352
    hidden_size: int = 3840
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 30
    num_key_value_heads: int = 30
    head_dim: int = 128
    layer_types: Tuple[str, ...] = _PERIOD * 8
    linear_num_key_heads: int = 30
    linear_num_value_heads: int = 30
    linear_key_head_dim: int = 96
    linear_value_head_dim: int = 192
    linear_conv_kernel_dim: int = 4
    linear_allow_neg_eigval: bool = True
    rms_norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16        # compute dtype

    def __post_init__(self):
        if len(self.layer_types) != self.num_hidden_layers:
            raise ValueError("layer_types names every layer")
        if self.linear_num_key_heads != self.linear_num_value_heads:
            raise ValueError("linear layers: one key head a value head")
        if self.num_key_value_heads != self.num_attention_heads:
            raise ValueError("full layers: every head a key/value head")

    @property
    def full_layers(self) -> Tuple[int, ...]:
        return tuple(i for i, t in enumerate(self.layer_types)
                     if t == "full_attention")

    @property
    def num_linear_layers(self) -> int:
        return self.num_hidden_layers - len(self.full_layers)

    @property
    def conv_channels(self) -> int:
        """What the causal conv runs over: q~, k~ and v~ side by side."""
        return (2 * self.linear_key_head_dim
                + self.linear_value_head_dim) * self.linear_num_value_heads

    def segments(self) -> List[Tuple[str, int, int]]:
        """The layers in order, as ``("linear", first, count)`` runs of the
        stacked linear layers and ``("full", index, 1)``."""
        out: List[Tuple[str, int, int]] = []
        m = a = 0
        for t in self.layer_types:
            if t == "full_attention":
                out.append(("full", a, 1))
                a += 1
            elif out and out[-1][0] == "linear":
                out[-1] = ("linear", out[-1][1], out[-1][2] + 1)
                m += 1
            else:
                out.append(("linear", m, 1))
                m += 1
        return out

    def scaled(self, **kw) -> "OlmoHybridConfig":
        return dataclasses.replace(self, **kw)

    def serving_description(self) -> "OlmoHybridServing":
        """What ``DecodeEngine`` builds its programs from
        (``serving/model.py``)."""
        return OlmoHybridServing(self)


OLMO_HYBRID_TINY = OlmoHybridConfig(
    vocab_size=256, hidden_size=64, intermediate_size=128,
    num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=4,
    head_dim=16, layer_types=_PERIOD, linear_num_key_heads=4,
    linear_num_value_heads=4, linear_key_head_dim=16,
    linear_value_head_dim=32, dtype=jnp.float32)

# leaves the engine holds in float32 whatever the weights' type: gains, the
# conv's taps and the decay's constants (50 k values a layer of 215 M)
F32_LEAVES = ("norm_mix", "norm_ff", "final_norm", "q_norm", "k_norm",
              "o_norm", "conv_w", "A_log", "dt_bias")


def leaf_shapes(cfg: OlmoHybridConfig) -> Dict[str, Any]:
    """The parameter tree as shapes. ``w_qkv`` is the three projections
    side by side (``[q | k | v]``, the order the conv's state keeps),
    ``conv_w`` ``[taps, channels]`` (tap ``taps - 1`` multiplies the
    current token), ``w_ab`` the decay's and the step's projections ``[a |
    b]``."""
    D, F = cfg.hidden_size, cfg.intermediate_size
    H, dv = cfg.linear_num_value_heads, cfg.linear_value_head_dim
    K, Cc = cfg.linear_conv_kernel_dim, cfg.conv_channels
    nh, hd = cfg.num_attention_heads, cfg.head_dim
    Ll = cfg.num_linear_layers
    linear = {"w_qkv": (Ll, D, Cc), "conv_w": (Ll, K, Cc),
              "w_ab": (Ll, D, 2 * H), "A_log": (Ll, H), "dt_bias": (Ll, H),
              "w_g": (Ll, D, H * dv), "o_norm": (Ll, dv),
              "w_o": (Ll, H * dv, D), "norm_mix": (Ll, D),
              **mlp_shapes(Ll, D, F)}
    full = {"wq": (D, nh * hd), "wk": (D, nh * hd), "wv": (D, nh * hd),
            "wo": (nh * hd, D), "q_norm": (nh * hd,), "k_norm": (nh * hd,),
            "norm_mix": (D,),
            **{k: s[1:] for k, s in mlp_shapes(1, D, F).items()}}
    return {"embed": (cfg.vocab_size, D), "final_norm": (D,),
            "lm_head": (D, cfg.vocab_size), "linear": linear,
            "full": [dict(full) for _ in cfg.full_layers]}


def init_params(key, cfg: OlmoHybridConfig) -> Dict[str, Any]:
    """Float32 parameters: matrices N(0, 0.02), gains 1, the conv's taps
    uniform in +-1/sqrt(taps), and Gated DeltaNet's init for the decay
    (``A`` uniform in [0, 16], logged; ``dt_bias`` the inverse softplus of
    a step log-uniform in [1e-3, 1e-1])."""
    shapes = leaf_shapes(cfg)
    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda s: isinstance(s, tuple))
    keys = jax.random.split(key, len(leaves))

    def draw(path, shape, k):
        name = path[-1].key
        if name.endswith("norm") or name.startswith("norm"):
            return jnp.ones(shape, jnp.float32)
        if name == "A_log":
            return jnp.log(jax.random.uniform(k, shape, jnp.float32,
                                              1e-3, 16.0))
        if name == "dt_bias":
            u = jax.random.uniform(k, shape, jnp.float32)
            dt = jnp.exp(u * (math.log(1e-1) - math.log(1e-3))
                         + math.log(1e-3))
            return dt + jnp.log(-jnp.expm1(-dt))
        if name == "conv_w":
            bound = 1.0 / math.sqrt(shape[-2])
            return jax.random.uniform(k, shape, jnp.float32, -bound, bound)
        return jax.random.normal(k, shape, jnp.float32) * 0.02

    return jax.tree_util.tree_unflatten(
        treedef, [draw(p, s, k) for (p, s), k in zip(leaves, keys)])


# ---------------------------------------------------------------------------
# the pieces of a layer
# ---------------------------------------------------------------------------

def _mlp(h, p, cfg):
    y = gated_mlp(h, p["gate"], p["up"], p["down"], cfg.dtype)
    return h + rms_norm(y, p["norm_ff"], cfg.rms_norm_eps)


def _delta_inputs(conv, x, p, cfg):
    """conv ``[..., Cc]`` float32 (the conv's output before its silu), x
    ``[..., D]`` -> (q, k ``[..., H, dk]``, v ``[..., H, dv]`` as the
    model's dtype, alpha_log, beta ``[..., H]`` float32)."""
    f32 = jnp.float32
    dt = cfg.dtype
    H, dk, dv = (cfg.linear_num_value_heads, cfg.linear_key_head_dim,
                 cfg.linear_value_head_dim)
    lead = conv.shape[:-1]
    qkv = jax.nn.silu(conv)

    def l2(t):
        t = t.reshape(lead + (H, dk))
        return t * jax.lax.rsqrt(jnp.sum(t * t, axis=-1, keepdims=True)
                                 + 1e-6)

    q = l2(qkv[..., :H * dk]) * (dk ** -0.5)
    k = l2(qkv[..., H * dk:2 * H * dk])
    v = qkv[..., 2 * H * dk:].reshape(lead + (H, dv))
    ab = jnp.dot(x, p["w_ab"].astype(dt), preferred_element_type=f32)
    alpha_log = -jnp.exp(p["A_log"].astype(f32)) * jax.nn.softplus(
        ab[..., :H] + p["dt_bias"].astype(f32))
    beta = jax.nn.sigmoid(ab[..., H:]) * (
        2.0 if cfg.linear_allow_neg_eigval else 1.0)
    return q.astype(dt), k.astype(dt), v.astype(dt), alpha_log, beta


def _delta_out(o, x, p, cfg):
    """The gate is applied on the flat ``[.., H * dv]`` axis, as the
    product with ``w_g`` comes out: a reshape to heads straight after a
    product is folded back into it by XLA, which then re-lays the weight
    (all 12 layers' ``w_g``, 0.53 GB) on every call."""
    dt = cfg.dtype
    H, dv = cfg.linear_num_value_heads, cfg.linear_value_head_dim
    gate = jnp.dot(x, p["w_g"].astype(dt))
    o = rms_norm(o, p["o_norm"], cfg.rms_norm_eps)
    o = o.reshape(o.shape[:-2] + (H * dv,)) * jax.nn.silu(gate)
    return jnp.dot(o, p["w_o"].astype(dt))


def delta_sequence(x, p, length, cfg):
    """The linear mixer over a padded sequence. x ``[T, D]`` (the residual
    stream), p one layer's leaves, length a traced scalar. Returns ``(out
    [T, D], conv state [(taps - 1) * Cc], St [H, dk, dv] float32)``: the
    states after position ``length - 1``, from an empty history."""
    f32 = jnp.float32
    K, Cc = cfg.linear_conv_kernel_dim, cfg.conv_channels
    T = x.shape[0]
    qkv = jnp.dot(x, p["w_qkv"].astype(cfg.dtype))
    padded = jnp.concatenate([jnp.zeros((K - 1, Cc), qkv.dtype), qkv])
    # row length - (K - 1) + j of qkv is row length + j of ``padded``
    conv_state = jax.lax.dynamic_slice(padded, (length, 0), (K - 1, Cc))
    w = p["conv_w"].astype(f32)
    conv = sum(w[j][None, :] * padded[j:j + T].astype(f32)
               for j in range(K))
    q, k, v, alpha_log, beta = _delta_inputs(conv, x, p, cfg)
    o, St = _gd.gated_delta_chunked(q, k, v, alpha_log, beta, length,
                                    chunk=DELTA_CHUNK)
    return _delta_out(o, x, p, cfg), conv_state.reshape(-1), St


def delta_step(x, p, conv_state, S, layer, slots, cfg):
    """The linear mixer for one token a slot. x ``[B, D]``, conv_state
    ``[B, (taps - 1) * Cc]``, S every linear layer's stored state
    ``[Ll, slots, ...]`` float32 with ``layer`` the one to advance, slots
    ``[B]`` (negative: the lane does not ride). Returns ``(out [B, D],
    conv_state, S)``; a lane that does not ride gets both states back
    unchanged, and its row of S is neither read nor written."""
    f32 = jnp.float32
    K, Cc = cfg.linear_conv_kernel_dim, cfg.conv_channels
    new = jnp.dot(x, p["w_qkv"].astype(cfg.dtype))
    window = jnp.concatenate([conv_state.astype(new.dtype), new], axis=1)
    w = p["conv_w"].astype(f32)
    conv = sum(w[j][None, :] * window[:, j * Cc:(j + 1) * Cc].astype(f32)
               for j in range(K))
    q, k, v, alpha_log, beta = _delta_inputs(conv, x, p, cfg)
    o, S = _gd.gated_delta_update(S, q, k, v, jnp.exp(alpha_log), beta,
                                  slots, layer=layer)
    conv_state = jnp.where((slots >= 0)[:, None],
                           window[:, Cc:].astype(conv_state.dtype),
                           conv_state)
    return _delta_out(o, x, p, cfg), conv_state, S


def _qkv(x, p, cfg):
    """Full layer: QK-norm over the whole projections, then heads."""
    dt, eps = cfg.dtype, cfg.rms_norm_eps
    heads = x.shape[:-1] + (cfg.num_attention_heads, cfg.head_dim)
    q = rms_norm(jnp.dot(x, p["wq"].astype(dt)), p["q_norm"], eps)
    k = rms_norm(jnp.dot(x, p["wk"].astype(dt)), p["k_norm"], eps)
    v = jnp.dot(x, p["wv"].astype(dt))
    return q.reshape(heads), k.reshape(heads), v.reshape(heads)


def _sequence_attention(q, k, v):
    """Causal attention over a rung ``[T, nh, hd]``: the flash kernel on a
    TPU (no ``[nh, T, T]`` scores: 2 GB at T = 4096), the plain form off
    it."""
    T = q.shape[0]
    if _pk._on_tpu() and T % 128 == 0:
        block = next(b for b in (512, 256, 128) if T % b == 0)
        return _pk.flash_attention(q[None], k[None], v[None], causal=True,
                                   block_q=block, block_k=block)[0]
    return prefill_attention(q[None], k[None], v[None])[0]


def _mix(x, out, p, cfg):
    return x + rms_norm(out, p["norm_mix"], cfg.rms_norm_eps)


def _attn_out(a, p, cfg):
    return jnp.dot(a.reshape(a.shape[:-2] + (-1,)),
                   p["wo"].astype(cfg.dtype))


def _over_layers(cfg, params, x, carry, linear_layer, full_layer):
    """The layers in order: a ``fori_loop`` a run of linear layers with
    ``carry`` (the caches) carried in place, a full layer between."""
    for kind, first, count in cfg.segments():
        if kind == "full":
            x, carry = full_layer(x, params["full"][first], first, carry)
            continue

        def body(m, xc):
            return linear_layer(xc[0], layer_at(params["linear"], m), m,
                                xc[1])

        x, carry = jax.lax.fori_loop(first, first + count, body, (x, carry))
    return x, carry


def _logits(params, h, cfg):
    return rms_logits(h, params["final_norm"], params["lm_head"],
                      cfg.rms_norm_eps, cfg.dtype)


def forward(params, tokens, cfg: OlmoHybridConfig):
    """tokens ``[T]`` -> logits ``[T, V]`` float32: the sequence forms with
    nothing cached (the engine's parity surface)."""
    T = tokens.shape[0]
    pad = (-T) % DELTA_CHUNK if T > DELTA_CHUNK else 0
    tokens = jnp.pad(tokens, (0, pad))      # causal: padding changes nothing

    def linear_layer(x, p, m, carry):
        out, _, _ = delta_sequence(x, p, jnp.int32(T), cfg)
        return _mlp(_mix(x, out, p, cfg), p, cfg), carry

    def full_layer(x, p, a, carry):
        att = _sequence_attention(*_qkv(x, p, cfg))
        return _mlp(_mix(x, _attn_out(att, p, cfg), p, cfg), p, cfg), carry

    x = params["embed"][tokens].astype(cfg.dtype)
    x, _ = _over_layers(cfg, params, x, (), linear_layer, full_layer)
    return _logits(params, x[:T], cfg)


# ---------------------------------------------------------------------------
# what the serving engine asks of a model (serving/model.py)
# ---------------------------------------------------------------------------

class OlmoHybridServing:
    """The model description ``DecodeEngine`` builds its paged prefill and
    decode programs from. The caches are ``(k pool, v pool, conv, ssm)``:
    pools ``[Lf, pages, page, nh * hd]`` for the full layers alone (a
    token's heads flat in the lanes: 30 heads are 3,840 lanes, no padded
    row), ``conv [Ll, slots, (taps - 1) * Cc]`` in the cache's dtype and
    ``ssm [Ll, slots, H / f, dk, f * dv]`` float32 (the matrix states,
    ``f`` heads folded into whole lane tiles)."""
    recurrent = True
    paged_kernel = True
    max_positions = None             # no positional table bounds max_seq

    def __init__(self, cfg: OlmoHybridConfig):
        self.cfg = cfg
        self.vocab_size = cfg.vocab_size
        H, dk, dv = (cfg.linear_num_value_heads, cfg.linear_key_head_dim,
                     cfg.linear_value_head_dim)
        f = _gd.state_fold(H, dv)
        self.cache_pools = {
            "layers": len(cfg.full_layers),
            "rows": ((cfg.num_key_value_heads * cfg.head_dim,),) * 2}
        self.state_geometry = {
            "layers": cfg.num_linear_layers,
            "conv": ((cfg.linear_conv_kernel_dim - 1) * cfg.conv_channels,),
            "ssm": (H // f, dk, f * dv)}

    def kernel_takes_pages(self, page_size, cache_dtype) -> bool:
        return _pk.paged_decode_tiles(self.cfg.num_key_value_heads,
                                      self.cfg.head_dim)

    def delta_chunks(self, tokens: int) -> int:
        """Chunks a prompt of ``tokens`` costs every linear layer."""
        return _gd.delta_chunks(tokens, DELTA_CHUNK)

    def hold(self, params, weight_dtype: str, chunk: int, sharded=False):
        """The serving storage: matrices in ``weight_dtype``, the leaves of
        ``F32_LEAVES`` float32, every leaf in its stored shape. (int8 and
        a ``sharded`` engine are refused where the engine is built.)"""
        return hold_leaves(params, weight_dtype, F32_LEAVES)

    def embed(self, qparams, tokens, positions):
        return qparams["embed"][tokens].astype(self.cfg.dtype)

    def logits(self, qparams, h, fused=False):
        return _logits(qparams, h, self.cfg)

    def forward(self, params, tokens):
        return forward(params, tokens[0], self.cfg)[None]

    def prefill_layers(self, qparams, x, caches, ctx):
        """x ``[1, T, D]``; ctx: ``length``, ``table_row``, ``slot``,
        ``page_size`` (a recurrent model is never given a prefix). Keys
        and values of the rung go into the slot's pages; the slot's state
        rows are overwritten with the states after ``length - 1``: a
        slot's state is born from nothing, never from what the rows
        held."""
        cfg = self.cfg
        T, ps = x.shape[1], ctx.page_size
        pages = ctx.table_row[:T // ps]

        def linear_layer(h, p, m, caches):
            kp, vp, conv, ssm = caches
            out, c, St = delta_sequence(h, p, ctx.length, cfg)
            conv = jax.lax.dynamic_update_slice(
                conv, c.astype(conv.dtype)[None, None], (m, ctx.slot, 0))
            ssm = jax.lax.dynamic_update_slice(
                ssm, _gd.fold_state(St)[None, None], (m, ctx.slot, 0, 0, 0))
            return _mlp(_mix(h, out, p, cfg), p, cfg), (kp, vp, conv, ssm)

        def full_layer(h, p, a, caches):
            kp, vp, conv, ssm = caches
            q, k, v = _qkv(h, p, cfg)
            rows = (T // ps, ps) + kp.shape[3:]
            kp = paged_page_write(kp, k.reshape(rows), pages, a)
            vp = paged_page_write(vp, v.reshape(rows), pages, a)
            att = _sequence_attention(q, k, v)
            return (_mlp(_mix(h, _attn_out(att, p, cfg), p, cfg), p, cfg),
                    (kp, vp, conv, ssm))

        h, caches = _over_layers(cfg, qparams, x[0], caches, linear_layer,
                                 full_layer)
        return h[None], caches

    def decode_layers(self, qparams, x, caches, ctx):
        """x ``[B, D]``; ctx: ``positions``, ``tables`` (zeroed for lanes
        that do not ride), ``actives``, ``page_size``, ``kv_path``. A lane
        is a slot; the linear layers advance the riders' state rows alone
        (``gated_delta_update``), the full layers read the riders' live
        pages through the page table (``kv_path`` ``pallas_paged``) or
        gather them."""
        cfg = self.cfg
        ps = ctx.page_size
        phys = jnp.take_along_axis(
            ctx.tables, (ctx.positions // ps)[:, None], axis=1)[:, 0]
        rows = ctx.positions % ps
        slots = jnp.where(ctx.actives != 0,
                          jnp.arange(x.shape[0], dtype=jnp.int32), -1)

        def linear_layer(h, p, m, caches):
            kp, vp, conv, ssm = caches
            out, c, ssm = delta_step(
                h, p, jax.lax.dynamic_index_in_dim(conv, m, 0,
                                                   keepdims=False),
                ssm, m, slots, cfg)
            conv = jax.lax.dynamic_update_index_in_dim(conv, c, m, 0)
            return _mlp(_mix(h, out, p, cfg), p, cfg), (kp, vp, conv, ssm)

        def full_layer(h, p, a, caches):
            kp, vp, conv, ssm = caches
            q, k, v = _qkv(h, p, cfg)
            if ctx.kv_path == "pallas_paged":
                att, kp, vp = _pk.fused_paged_decode_attention(
                    q, kp, vp, k, v, ctx.tables, ctx.positions, layer=a)
            else:
                row = (k.shape[0],) + kp.shape[3:]
                kp = paged_cache_update(kp, k.reshape(row), phys, rows, a)
                vp = paged_cache_update(vp, v.reshape(row), phys, rows, a)
                att = decode_attention(
                    q, paged_gather(kp, ctx.tables, a, k.shape[1:]),
                    paged_gather(vp, ctx.tables, a, k.shape[1:]),
                    ctx.positions + 1)
            return (_mlp(_mix(h, _attn_out(att, p, cfg), p, cfg), p, cfg),
                    (kp, vp, conv, ssm))

        return _over_layers(cfg, qparams, x, caches, linear_layer,
                            full_layer)
