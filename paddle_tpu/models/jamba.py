"""Jamba: a hybrid decoder of Mamba-1 and attention layers (serving path).

The layer pattern is data: layer ``i`` is attention iff ``i %
attn_layer_period == attn_layer_offset``, every other layer a Mamba-1
mixer; every layer ends in a gated (SwiGLU) MLP. All norms are RMSNorm
with a gain; no bias but the conv's and ``dt_proj``'s; no positional
embedding of any kind; the head is the embedding, transposed. Equations as
HF ``modeling_jamba.py`` (Jamba's own inner norms on ``dt``, ``B``, ``C``
included)::

    h = x + mixer(norm_in(x));  out = h + mlp(norm_ff(h))
    mlp(u) = down(silu(gate(u)) * up(u))

Every mixer has the two forms the serving engine runs
(``serving/engine.py``): over a whole padded sequence with a ``length``
(prefill: the state it leaves is that of position ``length - 1``, whatever
the padding holds), and one token for a batch of slots with an ``active``
mask (the decode tick: a slot that does not ride keeps its state bit for
bit). What a sequence carries between calls: keys and values of the
attention layers in the paged pool, and for each Mamba layer the scan's
state ``h`` (``[d_state, d_inner]`` float32) and the last ``d_conv - 1``
rows of the conv's input (``serving/paged_kv.py``).

Parameters: Mamba layers (with their MLPs) are stacked on a leading axis
and run as one loop per run of consecutive Mamba layers, indexed in place;
the few attention layers are a list. Training is not built: the scan has
no backward pass.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp

from ..ops import selective_scan as _ss
from ..ops.decode_attention import (decode_attention, paged_cache_update,
                                    paged_gather, paged_page_write,
                                    prefill_attention)
from .blocks import (gated_mlp, hold_leaves, layer_at, mlp_shapes,
                     rms_norm)

__all__ = ["JambaConfig", "JAMBA_TINY", "init_params", "forward",
           "JambaServing"]


@dataclasses.dataclass(frozen=True)
class JambaConfig:
    """The keys of the published ``config.json`` (``model_type: jamba``)
    that shape the program; ``num_experts`` is 1 (a plain MLP a layer)."""
    vocab_size: int = 65536
    hidden_size: int = 2560
    intermediate_size: int = 8192
    num_hidden_layers: int = 28
    num_attention_heads: int = 20
    num_key_value_heads: int = 1
    head_dim: int = 128
    attn_layer_period: int = 14
    attn_layer_offset: int = 7
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_dt_rank: int = 160
    rms_norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16        # compute dtype

    @property
    def d_inner(self) -> int:
        return self.mamba_expand * self.hidden_size

    def is_attention(self, i: int) -> bool:
        return i % self.attn_layer_period == self.attn_layer_offset

    @property
    def attention_layers(self) -> Tuple[int, ...]:
        return tuple(i for i in range(self.num_hidden_layers)
                     if self.is_attention(i))

    @property
    def num_mamba_layers(self) -> int:
        return self.num_hidden_layers - len(self.attention_layers)

    def segments(self) -> List[Tuple[str, int, int]]:
        """The layers in order, as ``("mamba", first, count)`` runs of the
        stacked Mamba layers and ``("attention", index, 1)``."""
        out: List[Tuple[str, int, int]] = []
        m = a = 0
        for i in range(self.num_hidden_layers):
            if self.is_attention(i):
                out.append(("attention", a, 1))
                a += 1
            elif out and out[-1][0] == "mamba":
                out[-1] = ("mamba", out[-1][1], out[-1][2] + 1)
                m += 1
            else:
                out.append(("mamba", m, 1))
                m += 1
        return out

    def scaled(self, **kw) -> "JambaConfig":
        return dataclasses.replace(self, **kw)

    def serving_description(self) -> "JambaServing":
        """What ``DecodeEngine`` builds its programs from
        (``serving/model.py``)."""
        return JambaServing(self)


JAMBA_TINY = JambaConfig(
    vocab_size=256, hidden_size=64, intermediate_size=128,
    num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=1,
    head_dim=16, attn_layer_period=4, attn_layer_offset=2, mamba_d_state=16,
    mamba_d_conv=4, mamba_expand=2, mamba_dt_rank=4, dtype=jnp.float32)

# leaves the engine holds in float32 whatever the weights' type: gains,
# biases and the scan's own constants (5 k values a layer against 104 M)
F32_LEAVES = ("norm_in", "norm_ff", "final_norm", "conv_b", "dt_norm",
              "b_norm", "c_norm", "dt_bias", "A_log", "D")


def leaf_shapes(cfg: JambaConfig) -> Dict[str, Any]:
    """The parameter tree as shapes. ``conv_w`` is ``[d_conv, d_inner]``
    (tap ``d_conv - 1`` multiplies the current token) and ``A_log``
    ``[d_state, d_inner]``: channels on the minor axis, as the states are
    laid out."""
    D, F, Di = cfg.hidden_size, cfg.intermediate_size, cfg.d_inner
    N, K, R = cfg.mamba_d_state, cfg.mamba_d_conv, cfg.mamba_dt_rank
    nh, kvh, hd = (cfg.num_attention_heads, cfg.num_key_value_heads,
                   cfg.head_dim)
    Lm = cfg.num_mamba_layers
    mamba = {"norm_in": (Lm, D), "in_proj": (Lm, D, 2 * Di),
             "conv_w": (Lm, K, Di), "conv_b": (Lm, Di),
             "x_proj": (Lm, Di, R + 2 * N), "dt_norm": (Lm, R),
             "b_norm": (Lm, N), "c_norm": (Lm, N),
             "dt_proj": (Lm, R, Di), "dt_bias": (Lm, Di),
             "A_log": (Lm, N, Di), "D": (Lm, Di),
             "out_proj": (Lm, Di, D), **mlp_shapes(Lm, D, F)}
    attn = {"norm_in": (D,), "wq": (D, nh * hd), "wk": (D, kvh * hd),
            "wv": (D, kvh * hd), "wo": (nh * hd, D),
            **{k: s[1:] for k, s in mlp_shapes(1, D, F).items()}}
    return {"embed": (cfg.vocab_size, D), "final_norm": (D,),
            "mamba": mamba,
            "attn": [dict(attn) for _ in cfg.attention_layers]}


def init_params(key, cfg: JambaConfig) -> Dict[str, Any]:
    """Float32 parameters: projections N(0, 0.02), out-projections scaled
    by ``1 / sqrt(2 L)``, gains 1, and Mamba's published init for the
    scan (``A_log = log(1..N)`` a channel, ``dt_bias`` the inverse
    softplus of a step log-uniform in [1e-3, 1e-1], ``D`` = 1)."""
    std = 0.02
    resid = std / math.sqrt(2 * cfg.num_hidden_layers)
    shapes = leaf_shapes(cfg)
    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda s: isinstance(s, tuple))
    keys = jax.random.split(key, len(leaves))

    def draw(path, shape, k):
        name = path[-1].key
        if name in ("norm_in", "norm_ff", "final_norm", "dt_norm", "b_norm",
                    "c_norm", "D"):
            return jnp.ones(shape, jnp.float32)
        if name == "conv_b":
            return jnp.zeros(shape, jnp.float32)
        if name == "A_log":
            n = jnp.arange(1, shape[-2] + 1, dtype=jnp.float32)
            return jnp.broadcast_to(jnp.log(n)[:, None], shape)
        if name == "dt_bias":
            u = jax.random.uniform(k, shape, jnp.float32)
            dt = jnp.exp(u * (math.log(1e-1) - math.log(1e-3))
                         + math.log(1e-3))
            return dt + jnp.log(-jnp.expm1(-dt))
        s = resid if name in ("out_proj", "wo", "down") else std
        return jax.random.normal(k, shape, jnp.float32) * s

    return jax.tree_util.tree_unflatten(
        treedef, [draw(p, s, k) for (p, s), k in zip(leaves, keys)])


# ---------------------------------------------------------------------------
# the pieces of a layer
# ---------------------------------------------------------------------------

def _mlp(h, p, cfg):
    u = rms_norm(h, p["norm_ff"], cfg.rms_norm_eps)
    return h + gated_mlp(u, p["gate"], p["up"], p["down"], cfg.dtype)


def _scan_inputs(xs, p, cfg):
    """xs ``[..., Di]`` after conv and silu -> (delta float32, B, C)."""
    dt = cfg.dtype
    R, N, eps = cfg.mamba_dt_rank, cfg.mamba_d_state, cfg.rms_norm_eps
    dbc = jnp.dot(xs, p["x_proj"].astype(dt))
    dtr = rms_norm(dbc[..., :R], p["dt_norm"], eps)
    Bm = rms_norm(dbc[..., R:R + N], p["b_norm"], eps)
    Cm = rms_norm(dbc[..., R + N:], p["c_norm"], eps)
    delta = jax.nn.softplus(
        jnp.dot(dtr, p["dt_proj"].astype(dt),
                preferred_element_type=jnp.float32)
        + p["dt_bias"].astype(jnp.float32))
    return delta, Bm, Cm


def _A_t(p):
    return -jnp.exp(p["A_log"].astype(jnp.float32))


def mamba_sequence(u, p, length, cfg):
    """The Mamba mixer over a padded sequence. u ``[T, D]`` (normed), p one
    layer's leaves, length a traced scalar. Returns ``(out [T, D], conv
    state [(d_conv - 1) * Di], scan state [N, Di] float32)``: the states
    after position ``length - 1``, from an empty history."""
    dt = cfg.dtype
    Di, K = cfg.d_inner, cfg.mamba_d_conv
    T = u.shape[0]
    xz = jnp.dot(u, p["in_proj"].astype(dt))
    xs, z = xz[:, :Di], xz[:, Di:]
    padded = jnp.concatenate([jnp.zeros((K - 1, Di), xs.dtype), xs])
    # row length - (K - 1) + j of xs is row length + j of ``padded``
    conv_state = jax.lax.dynamic_slice(padded, (length, 0), (K - 1, Di))
    w = p["conv_w"].astype(jnp.float32)
    conv = sum(w[k][None, :] * padded[k:k + T].astype(jnp.float32)
               for k in range(K)) + p["conv_b"].astype(jnp.float32)
    xs = jax.nn.silu(conv).astype(dt)
    delta, Bm, Cm = _scan_inputs(xs, p, cfg)
    y, h = _ss.selective_scan(xs, delta, _A_t(p), Bm, Cm, p["D"], z, length)
    return (jnp.dot(y, p["out_proj"].astype(dt)),
            conv_state.reshape(-1), h)


def mamba_step(u, p, conv_state, h, active, cfg):
    """The Mamba mixer for one token a slot. u ``[B, D]`` (normed),
    conv_state ``[B, (d_conv - 1) * Di]``, h ``[B, N, Di]`` float32,
    active ``[B]``. Returns ``(out [B, D], conv_state, h)``; a slot with
    ``active == 0`` gets both states back unchanged."""
    dt = cfg.dtype
    Di, K = cfg.d_inner, cfg.mamba_d_conv
    xz = jnp.dot(u, p["in_proj"].astype(dt))
    x_new, z = xz[:, :Di], xz[:, Di:]
    window = jnp.concatenate([conv_state.astype(x_new.dtype), x_new],
                             axis=1)                    # [B, K * Di]
    w = p["conv_w"].astype(jnp.float32)
    conv = sum(w[k][None, :]
               * window[:, k * Di:(k + 1) * Di].astype(jnp.float32)
               for k in range(K)) + p["conv_b"].astype(jnp.float32)
    xs = jax.nn.silu(conv).astype(dt)
    delta, Bm, Cm = _scan_inputs(xs, p, cfg)
    y, h = _ss.selective_state_update(h, xs, delta, _A_t(p), Bm, Cm, p["D"],
                                      z, active)
    conv_state = jnp.where((active != 0)[:, None],
                           window[:, Di:].astype(conv_state.dtype),
                           conv_state)
    return jnp.dot(y, p["out_proj"].astype(dt)), conv_state, h


def _qkv(u, p, cfg):
    dt = cfg.dtype
    nh, kvh, hd = (cfg.num_attention_heads, cfg.num_key_value_heads,
                   cfg.head_dim)
    lead = u.shape[:-1]
    return (jnp.dot(u, p["wq"].astype(dt)).reshape(lead + (nh, hd)),
            jnp.dot(u, p["wk"].astype(dt)).reshape(lead + (kvh, hd)),
            jnp.dot(u, p["wv"].astype(dt)).reshape(lead + (kvh, hd)))


def _attn_out(a, p, cfg):
    return jnp.dot(a.reshape(a.shape[:-2] + (-1,)),
                   p["wo"].astype(cfg.dtype))


def _over_layers(cfg, params, x, carry, mamba_layer, attn_layer):
    """The layers in order: a ``fori_loop`` a run of Mamba layers with
    ``carry`` (the caches) carried in place, an attention layer between."""
    for kind, first, count in cfg.segments():
        if kind == "attention":
            x, carry = attn_layer(x, params["attn"][first], first, carry)
            continue

        def body(m, xc):
            return mamba_layer(xc[0], layer_at(params["mamba"], m), m,
                               xc[1])

        x, carry = jax.lax.fori_loop(first, first + count, body, (x, carry))
    return x, carry


def forward(params, tokens, cfg: JambaConfig):
    """tokens ``[T]`` -> logits ``[T, V]`` float32: the sequence forms with
    nothing cached (the engine's parity surface)."""
    T = tokens.shape[0]
    eps = cfg.rms_norm_eps

    def mamba_layer(x, p, m, carry):
        out, _, _ = mamba_sequence(rms_norm(x, p["norm_in"], eps), p,
                                   jnp.int32(T), cfg)
        return _mlp(x + out, p, cfg), carry

    def attn_layer(x, p, a, carry):
        q, k, v = _qkv(rms_norm(x, p["norm_in"], eps), p, cfg)
        att = prefill_attention(q[None], k[None], v[None])[0]
        return _mlp(x + _attn_out(att, p, cfg), p, cfg), carry

    x = params["embed"][tokens].astype(cfg.dtype)
    x, _ = _over_layers(cfg, params, x, (), mamba_layer, attn_layer)
    return _logits(params, x, cfg)


def _logits(params, h, cfg):
    h = rms_norm(h, params["final_norm"], cfg.rms_norm_eps)
    return jnp.dot(h, params["embed"].astype(cfg.dtype).T,
                   preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# what the serving engine asks of a model (serving/model.py)
# ---------------------------------------------------------------------------

class JambaServing:
    """The model description ``DecodeEngine`` builds its paged prefill and
    decode programs from. The caches are ``(k pool, v pool, conv, ssm)``:
    pools ``[La, pages, page, kv heads, hd]`` for the attention layers
    alone, ``conv [Lm, slots, (d_conv - 1) * Di]`` in the cache's dtype and
    ``ssm [Lm, slots, N, Di]`` float32."""
    recurrent = True
    # 20 query heads over one key/value head: a group of 20 is no whole
    # sublane tile, so ``pallas_kernels.paged_decode_kernel`` (the one rule)
    # names no kernel for it and the tick gathers
    paged_kernel = False
    max_positions = None             # no positional table bounds max_seq

    def __init__(self, cfg: JambaConfig):
        self.cfg = cfg
        self.vocab_size = cfg.vocab_size
        self.cache_pools = {
            "layers": len(cfg.attention_layers),
            "rows": ((cfg.num_key_value_heads, cfg.head_dim),) * 2}
        self.state_geometry = {
            "layers": cfg.num_mamba_layers,
            "conv": ((cfg.mamba_d_conv - 1) * cfg.d_inner,),
            "ssm": (cfg.mamba_d_state, cfg.d_inner)}

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
        rows are overwritten with the states after ``length - 1``, which
        is how a slot's state is born: from nothing, never from what the
        rows held."""
        cfg, eps = self.cfg, self.cfg.rms_norm_eps
        T, ps = x.shape[1], ctx.page_size
        pages = ctx.table_row[:T // ps]

        def mamba_layer(h, p, m, caches):
            kp, vp, conv, ssm = caches
            out, c, s = mamba_sequence(rms_norm(h, p["norm_in"], eps), p,
                                       ctx.length, cfg)
            conv = jax.lax.dynamic_update_slice(
                conv, c.astype(conv.dtype)[None, None], (m, ctx.slot, 0))
            ssm = jax.lax.dynamic_update_slice(
                ssm, s[None, None], (m, ctx.slot, 0, 0))
            return _mlp(h + out, p, cfg), (kp, vp, conv, ssm)

        def attn_layer(h, p, a, caches):
            kp, vp, conv, ssm = caches
            q, k, v = _qkv(rms_norm(h, p["norm_in"], eps), p, cfg)
            kvh, hd = k.shape[-2:]
            kp = paged_page_write(kp, k.reshape(T // ps, ps, kvh, hd),
                                  pages, a)
            vp = paged_page_write(vp, v.reshape(T // ps, ps, kvh, hd),
                                  pages, a)
            att = prefill_attention(q[None], k[None], v[None])[0]
            return (_mlp(h + _attn_out(att, p, cfg), p, cfg),
                    (kp, vp, conv, ssm))

        h, caches = _over_layers(cfg, qparams, x[0], caches, mamba_layer,
                                 attn_layer)
        return h[None], caches

    def decode_layers(self, qparams, x, caches, ctx):
        """x ``[B, D]``; ctx: ``positions``, ``tables`` (zeroed for lanes
        that do not ride), ``actives``, ``page_size``. The attention
        layers gather the slot's pages (``kv_path`` ``xla_gather``: one
        key/value head is no page shape the paged kernel takes)."""
        cfg, eps = self.cfg, self.cfg.rms_norm_eps
        ps = ctx.page_size
        phys = jnp.take_along_axis(
            ctx.tables, (ctx.positions // ps)[:, None], axis=1)[:, 0]
        rows = ctx.positions % ps

        def mamba_layer(h, p, m, caches):
            kp, vp, conv, ssm = caches
            out, c, s = mamba_step(
                rms_norm(h, p["norm_in"], eps), p,
                jax.lax.dynamic_index_in_dim(conv, m, 0, keepdims=False),
                jax.lax.dynamic_index_in_dim(ssm, m, 0, keepdims=False),
                ctx.actives, cfg)
            conv = jax.lax.dynamic_update_index_in_dim(conv, c, m, 0)
            ssm = jax.lax.dynamic_update_index_in_dim(ssm, s, m, 0)
            return _mlp(h + out, p, cfg), (kp, vp, conv, ssm)

        def attn_layer(h, p, a, caches):
            kp, vp, conv, ssm = caches
            q, k, v = _qkv(rms_norm(h, p["norm_in"], eps), p, cfg)
            kp = paged_cache_update(kp, k, phys, rows, a)
            vp = paged_cache_update(vp, v, phys, rows, a)
            att = decode_attention(q, paged_gather(kp, ctx.tables, a),
                                   paged_gather(vp, ctx.tables, a),
                                   ctx.positions + 1)
            return (_mlp(h + _attn_out(att, p, cfg), p, cfg),
                    (kp, vp, conv, ssm))

        return _over_layers(cfg, qparams, x, caches, mamba_layer, attn_layer)
