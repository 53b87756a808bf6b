"""What the decode engine asks of a model: a *model description*.

``DecodeEngine``'s paged prefill and decode programs are ``embed -> layers
-> final norm -> head`` with the caches handed in and out as the loop's
carry; everything between embedding and logits is the model's. A
description is any object with:

- ``cfg`` (its ``dtype`` is the compute dtype), ``vocab_size``,
  ``max_positions`` (``None`` where no positional table bounds ``max_seq``);
- ``cache_pools``: ``{"layers": n, "rows": (shape, ...)}``: the page pools
  it asks of ``PagedKVCache``, one ``[layers, pages, page, *shape]`` a
  row shape, the attention layers alone: ``((heads * head_dim,),) * 2``
  for keys and values (a token's heads flat in the lanes, what the
  page-table kernel reads; with ``"heads": (heads, head_dim)`` where page
  contents travel as heads: prefix store, KV hand-off), ``((width,),)``
  for one pool of latent rows (then ``latent`` is true); or, where its
  layers keep different spans of the context,
  ``{"groups": [{"name", "layers", "rows", "window"}, ...]}``:
  several page groups under the one manager, each with its pools, free
  list and a table a slot, a group with a ``window`` bounded by it
  (``serving/paged_kv.py``); the programs are then handed the groups'
  table rows side by side and ``ctx.table_widths`` says where each
  starts;
- ``recurrent`` and ``state_geometry``: whether slots carry recurrent
  state beside their pages, and the shapes of a slot's two state rows a
  recurrent layer as the model states them, ``{"layers": n, "conv":
  shape, "ssm": shape}`` (``PagedKVCache``'s ``state``: the conv's last
  inputs in the cache's dtype, the recurrence's state float32, be it a
  scan's ``[d_state, d_inner]`` or a delta rule's matrix a head);
  optionally ``delta_chunks(tokens)``: the chunks a prompt costs a
  chunked recurrence (``serve/prefill`` records it);
- ``paged_kernel``: whether ``decode_layers`` can read the pools through
  a page-table kernel (``kv_path`` ``pallas_paged``), or always gathers;
  and where it can, ``kernel_takes_pages(page_size, cache_dtype)``:
  whether Mosaic takes this engine's page shape;
- ``hold(params, weight_dtype, chunk, sharded=False)``: the serving
  storage of a float32 parameter tree, a leaf re-laid where the programs
  contract it better so (``sharded``: the engine lays the tree over a mesh
  by the stored layout's plan, so every leaf keeps its stored shape);
- ``embed(qparams, tokens, positions)``;
- ``prefill_layers(qparams, x [1, T, D], caches, ctx)`` with ``ctx``:
  ``length``, ``prefix_len``, ``table_row``, ``slot``, ``page_size``,
  ``table_widths``;
- ``decode_layers(qparams, x [B, D], caches, ctx)`` with ``ctx``:
  ``positions``, ``tables``, ``actives``, ``page_size``, ``kv_path``,
  ``fused``, ``table_widths``; both return ``(x, caches)``, the caches a tuple ``(k pool, v
  pool[, conv, ssm])`` (or ``(latent pool,)``) updated in place, and a
  model with experts a third value, its layers' report (one small int32
  array that the engine hands out with the logits);
- ``logits(qparams, h, fused=False)``: final norm and head, float32;
- ``forward(params, tokens [1, T])``: the plain full forward pass (the
  engine's parity surface).

Six descriptions exist: :class:`GPTServing` here (``models/gpt.py``'s
block), ``models/jamba.py:JambaServing``,
``models/kimi_k2.py:KimiK2Serving``,
``models/olmo_hybrid.py:OlmoHybridServing``,
``models/cohere2_moe.py:Cohere2MoeServing`` (window and global layers: two
page groups) and ``models/solar_open2.py:SolarOpen2Serving`` (the first
that is ``recurrent`` AND has experts: caches ``(k pool, v pool, conv,
ssm)`` and the report together). The engine's verify program
is still written for the GPT block (ROADMAP D2) and uses the block
helpers below directly.
"""
from __future__ import annotations

import types

import jax
import jax.numpy as jnp

from ..models import gpt as gpt_mod
from ..ops import pallas_kernels as _pk
from ..ops.decode_attention import (decode_attention, paged_cache_update,
                                    paged_gather, paged_page_write,
                                    paged_prefill_attention)
from .quant import QuantizedLeaf, dequantize_params, quantize_params

__all__ = ["describe", "GPTServing", "embed_rows", "layers_over_pools",
           "qkv_heads", "block_tail"]


def embed_rows(qparams, tokens, positions, dt):
    """``wte[tokens] + wpe[positions]`` as ``dt``, summed in float32. The
    rows are gathered from the tables as they are stored and widened
    after: widening first has XLA write the whole float32 table (412 MB
    at 50257 x 2048) on every call before it gathers a few rows of it."""
    def rows(table, idx):
        if isinstance(table, QuantizedLeaf):     # int8: chunked, flat
            return dequantize_params(table)[idx]
        return table[idx].astype(jnp.float32)

    return (rows(qparams["wte"], tokens)
            + rows(qparams["wpe"], positions)).astype(dt)


def layers_over_pools(body, x, kp, vp, blocks):
    """Run ``body(h, layer_p, l, kp, vp) -> (h, kp, vp)`` over the stacked
    ``blocks`` with both KV pools as the loop's CARRY, in their stored
    ``[L, P, page, nh * hd]`` layout, and the layer index ``l`` a loop
    variable. A scan's ``xs``/``ys`` would slice a layer out of each pool
    and re-stack it into a new buffer every iteration; a carry is updated
    in place, so the donated pools alias the outputs and a program
    touches only the rows and pages it indexes at ``[l, page, row]``."""
    def step(carry, xs):
        layer_p, l = xs
        return body(carry[0], layer_p, l, carry[1], carry[2]), None

    layers = jnp.arange(kp.shape[0], dtype=jnp.int32)
    (x, kp, vp), _ = jax.lax.scan(step, (x, kp, vp), (blocks, layers))
    return x, kp, vp


def qkv_heads(h1, layer_p, cfg):
    """``q, k, v [..., nh, hd]`` of the normed rows ``h1 [..., d]``: the
    pre-attention product of a GPT block, for decode rows, a prefill rung
    and the verify window alike. It follows the weight's own shape. Held
    as ``[d, 3·nh·hd]`` with its bias ``[3·nh·hd]``
    (:meth:`GPTServing.hold`) it is a plain ``[rows, d] x [d, n]``
    product, the contracted axis and the output axis the two the TPU
    tiles; the bias is added and q, k and v are cut out of the flat
    result BEFORE anything is reshaped to heads: a reshape straight after
    the product is folded back into it by XLA, which then wants the weight
    re-laid again (tests/test_chip_compile.py holds the compiled programs
    to it). Stored as ``[d, 3, nh, hd]`` (the tensor-parallel engine,
    whose plan shards the head axis) it is contracted as ``models/gpt.py``
    contracts it."""
    dt = cfg.dtype
    w, b = layer_p["w_qkv"].astype(dt), layer_p["b_qkv"].astype(dt)
    if w.ndim == 2:
        flat = jnp.einsum("...d,dn->...n", h1, w) + b
        heads = (*h1.shape[:-1], cfg.num_heads, cfg.head_dim)
        return tuple(x.reshape(heads) for x in jnp.split(flat, 3, axis=-1))
    qkv = jnp.einsum("...d,dcnh->...cnh", h1, w) + b
    return qkv[..., 0, :, :], qkv[..., 1, :, :], qkv[..., 2, :, :]


def block_tail(h, a, layer_p, dt, ln, bt: str):
    """Shared post-attention half of a GPT block: projection, residual,
    MLP. ``bt`` is the einsum batch prefix ("b" for decode rows,
    "bt"/"bw" for prefill/verify)."""
    o = jnp.einsum(f"{bt}nh,nhd->{bt}d", a, layer_p["w_proj"].astype(dt))
    h = h + o + layer_p["b_proj"].astype(dt)
    h2 = ln(h, layer_p["ln2_scale"], layer_p["ln2_bias"])
    f = jnp.einsum(f"{bt}d,df->{bt}f", h2, layer_p["w_fc"].astype(dt))
    f = jax.nn.gelu(f + layer_p["b_fc"].astype(dt), approximate=True)
    o2 = jnp.einsum(f"{bt}f,fd->{bt}d", f, layer_p["w_out"].astype(dt))
    return h + o2 + layer_p["b_out"].astype(dt)


def decode_ln(fused: bool):
    """The decode tick's layernorm: the fused Pallas block kernel under
    ``EngineConfig.fused_decode``, else the XLA reference."""
    if fused:
        return lambda x, scale, bias: _pk.fused_ln(x, scale, bias, eps=1e-5)
    return gpt_mod._layer_norm


class GPTServing:
    """``models/gpt.py``'s block (LayerNorm, learned positions, equal
    heads, tanh-GELU MLP, untied head) over the paged pools."""
    recurrent = False
    state_geometry = None
    paged_kernel = True

    def __init__(self, cfg: gpt_mod.GPTConfig):
        self.cfg = cfg
        self.vocab_size = cfg.vocab_size
        self.max_positions = cfg.max_seq_len
        # a token's keys (and values) of all heads flat in the lanes, a
        # head a lane tile: what the page-table kernel reads
        # (ops/pallas_kernels.py). The bodies below follow the pool's own
        # row shape: a tensor-parallel engine, whose plan splits the head
        # axis, keeps ``(nh, hd)`` rows (serving/engine.py:_init_tp).
        # ``heads``: what a row is where page contents travel (the prefix
        # store, the KV hand-off).
        self.cache_pools = {"layers": cfg.num_layers,
                            "heads": (cfg.num_heads, cfg.head_dim),
                            "rows": ((cfg.num_heads * cfg.head_dim,),) * 2}

    def kernel_takes_pages(self, page_size, cache_dtype) -> bool:
        c = self.cfg
        return _pk.paged_decode_kernel(
            c.num_heads, c.num_heads, c.head_dim) == "paged_decode_attention"

    def hold(self, params, weight_dtype: str, chunk: int, sharded=False):
        """The serving storage: ``quantize_params`` of the stored tree
        with ``w_qkv [L, d, 3, nh, hd]`` held as ``[L, d, 3·nh·hd]`` and
        ``b_qkv`` as ``[L, 3·nh·hd]``, the layout :func:`qkv_heads`
        contracts without a copy. Stored, the two axes the TPU tiles are
        ``(nh, hd)`` and every decode tick re-laid all layers' weight to
        get ``d`` into a tile (1.83 ms of an 8.3 ms tick at 24 x 2048 x
        6144, PERF.md section 6, PR 32). The reshapes are row-major: no
        element moves, so the leaves' bytes and the int8 quantiser's flat
        chunks and scales are the stored layout's. A ``sharded`` engine
        keeps the stored layout: its plan
        (``sharding/plan.py:gpt_annotations``) splits the head axis, which
        the flat axis ``3·nh·hd`` no longer shows."""
        if not sharded:
            blocks = dict(params["blocks"])
            for leaf, lead in (("w_qkv", 2), ("b_qkv", 1)):
                x = blocks[leaf]
                blocks[leaf] = x.reshape(*x.shape[:lead], -1)
            params = {**params, "blocks": blocks}
        return quantize_params(params, weight_dtype, chunk)

    def embed(self, qparams, tokens, positions):
        return embed_rows(qparams, tokens, positions, self.cfg.dtype)

    def logits(self, qparams, h, fused=False):
        dt = self.cfg.dtype
        scale, bias, head = (dequantize_params(qparams[k]) for k in
                             ("ln_f_scale", "ln_f_bias", "lm_head"))
        if fused:
            logits = _pk.fused_logits_head(h, scale, bias, head.astype(dt))
        else:
            h = decode_ln(False)(h, scale, bias)
            logits = jnp.einsum("...d,dv->...v", h, head.astype(dt))
        return logits.astype(jnp.float32)

    def forward(self, params, tokens):
        return gpt_mod.forward(params, tokens, self.cfg)

    def prefill_layers(self, qparams, x, caches, ctx):
        """tokens ``[1, T]`` are the SUFFIX after ``prefix_len`` cached
        tokens: suffix K/V scatter into the slot's own pages, attention
        runs over the gathered full view (cached prefix + suffix)."""
        dt = self.cfg.dtype
        ln = gpt_mod._layer_norm
        ps = ctx.page_size
        n_pages = x.shape[1] // ps
        suffix_pages = jax.lax.dynamic_slice(
            ctx.table_row, (ctx.prefix_len // ps,), (n_pages,))

        def body(h, layer_p, l, kp, vp):
            h1 = ln(h, layer_p["ln1_scale"], layer_p["ln1_bias"])
            q, k, v = qkv_heads(h1, layer_p, self.cfg)
            pages = (n_pages, ps) + kp.shape[3:]
            kp = paged_page_write(kp, k[0].reshape(pages), suffix_pages, l)
            vp = paged_page_write(vp, v[0].reshape(pages), suffix_pages, l)
            a = paged_prefill_attention(
                q, paged_gather(kp, ctx.table_row[None], l),
                paged_gather(vp, ctx.table_row[None], l), ctx.prefix_len)
            return block_tail(h, a, layer_p, dt, ln, "bt"), kp, vp

        x, kp, vp = layers_over_pools(
            body, x, caches[0], caches[1],
            dequantize_params(qparams["blocks"]))
        return x, (kp, vp)

    def decode_layers(self, qparams, x, caches, ctx):
        """Per-slot page tables ``[B, max_pages]`` route the one-row write
        (a scatter on the carried pool) and the attention read through the
        shared pool. Lanes whose table row is all-zero write into the
        scratch page. The read has two lowerings of one algorithm
        (``ctx.kv_path``): the Pallas kernel that fetches only the live
        pages, or gather + masked softmax over the padded view."""
        dt = self.cfg.dtype
        ln = decode_ln(ctx.fused)
        ps, tables, positions = ctx.page_size, ctx.tables, ctx.positions
        if ctx.kv_path == "pallas_paged":
            def write_and_attend(q, k, v, kp, vp, l):
                return _pk.fused_paged_decode_attention(
                    q, kp, vp, k, v, tables, positions, layer=l)
        else:
            phys = jnp.take_along_axis(
                tables, (positions // ps)[:, None], axis=1)[:, 0]
            rows = positions % ps

            def write_and_attend(q, k, v, kp, vp, l):
                row = (k.shape[0],) + kp.shape[3:]
                kp = paged_cache_update(kp, k.reshape(row), phys, rows, l)
                vp = paged_cache_update(vp, v.reshape(row), phys, rows, l)
                a = decode_attention(
                    q, paged_gather(kp, tables, l, k.shape[1:]),
                    paged_gather(vp, tables, l, k.shape[1:]), positions + 1)
                return a, kp, vp

        def body(h, layer_p, l, kp, vp):
            h1 = ln(h, layer_p["ln1_scale"], layer_p["ln1_bias"])
            q, k, v = qkv_heads(h1, layer_p, self.cfg)
            # dead lanes' all-zero tables land the write on the scratch
            # page, which no live slot reads
            a, kp, vp = write_and_attend(q, k, v, kp, vp, l)
            return block_tail(h, a, layer_p, dt, ln, "b"), kp, vp

        x, kp, vp = layers_over_pools(
            body, x, caches[0], caches[1],
            dequantize_params(qparams["blocks"]))
        return x, (kp, vp)


def describe(cfg):
    """A model description from what ``DecodeEngine`` was given: one as it
    is, a config of a known family through its description."""
    if hasattr(cfg, "prefill_layers"):
        return cfg
    if isinstance(cfg, gpt_mod.GPTConfig):
        return GPTServing(cfg)
    from ..models import jamba as jamba_mod

    if isinstance(cfg, jamba_mod.JambaConfig):
        return jamba_mod.JambaServing(cfg)
    from ..models import kimi_k2 as kimi_mod

    if isinstance(cfg, kimi_mod.KimiK2Config):
        return kimi_mod.KimiK2Serving(cfg)
    from ..models import olmo_hybrid as olmo_mod

    if isinstance(cfg, olmo_mod.OlmoHybridConfig):
        return olmo_mod.OlmoHybridServing(cfg)
    from ..models import cohere2_moe as cohere_mod

    if isinstance(cfg, cohere_mod.Cohere2MoeConfig):
        return cohere_mod.Cohere2MoeServing(cfg)
    from ..models import solar_open2 as solar_mod

    if isinstance(cfg, solar_mod.SolarOpen2Config):
        return solar_mod.SolarOpen2Serving(cfg)
    raise TypeError(
        f"DecodeEngine: no model description for {type(cfg).__name__}; "
        "pass an object with the surface serving/model.py lists")


def ctx(**kw):
    """The per-call context a program hands its model's layers."""
    return types.SimpleNamespace(**kw)
