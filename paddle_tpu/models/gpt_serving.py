"""The GPT block's model description (``serving/model.py`` states the
protocol): ``models/gpt.py``'s block over the paged pools, and the block's
serving helpers. The one description with a verify window
(``verify_layers``) and the one whose weights the int8 quantiser holds
(``serving/quant.py``, a leaf that imports nothing of ``serving/``; where
that file belongs is ROADMAP D1c's to decide).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..ops import pallas_kernels as _pk
from ..ops.decode_attention import (decode_attention, paged_cache_update,
                                    paged_gather, paged_page_write,
                                    paged_prefill_attention,
                                    window_attention)
from ..serving.quant import (QuantizedLeaf, dequantize_params,
                             quantize_params)
from . import gpt as gpt_mod

__all__ = ["GPTServing", "embed_rows", "layers_over_pools", "qkv_heads",
           "block_tail", "decode_ln"]


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

    def verify_layers(self, qparams, x, caches, ctx):
        """The verify window: x ``[B, W, D]``; ctx: ``starts`` ``[B]``,
        ``positions`` ``[B, W]``, ``tables``, ``page_size``. B*W rows
        scatter through the page tables, attention reads the gathered
        per-slot views (a lane that sits out has a zero table row)."""
        dt = self.cfg.dtype
        ln = gpt_mod._layer_norm
        ps, tables, starts = ctx.page_size, ctx.tables, ctx.starts
        B, W = ctx.positions.shape
        phys = jnp.take_along_axis(tables, ctx.positions // ps, axis=1)
        rows = ctx.positions % ps

        def body(h, layer_p, l, kp, vp):
            h1 = ln(h, layer_p["ln1_scale"], layer_p["ln1_bias"])
            q, k, v = qkv_heads(h1, layer_p, self.cfg)
            row = (B * W,) + kp.shape[3:]
            kp = paged_cache_update(
                kp, k.reshape(row), phys.reshape(-1), rows.reshape(-1), l)
            vp = paged_cache_update(
                vp, v.reshape(row), phys.reshape(-1), rows.reshape(-1), l)
            a = window_attention(q, paged_gather(kp, tables, l, k.shape[2:]),
                                 paged_gather(vp, tables, l, k.shape[2:]),
                                 starts)
            return block_tail(h, a, layer_p, dt, ln, "bw"), kp, vp

        x, kp, vp = layers_over_pools(
            body, x, caches[0], caches[1],
            dequantize_params(qparams["blocks"]))
        return x, (kp, vp)
