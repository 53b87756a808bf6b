"""Hand-written TPU Pallas kernels for the hot ops XLA fusion can't cover.

The reference reaches for native codegen in exactly these situations —
`operators/jit/` (xbyak CPU JIT) and `framework/ir/fusion_group/` (NVRTC
runtime CUDA codegen) generate fused kernels at runtime. On TPU the
equivalent is Pallas (Mosaic): VMEM-tiled kernels feeding the MXU.

Currently:
  * ``flash_attention`` — FlashAttention-2 style causal attention
    (tiled online softmax, O(T) memory instead of the O(T^2) logits
    materialization of the plain XLA path in models/gpt.py), with a
    hand-written backward (custom_vjp) in the same tiling.
  * ``chunked_lm_loss`` — fused vocab-projection + cross-entropy that
    blocks over the row (batch*time) and vocab axes: online-logsumexp
    forward (Pallas-tiled on TPU, pure-lax scan elsewhere) and a chunked
    custom_vjp backward, so the full-precision ``[rows, V]`` logits never
    hit HBM. ``chunked_softmax_ce_from_logits`` is the same trick applied
    to already-materialized logits (the ``softmax_with_cross_entropy``
    op's ``vocab_chunk`` lowering variant): the f32 log-softmax
    intermediates stay chunk-sized.

Layout convention: the public API takes ``[B, T, nh, hd]`` (the GPT model's
activation layout); kernels run on ``[BH, T, hd]`` with a 3-D grid
``(BH, q_blocks, kv_blocks)`` whose last axis is sequential ("arbitrary"),
so the running max / sum / accumulator live in VMEM scratch across kv steps.
The softmax statistics are kept lane-replicated ``(block_q, 128)`` — the
native TPU layout for per-row scalars.

Tests run the same kernels in interpreter mode on CPU (tests/test_pallas.py);
on TPU they compile via Mosaic.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NUM_LANES = 128
_NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)

_CompilerParams = pltpu.CompilerParams


def _on_tpu() -> bool:
    """The module's one backend question: Mosaic kernels (and the Pallas
    auto-switches below) are chosen when the process's backend is a TPU.
    A compile for a described, unattached chip runs under the CPU backend,
    so tests/test_chip_compile.py patches this one function to True."""
    return jax.default_backend() == "tpu"


def _interpret() -> bool:
    return not _on_tpu()


def _bcast_lanes(x, n):
    """``x`` is (rows, 128) lane-replicated; return (rows, n) with the same
    per-row value in every lane."""
    if n == NUM_LANES:
        return x
    if n < NUM_LANES:
        return x[:, :n]
    rep, rem = divmod(n, NUM_LANES)
    if rem:
        raise ValueError(f"width {n} not a multiple of {NUM_LANES}")
    return jnp.tile(x, (1, rep))


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _fwd_kernel(*refs, sm_scale, causal, block_q, block_k, num_k, has_bias):
    if has_bias:
        (q_ref, k_ref, v_ref, bias_ref, o_ref, lse_ref,
         m_scr, l_scr, acc_scr) = refs
    else:
        q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr = refs
        bias_ref = None
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full(m_scr.shape, -jnp.inf, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    # Causal: kv block strictly above the diagonal band contributes nothing.
    needed = True
    if causal:
        needed = ki * block_k <= qi * block_q + block_q - 1

    @pl.when(needed)
    def _compute():
        q = q_ref[0]                         # (block_q, hd)
        k = k_ref[0]                         # (block_k, hd)
        v = v_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale  # (bq, bk)
        if bias_ref is not None:
            bias = bias_ref[0].astype(jnp.float32)   # (bq or 1, bk)
            s = s + jnp.broadcast_to(bias, s.shape)
        if causal:
            qpos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            kpos = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(qpos >= kpos, s, _NEG_INF)

        m_prev = m_scr[...]                             # (bq, 128) replicated
        l_prev = l_scr[...]
        m_curr = jnp.max(s, axis=1)[:, None]            # (bq, 1)
        m_next = jnp.maximum(m_prev, m_curr)            # (bq, 128) replicated
        alpha = jnp.exp(m_prev - m_next)                # (bq, 128)
        p = jnp.exp(s - _bcast_lanes(m_next, block_k))  # (bq, bk)
        l_next = alpha * l_prev + jnp.sum(p, axis=1)[:, None]
        m_scr[...] = m_next
        l_scr[...] = l_next

        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)         # (bq, hd)
        hd = acc_scr.shape[-1]
        acc_scr[...] = acc_scr[...] * _bcast_lanes(alpha, hd) + pv

    @pl.when(ki == num_k - 1)
    def _finish():
        hd = acc_scr.shape[-1]
        l = l_scr[...]
        l_inv = jnp.where(l == 0.0, 1.0, 1.0 / l)
        o_ref[0] = (acc_scr[...] * _bcast_lanes(l_inv, hd)).astype(o_ref.dtype)
        lse_ref[0] = m_scr[...] + jnp.log(jnp.where(l == 0.0, 1.0, l))


def _bias_spec(bias, bh, block_q, block_k):
    """BlockSpec for an additive bias [BB, SQ, Sk] where BB divides bh
    (per-head vs per-batch broadcast) and SQ is 1 (row-broadcast padding
    mask) or the full query length."""
    bb, sq, _sk = bias.shape
    heads_per = bh // bb
    q_bcast = sq == 1
    bq_blk = 1 if q_bcast else block_q

    def idx(b, qi, ki):
        return (b // heads_per, 0 if q_bcast else qi, ki)

    return pl.BlockSpec((1, bq_blk, block_k), idx)


def _fwd(q, k, v, bias, causal, sm_scale, block_q, block_k):
    bh, t, hd = q.shape
    tk = k.shape[1]
    block_q = min(block_q, t)
    block_k = min(block_k, tk)
    if t % block_q or tk % block_k:
        raise ValueError(f"seq lens ({t},{tk}) must divide blocks ({block_q},{block_k})")
    nq, nk = t // block_q, tk // block_k

    grid = (bh, nq, nk)
    kern = functools.partial(
        _fwd_kernel, sm_scale=sm_scale, causal=causal,
        block_q=block_q, block_k=block_k, num_k=nk,
        has_bias=bias is not None)
    in_specs = [
        pl.BlockSpec((1, block_q, hd), lambda b, qi, ki: (b, qi, 0)),
        pl.BlockSpec((1, block_k, hd), lambda b, qi, ki: (b, ki, 0)),
        pl.BlockSpec((1, block_k, hd), lambda b, qi, ki: (b, ki, 0)),
    ]
    args = [q, k, v]
    if bias is not None:
        in_specs.append(_bias_spec(bias, bh, block_q, block_k))
        args.append(bias)
    with jax.named_scope("flash_attention"):
        o, lse = pl.pallas_call(
            kern,
            grid=grid,
            in_specs=in_specs,
            out_specs=[
                pl.BlockSpec((1, block_q, hd), lambda b, qi, ki: (b, qi, 0)),
                pl.BlockSpec((1, block_q, NUM_LANES), lambda b, qi, ki: (b, qi, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((bh, t, hd), q.dtype),
                jax.ShapeDtypeStruct((bh, t, NUM_LANES), jnp.float32),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_q, NUM_LANES), jnp.float32),
                pltpu.VMEM((block_q, NUM_LANES), jnp.float32),
                pltpu.VMEM((block_q, hd), jnp.float32),
            ],
            compiler_params=_CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")),
            interpret=_interpret(),
            name="flash_fwd",
        )(*args)
    return o, lse


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------


def _bwd_dq_kernel(*refs, sm_scale, causal, block_q, block_k, num_k,
                   has_bias):
    if has_bias:
        (q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref, bias_ref,
         dq_ref, dq_scr) = refs
    else:
        q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref, dq_ref, dq_scr = refs
        bias_ref = None
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dq_scr[...] = jnp.zeros(dq_scr.shape, jnp.float32)

    needed = True
    if causal:
        needed = ki * block_k <= qi * block_q + block_q - 1

    @pl.when(needed)
    def _compute():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        o = o_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0]                                 # (bq, 128) replicated

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale
        if bias_ref is not None:
            s = s + jnp.broadcast_to(
                bias_ref[0].astype(jnp.float32), s.shape)
        if causal:
            qpos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            kpos = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(qpos >= kpos, s, _NEG_INF)
        p = jnp.exp(s - _bcast_lanes(lse, block_k))      # (bq, bk)

        dp = jax.lax.dot_general(
            do, v.astype(jnp.float32), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)          # (bq, bk)
        di = jnp.sum(do * o, axis=1)[:, None]            # (bq, 1)
        ds = p * (dp - di) * sm_scale                    # (bq, bk)
        dq_scr[...] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ki == num_k - 1)
    def _finish():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel(*refs, sm_scale, causal, block_q, block_k, num_q,
                    has_bias):
    if has_bias:
        (q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref, bias_ref,
         dk_ref, dv_ref, dk_scr, dv_scr) = refs
    else:
        (q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref,
         dk_ref, dv_ref, dk_scr, dv_scr) = refs
        bias_ref = None
    ki = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_scr[...] = jnp.zeros(dk_scr.shape, jnp.float32)
        dv_scr[...] = jnp.zeros(dv_scr.shape, jnp.float32)

    needed = True
    if causal:
        needed = qi * block_q + block_q - 1 >= ki * block_k

    @pl.when(needed)
    def _compute():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        o = o_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0]

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale
        if bias_ref is not None:
            s = s + jnp.broadcast_to(
                bias_ref[0].astype(jnp.float32), s.shape)
        if causal:
            qpos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            kpos = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(qpos >= kpos, s, _NEG_INF)
        p = jnp.exp(s - _bcast_lanes(lse, block_k))      # (bq, bk)

        # dV += P^T dO
        dv_scr[...] += jax.lax.dot_general(
            p.astype(do_ref.dtype).astype(jnp.float32), do,
            (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)          # (bk, hd)
        dp = jax.lax.dot_general(
            do, v.astype(jnp.float32), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)          # (bq, bk)
        di = jnp.sum(do * o, axis=1)[:, None]
        ds = p * (dp - di) * sm_scale                    # (bq, bk)
        # dK += dS^T Q
        dk_scr[...] += jax.lax.dot_general(
            ds, q.astype(jnp.float32), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(qi == num_q - 1)
    def _finish():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _bwd(q, k, v, o, lse, do, bias, causal, sm_scale, block_q, block_k):
    bh, t, hd = q.shape
    tk = k.shape[1]
    block_q = min(block_q, t)
    block_k = min(block_k, tk)
    nq, nk = t // block_q, tk // block_k
    has_bias = bias is not None

    dq_kern = functools.partial(
        _bwd_dq_kernel, sm_scale=sm_scale, causal=causal,
        block_q=block_q, block_k=block_k, num_k=nk, has_bias=has_bias)
    in_specs = [
        pl.BlockSpec((1, block_q, hd), lambda b, qi, ki: (b, qi, 0)),
        pl.BlockSpec((1, block_k, hd), lambda b, qi, ki: (b, ki, 0)),
        pl.BlockSpec((1, block_k, hd), lambda b, qi, ki: (b, ki, 0)),
        pl.BlockSpec((1, block_q, hd), lambda b, qi, ki: (b, qi, 0)),
        pl.BlockSpec((1, block_q, hd), lambda b, qi, ki: (b, qi, 0)),
        pl.BlockSpec((1, block_q, NUM_LANES), lambda b, qi, ki: (b, qi, 0)),
    ]
    args = [q, k, v, o, do, lse]
    if has_bias:
        in_specs.append(_bias_spec(bias, bh, block_q, block_k))
        args.append(bias)
    with jax.named_scope("flash_attention"):
        dq = pl.pallas_call(
            dq_kern,
            grid=(bh, nq, nk),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, block_q, hd), lambda b, qi, ki: (b, qi, 0)),
            out_shape=jax.ShapeDtypeStruct((bh, t, hd), q.dtype),
            scratch_shapes=[pltpu.VMEM((block_q, hd), jnp.float32)],
            compiler_params=_CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")),
            interpret=_interpret(),
            name="flash_bwd_dq",
        )(*args)

    dkv_kern = functools.partial(
        _bwd_dkv_kernel, sm_scale=sm_scale, causal=causal,
        block_q=block_q, block_k=block_k, num_q=nq, has_bias=has_bias)
    in_specs2 = [
        pl.BlockSpec((1, block_q, hd), lambda b, ki, qi: (b, qi, 0)),
        pl.BlockSpec((1, block_k, hd), lambda b, ki, qi: (b, ki, 0)),
        pl.BlockSpec((1, block_k, hd), lambda b, ki, qi: (b, ki, 0)),
        pl.BlockSpec((1, block_q, hd), lambda b, ki, qi: (b, qi, 0)),
        pl.BlockSpec((1, block_q, hd), lambda b, ki, qi: (b, qi, 0)),
        pl.BlockSpec((1, block_q, NUM_LANES), lambda b, ki, qi: (b, qi, 0)),
    ]
    args2 = [q, k, v, o, do, lse]
    if has_bias:
        bspec = _bias_spec(bias, bh, block_q, block_k)

        def idx2(b, ki, qi, _inner=bspec.index_map):
            return _inner(b, qi, ki)

        in_specs2.append(pl.BlockSpec(bspec.block_shape, idx2))
        args2.append(bias)
    with jax.named_scope("flash_attention"):
        dk, dv = pl.pallas_call(
            dkv_kern,
            grid=(bh, nk, nq),
            in_specs=in_specs2,
            out_specs=[
                pl.BlockSpec((1, block_k, hd), lambda b, ki, qi: (b, ki, 0)),
                pl.BlockSpec((1, block_k, hd), lambda b, ki, qi: (b, ki, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((bh, tk, hd), k.dtype),
                jax.ShapeDtypeStruct((bh, tk, hd), v.dtype),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_k, hd), jnp.float32),
                pltpu.VMEM((block_k, hd), jnp.float32),
            ],
            compiler_params=_CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")),
            interpret=_interpret(),
            name="flash_bwd_dkv",
        )(*args2)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# public API (custom_vjp over [BH, T, hd])
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _flash(q, k, v, bias, causal, sm_scale, block_q, block_k):
    o, _ = _fwd(q, k, v, bias, causal, sm_scale, block_q, block_k)
    return o


def _flash_fwd(q, k, v, bias, causal, sm_scale, block_q, block_k):
    o, lse = _fwd(q, k, v, bias, causal, sm_scale, block_q, block_k)
    # lse is lane-replicated (bh, t, 128): save ONE lane as the residual —
    # the full tensor is ~hd/1 x larger than o itself in f32 and would
    # dominate live activation memory in no-remat training.
    return o, (q, k, v, o, lse[..., :1], bias)


def _flash_bwd(causal, sm_scale, block_q, block_k, res, do):
    q, k, v, o, lse, bias = res
    lse = jnp.broadcast_to(lse, lse.shape[:-1] + (NUM_LANES,))
    dq, dk, dv = _bwd(q, k, v, o, lse, do, bias, causal, sm_scale,
                      block_q, block_k)
    # bias is an additive mask, not a trainable tensor — zero cotangent
    # (the reference's BiasQK likewise carries no grad)
    dbias = None if bias is None else jnp.zeros_like(bias)
    return dq, dk, dv, dbias


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v, causal: bool = True,
                    sm_scale: Optional[float] = None,
                    block_q: int = 512, block_k: int = 512,
                    bias=None, window: Optional[int] = None):
    """FlashAttention-2 on TPU (Pallas). q,k,v: [B, T, nh, hd] -> [B, T, nh, hd].

    Grouped-query heads (k, v ``[B, T, kvh, hd]``, ``kvh`` dividing ``nh``)
    and a sliding ``window`` (query ``i`` sees keys ``i - window < j <=
    i``) go to :func:`band_flash_attention`: forward only, causal, no
    bias. A call with equal heads and no window is the kernel below,
    untouched.

    Replaces the O(T^2)-memory XLA attention in models/gpt.py when
    ``GPTConfig.use_flash``; differentiable via hand-written Pallas backward.

    ``bias`` is an optional additive logit bias (padding / attention
    mask): [B, nh, T, Tk], [B, 1, T, Tk], or the O(B*T)-memory padding
    form [B, 1, 1, Tk] — broadcast INSIDE the kernel, so a row mask never
    materializes the [T, Tk] square.

    NOT differentiable w.r.t. ``bias``: it is treated as a constant mask
    (the cotangent is zero, matching the reference's BiasQK semantics).
    A trainable bias (learned relative position / ALiBi) must use the
    plain XLA attention path instead.
    """
    b, t, nh, hd = q.shape
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(hd)
    if window is not None or k.shape[2] != nh:
        if bias is not None or not causal:
            raise ValueError("flash_attention: grouped heads and a window "
                             "are causal and take no bias")
        return band_flash_attention(
            q.reshape(b, t, nh * hd), k.reshape(b, t, -1),
            v.reshape(b, t, -1), nh, k.shape[2], window=window,
            sm_scale=sm_scale, block_q=block_q, block_k=block_k
        ).reshape(b, t, nh, hd)

    def to_bh(x):
        return x.transpose(0, 2, 1, 3).reshape(b * nh, x.shape[1], hd)

    def from_bh(x):
        return x.reshape(b, nh, t, hd).transpose(0, 2, 1, 3)

    bias_bh = None
    if bias is not None:
        bb, bn, bq_, bk_ = bias.shape
        if bn == nh:                       # per-head: fold into BH
            bias_bh = bias.reshape(b * nh, bq_, bk_)
        elif bn == 1:                      # per-batch: kernel broadcasts
            bias_bh = bias.reshape(b, bq_, bk_)
        else:
            raise ValueError(f"bias head dim {bn} must be 1 or {nh}")

    o = _flash(to_bh(q), to_bh(k), to_bh(v), bias_bh, causal, sm_scale,
               block_q, block_k)
    return from_bh(o)


# ---------------------------------------------------------------------------
# causal attention inside a band, grouped heads (forward only: serving)
# ---------------------------------------------------------------------------
#
# The rung of a model with sliding-window layers: query ``i`` sees keys ``i -
# window < j <= i``. The arrays stay as the projections leave them, ``[B, T,
# heads * hd]`` flat: a block is ``(block, hd)`` lanes of one head, so no
# transposed copy of q, k, v or the output is made (half a gigabyte each at
# 16,384 tokens x 128 heads). Query head ``h`` reads key/value head ``h //
# (nh / kvh)``. The last grid axis walks only the key blocks that meet the
# band of the query block: ``visits`` of them from the band's first, a
# block index past the diagonal clamped onto it (no copy is made for a
# repeated index) and its step skipped; a block on the band's edge is
# masked.


def band_blocks(t: int, block_q: int, block_k: int,
                window: Optional[int]) -> int:
    """Key blocks a query block visits at most."""
    nk = t // block_k
    if window is None:
        return nk
    return min(nk, (block_q + window - 2) // block_k + 2)


def _band_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                 sm_scale, block_q, block_k, window, visits):
    qi = pl.program_id(2)
    kj = pl.program_id(3)
    lo, hi = _band_range(qi, block_q, block_k, window)
    ki = lo + kj

    @pl.when(kj == 0)
    def _init():
        m_scr[...] = jnp.full(m_scr.shape, -jnp.inf, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    @pl.when(ki <= hi)
    def _compute():
        s = jax.lax.dot_general(
            q_ref[...], k_ref[...], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale  # (bq, bk)
        qpos = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        kpos = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        seen = qpos >= kpos
        if window is not None:
            seen &= qpos - kpos < window
        s = jnp.where(seen, s, _NEG_INF)
        m_prev = m_scr[...]                             # (bq, 128) replicated
        m_next = jnp.maximum(m_prev, jnp.max(s, axis=1)[:, None])
        alpha = jnp.exp(m_prev - m_next)
        # a row none of whose keys is in this block keeps p at 0
        p = jnp.where(seen, jnp.exp(s - _bcast_lanes(m_next, block_k)), 0.0)
        l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=1)[:, None]
        m_scr[...] = m_next
        hd = acc_scr.shape[-1]
        acc_scr[...] = acc_scr[...] * _bcast_lanes(alpha, hd) + jnp.dot(
            p.astype(v_ref.dtype), v_ref[...],
            preferred_element_type=jnp.float32)

    @pl.when(kj == visits - 1)
    def _finish():
        hd = acc_scr.shape[-1]
        l = l_scr[...]
        l_inv = jnp.where(l == 0.0, 1.0, 1.0 / l)
        o_ref[...] = (acc_scr[...] * _bcast_lanes(l_inv, hd)
                      ).astype(o_ref.dtype)


def _band_range(qi, block_q, block_k, window):
    """(first, last) key block that meets the band of query block ``qi``."""
    hi = (qi * block_q + block_q - 1) // block_k
    if window is None:
        return 0, hi
    return jnp.maximum(qi * block_q - window + 1, 0) // block_k, hi


def _band_block(t: int, cap: int) -> int:
    """The largest block of at most ``cap`` rows that divides ``t``,
    halving from ``cap``; ``t`` itself where none does."""
    b = min(cap, t)
    while b > 8 and t % b:
        b //= 2
    return b if t % b == 0 else t


def band_flash_attention(q, k, v, num_heads: int, kv_heads: int,
                         window: Optional[int] = None,
                         sm_scale: Optional[float] = None,
                         block_q: int = 512, block_k: int = 512):
    """Causal attention with grouped heads inside a band. q ``[B, T,
    num_heads * hd]``, k, v ``[B, T, kv_heads * hd]`` -> ``[B, T, num_heads
    * hd]`` in q's dtype; ``window`` None is plain causal attention."""
    b, t, width = q.shape
    hd = width // num_heads
    group = num_heads // kv_heads
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(hd)
    block_q, block_k = _band_block(t, block_q), _band_block(t, block_k)
    visits = band_blocks(t, block_q, block_k, window)

    def kv_index(b_i, h, qi, kj):
        lo, hi = _band_range(qi, block_q, block_k, window)
        return b_i, jnp.minimum(lo + kj, hi), h // group

    q_spec = pl.BlockSpec((None, block_q, hd),
                          lambda b_i, h, qi, kj: (b_i, qi, h))
    kv_spec = pl.BlockSpec((None, block_k, hd), kv_index)
    _count_launch("window_flash")
    with jax.named_scope("window_flash_attention"):
        return pl.pallas_call(
            functools.partial(_band_kernel, sm_scale=sm_scale,
                              block_q=block_q, block_k=block_k,
                              window=window, visits=visits),
            grid=(b, num_heads, t // block_q, visits),
            in_specs=[q_spec, kv_spec, kv_spec],
            out_specs=q_spec,
            out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
            scratch_shapes=[pltpu.VMEM((block_q, NUM_LANES), jnp.float32),
                            pltpu.VMEM((block_q, NUM_LANES), jnp.float32),
                            pltpu.VMEM((block_q, hd), jnp.float32)],
            compiler_params=_CompilerParams(dimension_semantics=(
                "parallel", "parallel", "parallel", "arbitrary")),
            interpret=_interpret(),
            name="window_flash_fwd",
        )(q, k, v)


# ---------------------------------------------------------------------------
# Chunked vocab-projection cross-entropy (fused linear + CE)
# ---------------------------------------------------------------------------
#
# The LM-head matmul [rows, D] x [D, V] followed by softmax CE is the last
# place a GPT training step touches an O(rows * V) buffer. Blocking over
# both axes with an online logsumexp keeps every live temporary at
# [row_chunk, vocab_chunk]; the backward recomputes each chunk's logits from
# (x, head, lse) — one extra chunk matmul, the same trade flash attention
# makes for the T^2 score matrix.


def _ce_chunk_logits(x, head, bias, i, v_chunk, vocab, layout):
    """Logits for vocab chunk ``i`` in f32, padded columns masked to -inf.

    ``layout`` is "dv" (head [D, Vp]) or "vd" (head [Vp, D] — e.g. a tied
    embedding decoder); slicing the chunk out of ``head`` never transposes
    or materializes the full projection.
    """
    if layout == "dv":
        h = jax.lax.dynamic_slice_in_dim(head, i * v_chunk, v_chunk, axis=1)
        lg = jnp.dot(x, h, preferred_element_type=jnp.float32)
    else:
        h = jax.lax.dynamic_slice_in_dim(head, i * v_chunk, v_chunk, axis=0)
        lg = jnp.dot(x, h.T, preferred_element_type=jnp.float32)
    lg = lg.astype(jnp.float32)
    if bias is not None:
        lg = lg + jax.lax.dynamic_slice_in_dim(
            bias, i * v_chunk, v_chunk, axis=0).astype(jnp.float32)
    col = i * v_chunk + jnp.arange(v_chunk)
    lg = jnp.where(col[None, :] < vocab, lg, _NEG_INF)
    return lg, col, h


def _ce_fwd_lax(x, head, bias, labels, v_chunk, vocab, layout):
    """Online-logsumexp sweep over vocab chunks. Returns (lse, gold) f32 [n]."""
    n = x.shape[0]
    nv = (head.shape[1] if layout == "dv" else head.shape[0]) // v_chunk

    def body(carry, i):
        m, s, gold = carry
        lg, col, _ = _ce_chunk_logits(x, head, bias, i, v_chunk, vocab, layout)
        m_new = jnp.maximum(m, jnp.max(lg, axis=1))
        s = s * jnp.exp(m - m_new) + jnp.sum(
            jnp.exp(lg - m_new[:, None]), axis=1)
        gold = gold + jnp.sum(
            jnp.where(col[None, :] == labels[:, None], lg, 0.0), axis=1)
        return (m_new, s, gold), None

    carry0 = (jnp.full((n,), -jnp.inf, jnp.float32),
              jnp.zeros((n,), jnp.float32),
              jnp.zeros((n,), jnp.float32))
    (m, s, gold), _ = jax.lax.scan(body, carry0, jnp.arange(nv))
    return m + jnp.log(s), gold


def _ce_fwd_kernel(*refs, block_v, num_v, vocab, has_bias):
    """Pallas forward: grid (row_blocks, vocab_blocks), vocab sequential.
    Per-row running max / sum / gold-logit live lane-replicated in VMEM
    scratch across vocab steps (same statistics layout as flash attention).
    """
    if has_bias:
        x_ref, h_ref, lab_ref, b_ref, lse_ref, gold_ref, m_scr, l_scr, g_scr \
            = refs
    else:
        x_ref, h_ref, lab_ref, lse_ref, gold_ref, m_scr, l_scr, g_scr = refs
        b_ref = None
    vi = pl.program_id(1)

    @pl.when(vi == 0)
    def _init():
        m_scr[...] = jnp.full(m_scr.shape, -jnp.inf, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        g_scr[...] = jnp.zeros(g_scr.shape, jnp.float32)

    x = x_ref[...]                                     # (rb, D)
    h = h_ref[...]                                     # (D, bv)
    s = jax.lax.dot_general(
        x, h, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)            # (rb, bv)
    if b_ref is not None:
        s = s + jnp.broadcast_to(b_ref[...].astype(jnp.float32), s.shape)
    rb = s.shape[0]
    col = vi * block_v + jax.lax.broadcasted_iota(
        jnp.int32, (rb, block_v), 1)
    s = jnp.where(col < vocab, s, _NEG_INF)

    m_prev = m_scr[...]                                # (rb, 128) replicated
    m_curr = jnp.max(s, axis=1)[:, None]
    m_next = jnp.maximum(m_prev, m_curr)
    alpha = jnp.exp(m_prev - m_next)
    p = jnp.exp(s - _bcast_lanes(m_next, block_v))
    l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=1)[:, None]
    m_scr[...] = m_next

    lab = lab_ref[...][:, :1]                          # (rb, 1) lane 0
    g_scr[...] += jnp.sum(jnp.where(col == lab, s, 0.0), axis=1)[:, None]

    @pl.when(vi == num_v - 1)
    def _finish():
        l = l_scr[...]
        lse_ref[...] = m_scr[...] + jnp.log(l)
        gold_ref[...] = g_scr[...]


def _ce_fwd_pallas(x, head, bias, labels, v_chunk, vocab,
                   block_rows: int = 256):
    """Pallas-tiled (lse, gold) for head layout "dv". Requires row count
    divisible by the row block and head width by ``v_chunk`` (the wrapper
    pads both)."""
    n, d = x.shape
    vp = head.shape[1]
    rb = block_rows if n % block_rows == 0 else n
    nv = vp // v_chunk
    grid = (n // rb, nv)
    kern = functools.partial(_ce_fwd_kernel, block_v=v_chunk, num_v=nv,
                             vocab=vocab, has_bias=bias is not None)
    labs = jnp.broadcast_to(labels.astype(jnp.int32)[:, None],
                            (n, NUM_LANES))
    in_specs = [
        pl.BlockSpec((rb, d), lambda ri, vi: (ri, 0)),
        pl.BlockSpec((d, v_chunk), lambda ri, vi: (0, vi)),
        pl.BlockSpec((rb, NUM_LANES), lambda ri, vi: (ri, 0)),
    ]
    args = [x, head, labs]
    if bias is not None:
        in_specs.append(pl.BlockSpec((1, v_chunk), lambda ri, vi: (0, vi)))
        args.append(bias.reshape(1, vp))
    lse, gold = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((rb, NUM_LANES), lambda ri, vi: (ri, 0)),
            pl.BlockSpec((rb, NUM_LANES), lambda ri, vi: (ri, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, NUM_LANES), jnp.float32),
            jax.ShapeDtypeStruct((n, NUM_LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((rb, NUM_LANES), jnp.float32),
            pltpu.VMEM((rb, NUM_LANES), jnp.float32),
            pltpu.VMEM((rb, NUM_LANES), jnp.float32),
        ],
        compiler_params=_CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=_interpret(),
    )(*args)
    return lse[:, 0], gold[:, 0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _chunked_ce(x, head, bias, labels, valid, v_chunk, vocab, layout,
                use_pallas):
    """Per-row CE [n] f32 from hidden rows x [n, D] and projection head,
    never materializing [n, Vp]. ``valid`` (bool [n] or None) zeroes rows."""
    ce, _ = _chunked_ce_fwd(x, head, bias, labels, valid, v_chunk, vocab,
                            layout, use_pallas)
    return ce


def _chunked_ce_fwd(x, head, bias, labels, valid, v_chunk, vocab, layout,
                    use_pallas):
    labels = labels.astype(jnp.int32)
    # lane-replicated statistics need a lane-aligned vocab block
    if use_pallas and layout == "dv" and v_chunk % NUM_LANES == 0:
        lse, gold = _ce_fwd_pallas(x, head, bias, labels, v_chunk, vocab)
    else:
        lse, gold = _ce_fwd_lax(x, head, bias, labels, v_chunk, vocab, layout)
    ce = lse - gold
    if valid is not None:
        ce = jnp.where(valid, ce, 0.0)
    return ce, (x, head, bias, labels, valid, lse)


def _chunked_ce_bwd(v_chunk, vocab, layout, use_pallas, res, ct):
    import numpy as _onp

    x, head, bias, labels, valid, lse = res
    n, d = x.shape
    vp = head.shape[1] if layout == "dv" else head.shape[0]
    nv = vp // v_chunk
    g = ct.astype(jnp.float32)
    if valid is not None:
        g = jnp.where(valid, g, 0.0)

    def body(carry, i):
        dx, dhead, dbias = carry
        lg, col, h = _ce_chunk_logits(x, head, bias, i, v_chunk, vocab,
                                      layout)
        p = jnp.exp(lg - lse[:, None])                 # masked cols -> 0
        onehot = (col[None, :] == labels[:, None]).astype(jnp.float32)
        dl = (p - onehot) * g[:, None]                 # (n, vc) f32
        hf = h.astype(jnp.float32)
        if layout == "dv":
            dx = dx + jnp.dot(dl, hf.T)
            dh = jnp.dot(x.astype(jnp.float32).T, dl)  # (D, vc)
            dhead = jax.lax.dynamic_update_slice_in_dim(
                dhead, dh, i * v_chunk, axis=1)
        else:
            dx = dx + jnp.dot(dl, hf)
            dh = jnp.dot(dl.T, x.astype(jnp.float32))  # (vc, D)
            dhead = jax.lax.dynamic_update_slice_in_dim(
                dhead, dh, i * v_chunk, axis=0)
        if bias is not None:
            dbias = jax.lax.dynamic_update_slice_in_dim(
                dbias, jnp.sum(dl, axis=0), i * v_chunk, axis=0)
        return (dx, dhead, dbias), None

    dhead0 = jnp.zeros((d, vp) if layout == "dv" else (vp, d), jnp.float32)
    carry0 = (jnp.zeros((n, d), jnp.float32), dhead0,
              jnp.zeros((vp,), jnp.float32))
    (dx, dhead, dbias), _ = jax.lax.scan(body, carry0, jnp.arange(nv))
    f0 = jax.dtypes.float0
    return (dx.astype(x.dtype), dhead.astype(head.dtype),
            None if bias is None else dbias.astype(bias.dtype),
            _onp.zeros(labels.shape, f0),
            None if valid is None else _onp.zeros(valid.shape, f0))


_chunked_ce.defvjp(_chunked_ce_fwd, _chunked_ce_bwd)


def chunked_lm_loss(x, head, labels, bias=None, valid=None,
                    vocab_chunk: int = 1024, row_chunk: int = 0,
                    head_layout: str = "dv",
                    use_pallas: Optional[bool] = None):
    """Summed token cross-entropy from hidden states, fused with the vocab
    projection and blocked over both the row (batch*time) and vocab axes.

    ``x`` [..., D]; ``head`` [D, V] (``head_layout="dv"``) or a tied
    embedding table [V, D] (``"vd"``); ``labels`` int [...] matching x's
    leading dims; ``bias`` optional [V]; ``valid`` optional bool [...]
    masks rows out of the sum (padding / unmasked MLM slots).

    Matches ``sum(lse - gold)`` (models/gpt.token_ce) to f32 reduction
    tolerance; callers normalize, so distributed shards can psum partials.
    On TPU the forward statistics (lse, gold) run as one Pallas kernel;
    the backward is a pure-lax chunk sweep everywhere (each chunk's logits
    are recomputed from x, head, lse — never more than
    ``[row_chunk, vocab_chunk]`` live at once).
    """
    d = x.shape[-1]
    rows = x.reshape(-1, d)
    labs = labels.reshape(-1).astype(jnp.int32)
    n = rows.shape[0]
    v = head.shape[-1] if head_layout == "dv" else head.shape[0]
    labs = jnp.clip(labs, 0, v - 1)
    vmask = None if valid is None else valid.reshape(-1)
    vc = max(1, min(int(vocab_chunk) or v, v))
    if use_pallas is None:
        use_pallas = head_layout == "dv" and _on_tpu()

    # pad the vocab axis to a chunk multiple (masked to -inf in-chunk; the
    # pad's transpose slices the head cotangent back automatically)
    pad_v = (-v) % vc
    if pad_v:
        if head_layout == "dv":
            head = jnp.pad(head, ((0, 0), (0, pad_v)))
        else:
            head = jnp.pad(head, ((0, pad_v), (0, 0)))
        if bias is not None:
            bias = jnp.pad(bias, (0, pad_v))

    rc = max(1, min(int(row_chunk) or n, n))
    pad_r = (-n) % rc
    if pad_r:
        rows = jnp.concatenate(
            [rows, jnp.zeros((pad_r, d), rows.dtype)])
        labs = jnp.concatenate([labs, jnp.zeros((pad_r,), labs.dtype)])
        vmask = jnp.concatenate(
            [jnp.ones((n,), bool) if vmask is None else vmask,
             jnp.zeros((pad_r,), bool)])
    nr = (n + pad_r) // rc
    if nr == 1:
        ce = _chunked_ce(rows, head, bias, labs, vmask, vc, v, head_layout,
                         use_pallas)
        return jnp.sum(ce)

    xcs = rows.reshape(nr, rc, d)
    lcs = labs.reshape(nr, rc)
    vms = None if vmask is None else vmask.reshape(nr, rc)

    def body(acc, args):
        if vms is None:
            xc, lc = args
            vm = None
        else:
            xc, lc, vm = args
        ce = _chunked_ce(xc, head, bias, lc, vm, vc, v, head_layout,
                         use_pallas)
        return acc + jnp.sum(ce), None

    seq = (xcs, lcs) if vms is None else (xcs, lcs, vms)
    total, _ = jax.lax.scan(body, jnp.float32(0.0), seq)
    return total


# ---------------------------------------------------------------------------
# Chunked CE over already-materialized logits (the softmax_with_cross_entropy
# op's vocab_chunk lowering variant): the logits buffer exists, but the f32
# log-softmax / softmax intermediates — the usual 2-4x blowup on a bf16
# [B, T, V] head — stay [rows, vocab_chunk].
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def chunked_softmax_ce_from_logits(logits, labels, v_chunk: int):
    """Per-row CE [n] f32 for logits [n, V] (V divisible by ``v_chunk``;
    pad with -inf columns otherwise), labels int [n] in [0, V)."""
    ce, _ = _logits_ce_fwd(logits, labels, v_chunk)
    return ce


def _logits_chunk(logits, i, v_chunk):
    return jax.lax.dynamic_slice_in_dim(
        logits, i * v_chunk, v_chunk, axis=1).astype(jnp.float32)


def _logits_ce_fwd(logits, labels, v_chunk):
    n, vp = logits.shape
    nv = vp // v_chunk
    labels = labels.astype(jnp.int32)

    def body(carry, i):
        m, s, gold = carry
        lg = _logits_chunk(logits, i, v_chunk)
        col = i * v_chunk + jnp.arange(v_chunk)
        m_new = jnp.maximum(m, jnp.max(lg, axis=1))
        s = s * jnp.exp(m - m_new) + jnp.sum(
            jnp.exp(lg - m_new[:, None]), axis=1)
        gold = gold + jnp.sum(
            jnp.where(col[None, :] == labels[:, None], lg, 0.0), axis=1)
        return (m_new, s, gold), None

    carry0 = (jnp.full((n,), -jnp.inf, jnp.float32),
              jnp.zeros((n,), jnp.float32), jnp.zeros((n,), jnp.float32))
    (m, s, gold), _ = jax.lax.scan(body, carry0, jnp.arange(nv))
    lse = m + jnp.log(s)
    return lse - gold, (logits, labels, lse)


def _logits_ce_bwd(v_chunk, res, ct):
    import numpy as _onp

    logits, labels, lse = res
    n, vp = logits.shape
    nv = vp // v_chunk
    g = ct.astype(jnp.float32)

    def body(dlogits, i):
        lg = _logits_chunk(logits, i, v_chunk)
        col = i * v_chunk + jnp.arange(v_chunk)
        p = jnp.exp(lg - lse[:, None])
        onehot = (col[None, :] == labels[:, None]).astype(jnp.float32)
        dl = ((p - onehot) * g[:, None]).astype(logits.dtype)
        return jax.lax.dynamic_update_slice_in_dim(
            dlogits, dl, i * v_chunk, axis=1), None

    dlogits, _ = jax.lax.scan(body, jnp.zeros_like(logits), jnp.arange(nv))
    return dlogits, _onp.zeros(labels.shape, jax.dtypes.float0)


chunked_softmax_ce_from_logits.defvjp(_logits_ce_fwd, _logits_ce_bwd)


# ---------------------------------------------------------------------------
# Megakernel launch accounting (ISSUE 16)
# ---------------------------------------------------------------------------


def _count_launch(kernel: str) -> None:
    """Tick ``paddle_megakernel_launches_total{kernel}``.

    Incremented at TRACE time — once per megakernel instance traced into
    a compiled executable (e.g. one per (dtype, hparam-signature) group
    for the optimizer sweep), NOT once per executed step: Python cannot
    observe device-side replays of a jitted program.
    tools/metrics_check.py gates an exact delta for a fused-opt smoke
    train on this definition."""
    from paddle_tpu.observability.metrics import default_registry

    default_registry().counter(
        "paddle_megakernel_launches_total",
        "Pallas megakernel launches traced into compiled executables "
        "(counted per trace/compile, not per executed step)",
        labelnames=("kernel",)).labels(kernel).inc()


# ---------------------------------------------------------------------------
# Fused layernorm + residual (+ bias-add / dropout) block kernel (ISSUE 16a)
# ---------------------------------------------------------------------------
#
# The train-step attribution (ATTRIBUTION.json) ranks a layernorm residue
# group plus the elementwise adds feeding it: every transformer block's
# ``x + o + b`` residual add and the following layernorm (forward AND its
# grads) lower as separate small fusions, each paying an HBM round-trip at
# [B*T, D]. This kernel computes
#
#     s = dropout(x) + residual + bias_add    (in x.dtype — the exact
#                                              "(x + o) + b" association
#                                              of models/gpt.py block_fn)
#     y = (s - mu) * rsqrt(var + eps) * scale + bias   (statistics in f32,
#                                              y cast back to x.dtype)
#
# in ONE launch, emits the lane-replicated (mu, rstd) statistics, and
# differentiates through a hand-written Pallas backward (custom_vjp) in
# the same row tiling. models/gpt.py and models/ernie.py route every block
# layernorm through fused_ln behind their ``fused_ln`` config flags
# (default off: interpret-mode Pallas is slower than XLA off-TPU).


def _ln_fwd_kernel(*refs, eps, has_res, has_badd, has_mask, inv_keep,
                   emit_s):
    it = iter(refs)
    x_ref = next(it)
    res_ref = next(it) if has_res else None
    badd_ref = next(it) if has_badd else None
    mask_ref = next(it) if has_mask else None
    scale_ref = next(it)
    bias_ref = next(it)
    y_ref = next(it)
    s_ref = next(it) if emit_s else None
    mu_ref = next(it)
    rstd_ref = next(it)

    s = x_ref[...]
    if mask_ref is not None:
        s = s * mask_ref[...].astype(s.dtype) * jnp.asarray(
            inv_keep, s.dtype)
    if res_ref is not None:
        s = res_ref[...] + s
    if badd_ref is not None:
        s = s + badd_ref[...]
    if s_ref is not None:
        s_ref[...] = s

    s32 = s.astype(jnp.float32)
    mu = jnp.mean(s32, axis=1, keepdims=True)             # (br, 1)
    var = jnp.var(s32, axis=1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    y = (s32 - mu) * rstd
    y = y * scale_ref[...].astype(jnp.float32) \
        + bias_ref[...].astype(jnp.float32)
    y_ref[...] = y.astype(y_ref.dtype)
    mu_ref[...] = jnp.broadcast_to(mu, mu_ref.shape)
    rstd_ref[...] = jnp.broadcast_to(rstd, rstd_ref.shape)


def _ln_bwd_kernel(*refs, has_dsx, has_mask, inv_keep):
    it = iter(refs)
    sx_ref = next(it)
    mu_ref = next(it)
    rstd_ref = next(it)
    scale_ref = next(it)
    dy_ref = next(it)
    dsx_ref = next(it) if has_dsx else None
    mask_ref = next(it) if has_mask else None
    ds_ref = next(it)
    dx_ref = next(it) if has_mask else None
    dscale_ref = next(it)
    dbias_ref = next(it)

    s32 = sx_ref[...].astype(jnp.float32)
    mu = mu_ref[...][:, :1]
    rstd = rstd_ref[...][:, :1]
    xhat = (s32 - mu) * rstd
    dy = dy_ref[...].astype(jnp.float32)
    g = dy * scale_ref[...].astype(jnp.float32)
    gm = jnp.mean(g, axis=1, keepdims=True)
    gxm = jnp.mean(g * xhat, axis=1, keepdims=True)
    ds = rstd * (g - gm - xhat * gxm)
    if dsx_ref is not None:
        ds = ds + dsx_ref[...].astype(jnp.float32)
    ds_ref[...] = ds.astype(ds_ref.dtype)
    if dx_ref is not None:
        dx = ds * mask_ref[...].astype(jnp.float32) * inv_keep
        dx_ref[...] = dx.astype(dx_ref.dtype)
    # per-grid-block partial reductions; the caller sums the
    # (ngrid, 1, D) partials so the row grid stays embarrassingly parallel
    dscale_ref[...] = jnp.sum(dy * xhat, axis=0, keepdims=True)
    dbias_ref[...] = jnp.sum(dy, axis=0, keepdims=True)


def _ln_pad_rows(a, rp):
    r = a.shape[0]
    if r == rp:
        return a
    return jnp.concatenate(
        [a, jnp.zeros((rp - r,) + a.shape[1:], a.dtype)], axis=0)


def _ln_fwd(x, scale, bias, residual, badd, mask, eps, keep, block_rows):
    r, d = x.shape
    br = min(block_rows, max(r, 1))
    ng = -(-r // br)
    rp = ng * br
    emit_s = residual is not None or badd is not None or mask is not None
    row_spec = pl.BlockSpec((br, d), lambda i: (i, 0))
    vec_spec = pl.BlockSpec((1, d), lambda i: (0, 0))
    xp = _ln_pad_rows(x, rp)
    args, in_specs = [xp], [row_spec]
    if residual is not None:
        args.append(_ln_pad_rows(residual, rp))
        in_specs.append(row_spec)
    if badd is not None:
        args.append(badd.reshape(1, d))
        in_specs.append(vec_spec)
    if mask is not None:
        args.append(_ln_pad_rows(mask, rp))
        in_specs.append(row_spec)
    args += [scale.reshape(1, d), bias.reshape(1, d)]
    in_specs += [vec_spec, vec_spec]
    stat_spec = pl.BlockSpec((br, NUM_LANES), lambda i: (i, 0))
    out_specs = [row_spec]
    out_shape = [jax.ShapeDtypeStruct((rp, d), x.dtype)]
    if emit_s:
        out_specs.append(row_spec)
        out_shape.append(jax.ShapeDtypeStruct((rp, d), x.dtype))
    out_specs += [stat_spec, stat_spec]
    out_shape += [jax.ShapeDtypeStruct((rp, NUM_LANES), jnp.float32)] * 2
    kern = functools.partial(
        _ln_fwd_kernel, eps=eps, has_res=residual is not None,
        has_badd=badd is not None, has_mask=mask is not None,
        inv_keep=1.0 / keep, emit_s=emit_s)
    with jax.named_scope("fused_layernorm_fwd"):
        outs = pl.pallas_call(
            kern, grid=(ng,), in_specs=in_specs, out_specs=out_specs,
            out_shape=out_shape,
            compiler_params=_CompilerParams(
                dimension_semantics=("parallel",)),
            interpret=_interpret(),
        )(*args)
    if emit_s:
        y, s, mu, rstd = outs
        sx = s
    else:
        y, mu, rstd = outs
        s, sx = None, xp
    return y[:r], (None if s is None else s[:r]), sx, mu, rstd


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9))
def _fused_ln(x, scale, bias, residual, badd, mask, eps, keep,
              return_residual, block_rows):
    y, s, _sx, _mu, _rstd = _ln_fwd(x, scale, bias, residual, badd, mask,
                                    eps, keep, block_rows)
    return (y, s) if return_residual else y


def _fused_ln_vjp_fwd(x, scale, bias, residual, badd, mask, eps, keep,
                      return_residual, block_rows):
    y, s, sx, mu, rstd = _ln_fwd(x, scale, bias, residual, badd, mask,
                                 eps, keep, block_rows)
    # zero-size tags carry the optional operands' dtypes to the bwd pass
    # without holding their values live
    res_tag = None if residual is None else jnp.zeros((0,), residual.dtype)
    badd_tag = None if badd is None else jnp.zeros((0,), badd.dtype)
    bias_tag = jnp.zeros((0,), bias.dtype)
    maskp = None if mask is None else _ln_pad_rows(mask, sx.shape[0])
    out = (y, s) if return_residual else y
    return out, (sx, mu, rstd, scale, maskp, res_tag, badd_tag, bias_tag)


def _fused_ln_vjp_bwd(eps, keep, return_residual, block_rows, res, ct):
    import numpy as _onp

    sx, mu, rstd, scale, maskp, res_tag, badd_tag, bias_tag = res
    if return_residual:
        dy, dsx = ct
    else:
        dy, dsx = ct, None
    r, d = dy.shape
    rp = sx.shape[0]
    br = min(block_rows, max(r, 1))
    ng = rp // br
    has_mask = maskp is not None
    row_spec = pl.BlockSpec((br, d), lambda i: (i, 0))
    vec_spec = pl.BlockSpec((1, d), lambda i: (0, 0))
    stat_spec = pl.BlockSpec((br, NUM_LANES), lambda i: (i, 0))
    # (ng, 1, d) partials: a (1, d) block of an (ng, d) array has a
    # second-minor dim Mosaic refuses (neither a multiple of 8 nor the
    # array's own); with the grid axis leading and squeezed, the block's
    # last two dims ARE the array's
    part_spec = pl.BlockSpec((None, 1, d), lambda i: (i, 0, 0))
    args = [sx, mu, rstd, scale.reshape(1, d), _ln_pad_rows(dy, rp)]
    in_specs = [row_spec, stat_spec, stat_spec, vec_spec, row_spec]
    if dsx is not None:
        args.append(_ln_pad_rows(dsx, rp))
        in_specs.append(row_spec)
    if has_mask:
        args.append(maskp)
        in_specs.append(row_spec)
    out_specs = [row_spec]
    out_shape = [jax.ShapeDtypeStruct((rp, d), sx.dtype)]
    if has_mask:
        out_specs.append(row_spec)
        out_shape.append(jax.ShapeDtypeStruct((rp, d), sx.dtype))
    out_specs += [part_spec, part_spec]
    out_shape += [jax.ShapeDtypeStruct((ng, 1, d), jnp.float32)] * 2
    kern = functools.partial(
        _ln_bwd_kernel, has_dsx=dsx is not None, has_mask=has_mask,
        inv_keep=1.0 / keep)
    with jax.named_scope("fused_layernorm_bwd"):
        outs = pl.pallas_call(
            kern, grid=(ng,), in_specs=in_specs, out_specs=out_specs,
            out_shape=out_shape,
            compiler_params=_CompilerParams(
                dimension_semantics=("parallel",)),
            interpret=_interpret(),
        )(*args)
    if has_mask:
        ds_p, dx_p, dscale_p, dbias_p = outs
        dx = dx_p[:r]
    else:
        ds_p, dscale_p, dbias_p = outs
        dx = ds_p[:r]
    ds = ds_p[:r]
    dscale = jnp.sum(dscale_p[:, 0], axis=0).astype(scale.dtype)
    dbias = jnp.sum(dbias_p[:, 0], axis=0).astype(bias_tag.dtype)
    dres = None if res_tag is None else ds.astype(res_tag.dtype)
    dbadd = None if badd_tag is None \
        else jnp.sum(ds, axis=0).astype(badd_tag.dtype)
    dmask = None if maskp is None \
        else _onp.zeros((r, d), jax.dtypes.float0)
    return dx, dscale, dbias, dres, dbadd, dmask


_fused_ln.defvjp(_fused_ln_vjp_fwd, _fused_ln_vjp_bwd)


def fused_ln(x, scale, bias, residual=None, bias_add=None, *,
             eps: float = 1e-5, dropout_rate: float = 0.0,
             dropout_key=None, return_residual: bool = False,
             block_rows: int = 128):
    """Fused layernorm(+residual+bias-add+dropout) block kernel.

    Computes ``s = dropout(x) + residual + bias_add`` in ``x.dtype``
    (matching the models' ``(x + o) + b`` association) followed by a
    layernorm over the last axis with f32 statistics — one Pallas launch
    forward, one backward (custom_vjp), instead of the
    add / layernorm / layernorm-grad small-fusion residue the step
    attribution ranks (docs/kernels.md).

    x:            [..., D]
    scale, bias:  [D]
    residual:     optional [..., D] — added to (dropped-out) ``x``
    bias_add:     optional [D]     — broadcast-added after the residual
    dropout_rate: inverted dropout on ``x`` (requires ``dropout_key``);
                  the mask is drawn outside the kernel and applied inside
    return_residual: also return ``s`` (the pre-norm sum — the models
                  carry it forward as the next residual stream)

    Returns ``y`` or ``(y, s)``, both shaped/typed like ``x``.
    """
    d = x.shape[-1]
    lead = x.shape[:-1]
    r = 1
    for n in lead:
        r *= int(n)
    x2 = x.reshape(r, d)
    res2 = None if residual is None else residual.reshape(r, d)
    badd = None if bias_add is None else bias_add.reshape(d)
    mask = None
    keep = 1.0
    if dropout_rate:
        if dropout_key is None:
            raise ValueError("dropout_rate > 0 requires dropout_key")
        keep = 1.0 - float(dropout_rate)
        mask = jax.random.bernoulli(dropout_key, keep, (r, d))
    _count_launch("fused_ln")
    out = _fused_ln(x2, scale, bias, res2, badd, mask, float(eps), keep,
                    return_residual, block_rows)
    if return_residual:
        y, s = out
        return y.reshape(x.shape), s.reshape(x.shape)
    return out.reshape(x.shape)


# ---------------------------------------------------------------------------
# Optimizer megakernel (ISSUE 16b)
# ---------------------------------------------------------------------------
#
# The attribution's optimizer residue group (~59 multiply_add_fusion
# events/step at the smoke config) is the per-group tail of the fused
# flat-buffer sweep: even over PR 2's [numel] megabuffers, XLA splits the
# update expression into a stream of small elementwise fusions. This
# single kernel sweeps the flat buffers once — ONE launch per
# (dtype, hparam-signature) group — reproducing each unfused expression
# ORDER exactly, so parity is bitwise at f32. Reductions (grad norm /
# clip scale) stay outside; their results ride in as dynamic scalars via
# scalar-prefetch SMEM next to lr and the Adam bias-correction powers.

_OPT_SCALAR_SLOTS = 8


def _opt_kernel(scal_ref, *refs, kind, b1=0.9, b2=0.999, eps=1e-8,
                mu=0.9, nesterov=False, coeff=0.0, weight_decay=0.0):
    # scal_ref (SMEM, f32[8]): [lr, b1pow, b2pow, clip_scale, c1, c2, -, -]
    lr = scal_ref[0]
    if kind == "sgd":
        # fluid fused_sgd: dtype-native p - lr * g
        p_ref, g_ref, po_ref = refs
        p = p_ref[...]
        po_ref[...] = p - lr.astype(p.dtype) * g_ref[...]
    elif kind == "momentum":
        p_ref, g_ref, v_ref, po_ref, vo_ref = refs
        gf = g_ref[...].astype(jnp.float32)
        pf = p_ref[...].astype(jnp.float32)
        v_new = mu * v_ref[...].astype(jnp.float32) + gf
        if nesterov:
            p_new = pf - (gf + mu * v_new) * lr
        else:
            p_new = pf - lr * v_new
        po_ref[...] = p_new.astype(po_ref.dtype)
        vo_ref[...] = v_new.astype(vo_ref.dtype)
    elif kind == "adam":
        # fluid _fused_adam_impl (coeff > 0 -> AdamW decoupled decay)
        p_ref, g_ref, m_ref, v_ref, po_ref, mo_ref, vo_ref = refs
        b1p, b2p = scal_ref[1], scal_ref[2]
        gf = g_ref[...].astype(jnp.float32)
        pf = p_ref[...].astype(jnp.float32)
        m_new = b1 * m_ref[...] + (1 - b1) * gf
        v_new = b2 * v_ref[...] + (1 - b2) * gf * gf
        lr_t = lr * jnp.sqrt(1 - b2p * b2) / (1 - b1p * b1)
        p_new = pf - lr_t * m_new / (jnp.sqrt(v_new) + eps)
        if coeff:
            p_new = p_new - lr * coeff * pf
        po_ref[...] = p_new.astype(po_ref.dtype)
        mo_ref[...] = m_new
        vo_ref[...] = v_new
    else:  # "adamw_mask": parallel/parallelize.py flat AdamW sweep
        p_ref, g_ref, m_ref, v_ref, wd_ref, po_ref, mo_ref, vo_ref = refs
        scale, c1, c2 = scal_ref[3], scal_ref[4], scal_ref[5]
        gf = g_ref[...].astype(jnp.float32) * scale
        pf = p_ref[...]
        mf = b1 * m_ref[...].astype(jnp.float32) + (1 - b1) * gf
        vf = b2 * v_ref[...].astype(jnp.float32) + (1 - b2) * gf * gf
        u = (mf / c1) / (jnp.sqrt(vf / c2) + eps)
        po_ref[...] = pf - lr * (u + weight_decay * wd_ref[...] * pf)
        mo_ref[...] = mf.astype(mo_ref.dtype)
        vo_ref[...] = vf.astype(vo_ref.dtype)


def _opt_megakernel(kind, ins, outs_dtype, scalars, aliases,
                    block_rows=256, **static):
    """One Pallas launch over flat [n] optimizer megabuffers.

    ``ins`` are flat [n] arrays (param, grad, moments, mask —
    kind-specific order), padded to (rows, 128) lanes and swept by one
    row-block grid. Elementwise only — each expression matches its
    unfused reference bit-for-bit at f32. ``aliases`` maps in-index ->
    out-index for in-place param/moment updates (indices count the
    scalar operand first, per pallas aliasing numbering)."""
    n = ins[0].shape[0]
    rows = -(-n // NUM_LANES)
    br = min(block_rows, max(rows, 1))
    ng = -(-rows // br)
    padded = ng * br * NUM_LANES

    def pad2(a):
        a = a.reshape(-1)
        if a.shape[0] != padded:
            a = jnp.concatenate(
                [a, jnp.zeros((padded - a.shape[0],), a.dtype)])
        return a.reshape(ng * br, NUM_LANES)

    pad_s = _OPT_SCALAR_SLOTS - len(scalars)
    scal = jnp.stack([jnp.asarray(v, jnp.float32) for v in scalars]
                     + [jnp.zeros((), jnp.float32)] * pad_s)
    row_spec = pl.BlockSpec((br, NUM_LANES), lambda i, s: (i, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(ng,),
        in_specs=[row_spec] * len(ins),
        out_specs=[row_spec] * len(outs_dtype))
    outs = pl.pallas_call(
        functools.partial(_opt_kernel, kind=kind, **static),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((ng * br, NUM_LANES), dt)
                   for dt in outs_dtype],
        input_output_aliases=dict(aliases),
        compiler_params=_CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=_interpret(),
    )(scal, *[pad2(a) for a in ins])
    if not isinstance(outs, (list, tuple)):
        outs = [outs]
    return [o.reshape(-1)[:n] for o in outs]


def megakernel_sgd(p, g, lr):
    """p_new = p - lr.astype(p.dtype) * g over a flat [n] group."""
    _count_launch("opt_sgd")
    with jax.named_scope("fused_opt_megakernel/sgd"):
        (p_new,) = _opt_megakernel("sgd", [p, g], [p.dtype], [lr],
                                   {1: 0})
    return p_new


def megakernel_momentum(p, g, v, lr, *, mu=0.9, nesterov=False):
    _count_launch("opt_momentum")
    with jax.named_scope("fused_opt_megakernel/momentum"):
        p_new, v_new = _opt_megakernel(
            "momentum", [p, g, v], [p.dtype, v.dtype], [lr],
            {1: 0, 3: 1}, mu=float(mu), nesterov=bool(nesterov))
    return p_new, v_new


def megakernel_adam(p, g, m, v, lr, b1p, b2p, *, b1=0.9, b2=0.999,
                    eps=1e-8, coeff=0.0):
    """fluid fused_adam/fused_adamw flat group (f32 moments; the
    Beta1Pow/Beta2Pow scalar updates stay outside)."""
    _count_launch("opt_adamw" if coeff else "opt_adam")
    with jax.named_scope("fused_opt_megakernel/adam"):
        p_new, m_new, v_new = _opt_megakernel(
            "adam", [p, g, m, v], [p.dtype, jnp.float32, jnp.float32],
            [lr, b1p, b2p], {1: 0, 3: 1, 4: 2}, b1=float(b1),
            b2=float(b2), eps=float(eps), coeff=float(coeff))
    return p_new, m_new, v_new


def megakernel_adamw_flat(p, g, m, v, wd_mask, lr, scale, c1, c2, *,
                          b1=0.9, b2=0.95, eps=1e-8, weight_decay=0.1):
    """parallelize._adamw_update_fused elementwise sweep: p/g flat f32,
    m/v flat in their storage dtype, wd_mask flat f32; grad-norm clip
    ``scale`` and bias corrections c1/c2 precomputed outside."""
    _count_launch("opt_adamw_flat")
    with jax.named_scope("fused_opt_megakernel/adamw_flat"):
        p_new, m_new, v_new = _opt_megakernel(
            "adamw_mask", [p, g, m, v, wd_mask],
            [p.dtype, m.dtype, v.dtype],
            [lr, 0.0, 0.0, scale, c1, c2], {1: 0, 3: 1, 4: 2},
            b1=float(b1), b2=float(b2), eps=float(eps),
            weight_decay=float(weight_decay))
    return p_new, m_new, v_new


def use_opt_megakernel(override=None) -> bool:
    """Resolve the optimizer-megakernel lever: explicit True/False wins;
    None = auto (Pallas/Mosaic on TPU, plain XLA elsewhere — interpret
    mode would only slow the CPU lane down)."""
    if override is not None:
        return bool(override)
    return _on_tpu()


# ---------------------------------------------------------------------------
# Fused decode step (ISSUE 16c)
# ---------------------------------------------------------------------------
#
# ATTRIBUTION_DECODE.json ranks the decode tick's residue: per layer, the
# cache row scatter (paged_cache_update), the paged-view gather, and the
# masked one-token softmax each lower as separate fusions with their own
# HBM round trips over the gathered [B, S, nh, hd] views. These kernels
# collapse a decode tick to one launch per layer (attention read through
# the page table, the row write a scatter before it) plus one launch for
# the final layernorm + LM-head projection. The logits head sits behind
# EngineConfig(fused_decode=True); the paged kernel is what the engine
# runs on a TPU (engine.kv_path).


# The decode tick reads its paged cache where it lies. A pool is
# ``[L, P, page, kvh * hd]``: a token's keys (or values) of all ``kvh``
# key/value heads flat in the lanes, a head a whole lane tile, so a page is
# ``page`` sublane rows of whole lanes and a head's keys are a free lane
# slice of it. The whole pool stays in HBM (``pl.ANY``); the layer index,
# the page tables and the positions are scalar prefetch, and the kernel
# copies just the pages that hold rows of a slot's span into VMEM, a chunk
# of pages at a time, double-buffered across chunks AND slots. The work of
# a tick is then the live pages' and nothing else's: no grid step, copy or
# product exists for a page no token lives on. Each key/value head's query
# rows against a chunk of its keys are one product on the MXU, and the
# probabilities against its values another, all heads' products of a chunk
# side by side (``_gqa_decode_kernel``: the fold runs within 2 % of its own
# page copies, which reach three quarters of the HBM rate at pages of 64
# KB and more at larger ones; ``_mla_decode_kernel`` is the same walk over
# one shared latent row a token).
_PAGED_CHUNK_BYTES = 1 << 20         # bytes of K (and of V) a chunk holds
_QUERY_TILE = 8                      # query rows an equal head is given


def _chunk_pages(max_pages: int, page: int, width: int, dtype) -> int:
    """Pages a chunk of the paged kernels holds: a megabyte of keys (512
    rows of 1,024 bfloat16 lanes, 256 of 2,048, 128 of 3,840), so that the
    two double-buffered pools take 4 MB of VMEM at any row width. A K or V
    tile of a product is 128 rows whatever the chunk, and a product works
    on whole chunks where the copies are by live pages. Half and twice
    the bytes read the same at 2,048 lanes (211.4, 211.4, 213.1 us a layer
    at 128, 256, 512 rows), four times slower (221.5: my chip run, PR
    42)."""
    row = width * jnp.dtype(dtype).itemsize
    return max(1, min(max_pages, _PAGED_CHUNK_BYTES // (page * row)))


def paged_decode_kernel(num_heads: int, kv_heads: int,
                        head_dim: int) -> Optional[str]:
    """Which entry to the paged decode kernel reads a cache of ``kv_heads``
    key/value heads for ``num_heads`` query heads of ``head_dim``, by name,
    or None where the tick gathers: the one statement of the rule, asked by
    every model description before the engine chooses ``kv_path``.

    * heads whose width is not whole lanes (64): none. A head is a lane
      slice of a page's rows, and a page's copy must be whole tiles
      (tests/test_chip_compile.py).
    * equal heads, ANY count (16, or 30: 3,840 lanes):
      :func:`paged_decode_attention`, the kernel at a group of one: each
      head's one query row fills a sublane tile of its own.
    * grouped heads, each key/value head serving a multiple of eight
      query heads (128 over 8; 64 over 8, a group of EIGHT, compiled for
      the v5e and run on it since PR 44: half a packed bfloat16 tile a
      group, which Mosaic takes as it is): :func:`gqa_paged_decode_attention`.
      A group's query rows are whole sublane tiles.
    * any other grouping (20 query heads over 1: a group that is no whole
      tile, and no entry pads one) gathers."""
    if head_dim % NUM_LANES or num_heads % max(kv_heads, 1):
        return None
    if kv_heads == num_heads:
        return "paged_decode_attention"
    if (num_heads // kv_heads) % 8 == 0:
        return "gqa_paged_decode_attention"
    return None


def paged_decode_tiles(num_heads: int, head_dim: int) -> bool:
    """Whether Mosaic takes :func:`paged_decode_attention`'s page copies
    for equal heads (:func:`paged_decode_kernel` has the rule). The engine
    asks before it chooses the kernel; interpret mode takes any shape."""
    return paged_decode_kernel(num_heads, num_heads, head_dim) is not None


# Latent (MLA) decode attention in absorbed form. The cache holds ONE row a
# token and layer, ``[c_kv | k_rope | 0..]`` (``rank + rope`` values, 512 +
# 64, and zeros up to whole lanes: Mosaic refuses a page copy whose minor
# dimension is not whole 128-lane tiles, and the TPU stores a 576-wide
# bfloat16 row in 640 lanes whatever its shape says), shared by all heads;
# a head's query is ``[q_nope W_uk | q_rope | 0..]`` of the same width, its
# score the plain product with the row, and its output the
# probabilities times the row's first ``rank`` values (``W_uv`` is applied
# outside). That is 64 query heads against one key/value head whose value
# is a slice of its key: M = heads, so scores and the weighted sum run on
# the MXU. The pool ``[L, P, page, rank + rope]`` stays in HBM, one grid
# step a slot (its query and output blocks are pipelined by Pallas), and
# the page copies of ``_gqa_decode_kernel``, double-buffered across chunks
# AND grid steps: the parity of the flat work-item count lives in SMEM.
#
# A page is 20 KB at the deployment's pages of 16 rows, some 57 a rider
# and layer, and the kernel is bound by its own instruction stream, not by
# HBM: the copies' time and the fold's add. Mosaic turns a ``pl.when``
# around ONE copy into a predicated copy, which costs its whole issue live
# or not, and checks every copy's bounds twice (12 of the 22 bundles a
# page's start took). So a chunk whose pages are all live is started in
# straight-line code off the flat table and awaited ONCE, the span's last
# chunk goes in pieces of 2^k pages (a real branch and one wait a piece),
# and no copy is checked. Every chunk folds under the span's masks: a
# second, unmasked fold for the chunks before the last read the same time
# a launch at the cell's spans, under two chunks a rider, and is not kept
# (PERF.md section 6, PR 50). The program's SIZE is budgeted too: every process that serves the model traces and lowers the
# kernel in Python at start-up, compile cache warm or not, some 5 ms an
# equation of its jaxpr on the benchmark's host (PERF.md section 7, PR 50),
# which is what chunks of 1,024 rows, a fold a count of live row groups and
# a second site of starts cost PR 49 (13.4 s of set-up for 6 % of the
# kernel's time). tests/test_chip_compile.py holds the starts and products
# to what is here.
_MLA_CHUNK_ROWS = 512


def mla_decode_tiles(page_size: int, dtype) -> bool:
    """Whether Mosaic takes the latent kernel's page copies: a page lands
    in VMEM as ``(page, width)`` rows on sublanes, and a chunk of pages is
    read as one ``(pages * page, width)`` matrix, which needs a page to be
    whole sublane tiles (16 rows of bfloat16, 8 of float32); the row's
    width is whole lanes by the model's choice of ``cache_width``."""
    return page_size % (32 // jnp.dtype(dtype).itemsize) == 0


def _mla_decode_kernel(layer_ref, tbl_ref, pos_ref, q_ref, pool_hbm, o_ref,
                       buf, sems, w_smem, m_scr, l_scr, acc_scr, *,
                       sm_scale, page, pages_per_chunk, batch, rank):
    G, M = pages_per_chunk, tbl_ref.shape[0] // batch    # the table is flat
    layer = layer_ref[0]
    b = pl.program_id(0)
    R = G * page

    def live_pages(s):               # pages holding rows [0, pos[s]]
        return pos_ref[s] // page + 1

    def pages(s, c, slot, at, n, go):
        """``n`` pages of chunk ``c`` of slot ``s`` from the chunk's page
        ``at`` on: ``n`` copies started one behind the other with no guard
        between them, or ONE wait for the bytes of all ``n`` (a wait
        counts its descriptor's bytes on the semaphore, whoever sent
        them)."""
        if not go:
            dst = buf.at[slot, pl.ds(at, n)]
            pltpu.make_async_copy(dst, dst, sems.at[slot]).wait()
            return
        for i in range(n):
            pltpu.make_async_copy(
                pool_hbm.at[layer, tbl_ref[s * M + c * G + at + i]],
                buf.at[slot, at + i], sems.at[slot]).start()

    def copies(s, c, slot, go):
        """Chunk ``c`` of slot ``s``, started or awaited: whole where all
        its pages are live, else its live pages in pieces of 2^k pages,
        the larger first, one guard a piece. No copy exists for a page no
        token lives on."""
        left = live_pages(s) - c * G
        pl.when(left >= G)(lambda: pages(s, c, slot, 0, G, go))

        @pl.when(left < G)
        def _tail():
            n = 1 << max(G - 1, 1).bit_length() - 1
            while n:
                pl.when(left & n != 0)(functools.partial(
                    pages, s, c, slot, left & -(2 * n), n, go))
                n //= 2

    pos = pos_ref[b]
    nc = (live_pages(b) + G - 1) // G
    q = q_ref[0]                                         # (H, rank + rope)

    def fold(c, slot):
        """Chunk ``c`` into the running softmax. Rows past ``pos`` may be
        VMEM no copy has written: masked in the scores AND zeroed where
        they are values."""
        rows = buf[slot].reshape(R, buf.shape[-1])
        live = c * R + jax.lax.broadcasted_iota(jnp.int32, (R, 1), 0) <= pos
        rows = jnp.where(live, rows, 0).astype(rows.dtype)
        s = jax.lax.dot_general(
            q, rows, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale   # (H, R)
        m_prev = m_scr[...]                              # (H, 1)
        valid = c * R + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1) <= pos
        s = jnp.where(valid, s, -jnp.inf)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        e = jnp.where(valid, jnp.exp(s - m_safe), 0.0)
        alpha = jnp.exp(m_prev - m_safe)
        l_scr[...] = alpha * l_scr[...] + jnp.sum(e, axis=1, keepdims=True)
        acc_scr[...] = alpha * acc_scr[...] + jnp.dot(
            e.astype(rows.dtype), rows[:, :rank],
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    def chunk(c, w):
        """One pass: start the work item behind ``(b, c)`` into the other
        buffer, then await and fold ``(b, c)``. The launch's first item is
        started by a pass of its own, ``c == -1`` at ``b == 0``, that folds
        nothing: the copies' starts are in the program ONCE."""
        slot = w % 2
        last = c + 1 == nc
        nb = jnp.where(last, b + 1, b)
        nxt = jnp.where(last, 0, c + 1)

        @pl.when(nb < batch)
        def _prefetch():
            copies(nb, nxt, 1 - slot, True)

        @pl.when(c >= 0)
        def _work():
            copies(b, c, slot, False)

            @pl.when(c == 0)
            def _init():
                m_scr[...] = jnp.full(m_scr.shape, -jnp.inf, jnp.float32)
                l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
                acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

            fold(c, slot)

        return w + 1

    first = b == 0
    w_smem[0] = jax.lax.fori_loop(
        jnp.where(first, -1, 0), nc, chunk,
        jnp.where(first, 0, w_smem[0]))
    o_ref[0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
                ).astype(o_ref.dtype)


def mla_paged_decode_attention(q_lat, pool, new_rows, tables, positions,
                               layer, rank: int, sm_scale: float):
    """The latent decode step of one layer: the riders' new rows written
    through the page table (a scatter on the carried pool), then one-token
    absorbed attention over each slot's LIVE pages, read where they lie.

    q_lat ``[B, H, W]`` (``[q_nope W_uk | q_rope | 0..]``, ``W`` whole
    lanes); pool ``[L, P, page, W]``; new_rows ``[B, W]`` (``[c_kv | k_rope
    | 0..]`` of this step's token); tables ``[B, M]`` int32 (all-zero rows
    = idle lanes writing the scratch page); positions ``[B]`` int32 in
    ``[0, M * page)``; layer: int32 scalar (traced). Returns ``(out [B, H,
    rank] in q_lat's dtype: softmax(q . row) . row[:rank], pool')``."""
    from .decode_attention import paged_cache_update

    B, M = tables.shape
    page, width = pool.shape[2], pool.shape[3]
    H = q_lat.shape[1]
    phys = jnp.take_along_axis(
        tables, (positions // page)[:, None], axis=1)[:, 0]
    pool = paged_cache_update(pool, new_rows, phys, positions % page,
                              layer=layer)
    _count_launch("mla_paged_decode")
    G = max(1, min(M, _MLA_CHUNK_ROWS // page))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3, grid=(B,),
        in_specs=[pl.BlockSpec((1, H, width), lambda b, *_: (b, 0, 0)),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, H, rank), lambda b, *_: (b, 0, 0)),
        scratch_shapes=[pltpu.VMEM((2, G, page, width), pool.dtype),
                        pltpu.SemaphoreType.DMA((2,)),
                        pltpu.SMEM((1,), jnp.int32),
                        pltpu.VMEM((H, 1), jnp.float32),
                        pltpu.VMEM((H, 1), jnp.float32),
                        pltpu.VMEM((H, rank), jnp.float32)])
    with jax.named_scope("mla_paged_decode_attention"):
        out = pl.pallas_call(
            functools.partial(_mla_decode_kernel, sm_scale=sm_scale,
                              page=page, pages_per_chunk=G, batch=B,
                              rank=rank),
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((B, H, rank), q_lat.dtype),
            # no check of a copy's bounds (a fifth of the kernel's time): a
            # page index is an entry of the cache manager's table, in
            # [0, P) (tests/test_kimi_k2.py holds it to that), a buffer
            # index a Python ``range``
            compiler_params=_CompilerParams(
                dimension_semantics=("arbitrary",),
                disable_bounds_checks=True),
            interpret=_interpret(),
            name="mla_paged_decode",
        )(jnp.reshape(layer, (1,)).astype(jnp.int32),
          tables.reshape(-1).astype(jnp.int32), positions.astype(jnp.int32),
          q_lat.astype(pool.dtype), pool)
    return out, pool


# Decode attention over a page table, grouped heads, with a lower bound.
# Each key/value head serves ``g = H / kvh`` query heads: their rows
# against a chunk of the head's keys are one ``[g, hd] x [hd, rows]``
# product on the MXU. One grid step a slot; the live pages of ``(position -
# window, position]`` (``window`` None: ``[0, position]``) are copied in as
# in ``_mla_decode_kernel``, double-buffered across chunks AND grid steps.
# ``ring``: the table is a ring, logical page ``j`` at entry ``j % ring``
# (serving/paged_kv.py: a window group's table). Only the chunks that hold
# an end of the span are masked: the last (rows past ``position`` may be
# VMEM no copy has written) and, under a window, the first.
#
# ``split`` (equal heads, :func:`paged_decode_attention`): every row of a
# head's tile is a copy of its one query row, so all rows carry the same
# probabilities ``e``. Rounded to a pool narrower than float32 they would
# lose 2^-9 each; row 0 takes ``e_hi = round(e)`` and the rows behind it
# the remainder ``e_lo = round(e - e_hi)`` through the SAME product with
# the same loaded V tile, and the two are summed at the end: probabilities
# to 2^-17, at no tile load.


def _gqa_decode_kernel(layer_ref, tbl_ref, pos_ref, q_ref, kp_hbm, vp_hbm,
                       o_ref, kbuf, vbuf, sems, w_smem, m_scr, l_scr,
                       acc_scr, *, sm_scale, page, pages_per_chunk, batch,
                       kv_heads, window, ring, split):
    G = pages_per_chunk
    layer = layer_ref[0]
    b = pl.program_id(0)
    hd = q_ref.shape[-1]
    group = q_ref.shape[1] // kv_heads

    def first_page(s):               # the first page a row of the span is on
        if window is None:
            return 0
        return jnp.maximum(pos_ref[s] - window + 1, 0) // page

    def live_pages(s):               # pages holding the span's rows
        return pos_ref[s] // page + 1 - first_page(s)

    def copies(s, c, slot, go):
        n, lo = live_pages(s), first_page(s)
        for i in range(G):
            pg = c * G + i

            @pl.when(pg < n)
            def _():
                j = lo + pg
                phys = tbl_ref[s, j % ring if ring else j]
                for hbm, vmem, kv in ((kp_hbm, kbuf, 0), (vp_hbm, vbuf, 1)):
                    cp = pltpu.make_async_copy(
                        hbm.at[layer, phys], vmem.at[slot, i],
                        sems.at[kv, slot])
                    if go:
                        cp.start()
                    else:
                        cp.wait()

    @pl.when(b == 0)
    def _first():
        w_smem[0] = 0
        copies(0, 0, 0, True)

    pos = pos_ref[b]
    lo_row = first_page(b) * page
    nc = (live_pages(b) + G - 1) // G
    R = G * page
    floor = -1 if window is None else pos - window

    def fold(c, slot, masked):
        """Chunk ``c`` into the running softmax; ``masked``: the chunk may
        hold rows outside the span. Three passes, each over ALL heads:
        the score products, one softmax step on ``(H, R)``, the value
        products. Head after head, every head's chain of product,
        reduction, exponential and product waits for itself (0.12 us a
        head and chunk, twice the copies' time at 16 heads and 256 rows:
        my chip run, PR 42); side by side the products follow each other
        through the MXU."""
        k = kbuf[slot].reshape(R, kbuf.shape[-1])
        v = vbuf[slot].reshape(R, vbuf.shape[-1])
        H = kv_heads * group
        heads = [(slice(h * group, (h + 1) * group),
                  slice(h * hd, (h + 1) * hd)) for h in range(kv_heads)]
        s = jnp.concatenate([
            jax.lax.dot_general(
                q_ref[0, rows, :], k[:, lanes], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            for rows, lanes in heads], axis=0) * sm_scale       # (H, R)
        m_prev = m_scr[...]                                     # (H, 1)
        if masked:
            # masked in the scores AND zeroed where they are values
            at = lo_row + c * R + jax.lax.broadcasted_iota(
                jnp.int32, (R, 1), 0)
            v = jnp.where(at <= pos, v, 0).astype(v.dtype)
            at = lo_row + c * R + jax.lax.broadcasted_iota(
                jnp.int32, (H, R), 1)
            valid = (at <= pos) & (at > floor)
            s = jnp.where(valid, s, -jnp.inf)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        if masked:
            m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
            e = jnp.where(valid, jnp.exp(s - m_safe), 0.0)
        else:
            m_safe = m_new
            e = jnp.exp(s - m_safe)
        alpha = jnp.exp(m_prev - m_safe)
        l_scr[...] = alpha * l_scr[...] + jnp.sum(e, axis=1, keepdims=True)
        p = e.astype(v.dtype)
        if split:
            behind = jax.lax.broadcasted_iota(
                jnp.int32, (H, R), 0) % group > 0
            p = jnp.where(behind, (e - p.astype(jnp.float32)
                                   ).astype(v.dtype), p)
        acc_scr[...] = alpha * acc_scr[...] + jnp.concatenate([
            jnp.dot(p[rows], v[:, lanes], preferred_element_type=jnp.float32)
            for rows, lanes in heads], axis=0)                  # (H, hd)
        m_scr[...] = m_new

    def chunk(c, w):
        slot = w % 2
        last = c + 1 == nc
        nb = jnp.where(last, b + 1, b)
        nxt = jnp.where(last, 0, c + 1)

        @pl.when(nb < batch)
        def _prefetch():
            copies(nb, nxt, 1 - slot, True)

        copies(b, c, slot, False)

        @pl.when(c == 0)
        def _init():
            m_scr[...] = jnp.full(m_scr.shape, -jnp.inf, jnp.float32)
            l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
            acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

        ends = last if window is None else last | (c == 0)
        pl.when(ends)(lambda: fold(c, slot, True))
        pl.when(jnp.logical_not(ends))(lambda: fold(c, slot, False))
        return w + 1

    w_smem[0] = jax.lax.fori_loop(0, nc, chunk, w_smem[0])
    acc = acc_scr[...]
    if split:                        # row 0 of a tile: e_hi's sum + e_lo's
        acc = acc + pltpu.roll(acc, acc.shape[0] - 1, 0)
    o_ref[0] = (acc / jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def _paged_attention_call(name, q, k_pool, v_pool, layer, tables, positions,
                          *, kv_heads, window=None, ring=False,
                          sm_scale=None, split=False):
    """The one builder of the kernel's call: q ``[B, H, hd]`` (``H /
    kv_heads`` rows a key/value head, whole sublane tiles) against the
    pools ``[L, P, page, kv_heads * hd]``, this tick's rows already
    written; ``name`` is the device operation's."""
    B, M = tables.shape
    page, width = k_pool.shape[2], k_pool.shape[3]
    H, hd = q.shape[1], q.shape[2]
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(hd)
    G = _chunk_pages(M, page, width, k_pool.dtype)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3, grid=(B,),
        in_specs=[pl.BlockSpec((1, H, hd), lambda b, *_: (b, 0, 0)),
                  pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, H, hd), lambda b, *_: (b, 0, 0)),
        scratch_shapes=[pltpu.VMEM((2, G, page, width), k_pool.dtype),
                        pltpu.VMEM((2, G, page, width), v_pool.dtype),
                        pltpu.SemaphoreType.DMA((2, 2)),
                        pltpu.SMEM((1,), jnp.int32),
                        pltpu.VMEM((H, 1), jnp.float32),
                        pltpu.VMEM((H, 1), jnp.float32),
                        pltpu.VMEM((H, hd), jnp.float32)])
    return pl.pallas_call(
        functools.partial(
            _gqa_decode_kernel, sm_scale=sm_scale, page=page,
            pages_per_chunk=G, batch=B, kv_heads=kv_heads,
            window=window, ring=M if ring else 0, split=split),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, hd), q.dtype),
        compiler_params=_CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=_interpret(),
        name=name,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32),
      tables.astype(jnp.int32), positions.astype(jnp.int32),
      q.astype(k_pool.dtype), k_pool, v_pool)


def _write_rows(k_pool, v_pool, new_k, new_v, tables, positions, layer,
                ring=False):
    """This tick's rows ``new_k``/``new_v [B, kv_heads * hd]`` through the
    page table: a scatter of B rows on each carried pool."""
    from .decode_attention import paged_cache_update

    M, page = tables.shape[1], k_pool.shape[2]
    logical = positions // page
    phys = jnp.take_along_axis(
        tables, (logical % M if ring else logical)[:, None], axis=1)[:, 0]
    return tuple(paged_cache_update(pool, new, phys, positions % page,
                                    layer=layer)
                 for pool, new in ((k_pool, new_k), (v_pool, new_v)))


def gqa_paged_decode_attention(q, k_pool, v_pool, new_k, new_v, tables,
                               positions, layer, kv_heads: int,
                               window: Optional[int] = None,
                               ring: bool = False,
                               sm_scale: Optional[float] = None):
    """The grouped-query decode step of one layer: this tick's rows written
    through the page table (a scatter on the carried pools), then one-token
    attention over each slot's live pages of ``(position - window,
    position]`` (``window`` None: ``[0, position]``), read where they lie.

    q ``[B, H, hd]``, query head ``i`` served by key/value head ``i // (H /
    kv_heads)``; k_pool/v_pool ``[L, P, page, kv_heads * hd]``; new_k/new_v
    ``[B, kv_heads * hd]``; tables ``[B, M]`` int32 (all-zero rows = idle
    lanes writing the scratch page), with ``ring`` a ring of ``M`` entries,
    logical page ``j`` at ``j % M``; positions ``[B]`` int32; layer: int32
    scalar (traced). Returns ``(out [B, H, hd] in q's dtype, k_pool',
    v_pool')``."""
    k_pool, v_pool = _write_rows(k_pool, v_pool, new_k, new_v, tables,
                                 positions, layer, ring)
    _count_launch("gqa_paged_decode")
    with jax.named_scope("gqa_paged_decode_attention"):
        out = _paged_attention_call(
            "gqa_paged_decode", q, k_pool, v_pool, layer, tables, positions,
            kv_heads=kv_heads, window=window, ring=ring, sm_scale=sm_scale)
    return out, k_pool, v_pool


def paged_decode_attention(q, k_pool, v_pool, layer, tables, positions,
                           sm_scale=None):
    """One-token attention of equal heads read through the page table: the
    kernel at a group of one. The pool is not sliced, gathered or converted
    outside it.

    q [B, nh, hd]; k_pool/v_pool [L, P, page, nh * hd] (the engine's stored
    layout, this step's rows already written); layer: int32 scalar
    (traced: the layer loop's variable); tables [B, M] int32; positions
    [B] int32 in [0, M*page): slot b attends rows [0, positions[b]] of
    its pages ``tables[b, :positions[b]//page + 1]`` and touches no
    other. A dead lane (all-zero table, position 0) reads one row of the
    scratch page. Returns [B, nh, hd] in q's dtype.

    A head's one query row is laid out as a whole sublane tile (its
    copies: the MXU takes tiles, and the scores of a tile are one float32
    vreg a 128 keys), and the copies carry what a narrow pool's rounding
    would drop of the probabilities (``_gqa_decode_kernel``, ``split``)."""
    B, nh, hd = q.shape
    _count_launch("decode_paged")
    rows = jnp.broadcast_to(q[:, :, None], (B, nh, _QUERY_TILE, hd))
    with jax.named_scope("paged_decode_attention"):
        out = _paged_attention_call(
            "paged_decode_attention", rows.reshape(B, nh * _QUERY_TILE, hd),
            k_pool, v_pool, layer, tables, positions, kv_heads=nh,
            sm_scale=sm_scale,
            split=jnp.dtype(k_pool.dtype).itemsize < 4)
    return out.reshape(B, nh, _QUERY_TILE, hd)[:, :, 0]


def fused_paged_decode_attention(q, k_pool, v_pool, new_k, new_v, tables,
                                 positions, layer=None, sm_scale=None):
    """The paged decode step of one layer of equal heads: row write through
    the page table + :func:`paged_decode_attention` (subsumes
    paged_cache_update + paged_gather + decode_attention).

    q/new_k/new_v [B, nh, hd]; k_pool/v_pool [L, P, page, nh * hd] with
    ``layer`` (the engine's carried pools), or one layer's
    [P, page, nh * hd] without; tables [B, M] int32 (all-zero rows = dead
    lanes writing the scratch page); positions [B] int32 in [0, M*page).

    Returns (out [B, nh, hd], k_pool', v_pool'): the row write is a
    scatter of B rows on the pool, in place under donation or as a
    loop's carry; the kernel only reads.
    """
    one_layer = layer is None
    if one_layer:
        k_pool, v_pool, layer = k_pool[None], v_pool[None], 0
    B = q.shape[0]
    k_pool, v_pool = _write_rows(
        k_pool, v_pool, new_k.reshape(B, -1), new_v.reshape(B, -1), tables,
        positions, layer)
    out = paged_decode_attention(q, k_pool, v_pool, layer, tables,
                                 positions, sm_scale=sm_scale)
    if one_layer:
        k_pool, v_pool = k_pool[0], v_pool[0]
    return out, k_pool, v_pool


def _logits_head_kernel(x_ref, scale_ref, bias_ref, w_ref, o_ref, *, eps):
    x32 = x_ref[...].astype(jnp.float32)
    mu = jnp.mean(x32, axis=1, keepdims=True)
    var = jnp.var(x32, axis=1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    y = (y * scale_ref[...].astype(jnp.float32)
         + bias_ref[...].astype(jnp.float32)).astype(x_ref.dtype)
    o_ref[...] = jax.lax.dot_general(
        y, w_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(o_ref.dtype)


def fused_logits_head(x, scale, bias, lm_head, *, eps: float = 1e-5,
                      block_v: int = 1024):
    """Final layernorm + LM-head projection in one launch per vocab tile
    (the decode tick's ln_f + [B, D] x [D, V] matmul). The LN statistics
    are recomputed per tile (D-length row math is free next to the
    matmul); the product accumulates in f32 and rounds through the
    compute dtype exactly like the unfused einsum, so greedy argmax
    parity holds.

    x [B, D]; scale/bias [D]; lm_head [D, V] -> logits [B, V] in x.dtype.
    """
    B, D = x.shape
    V = lm_head.shape[1]
    bv = min(block_v, V)
    nv = -(-V // bv)
    vp = nv * bv
    w = lm_head if vp == V else jnp.concatenate(
        [lm_head, jnp.zeros((D, vp - V), lm_head.dtype)], axis=1)
    _count_launch("decode_logits_head")
    with jax.named_scope("fused_logits_matmul"):
        out = pl.pallas_call(
            functools.partial(_logits_head_kernel, eps=float(eps)),
            grid=(nv,),
            in_specs=[
                pl.BlockSpec((B, D), lambda j: (0, 0)),
                pl.BlockSpec((1, D), lambda j: (0, 0)),
                pl.BlockSpec((1, D), lambda j: (0, 0)),
                pl.BlockSpec((D, bv), lambda j: (0, j)),
            ],
            out_specs=pl.BlockSpec((B, bv), lambda j: (0, j)),
            out_shape=jax.ShapeDtypeStruct((B, vp), x.dtype),
            compiler_params=_CompilerParams(
                dimension_semantics=("parallel",)),
            interpret=_interpret(),
        )(x, scale.reshape(1, D), bias.reshape(1, D), w)
    return out[:, :V]
