"""Gated delta rule (Gated DeltaNet, arXiv:2412.06464): the recurrence of a
linear-attention mixer whose state is a matrix a head.

For every head, with ``k`` l2-normalised, ``alpha`` in (0, 1] and ``beta``
in [0, 2] (above 1 the transition ``I - beta k k^T`` has a negative
eigenvalue)::

    S[t] = alpha[t] * S[t-1] (I - beta[t] k[t] k[t]^T) + beta[t] v[t] k[t]^T
    o[t] = S[t] q[t]                      S in R^{dv x dk}

The state is held transposed, ``St = S^T`` ``[dk, dv]``: ``dv`` (192) lies on
the lanes and the products with ``k`` and ``q`` reduce over sublanes, which
the VPU does with plain adds. In that layout ``St[t] = alpha St[t-1] + k
(beta (v - alpha k^T St[t-1]))^T``.

Two forms, as every mixer of the serving engine has:

- :func:`gated_delta_chunked`: a whole padded sequence with a ``length``,
  from a zero state, in the chunkwise (WY / UT transform) form. Inside a
  chunk of ``C`` tokens with ``g`` the running sum of ``log alpha``::

      A[i, j] = beta[i] exp(g[i] - g[j]) (k[i] . k[j])        for j < i
      (I + A) U = diag(beta) V - diag(beta exp(g)) K St0
      O = diag(exp(g)) Q St0 + (exp(g[i] - g[j]) (q[i] . k[j]))_{j <= i} U
      St = exp(g[C]) St0 + (diag(exp(g[C] - g)) K)^T U

  The unit triangular system is solved in float32 whatever the inputs' type
  (the kernel inverts ``I + A`` in diagonal blocks of 32, ``_unit_lower_
  inverse``; the XLA form calls ``solve_triangular``); the other products
  take the inputs' type with float32 sums; the state is float32 and is
  carried across chunks.
  Positions at or past ``length`` get ``log alpha = 0`` and ``beta = 0``:
  they leave the state as it is. On a TPU it is the Pallas kernel
  ``gated_delta_chunk_fwd`` (grid heads x chunks, the state in VMEM); off
  the TPU the same algebra is a ``lax.scan`` over chunks.
- :func:`gated_delta_update`: one token for each rider of a decode tick.
  ``S`` is a layer's whole array of state rows (or all layers' with a
  ``layer``) in the stored layout of :func:`fold_state`, aliased in and
  out; only the rows named by ``slots``
  are read and written, so a slot that does not ride keeps its state bit
  for bit and costs no bytes. On a TPU it is the Pallas kernel
  ``gated_delta_update_rows`` (the array stays in HBM, a rider's row is
  copied in, updated and copied back, double-buffered); off the TPU a
  gather, the update and a scatter.

:func:`gated_delta_recurrence` is the definition, token by token.

**A gate a channel** (Kimi Delta Attention, arXiv:2510.26692):
``alpha[t]`` is a vector over ``dk``, ``St[t] = Diag(alpha[t]) St[t-1] + k
(beta (v - k^T Diag(alpha[t]) St[t-1]))^T``: the same update with
``alpha`` a column down the sublanes where it was a number.
:func:`kda_update` is the one-token kernel with that column
(``kda_update_rows``: the riders in blocks of eight a grid step, so that 80
riders' inputs need not lie in VMEM at once; the row DMA and its double
buffer run on across the steps). :func:`kda_chunked` is another algebra in
kind: with ``G`` the running sum of ``log alpha`` a channel::

    A[i, j] = beta[i] sum_d k[i, d] k[j, d] exp(G[i, d] - G[j, d])

is no product of a decay with ``k[i] . k[j]``. Written as ``(k[i] exp(G[i]
- r)) . (k[j] exp(r - G[j]))`` about one reference ``r`` it overflows where
a channel decays fast across a chunk (``exp(r - G[j])`` at 64 tokens of
``log alpha = -2``), so the chunk is worked in sub-blocks of 16 tokens:
a block of rows ``I`` against the tokens before it takes ``r = G`` at the
block's first row (both exponents are then at most 0), and inside a
diagonal block the differences ``G[i] - G[j]`` are taken pair by pair on
the VPU, where they too are at most 0. The rest (the triangle's inverse,
``U``, ``O`` and the state's step with ``exp(G)`` a factor of ``k`` and
``q`` a channel) is the scalar form's. On a TPU the Pallas kernel
``kda_chunk_fwd``; off it the same algebra in ``jax.numpy``.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import pallas_kernels as _pk

__all__ = ["gated_delta_chunked", "gated_delta_update", "kda_chunked",
           "kda_update", "gated_delta_recurrence", "delta_chunks",
           "state_fold", "fold_state", "unfold_state"]

LANES = 128

_HI = jax.lax.Precision.HIGHEST
_NT = (((1,), (1,)), ((), ()))          # a @ b.T
_TN = (((0,), (0,)), ((), ()))          # a.T @ b


def delta_chunks(tokens: int, chunk: int = 64) -> int:
    """Chunks the chunked form has to process for ``tokens`` live tokens."""
    return -(-int(tokens) // chunk)


def _masked(alpha_log, beta, length):
    """``log alpha`` ``[T, H]`` or, a gate a channel, ``[T, H, dk]``."""
    live = jnp.arange(alpha_log.shape[0], dtype=jnp.int32)[:, None] < length
    gate_live = live if alpha_log.ndim == 2 else live[:, :, None]
    return (jnp.where(gate_live, alpha_log.astype(jnp.float32), 0.0),
            jnp.where(live, beta.astype(jnp.float32), 0.0))


def gated_delta_recurrence(q, k, v, alpha_log, beta, length, state=None):
    """The definition as a ``lax.scan`` over tokens, float32 inside.
    q, k ``[T, H, dk]``, v ``[T, H, dv]``, beta ``[T, H]``, alpha_log ``[T,
    H]`` (a gate a head) or ``[T, H, dk]`` (a gate a channel).
    Returns ``(o [T, H, dv] as v, St [H, dk, dv] float32)``."""
    f32 = jnp.float32
    g, b = _masked(alpha_log, beta, length)
    H, dk, dv = q.shape[1], q.shape[2], v.shape[2]

    def step(St, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        St = jnp.exp(g_t).reshape(H, -1, 1) * St
        r = jnp.einsum("hd,hde->he", k_t, St, precision=_HI)
        St = St + k_t[:, :, None] * (b_t[:, None] * (v_t - r))[:, None, :]
        return St, jnp.einsum("hd,hde->he", q_t, St, precision=_HI)

    St0 = jnp.zeros((H, dk, dv), f32) if state is None else state
    St, o = jax.lax.scan(step, St0, (q.astype(f32), k.astype(f32),
                                     v.astype(f32), g, b))
    return o.astype(v.dtype), St


# ---------------------------------------------------------------------------
# chunked form
# ---------------------------------------------------------------------------

def _chunked_xla(q, k, v, g, beta, chunk):
    """The chunkwise algebra over all heads at once, a ``lax.scan`` over
    chunks. g, beta already masked past ``length``."""
    f32 = jnp.float32
    T, H, dk = q.shape
    dv = v.shape[-1]
    nc, C, mm = T // chunk, chunk, q.dtype

    def heads(x):
        return x.reshape(nc, C, H, -1).transpose(0, 2, 1, 3)

    gc = jnp.cumsum(g.reshape(nc, C, H), axis=1).transpose(0, 2, 1)
    bc = beta.reshape(nc, C, H).transpose(0, 2, 1)
    lower = jnp.tril(jnp.ones((C, C), bool), -1)
    lower_eq = jnp.tril(jnp.ones((C, C), bool))
    eye = jnp.eye(C, dtype=f32)

    def dot(spec, a, b, precision=None):
        return jnp.einsum(spec, a, b, preferred_element_type=f32,
                          precision=precision)

    def step(St, xs):
        q_, k_, v_, g_, b_ = xs             # [H, C, .], [H, C]
        diff = g_[:, :, None] - g_[:, None, :]
        A = jnp.where(lower, b_[:, :, None]
                      * jnp.exp(jnp.where(lower, diff, 0.0))
                      * dot("hid,hjd->hij", k_, k_), 0.0)
        Tm = jax.scipy.linalg.solve_triangular(
            eye + A, jnp.broadcast_to(eye, A.shape), lower=True,
            unit_diagonal=True)
        u0 = dot("hij,hje->hie", Tm, b_[..., None] * v_.astype(f32), _HI)
        w = dot("hij,hjd->hid", Tm,
                (b_ * jnp.exp(g_))[..., None] * k_.astype(f32), _HI)
        Sm = St.astype(mm)
        u = u0 - dot("hid,hde->hie", w.astype(mm), Sm)
        M = jnp.where(lower_eq, jnp.exp(jnp.where(lower_eq, diff, 0.0))
                      * dot("hid,hjd->hij", q_, k_), 0.0)
        o = dot("hid,hde->hie",
                (jnp.exp(g_)[..., None] * q_.astype(f32)).astype(mm), Sm) \
            + dot("hij,hje->hie", M.astype(mm), u.astype(mm))
        gl = g_[:, -1]
        kd = (jnp.exp(gl[:, None] - g_)[..., None] * k_.astype(f32))
        St = jnp.exp(gl)[:, None, None] * St \
            + dot("hid,hie->hde", kd.astype(mm), u.astype(mm))
        return St, o

    St, o = jax.lax.scan(step, jnp.zeros((H, dk, dv), f32),
                         (heads(q), heads(k), heads(v), gc, bc))
    return o.transpose(0, 2, 1, 3).reshape(T, H, dv).astype(v.dtype), St


def _dot(a, b, dims=None, precision=None):
    """A kernel's matrix product: float32 sums, ``a @ b`` unless ``dims``
    says otherwise."""
    if dims is None:
        dims = (((1,), (0,)), ((), ()))
    return jax.lax.dot_general(a, b, dims, precision=precision,
                               preferred_element_type=jnp.float32)


def _column(row, n):
    """A ``[1, n]`` row as a ``[n, 1]`` column, inside a kernel: the
    diagonal of its broadcast, summed along the lanes."""
    eye = (jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (n, n), 1))
    return jnp.sum(jnp.where(eye, jnp.broadcast_to(row, (n, n)), 0.0),
                   axis=1, keepdims=True)


_INVERSE_BLOCK = 32


def _unit_lower_inverse(at):
    """``T = (I + A)^-1`` of a unit lower triangular system, float32,
    inside a kernel; ``at[j, i] = A[i, j]``, so ``I + at`` is upper
    triangular and ``T`` its inverse transposed. Blocked: the diagonal
    blocks of ``b`` rows (32 where that divides a larger ``C``, else the
    one block of ``C``) are inverted side by side by backward
    substitution, block ``k`` in rows and columns ``[k b, (k + 1) b)`` of
    one ``[C, C]`` tile: step ``j`` of ``b - 1`` takes from the rows above
    ``j`` of every block's stripe (rounded up to 8 sublanes) their entry
    of column ``j`` times the stripe's row ``j``, which is final by then.
    The entry is read as a masked sum along the lanes: a sliced column or
    a lane gather is slower on the chip (docs/kernels.md). The stripe's
    other columns take the same steps, so they end as ``N = D M`` (``D``
    the blocks' inverses, ``M`` the part of ``at`` outside the blocks),
    nilpotent block by block, and ``(I + N)^-1 D = (I + N^2)(I + N^4) ..
    (I - N) D`` is products on the MXU: one for two blocks."""
    f32 = jnp.float32
    C = at.shape[0]
    b = _INVERSE_BLOCK if C > _INVERSE_BLOCK and C % _INVERSE_BLOCK == 0 \
        else C
    nb = C // b
    rows = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    same = rows // b == cols // b
    x = jnp.where(same, (rows == cols).astype(f32), at)
    stripes = [x[k * b:(k + 1) * b] for k in range(nb)]
    for j in range(b - 1, 0, -1):
        h = min(-(-j // 8) * 8, b)
        # an iota of its own: Mosaic aborts on ``cols[:h]`` compared
        lane = jax.lax.broadcasted_iota(jnp.int32, (h, C), 1)
        for k, s in enumerate(stripes):
            coef = jnp.sum(jnp.where(lane == k * b + j,
                                     at[k * b:k * b + h], 0.0),
                           axis=1, keepdims=True)
            top = s[:h] - coef * s[j:j + 1]
            stripes[k] = top if h == b else jnp.concatenate(
                [top, s[h:]], axis=0)
    x = jnp.concatenate(stripes, axis=0)
    if nb > 1:
        d = jnp.where(same, x, 0.0)
        n = x - d
        x = d - _dot(n, d, precision=_HI)
        power = 2
        while power < nb:
            n = _dot(n, n, precision=_HI)
            x = x + _dot(n, x, precision=_HI)
            power *= 2
    return x.T


def _chunk_kernel(len_ref, q_ref, k_ref, v_ref, gb_ref, o_ref, s_ref,
                  st_scr, *, chunk):
    f32 = jnp.float32
    C = chunk
    c = pl.program_id(1)
    mm = q_ref.dtype
    dk = q_ref.shape[2]

    @pl.when(c == 0)
    def _():
        st_scr[...] = jnp.zeros_like(st_scr)

    live = c * C < len_ref[0]

    @pl.when(jnp.logical_not(live))
    def _():                            # a chunk of padding: no work
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(live)
    def _():
        dot = _dot
        q, k, v = q_ref[0], k_ref[0], v_ref[0]
        qf, kf, vf = q.astype(f32), k.astype(f32), v.astype(f32)
        gb = gb_ref[0, 0]
        g_row, b_row = gb[0:1, :], gb[1:2, :]               # [1, C]
        rows = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
        g_col, b_col = _column(g_row, C), _column(b_row, C)
        # at[j, i] = A[i, j]: the column of A a row of T needs lies down
        # the sublanes
        upper = rows < cols
        at = jnp.where(upper, b_row * jnp.exp(
            jnp.where(upper, g_row - g_col, 0.0)) * dot(k, k, _NT), 0.0)
        tm = _unit_lower_inverse(at)
        st = st_scr[...]
        sm = st.astype(mm)
        u0 = dot(tm, b_col * vf, precision=_HI)
        w = dot(tm, (b_col * jnp.exp(g_col)) * kf, precision=_HI)
        u = u0 - dot(w.astype(mm), sm)
        um = u.astype(mm)
        lower_eq = rows >= cols
        m = jnp.where(lower_eq, jnp.exp(
            jnp.where(lower_eq, g_col - g_row, 0.0)) * dot(q, k, _NT), 0.0)
        o = dot((jnp.exp(g_col) * qf).astype(mm), sm) + dot(m.astype(mm), um)
        o_ref[0] = o.astype(o_ref.dtype)
        # g never rises inside a chunk: its minimum is its last value
        gl_c = jnp.min(jnp.broadcast_to(g_row, (C, C)), axis=1,
                       keepdims=True)
        gl_k = jnp.min(jnp.broadcast_to(g_row, (dk, C)), axis=1,
                       keepdims=True)
        kd = (jnp.exp(gl_c - g_col) * kf).astype(mm)
        st_scr[...] = jnp.exp(gl_k) * st + dot(kd, um, _TN)

    @pl.when(c == pl.num_programs(1) - 1)
    def _():
        s_ref[0] = st_scr[...]


def _chunked_pallas(q, k, v, g, beta, length, chunk):
    f32 = jnp.float32
    T, H, dk = q.shape
    dv = v.shape[-1]
    nc, C = T // chunk, chunk
    gc = jnp.cumsum(g.reshape(nc, C, H), axis=1)
    gb = jnp.stack([gc, beta.reshape(nc, C, H)], axis=0)    # [2, nc, C, H]
    gb = gb.transpose(3, 1, 0, 2)                           # [H, nc, 2, C]
    block = lambda h, c, *_: (h, c, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(H, nc),
        in_specs=[pl.BlockSpec((1, C, dk), block),
                  pl.BlockSpec((1, C, dk), block),
                  pl.BlockSpec((1, C, dv), block),
                  pl.BlockSpec((1, 1, 2, C), lambda h, c, *_: (h, c, 0, 0))],
        out_specs=[pl.BlockSpec((1, C, dv), block),
                   pl.BlockSpec((1, dk, dv), lambda h, c, *_: (h, 0, 0))],
        scratch_shapes=[pltpu.VMEM((dk, dv), f32)])
    with jax.named_scope("gated_delta_chunk"):
        o, St = pl.pallas_call(
            functools.partial(_chunk_kernel, chunk=C),
            grid_spec=grid_spec,
            out_shape=[jax.ShapeDtypeStruct((H, T, dv), v.dtype),
                       jax.ShapeDtypeStruct((H, dk, dv), f32)],
            compiler_params=_pk._CompilerParams(
                dimension_semantics=("parallel", "arbitrary")),
            interpret=_pk._interpret(),
            name="gated_delta_chunk_fwd",
        )(jnp.reshape(length, (1,)).astype(jnp.int32),
          q.transpose(1, 0, 2), k.transpose(1, 0, 2), v.transpose(1, 0, 2),
          gb)
    return o.transpose(1, 0, 2), St


def gated_delta_chunked(q, k, v, alpha_log, beta, length, chunk=64, *,
                        use_pallas=None):
    """q, k ``[T, H, dk]`` (k l2-normalised, q scaled), v ``[T, H, dv]``,
    alpha_log (``log alpha <= 0``) and beta ``[T, H]``, length a traced
    int32 scalar. Returns ``(o [T, H, dv] as v, St [H, dk, dv] float32)``:
    the outputs and the state after position ``length - 1``, from a zero
    state. ``chunk`` must divide ``T``."""
    T = q.shape[0]
    chunk = min(chunk, T)
    if T % chunk:
        raise ValueError(f"chunk {chunk} does not divide T {T}")
    g, b = _masked(alpha_log, beta, length)
    if use_pallas is None:
        use_pallas = _pk._on_tpu()
    if not use_pallas:
        return _chunked_xla(q, k, v, g, b, chunk)
    return _chunked_pallas(q, k, v, g, b, length, chunk)


# ---------------------------------------------------------------------------
# chunked form, a gate a channel
# ---------------------------------------------------------------------------

KDA_SUB = 16                 # tokens of a sub-block of the chunk


def _kda_scores_xla(a, b, G, sub):
    """``S[i, j] = sum_d a[i, d] b[j, d] exp(G[i, d] - G[j, d])`` for ``j
    <= i``, 0 above the diagonal. a, b ``[H, C, dk]``, G ``[H, C, dk]``
    float32 and never rising along ``C``. Sub-blocks of ``sub`` rows: a
    block against the tokens before it about ``G`` at its first row, a
    diagonal block pair by pair; no exponent is positive."""
    f32 = jnp.float32
    H, C, _ = a.shape
    mm = a.dtype
    af, bf = a.astype(f32), b.astype(f32)
    lower_eq = jnp.tril(jnp.ones((sub, sub), bool))[None, :, :, None]
    out = []
    for lo in range(0, C, sub):
        r = slice(lo, lo + sub)
        diff = G[:, r, None, :] - G[:, None, r, :]      # [H, sub, sub, dk]
        decay = jnp.where(lower_eq, jnp.exp(jnp.where(lower_eq, diff, 0.0)),
                          0.0)
        row = [jnp.sum(af[:, r, None, :] * bf[:, None, r, :] * decay,
                       axis=-1)]
        if lo:
            ref = G[:, lo:lo + 1]
            row.insert(0, jnp.einsum(
                "hid,hjd->hij",
                (af[:, r] * jnp.exp(G[:, r] - ref)).astype(mm),
                (bf[:, :lo] * jnp.exp(ref - G[:, :lo])).astype(mm),
                preferred_element_type=f32))
        if lo + sub < C:
            row.append(jnp.zeros((H, sub, C - lo - sub), f32))
        out.append(jnp.concatenate(row, axis=-1))
    return jnp.concatenate(out, axis=1)


def _kda_chunked_xla(q, k, v, g, beta, chunk):
    """The chunkwise algebra with a gate a channel over all heads at once,
    a ``lax.scan`` over chunks. g ``[T, H, dk]``, beta ``[T, H]`` already
    masked past ``length``."""
    f32 = jnp.float32
    T, H, dk = q.shape
    dv = v.shape[-1]
    nc, C, mm = T // chunk, chunk, q.dtype
    sub = KDA_SUB if C % KDA_SUB == 0 else C

    def heads(x):
        return x.reshape(nc, C, H, -1).transpose(0, 2, 1, 3)

    Gc = heads(jnp.cumsum(g.reshape(nc, C, H, dk), axis=1))
    bc = beta.reshape(nc, C, H).transpose(0, 2, 1)
    lower = jnp.tril(jnp.ones((C, C), bool), -1)
    eye = jnp.eye(C, dtype=f32)

    def dot(spec, a, b, precision=None):
        return jnp.einsum(spec, a, b, preferred_element_type=f32,
                          precision=precision)

    def step(St, xs):
        q_, k_, v_, G_, b_ = xs             # [H, C, .], [H, C, dk], [H, C]
        A = jnp.where(lower, b_[:, :, None] * _kda_scores_xla(k_, k_, G_,
                                                              sub), 0.0)
        Tm = jax.scipy.linalg.solve_triangular(
            eye + A, jnp.broadcast_to(eye, A.shape), lower=True,
            unit_diagonal=True)
        eg = jnp.exp(G_)
        u0 = dot("hij,hje->hie", Tm, b_[..., None] * v_.astype(f32), _HI)
        w = dot("hij,hjd->hid", Tm,
                b_[..., None] * eg * k_.astype(f32), _HI)
        Sm = St.astype(mm)
        u = u0 - dot("hid,hde->hie", w.astype(mm), Sm)
        M = _kda_scores_xla(q_, k_, G_, sub)
        o = dot("hid,hde->hie", (eg * q_.astype(f32)).astype(mm), Sm) \
            + dot("hij,hje->hie", M.astype(mm), u.astype(mm))
        gl = G_[:, -1]                                      # [H, dk]
        kd = jnp.exp(gl[:, None, :] - G_) * k_.astype(f32)
        St = jnp.exp(gl)[:, :, None] * St \
            + dot("hid,hie->hde", kd.astype(mm), u.astype(mm))
        return St, o

    St, o = jax.lax.scan(step, jnp.zeros((H, dk, dv), f32),
                         (heads(q), heads(k), heads(v), Gc, bc))
    return o.transpose(0, 2, 1, 3).reshape(T, H, dv).astype(v.dtype), St


def _kda_chunk_kernel(len_ref, q_ref, k_ref, v_ref, g_ref, b_ref, o_ref,
                      s_ref, st_scr, qf_scr, kf_scr, *, chunk, sub):
    f32 = jnp.float32
    C = chunk
    c = pl.program_id(1)
    mm = q_ref.dtype
    dk = q_ref.shape[2]

    @pl.when(c == 0)
    def _():
        st_scr[...] = jnp.zeros_like(st_scr)

    live = c * C < len_ref[0]

    @pl.when(jnp.logical_not(live))
    def _():                            # a chunk of padding: no work
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(live)
    def _():
        dot = _dot
        q, k, v = q_ref[0], k_ref[0], v_ref[0]
        qf, kf, vf = q.astype(f32), k.astype(f32), v.astype(f32)
        qf_scr[...] = qf                # rows are read back one by one
        kf_scr[...] = kf
        G = g_ref[0]                                        # [C, dk]
        b_row = b_ref[0, 0]                                 # [1, C]
        b_col = _column(b_row, C)
        rows = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
        # at[j, i] = A[i, j] / beta[i] and m[i, j], j <= i, both sums over
        # the channels of a product with exp(G[i] - G[j]). A block of
        # tokens I against the tokens before it: about G at the block's
        # first row, both factors' exponents at most 0, full tiles on the
        # MXU and the block's part selected (the clamp keeps the rows that
        # are not selected finite)
        at = jnp.zeros((C, C), f32)
        m = jnp.zeros((C, C), f32)
        for lo in range(sub, C, sub):
            ref = g_ref[0, lo:lo + 1, :]                    # [1, dk]
            dec = jnp.exp(jnp.minimum(G - ref, 0.0))
            inc = (kf * jnp.exp(jnp.minimum(ref - G, 0.0))).astype(mm)
            block = (cols >= lo) & (cols < lo + sub) & (rows < lo)
            at = jnp.where(block, dot(inc, (kf * dec).astype(mm), _NT), at)
            block = (rows >= lo) & (rows < lo + sub) & (cols < lo)
            m = jnp.where(block, dot((qf * dec).astype(mm), inc, _NT), m)
        # inside a diagonal block pair by pair, token t against its
        # block's rows: G[t] - G[j] for j < t (a column of at), G[i] - G[t]
        # for i >= t (a column of m)
        srow = jax.lax.broadcasted_iota(jnp.int32, (sub, 1), 0)
        scol = jax.lax.broadcasted_iota(jnp.int32, (sub, C), 1)
        at_diag, m_diag = [], []
        for lo in range(0, C, sub):
            Gb = g_ref[0, lo:lo + sub, :]                   # [sub, dk]
            kb = kf_scr[lo:lo + sub, :]
            qb = qf_scr[lo:lo + sub, :]
            a_tile = jnp.zeros((sub, C), f32)
            m_tile = jnp.zeros((sub, C), f32)
            for t in range(lo, lo + sub):
                k_t = kf_scr[t:t + 1, :]                    # [1, dk]
                diff = Gb - g_ref[0, t:t + 1, :]            # G[.] - G[t]
                before = srow < t - lo
                e_a = jnp.where(before, jnp.exp(
                    jnp.where(before, -diff, 0.0)), 0.0)
                e_m = jnp.where(before, 0.0, jnp.exp(
                    jnp.where(before, 0.0, diff)))
                a_col = jnp.sum(kb * k_t * e_a, axis=1, keepdims=True)
                m_col = jnp.sum(qb * k_t * e_m, axis=1, keepdims=True)
                a_tile = jnp.where(scol == t, a_col, a_tile)
                m_tile = jnp.where(scol == t, m_col, m_tile)
            at_diag.append(a_tile)
            m_diag.append(m_tile)
        at = (at + jnp.concatenate(at_diag, axis=0)) * b_row
        m = m + jnp.concatenate(m_diag, axis=0)
        tm = _unit_lower_inverse(at)
        st = st_scr[...]
        sm = st.astype(mm)
        eg = jnp.exp(G)
        u0 = dot(tm, b_col * vf, precision=_HI)
        w = dot(tm, b_col * (eg * kf), precision=_HI)
        u = u0 - dot(w.astype(mm), sm)
        um = u.astype(mm)
        o = dot((eg * qf).astype(mm), sm) + dot(m.astype(mm), um)
        o_ref[0] = o.astype(o_ref.dtype)
        g_last = g_ref[0, C - 1:C, :]                       # [1, dk]
        kd = (jnp.exp(g_last - G) * kf).astype(mm)
        st_scr[...] = jnp.exp(_column(g_last, dk)) * st + dot(kd, um, _TN)

    @pl.when(c == pl.num_programs(1) - 1)
    def _():
        s_ref[0] = st_scr[...]


def _kda_chunked_pallas(q, k, v, g, beta, length, chunk):
    f32 = jnp.float32
    T, H, dk = q.shape
    dv = v.shape[-1]
    nc, C = T // chunk, chunk
    sub = KDA_SUB if C % KDA_SUB == 0 else C
    Gc = jnp.cumsum(g.reshape(nc, C, H, dk), axis=1).reshape(T, H, dk)
    b = beta.reshape(nc, C, H).transpose(2, 0, 1)[:, :, None, :]
    block = lambda h, c, *_: (h, c, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(H, nc),
        in_specs=[pl.BlockSpec((1, C, dk), block),
                  pl.BlockSpec((1, C, dk), block),
                  pl.BlockSpec((1, C, dv), block),
                  pl.BlockSpec((1, C, dk), block),
                  pl.BlockSpec((1, 1, 1, C), lambda h, c, *_: (h, c, 0, 0))],
        out_specs=[pl.BlockSpec((1, C, dv), block),
                   pl.BlockSpec((1, dk, dv), lambda h, c, *_: (h, 0, 0))],
        scratch_shapes=[pltpu.VMEM((dk, dv), f32),
                        pltpu.VMEM((C, dk), f32),
                        pltpu.VMEM((C, dk), f32)])
    with jax.named_scope("kda_chunk"):
        o, St = pl.pallas_call(
            functools.partial(_kda_chunk_kernel, chunk=C, sub=sub),
            grid_spec=grid_spec,
            out_shape=[jax.ShapeDtypeStruct((H, T, dv), v.dtype),
                       jax.ShapeDtypeStruct((H, dk, dv), f32)],
            compiler_params=_pk._CompilerParams(
                dimension_semantics=("parallel", "arbitrary")),
            interpret=_pk._interpret(),
            name="kda_chunk_fwd",
        )(jnp.reshape(length, (1,)).astype(jnp.int32),
          q.transpose(1, 0, 2), k.transpose(1, 0, 2), v.transpose(1, 0, 2),
          Gc.transpose(1, 0, 2), b)
    return o.transpose(1, 0, 2), St


def kda_chunked(q, k, v, alpha_log, beta, length, chunk=64, *,
                use_pallas=None):
    """:func:`gated_delta_chunked` with a gate a channel: alpha_log ``[T,
    H, dk]`` (``log alpha <= 0``), everything else as there. Returns ``(o
    [T, H, dv] as v, St [H, dk, dv] float32)`` from a zero state; positions
    at or past ``length`` leave the state as it is."""
    T = q.shape[0]
    chunk = min(chunk, T)
    if T % chunk:
        raise ValueError(f"chunk {chunk} does not divide T {T}")
    g, b = _masked(alpha_log, beta, length)
    if use_pallas is None:
        use_pallas = _pk._on_tpu()
    if not use_pallas:
        return _kda_chunked_xla(q, k, v, g, b, chunk)
    return _kda_chunked_pallas(q, k, v, g, b, length, chunk)


# ---------------------------------------------------------------------------
# one token a rider
# ---------------------------------------------------------------------------

def state_fold(heads: int, dv: int) -> int:
    """Heads that share a row of the stored state. The TPU tiles an
    array's minor axis in 128 lanes, so a ``[dk, 192]`` float32 matrix a
    head would lie in HBM as ``[dk, 256]``: a third more bytes held, read
    and written. ``f`` heads side by side (``[dk, f * dv]``, 384 lanes for
    two heads of 192) fill whole tiles."""
    f = LANES // math.gcd(dv, LANES)
    return f if heads % f == 0 else 1


def fold_state(St):
    """``[..., H, dk, dv]`` -> the stored ``[..., H / f, dk, f * dv]``."""
    *lead, H, dk, dv = St.shape
    f = state_fold(H, dv)
    St = St.reshape(*lead, H // f, f, dk, dv)
    return jnp.swapaxes(St, -3, -2).reshape(*lead, H // f, dk, f * dv)


def unfold_state(S, dv: int):
    """The stored ``[..., G, dk, f * dv]`` -> ``[..., G * f, dk, dv]``."""
    *lead, G, dk, fdv = S.shape
    f = fdv // dv
    S = jnp.swapaxes(S.reshape(*lead, G, dk, f, dv), -3, -2)
    return S.reshape(*lead, G * f, dk, dv)


def _update_rows(St, q, k, v, alpha, beta):
    """St ``[B, H, dk, dv]`` float32, q, k ``[B, H, dk]``, v ``[B, H,
    dv]``, beta ``[B, H]``, alpha ``[B, H]`` or, a gate a channel, ``[B,
    H, dk]``, all float32: one step of every row."""
    Sa = alpha.reshape(alpha.shape[:2] + (-1, 1)) * St
    r = jnp.sum(k[..., None] * Sa, axis=2)
    St = Sa + k[..., None] * (beta[..., None] * (v - r))[:, :, None, :]
    return jnp.sum(q[..., None] * St, axis=2), St


def _update_kernel(layer_ref, slots_ref, n_ref, qt_ref, kt_ref, v_ref,
                   a_ref, b_ref, s_hbm, o_ref, s_out, buf, sem_in, sem_out,
                   *, fold, dv, channel_gate=False, rider_block=None):
    """``channel_gate``: ``a_ref`` is ``[riders, dk, H]`` as ``kt_ref``
    (alpha a column down the sublanes), not a number a head over its
    lanes, and ``v_ref``, ``b_ref`` and ``o_ref`` are ``[riders, groups,
    width]``, a rider's slab taken whole by its leading index (Mosaic loads
    one row of 128 lanes at a traced sublane offset from no ``[riders,
    groups * width]`` array; of 384 it does). ``rider_block``: the riders
    of one grid step where the call's inputs come in blocks (None: one
    step has them all); the row DMA and its double buffer run on across
    the steps."""
    layer, n = layer_ref[0], n_ref[0]
    groups, dk, width = buf.shape[1:]
    blocked = rider_block is not None
    step = pl.program_id(0) if blocked else 0
    base = step * rider_block if blocked else 0

    def fetch(i, b):
        return pltpu.make_async_copy(s_hbm.at[layer, slots_ref[i]],
                                     buf.at[b], sem_in.at[b])

    def store(i, b):
        return pltpu.make_async_copy(buf.at[b],
                                     s_out.at[layer, slots_ref[i]],
                                     sem_out.at[b])

    o_ref[...] = jnp.zeros_like(o_ref)
    lane = jax.lax.broadcasted_iota(jnp.int32, (dk, width), 1)

    def columns(xt, g):
        """The ``fold`` heads' vectors of group ``g`` down the sublanes,
        each over its own ``dv`` lanes: [dk, fold * dv]."""
        out = jnp.broadcast_to(xt[:, g * fold:g * fold + 1], (dk, width))
        for a in range(1, fold):
            h = g * fold + a
            out = jnp.where(lane >= a * dv, xt[:, h:h + 1], out)
        return out

    @pl.when((n > 0) & (step == 0) if blocked else n > 0)
    def _():
        fetch(0, 0).start()

    def rider(local, carry):            # the rider's row of its block
        i = base + local if blocked else local
        b = i % 2

        @pl.when(i >= 1)
        def _():                        # the other buffer is free again
            store(i - 1, 1 - b).wait()

        @pl.when(i + 1 < n)
        def _():
            fetch(i + 1, 1 - b).start()

        fetch(i, b).wait()
        qt, kt = qt_ref[local], kt_ref[local]           # [dk, H]
        if channel_gate:
            at, vt, bt = a_ref[local], v_ref[local], b_ref[local]
        row = pl.ds(local, 1)
        for g in range(groups):
            lanes = pl.ds(g * width, width)
            kc = columns(kt, g)
            if channel_gate:
                decay, v_g, b_g = columns(at, g), vt[g:g + 1], bt[g:g + 1]
            else:
                decay, v_g, b_g = (a_ref[row, lanes], v_ref[row, lanes],
                                   b_ref[row, lanes])
            sa = decay * buf[b, g]                      # [dk, f * dv]
            r = jnp.sum(kc * sa, axis=0, keepdims=True)
            u = b_g * (v_g - r)
            new = sa + kc * u
            buf[b, g] = new
            o_g = jnp.sum(columns(qt, g) * new, axis=0, keepdims=True)
            if channel_gate:
                o_ref[local, g:g + 1, :] = o_g
            else:
                o_ref[row, lanes] = o_g
        store(i, b).start()
        return carry

    jax.lax.fori_loop(
        0, jnp.clip(n - base, 0, rider_block) if blocked else n, rider, 0)

    # the step that holds the last rider waits for its row's way back
    @pl.when((n > 0) & (step == (n - 1) // rider_block) if blocked
             else n > 0)
    def _():
        store(n - 1, (n - 1) % 2).wait()


_RIDER_BLOCK = 8             # riders a grid step where a call's are blocked
_UPDATE_VMEM = 40 << 20      # what the inputs and the row buffers may take


def _rider_block(B, H, dk, dv, buf_bytes, channel_gate):
    """None where all ``B`` riders' inputs fit VMEM beside the two row
    buffers (Pallas holds an input twice), else :data:`_RIDER_BLOCK`. A
    ``[dk, H]`` float32 slab lies in whole 128-lane tiles."""
    slab = dk * (-(-H // LANES) * LANES) * 4
    per_rider = (3 if channel_gate else 2) * slab \
        + (3 if channel_gate else 4) * H * dv * 4
    if (2 * B * per_rider + buf_bytes <= _UPDATE_VMEM
            or B % _RIDER_BLOCK or B == _RIDER_BLOCK):
        return None
    return _RIDER_BLOCK


def _update_pallas(S, q, k, v, alpha, beta, slots, layer):
    f32 = jnp.float32
    B, H, dk = q.shape
    dv = v.shape[-1]
    G, width = S.shape[2], S.shape[4]
    channel_gate = alpha.ndim == 3
    rb = _rider_block(B, H, dk, dv, 2 * G * dk * width * 4, channel_gate)
    # riders first, in lane order: the kernel walks the first n lanes
    rides = slots >= 0
    order = jnp.argsort(jnp.logical_not(rides), stable=True)
    n = jnp.sum(rides).astype(jnp.int32)
    take = lambda x: jnp.take(x.astype(f32), order, axis=0)
    wide = lambda x: jnp.repeat(take(x), dv, axis=1)    # [B, H] -> [B, H dv]
    def riders(*shape):
        """The riders' ``[B, *shape]`` input: whole, or ``rb`` a step."""
        if rb is None:
            return pl.BlockSpec((B,) + shape,
                                lambda i, *_: (0,) * (len(shape) + 1))
        return pl.BlockSpec((rb,) + shape,
                            lambda i, *_: (i,) + (0,) * len(shape))

    steps = 1 if rb is None else B // rb
    heads_t = lambda x: take(x).transpose(0, 2, 1)      # [B, dk, H]
    kernel = functools.partial(_update_kernel, fold=width // dv, dv=dv,
                               channel_gate=channel_gate, rider_block=rb)
    if channel_gate:
        gate_spec, gate = riders(dk, H), heads_t(alpha)
        flat = (G, width)               # a rider's slab, by leading index
        scope, name = "kda_update", "kda_update_rows"
    else:
        gate_spec, gate = riders(H * dv), wide(alpha)
        flat = (H * dv,)
        scope, name = "gated_delta_update", "gated_delta_update_rows"
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3, grid=(steps,),
        in_specs=[riders(dk, H), riders(dk, H), riders(*flat),
                  gate_spec, riders(*flat),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=[riders(*flat), pl.BlockSpec(memory_space=pl.ANY)],
        scratch_shapes=[pltpu.VMEM((2, G, dk, width), f32),
                        pltpu.SemaphoreType.DMA((2,)),
                        pltpu.SemaphoreType.DMA((2,))])
    with jax.named_scope(scope):
        o, S = pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=[jax.ShapeDtypeStruct((B,) + flat, f32),
                       jax.ShapeDtypeStruct(S.shape, S.dtype)],
            # operands count the scalar prefetch: S is the ninth
            input_output_aliases={8: 1},
            compiler_params=_pk._CompilerParams(
                dimension_semantics=("arbitrary",),
                vmem_limit_bytes=48 << 20),
            interpret=_pk._interpret(),
            name=name,
        )(jnp.reshape(layer, (1,)).astype(jnp.int32),
          jnp.take(jnp.maximum(slots, 0).astype(jnp.int32), order),
          jnp.reshape(n, (1,)),
          heads_t(q), heads_t(k),
          take(v).reshape((B,) + flat), gate,
          wide(beta).reshape((B,) + flat), S)
    back = jnp.argsort(order)
    return jnp.take(o, back, axis=0).reshape(B, H, dv), S


def kda_update(S, q, k, v, alpha, beta, slots, layer=None, *,
               use_pallas=None):
    """:func:`gated_delta_update` with a gate a channel: alpha ``[B, H,
    dk]``, everything else as there (on a TPU the kernel
    ``kda_update_rows``)."""
    if alpha.ndim != 3:
        raise ValueError(f"alpha {alpha.shape}: a gate a channel is [B, H, "
                         "dk]")
    return _update(S, q, k, v, alpha, beta, slots, layer, use_pallas)


def gated_delta_update(S, q, k, v, alpha, beta, slots, layer=None, *,
                       use_pallas=None):
    """One token for each rider. S is a layer's whole state array, float32
    in the stored layout of :func:`fold_state`, ``[slots, H / f, dk, f *
    dv]``, or all layers' ``[L, slots, ...]`` with ``layer`` (an int32
    scalar, traced: the layer loop's variable); q, k ``[B, H, dk]``, v
    ``[B, H, dv]``, alpha, beta ``[B, H]``; slots ``[B]`` int32: the state
    row lane ``b`` advances, negative for a lane that does not ride.
    Returns ``(o [B, H, dv] as v, S)``: only the rows ``slots`` names are
    read and written, every other row is what it was bit for bit, and a
    lane that does not ride gets zeros."""
    if alpha.ndim != 2:
        raise ValueError(f"alpha {alpha.shape}: a gate a head is [B, H]")
    return _update(S, q, k, v, alpha, beta, slots, layer, use_pallas)


def _update(S, q, k, v, alpha, beta, slots, layer, use_pallas):
    f32 = jnp.float32
    one_layer = layer is None
    if one_layer:
        S, layer = S[None], 0
    if use_pallas is None:
        use_pallas = _pk._on_tpu()
    if use_pallas:
        o, S = _update_pallas(S, q, k, v, alpha, beta, slots, layer)
    else:
        dv = v.shape[-1]
        rides = slots >= 0
        rows = jnp.where(rides, slots, 0)
        o, new = _update_rows(unfold_state(S[layer, rows], dv),
                              q.astype(f32), k.astype(f32), v.astype(f32),
                              alpha.astype(f32), beta.astype(f32))
        o = jnp.where(rides[:, None, None], o, 0.0)
        # a lane that does not ride scatters out of bounds: dropped
        S = S.at[layer, jnp.where(rides, slots, S.shape[1])].set(
            fold_state(new), mode="drop")
    return o.astype(v.dtype), (S[0] if one_layer else S)
