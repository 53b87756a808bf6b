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

  The unit triangular system is solved in float32 by forward substitution
  whatever the inputs' type; the other products take the inputs' type with
  float32 sums; the state is float32 and is carried across chunks.
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
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import pallas_kernels as _pk

__all__ = ["gated_delta_chunked", "gated_delta_update",
           "gated_delta_recurrence", "delta_chunks", "state_fold",
           "fold_state", "unfold_state"]

LANES = 128

_HI = jax.lax.Precision.HIGHEST
_NT = (((1,), (1,)), ((), ()))          # a @ b.T
_TN = (((0,), (0,)), ((), ()))          # a.T @ b


def delta_chunks(tokens: int, chunk: int = 64) -> int:
    """Chunks the chunked form has to process for ``tokens`` live tokens."""
    return -(-int(tokens) // chunk)


def _masked(alpha_log, beta, length):
    live = jnp.arange(alpha_log.shape[0], dtype=jnp.int32)[:, None] < length
    return (jnp.where(live, alpha_log.astype(jnp.float32), 0.0),
            jnp.where(live, beta.astype(jnp.float32), 0.0))


def gated_delta_recurrence(q, k, v, alpha_log, beta, length, state=None):
    """The definition as a ``lax.scan`` over tokens, float32 inside.
    q, k ``[T, H, dk]``, v ``[T, H, dv]``, alpha_log, beta ``[T, H]``.
    Returns ``(o [T, H, dv] as v, St [H, dk, dv] float32)``."""
    f32 = jnp.float32
    g, b = _masked(alpha_log, beta, length)
    H, dk, dv = q.shape[1], q.shape[2], v.shape[2]

    def step(St, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        St = jnp.exp(g_t)[:, None, None] * St
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


def _chunk_kernel(len_ref, q_ref, k_ref, v_ref, gb_ref, o_ref, s_ref,
                  st_scr, t_scr, *, chunk):
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
        def dot(a, b, dims=None, precision=None):
            if dims is None:
                dims = (((1,), (0,)), ((), ()))
            return jax.lax.dot_general(a, b, dims, precision=precision,
                                       preferred_element_type=f32)

        q, k, v = q_ref[0], k_ref[0], v_ref[0]
        qf, kf, vf = q.astype(f32), k.astype(f32), v.astype(f32)
        gb = gb_ref[0, 0]
        g_row, b_row = gb[0:1, :], gb[1:2, :]               # [1, C]
        rows = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
        eye = rows == cols

        def column(row):                # [1, C] -> [C, 1]
            return jnp.sum(jnp.where(eye, jnp.broadcast_to(row, (C, C)),
                                     0.0), axis=1, keepdims=True)

        g_col, b_col = column(g_row), column(b_row)
        # at[j, i] = A[i, j]: the column of A a row of T needs lies down
        # the sublanes
        upper = rows < cols
        at = jnp.where(upper, b_row * jnp.exp(
            jnp.where(upper, g_row - g_col, 0.0)) * dot(k, k, _NT), 0.0)
        # T = (I + A)^-1 by forward substitution, float32 on the VPU: row
        # i is e_i - A[i, :i] T[:i]
        t_scr[...] = eye.astype(f32)
        for i in range(1, C):
            r8 = -(-i // 8) * 8
            row = jnp.sum(at[:r8, i:i + 1] * t_scr[:r8, :], axis=0,
                          keepdims=True)
            t_scr[i:i + 1, :] = t_scr[i:i + 1, :] - row
        tm = t_scr[...]
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
        scratch_shapes=[pltpu.VMEM((dk, dv), f32),
                        pltpu.VMEM((C, C), f32)])
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
    dv]``, alpha, beta ``[B, H]``, all float32: one step of every row."""
    Sa = alpha[:, :, None, None] * St
    r = jnp.sum(k[..., None] * Sa, axis=2)
    St = Sa + k[..., None] * (beta[..., None] * (v - r))[:, :, None, :]
    return jnp.sum(q[..., None] * St, axis=2), St


def _update_kernel(layer_ref, slots_ref, n_ref, qt_ref, kt_ref, v_ref,
                   a_ref, b_ref, s_hbm, o_ref, s_out, buf, sem_in, sem_out,
                   *, fold, dv):
    layer, n = layer_ref[0], n_ref[0]
    groups, dk, width = buf.shape[1:]

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

    @pl.when(n > 0)
    def _():
        fetch(0, 0).start()

    def rider(i, carry):
        b = i % 2

        @pl.when(i >= 1)
        def _():                        # the other buffer is free again
            store(i - 1, 1 - b).wait()

        @pl.when(i + 1 < n)
        def _():
            fetch(i + 1, 1 - b).start()

        fetch(i, b).wait()
        qt, kt = qt_ref[i], kt_ref[i]                   # [dk, H]
        row = pl.ds(i, 1)
        for g in range(groups):
            lanes = pl.ds(g * width, width)
            kc = columns(kt, g)
            sa = a_ref[row, lanes] * buf[b, g]          # [dk, f * dv]
            r = jnp.sum(kc * sa, axis=0, keepdims=True)
            u = b_ref[row, lanes] * (v_ref[row, lanes] - r)
            new = sa + kc * u
            buf[b, g] = new
            o_ref[row, lanes] = jnp.sum(columns(qt, g) * new, axis=0,
                                        keepdims=True)
        store(i, b).start()
        return carry

    jax.lax.fori_loop(0, n, rider, 0)

    @pl.when(n > 0)
    def _():
        store(n - 1, (n - 1) % 2).wait()


def _update_pallas(S, q, k, v, alpha, beta, slots, layer):
    f32 = jnp.float32
    B, H, dk = q.shape
    dv = v.shape[-1]
    G, width = S.shape[2], S.shape[4]
    # riders first, in lane order: the kernel walks the first n lanes
    rides = slots >= 0
    order = jnp.argsort(jnp.logical_not(rides), stable=True)
    n = jnp.sum(rides).astype(jnp.int32)
    take = lambda x: jnp.take(x.astype(f32), order, axis=0)
    wide = lambda x: jnp.repeat(take(x), dv, axis=1)    # [B, H] -> [B, H dv]
    whole = lambda *shape: pl.BlockSpec(shape,
                                        lambda i, *_: (0,) * len(shape))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3, grid=(1,),
        in_specs=[whole(B, dk, H), whole(B, dk, H), whole(B, H * dv),
                  whole(B, H * dv), whole(B, H * dv),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=[whole(B, H * dv), pl.BlockSpec(memory_space=pl.ANY)],
        scratch_shapes=[pltpu.VMEM((2, G, dk, width), f32),
                        pltpu.SemaphoreType.DMA((2,)),
                        pltpu.SemaphoreType.DMA((2,))])
    with jax.named_scope("gated_delta_update"):
        o, S = pl.pallas_call(
            functools.partial(_update_kernel, fold=width // dv, dv=dv),
            grid_spec=grid_spec,
            out_shape=[jax.ShapeDtypeStruct((B, H * dv), f32),
                       jax.ShapeDtypeStruct(S.shape, S.dtype)],
            # operands count the scalar prefetch: S is the ninth
            input_output_aliases={8: 1},
            compiler_params=_pk._CompilerParams(
                dimension_semantics=("arbitrary",),
                vmem_limit_bytes=48 << 20),
            interpret=_pk._interpret(),
            name="gated_delta_update_rows",
        )(jnp.reshape(layer, (1,)).astype(jnp.int32),
          jnp.take(jnp.maximum(slots, 0).astype(jnp.int32), order),
          jnp.reshape(n, (1,)),
          take(q).transpose(0, 2, 1), take(k).transpose(0, 2, 1),
          take(v).reshape(B, H * dv), wide(alpha), wide(beta), S)
    back = jnp.argsort(order)
    return jnp.take(o, back, axis=0).reshape(B, H, dv), S


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
