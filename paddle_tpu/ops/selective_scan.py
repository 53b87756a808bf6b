"""Selective scan (Mamba-1): the recurrence of a state-space mixer.

For every channel ``d`` of ``Di`` and state ``n`` of ``N``::

    h[t] = exp(delta[t, d] * A[d, n]) * h[t-1] + delta[t, d] * x[t, d] * B[t, n]
    y[t, d] = sum_n h[t, d, n] * C[t, n] + D[d] * x[t, d]
    y[t] = y[t] * silu(z[t])

Two forms, as every mixer of the serving engine has (``models/jamba.py``):

- :func:`selective_scan`: a whole padded sequence ``[T, Di]`` with a
  ``length``. Positions at or past ``length`` get ``delta = 0``:
  ``exp(0) = 1`` and the input term is 0, so the state the call returns is
  the state after position ``length - 1`` whatever the padding holds. On a
  TPU the recurrence is the Pallas kernel ``selective_scan_fwd``: the state
  stays in VMEM in float32 across the chunks of ``T`` and ``[T, Di, N]``
  never reaches HBM (the plain XLA lowering writes it: 503 MB a layer at
  T = 1536, Di = 5120). Off the TPU the same function is a ``lax.scan``
  over tokens; ``use_pallas=True`` there runs the kernel in interpret mode
  (its own test).
- :func:`selective_state_update`: one token for a batch of slots with an
  ``active`` mask, plain ``jax.numpy``: one fused read and write of the
  state, and a lane that does not ride keeps its state bit for bit.

The state is laid out ``[N, Di]`` (state major, channels on the lanes):
``Di`` is a multiple of 128 and ``N`` of 8, so a float32 state fills whole
(8, 128) tiles. ``A`` is handed over in the same layout
(``A_t = -exp(A_log).T``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import pallas_kernels as _pk

__all__ = ["selective_scan", "selective_scan_reference",
           "selective_state_update", "scan_tiles"]

LANES = 128
# channels one inner loop carries in registers: 4 lane tiles of a [16, .]
# float32 state are 8 vregs, beside as many of A
_CHANNEL_BLOCK = 512


def scan_tiles(d_inner: int, d_state: int) -> bool:
    """Whether Mosaic takes the kernel's blocks at these sizes: channels
    in whole lane tiles, states in whole sublane tiles."""
    return d_inner % LANES == 0 and d_state % 8 == 0


def _masked_delta(delta, length):
    T = delta.shape[0]
    live = jnp.arange(T, dtype=jnp.int32)[:, None] < length
    return jnp.where(live, delta.astype(jnp.float32), 0.0)


def selective_scan_reference(x, delta, A_t, Bm, Cm, D, z, length):
    """The recurrence as a ``lax.scan`` over tokens, float32 inside."""
    f32 = jnp.float32
    delta = _masked_delta(delta, length)
    xf = x.astype(f32)

    def step(h, xs):
        x_t, d_t, b_t, c_t = xs
        h = jnp.exp(d_t[None, :] * A_t) * h \
            + (d_t * x_t)[None, :] * b_t[:, None]
        return h, jnp.sum(h * c_t[:, None], axis=0)

    h0 = jnp.zeros(A_t.shape, f32)
    h, y = jax.lax.scan(step, h0, (xf, delta, Bm.astype(f32),
                                   Cm.astype(f32)))
    y = (y + D.astype(f32)[None, :] * xf) * jax.nn.silu(z.astype(f32))
    return y.astype(x.dtype), h


def _scan_kernel(x_ref, d_ref, z_ref, b_ref, c_ref, a_ref, skip_ref,
                 y_ref, hout_ref, h_scr, dx_scr, y_scr, *, chunk, block):
    f32 = jnp.float32
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        h_scr[...] = jnp.zeros_like(h_scr)

    x = x_ref[...].astype(f32)                          # [chunk, Di]
    dx_scr[...] = d_ref[...] * x
    d_inner = x.shape[1]
    rep = block // LANES
    for j in range(d_inner // block):                   # static
        lanes = pl.ds(j * block, block)
        a = a_ref[:, lanes]                             # [N, block]

        def step(t, h, lanes=lanes, a=a):
            row = pl.ds(t, 1)
            d_t = d_ref[row, lanes]                     # [1, block]
            dx_t = dx_scr[row, lanes]
            b_t = jnp.tile(b_ref[t].astype(f32), (1, rep))   # [N, block]
            c_t = jnp.tile(c_ref[t].astype(f32), (1, rep))
            h = jnp.exp(d_t * a) * h + dx_t * b_t
            y_scr[row, lanes] = jnp.sum(h * c_t, axis=0, keepdims=True)
            return h

        h_scr[:, lanes] = jax.lax.fori_loop(0, chunk, step,
                                            h_scr[:, lanes])
    y = (y_scr[...] + skip_ref[...] * x) \
        * jax.nn.silu(z_ref[...].astype(f32))
    y_ref[...] = y.astype(y_ref.dtype)

    @pl.when(i == pl.num_programs(0) - 1)
    def _():
        hout_ref[...] = h_scr[...]


def _scan_pallas(x, delta, A_t, Bm, Cm, D, z, chunk):
    T, d_inner = x.shape
    n = A_t.shape[0]
    f32 = jnp.float32
    # B and C go in lane-replicated, [T, N, 128]: a step needs B[t] down
    # the sublanes of the state's tiles, and a [N, 1] column is no shape
    # Mosaic loads
    b_rep = jnp.broadcast_to(Bm[:, :, None], (T, n, LANES))
    c_rep = jnp.broadcast_to(Cm[:, :, None], (T, n, LANES))
    block = _CHANNEL_BLOCK if d_inner % _CHANNEL_BLOCK == 0 else LANES
    row = lambda i: (i, 0)
    fixed = lambda i: (0, 0)
    y, h = pl.pallas_call(
        functools.partial(_scan_kernel, chunk=chunk, block=block),
        grid=(T // chunk,),
        in_specs=[pl.BlockSpec((chunk, d_inner), row),
                  pl.BlockSpec((chunk, d_inner), row),
                  pl.BlockSpec((chunk, d_inner), row),
                  pl.BlockSpec((chunk, n, LANES), lambda i: (i, 0, 0)),
                  pl.BlockSpec((chunk, n, LANES), lambda i: (i, 0, 0)),
                  pl.BlockSpec((n, d_inner), fixed),
                  pl.BlockSpec((1, d_inner), fixed)],
        out_specs=[pl.BlockSpec((chunk, d_inner), row),
                   pl.BlockSpec((n, d_inner), fixed)],
        out_shape=[jax.ShapeDtypeStruct((T, d_inner), x.dtype),
                   jax.ShapeDtypeStruct((n, d_inner), f32)],
        scratch_shapes=[pltpu.VMEM((n, d_inner), f32),
                        pltpu.VMEM((chunk, d_inner), f32),
                        pltpu.VMEM((chunk, d_inner), f32)],
        compiler_params=_pk._CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=_pk._interpret(),
        name="selective_scan_fwd",
    )(x, delta, z, b_rep, c_rep, A_t.astype(f32),
      D.astype(f32).reshape(1, d_inner))
    return y, h


def selective_scan(x, delta, A_t, Bm, Cm, D, z, length, *, chunk=None,
                   use_pallas=None):
    """x, z ``[T, Di]`` (the model's dtype), delta ``[T, Di]`` (after its
    softplus; float32 is kept), A_t ``[N, Di]`` float32, Bm, Cm ``[T, N]``,
    D ``[Di]``, length a traced int32 scalar. Returns ``(y [T, Di] as x,
    h [N, Di] float32)``: the gated output and the state after position
    ``length - 1``, from a zero state."""
    T, d_inner = x.shape
    if use_pallas is None:
        use_pallas = _pk._on_tpu() and scan_tiles(d_inner, A_t.shape[0])
    if not use_pallas:
        return selective_scan_reference(x, delta, A_t, Bm, Cm, D, z, length)
    if chunk is None:
        chunk = next(c for c in (64, 32, 16, 8, T) if T % c == 0)
    if T % chunk:
        raise ValueError(f"chunk {chunk} does not divide T {T}")
    return _scan_pallas(x, _masked_delta(delta, length), A_t, Bm, Cm, D, z,
                        chunk)


def selective_state_update(h, x, delta, A_t, Bm, Cm, D, z, active):
    """One token a slot. h ``[B, N, Di]`` float32, x, z ``[B, Di]``, delta
    ``[B, Di]``, Bm, Cm ``[B, N]``, active ``[B]`` (non-zero: the slot
    rides this tick). Returns ``(y [B, Di] as x, h)``; a lane with
    ``active == 0`` keeps its state as it was."""
    f32 = jnp.float32
    xf, d = x.astype(f32), delta.astype(f32)
    new = jnp.exp(d[:, None, :] * A_t[None]) * h \
        + (d * xf)[:, None, :] * Bm.astype(f32)[:, :, None]
    y = jnp.sum(new * Cm.astype(f32)[:, :, None], axis=1)
    y = (y + D.astype(f32)[None, :] * xf) * jax.nn.silu(z.astype(f32))
    h = jnp.where((active != 0)[:, None, None], new, h)
    return y.astype(x.dtype), h
