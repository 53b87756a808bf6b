"""What the served families share (``jamba``, ``kimi_k2``, ``olmo_hybrid``,
``cohere2_moe``, ``solar_open2``): the pieces that are the same operations
in the same order in every family that has them. A family imports from
here and never from a sibling. A piece two families spell with one
operation's difference (the shared experts' product, a layer norm against
an RMS norm, a tied head) stays in each family's file: nothing here takes
an argument that says who calls.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["rms_norm", "gated_mlp", "rms_logits", "layer_at", "mlp_shapes",
           "hold_leaves", "ffn_chunk", "over_ffn_chunks"]


def rms_norm(x, gain, eps):
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps)
            * gain.astype(jnp.float32)).astype(x.dtype)


def gated_mlp(u, gate, up, down, dt):
    """``down(silu(gate(u)) * up(u))``, the weights as ``dt``."""
    g = jnp.dot(u, gate.astype(dt))
    return jnp.dot(jax.nn.silu(g) * jnp.dot(u, up.astype(dt)),
                   down.astype(dt))


def rms_logits(h, norm, head, eps, dt):
    """Final RMS norm, then the untied head ``[D, V]``: float32 logits."""
    h = rms_norm(h, norm, eps)
    return jnp.dot(h, head.astype(dt), preferred_element_type=jnp.float32)


def layer_at(stacked, l):
    """Layer ``l`` (static or traced) of stacked leaves, sliced where it is
    used: a loop's operand stays the whole stack, in place."""
    return jax.tree_util.tree_map(
        lambda a: jax.lax.dynamic_index_in_dim(a, l, 0, keepdims=False),
        stacked)


def mlp_shapes(n, D, F):
    """A gated MLP's leaves, ``n`` layers stacked, behind its norm."""
    return {"norm_ff": (n, D), "gate": (n, D, F), "up": (n, D, F),
            "down": (n, F, D)}


def hold_leaves(params, weight_dtype: str, f32_leaves):
    """A parameter tree as an engine holds it: the leaves named in
    ``f32_leaves`` float32, every other leaf in ``weight_dtype``
    (``"f32"`` or ``"bf16"``), each in its stored shape."""
    held = {"f32": jnp.float32, "bf16": jnp.bfloat16}[weight_dtype]

    def one(path, x):
        keep = path[-1].key in f32_leaves
        return jnp.asarray(x, jnp.float32 if keep else held)

    return jax.tree_util.tree_map_with_path(one, params)


# tokens of a rung whose experts run in one call, at most: the gathered
# rows, the shared experts' hidden rows, the 0/1 matrix that sums the pairs
# back and the share's full-size fallback (8 rows a token) are sized by it,
# whatever the rung (1.8 GB of a 16,384 rung's temporaries at 4096, half
# that at 2048; every chunk reads the layer's expert weights again, 2 GB in
# 2.4 ms beside 5 ms of products)
_FFN_ROWS = 2048


def ffn_chunk(T: int) -> int:
    """The largest divisor of ``T`` that is whole row tiles (128) and at
    most ``_FFN_ROWS``; ``T`` itself where it is no more than that, or has
    no such divisor."""
    if T <= _FFN_ROWS:
        return T
    for n in range(-(-T // _FFN_ROWS), T // 128 + 1):
        if T % n == 0 and (T // n) % 128 == 0:
            return T // n
    return T


def over_ffn_chunks(rows_fn, u, valid, experts_held: int):
    """``rows_fn(u [N, D], valid [N]) -> (ffn [N, D], report [G + 1]
    int32)`` over ``u [T, D]``, :func:`ffn_chunk` tokens at a time.
    Returns ``(ffn [T, D], report)``: tokens on each held expert, and the
    held pairs that reached no expert (0: nothing is dropped), summed over
    the chunks."""
    T, c = u.shape[0], ffn_chunk(u.shape[0])
    if c == T:
        return rows_fn(u, valid)

    def step(report, xs):
        y, r = rows_fn(xs[0], xs[1])
        return report + r, y

    report, y = jax.lax.scan(
        step, jnp.zeros((experts_held + 1,), jnp.int32),
        (u.reshape(T // c, c, -1), valid.reshape(T // c, c)))
    return y.reshape(T, -1), report
