"""Rotary position embedding with yarn frequencies.

A rotary layer turns each pair of a head's channels by an angle
``position * inv_freq[j]``. Yarn (arXiv:2309.00071) stretches a trained
context by ``factor``: frequencies that turn many times inside the original
context keep their value (``extra``), those that turn less than once are
divided by ``factor`` (``inter``), and a linear ramp over the pair index
blends the two between the *correction range* ``[low, high]``::

    extra_j = theta ** (-2j / dim)            inter_j = extra_j / factor
    ramp_j  = clip((j - low) / (high - low), 0, 1)
    inv_freq_j = inter_j * ramp_j + extra_j * (1 - ramp_j)

``low``/``high`` are the pair indices at which a channel makes ``beta_fast``
/ ``beta_slow`` turns over ``original_max_position_embeddings`` positions
(``dim * ln(orig / (beta * 2 pi)) / (2 ln theta)``, floored / ceiled,
clamped to ``[0, dim - 1]``). With ``mscale == mscale_all_dim`` cos and sin
are unscaled and the softmax scale carries ``(0.1 ln(factor) + 1) ** 2``.

Two pairings exist for the same rotation: the source of the latent
attention family rotates the *interleaved* pairs ``(2j, 2j + 1)``; a program
that keeps the two halves of a head apart rotates ``(j, j + dim / 2)`` and
permutes the rope columns of the projections that produce q and k once,
when it holds them (:func:`halves_from_interleaved`): the scores are the
same numbers.
"""
from __future__ import annotations

import math
from typing import Mapping, Optional, Tuple

import numpy as np

import jax.numpy as jnp

__all__ = ["yarn_correction_range", "yarn_inv_freq", "yarn_softmax_scale",
           "angles", "rotate", "halves_from_interleaved"]


def yarn_correction_range(dim: int, theta: float, original_max: int,
                          beta_fast: float, beta_slow: float
                          ) -> Tuple[int, int]:
    def turns_at(beta):
        return (dim * math.log(original_max / (beta * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(turns_at(beta_fast)), 0)
    high = min(math.ceil(turns_at(beta_slow)), dim - 1)
    return low, high


def yarn_inv_freq(dim: int, theta: float,
                  scaling: Optional[Mapping] = None) -> np.ndarray:
    """``[dim / 2]`` float32 frequencies; plain rotary where ``scaling`` is
    None, else the published ``rope_scaling`` group (``factor``,
    ``original_max_position_embeddings``, ``beta_fast``, ``beta_slow``)."""
    j = np.arange(dim // 2, dtype=np.float64)
    extra = theta ** (-2.0 * j / dim)
    if not scaling:
        return extra.astype(np.float32)
    low, high = yarn_correction_range(
        dim, theta, int(scaling["original_max_position_embeddings"]),
        float(scaling["beta_fast"]), float(scaling["beta_slow"]))
    span = (high - low) or 0.001
    ramp = np.clip((j - low) / span, 0.0, 1.0)
    inter = extra / float(scaling["factor"])
    return (inter * ramp + extra * (1.0 - ramp)).astype(np.float32)


def yarn_softmax_scale(qk_head_dim: int,
                       scaling: Optional[Mapping] = None) -> float:
    """``qk_head_dim ** -0.5``, times ``mscale ** 2`` under yarn with
    ``mscale = 0.1 * mscale_all_dim * ln(factor) + 1``."""
    s = qk_head_dim ** -0.5
    if scaling and scaling.get("mscale_all_dim"):
        m = (0.1 * float(scaling["mscale_all_dim"])
             * math.log(float(scaling["factor"])) + 1.0)
        s *= m * m
    return s


def angles(positions, inv_freq):
    """``(cos, sin)`` ``[..., dim / 2]`` float32 at integer ``positions``
    ``[...]``: a rung's ``prefix_len + t`` or a tick's per-slot positions."""
    a = positions.astype(jnp.float32)[..., None] * jnp.asarray(inv_freq)
    return jnp.cos(a), jnp.sin(a)


def rotate(x, cos, sin):
    """Rotate the halves ``(j, j + dim / 2)`` of the last axis of ``x [...,
    dim]``; ``cos``/``sin`` broadcast against ``x[..., :dim / 2]``. Float32
    inside, ``x``'s dtype out."""
    xf = x.astype(jnp.float32)
    half = xf.shape[-1] // 2
    a, b = xf[..., :half], xf[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           axis=-1).astype(x.dtype)


def halves_from_interleaved(dim: int) -> np.ndarray:
    """The column order that turns interleaved pairs into halves: even
    channels first, then odd."""
    return np.concatenate([np.arange(0, dim, 2), np.arange(1, dim, 2)])
