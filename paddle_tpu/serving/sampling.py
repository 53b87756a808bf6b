"""In-executable token sampling for the serving engine (docs/serving.md).

Temperature / top-k / top-p live INSIDE the compiled decode (and prefill
and verify) functions: per-slot parameters arrive as plain ``[max_batch]``
batch inputs and per-slot PRNG keys derive from a per-request integer
seed and the token position — so a request changing its sampling
knobs, or two requests with different knobs sharing a decode batch, never
changes a shape and never triggers a recompile (the zero-recompile
contract extends to sampling by construction).

Semantics per slot:

- ``temperature <= 0`` — greedy argmax, bit-identical to the pre-sampling
  engine (the parity bars and the reference token-match tests key off
  this lane);
- ``temperature > 0`` — logits are divided by the temperature, then
  masked by top-k (keep the k highest-logit tokens; ``k <= 0`` disables)
  and nucleus top-p (keep the smallest set of tokens whose probability
  mass reaches ``p``; ``p >= 1`` disables), then sampled with
  ``jax.random.categorical`` under the key ``(position, seed)`` —
  deterministic per (seed, position), independent across slots and steps.

What a call costs follows what its batch asks for. The rows of one call
run in lock step, so the work is chosen once for the whole batch, at run
time, by a ``lax.switch`` inside the one executable (:func:`sampler_path`
is the predicate, the same function on the host's numpy vectors and on
the traced ones):

- ``greedy`` — no row has ``temperature > 0``: the argmax and nothing
  else (no divide, no sort, no softmax, no PRNG);
- ``temperature`` — some row samples, none of those filters: one
  ``categorical`` over ``logits / temperature``, no sort;
- ``filtered`` — some sampling row has a top-k or a top-p: the
  vocabulary sort and softmax of :func:`_masked_logits` for every row (one
  filtered rider makes its batch pay the dearest path).

The tokens are the same whichever path runs: an unfiltered row's mask is
the row itself, and a greedy row never read the draw. The engine counts
its calls by path (``paddle_serve_sampler_path_total{path,program}``) and
stamps the path on its ``decode/run`` and ``prefill/run`` spans
(``sampler``).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

__all__ = ["SamplingParams", "GREEDY", "PATHS", "sampler_path",
           "path_name", "sample", "sample_token", "sample_batch",
           "sample_window", "batch_arrays", "feed_columns",
           "adjusted_probs_np"]


@dataclasses.dataclass(frozen=True)
class SamplingParams:
    """Per-request sampling knobs (host-side truth; becomes batch inputs).

    ``temperature == 0`` is greedy decode — the default, and exactly the
    engine's historical behavior."""
    temperature: float = 0.0
    top_k: int = 0            # 0 disables
    top_p: float = 1.0        # 1.0 disables
    seed: int = 0

    def __post_init__(self):
        if self.temperature < 0:
            raise ValueError(f"temperature {self.temperature} < 0")
        if not (0.0 < self.top_p <= 1.0):
            raise ValueError(f"top_p {self.top_p} outside (0, 1]")
        if self.top_k < 0:
            raise ValueError(f"top_k {self.top_k} < 0")

    @property
    def greedy(self) -> bool:
        return self.temperature <= 0.0


GREEDY = SamplingParams()


def _masked_logits(logits, temp, top_k, top_p):
    """[V] f32 logits -> temperature-scaled, top-k/top-p-masked logits.

    ONE descending sort serves both filters: the top-k threshold reads
    straight off it, and the nucleus threshold converts to logit space
    through the (monotone) softmax of the k-masked sorted row — keeping
    the executable's compile cost down (this runs inside every decode/
    prefill/verify program)."""
    V = logits.shape[-1]
    scaled = logits / jnp.maximum(temp, 1e-6)
    desc = jnp.sort(scaled)[::-1]
    # top-k: threshold at the k-th largest logit (k<=0 or k>=V disables)
    kk = jnp.where(top_k <= 0, V, jnp.minimum(top_k, V))
    k_thresh = desc[jnp.maximum(kk - 1, 0)]
    # top-p over the k-masked distribution, in sorted space: keep the
    # smallest descending-probability set whose cumulative mass reaches p
    in_k = jnp.arange(V) < kk
    e = jnp.where(in_k, jnp.exp(desc - desc[0]), 0.0)
    p_desc = e / jnp.sum(e)
    cum = jnp.cumsum(p_desc)
    idx = jnp.argmax(cum >= jnp.minimum(top_p, cum[-1]))
    thresh = jnp.where(top_p >= 1.0, k_thresh,
                       jnp.maximum(k_thresh, desc[idx]))
    return jnp.where(scaled >= thresh, scaled, -jnp.inf)


# the sampler's paths in the order of :func:`sampler_path`'s index, each
# dearer than the one before it
PATHS = ("greedy", "temperature", "filtered")


def sampler_path(temps, top_ks, top_ps):
    """Index into :data:`PATHS` of the cheapest path that serves every
    row: 0 when no row samples, 1 when some do and none of those filters,
    2 when a sampling row carries a top-k or a top-p. Operators and
    ``any`` alone, so the host's numpy vectors (or scalars) and the
    executable's traced ones go through the same lines."""
    sampling = temps > 0
    filtered = sampling & ((top_ks > 0) | (top_ps < 1))
    return sampling.any() * 1 + filtered.any() * 1


def path_name(temps, top_ks, top_ps) -> str:
    """Host side: the name of the path the executable will take for these
    (numpy) sampling parameters."""
    return PATHS[int(sampler_path(temps, top_ks, top_ps))]


def _draw(scaled, seed, position):
    """One categorical draw from one [V] row of scaled logits. The PRNG
    key is the raw pair ``(position, seed)`` — deterministic per
    (seed, position), independent across slots and steps, one threefry
    application per draw (a fold_in chain would compile two more)."""
    key = jnp.stack([position.astype(jnp.uint32), seed.astype(jnp.uint32)])
    return jax.random.categorical(key, scaled).astype(jnp.int32)


def _draw_plain(logits, temp, top_k, top_p, seed, position):
    # what _masked_logits returns when neither filter is on: its
    # threshold is then the row's minimum, so the mask keeps every entry
    return _draw(logits / jnp.maximum(temp, 1e-6), seed, position)


def _draw_filtered(logits, temp, top_k, top_p, seed, position):
    return _draw(_masked_logits(logits, temp, top_k, top_p), seed, position)


def sample(logits, temps, top_ks, top_ps, seeds, positions):
    """[..., V] logits, per-row positions [...], and sampling parameters
    over the leading batch axes -> [...] int32 tokens (jit-traceable).
    Holds the one predicate: the path is picked outside every ``vmap``
    (under one a ``switch`` turns back into a select that computes every
    side). A greedy batch runs the argmax alone — no PRNG consumed,
    bitwise what the host-side ``np.argmax`` used to produce.

    The engine's three programs call it by the shape they hand over:
    ``sample_token`` (prefill: one ``[V]`` row, scalar knobs, so a scalar
    ``switch`` on the request's own), ``sample_batch`` (decode:
    ``[B, V]`` beside ``[B]`` knobs and positions) and ``sample_window``
    (speculative verify: ``[B, W, V]`` beside ``[B]`` knobs and ``[B, W]``
    positions, every window position under its own key off the slot's
    seed)."""
    logits = logits.astype(jnp.float32)
    batch = logits.shape[:-1]
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def rows(a):
        a = jnp.asarray(a)
        return jnp.broadcast_to(
            a.reshape(a.shape + (1,) * (len(batch) - a.ndim)), batch)

    temps, top_ks, top_ps, seeds = map(rows, (temps, top_ks, top_ps, seeds))

    def drawn(draw):
        for _ in batch:
            draw = jax.vmap(draw)

        def branch():
            sampled = draw(logits, temps, top_ks, top_ps, seeds, positions)
            return jnp.where(temps <= 0.0, greedy, sampled)
        return branch

    return jax.lax.switch(
        sampler_path(temps, top_ks, top_ps),
        (lambda: greedy, drawn(_draw_plain), drawn(_draw_filtered)))


sample_token = sample_batch = sample_window = sample


def adjusted_probs_np(logits: np.ndarray, sp: SamplingParams
                      ) -> np.ndarray:
    """Numpy twin of the in-executable temperature/top-k/top-p masking:
    the normalized distribution a slot actually samples from. Used by
    the speculative-decoding rejection sampler (serving/spec_decode.py),
    where target-vs-draft acceptance must be computed against EXACTLY
    the adjusted distributions the executables sample.

    Greedy (temperature <= 0) returns the argmax one-hot."""
    logits = np.asarray(logits, np.float64).reshape(-1)
    V = logits.shape[0]
    if sp.greedy:
        out = np.zeros((V,), np.float64)
        out[int(np.argmax(logits))] = 1.0
        return out
    scaled = logits / max(sp.temperature, 1e-6)
    kk = V if sp.top_k <= 0 else min(sp.top_k, V)
    desc = np.sort(scaled)[::-1]
    masked = np.where(scaled >= desc[kk - 1], scaled, -np.inf)
    m = masked.max()
    probs = np.exp(masked - m)
    probs /= probs.sum()
    if sp.top_p < 1.0:
        p_desc = np.sort(probs)[::-1]
        cum = np.cumsum(p_desc)
        idx = int(np.argmax(cum >= min(sp.top_p, cum[-1])))
        probs = np.where(probs >= p_desc[idx], probs, 0.0)
        probs /= probs.sum()
    return probs


def batch_arrays(params_by_slot: Dict[int, SamplingParams],
                 max_batch: int, out: Optional[np.ndarray] = None
                 ) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Host helper: {slot: SamplingParams} -> the four [max_batch] feed
    vectors (temps f32, top_ks i32, top_ps f32, seeds i32). Slots absent
    from the map ride greedy. The vectors are the columns of ``out``, an
    int32 ``[max_batch, 4]`` block of an engine call's feed (the floats
    as their bit patterns; :func:`feed_columns` is the program's reader),
    or of a block of their own."""
    if out is None:
        out = np.empty((max_batch, 4), np.int32)
    temps, top_ps = out[:, 0].view(np.float32), out[:, 2].view(np.float32)
    top_ks, seeds = out[:, 1], out[:, 3]
    temps[:] = 0.0
    top_ks[:] = 0
    top_ps[:] = 1.0
    seeds[:] = 0
    for slot, sp in params_by_slot.items():
        temps[slot] = sp.temperature
        top_ks[slot] = sp.top_k
        top_ps[slot] = sp.top_p
        seeds[slot] = np.int32(np.uint32(sp.seed))
    return temps, top_ks, top_ps, seeds


def feed_columns(block):
    """Inside a program: the int32 ``[..., 4]`` block :func:`batch_arrays`
    wrote -> (temps, top_ks, top_ps, seeds) with the dtypes and the bits
    the host gave them."""
    def f32(col):
        return jax.lax.bitcast_convert_type(col, jnp.float32)

    return (f32(block[..., 0]), block[..., 1], f32(block[..., 2]),
            block[..., 3])
