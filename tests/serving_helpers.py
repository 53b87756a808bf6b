"""Oracles the serving tests share: greedy decoding through the engine's
cache-free float32 full forward (``DecodeEngine.reference_logits``), and
greedy decoding through the engine under test; and the engine calls' one
feed array (``serving/engine.py``, "the feed") written out a second time,
from the separate arrays a test plants."""
import numpy as np

import jax

from paddle_tpu.serving import engine as E

_PAD = 16           # reference sequences are padded to a multiple of this
_forwards = {}      # id(model description) -> (model, its jitted forward)


def _reference_last_logits(engine, seq):
    """``engine.reference_logits(seq)[-1]`` without its cost: the same
    forward on the same float32 parameters, jitted over the sequence
    padded to a few fixed lengths (a causal model's logits at a position
    do not depend on what follows it). The first call for a model holds
    the shortcut to ``reference_logits`` itself."""
    model = engine.model
    first = id(model) not in _forwards
    if first:
        _forwards[id(model)] = (model, jax.jit(model.forward))
    padded = np.zeros((1, -(-len(seq) // _PAD) * _PAD), np.int32)
    padded[0, :len(seq)] = seq
    last = np.asarray(_forwards[id(model)][1](
        engine._ref_params, padded)[0, len(seq) - 1], np.float32)
    if first:
        np.testing.assert_allclose(last, engine.reference_logits(seq)[-1],
                                   rtol=1e-5, atol=1e-5)
    return last


def greedy_reference(engine, prompt, n):
    """Greedy tokens from the full-forward f32 reference."""
    seq = list(prompt)
    out = []
    for _ in range(n):
        tok = int(np.argmax(_reference_last_logits(engine, seq)))
        out.append(tok)
        seq.append(tok)
    return out


def greedy_engine(engine, prompt, n):
    """Greedy tokens through prefill + decode ticks on a fresh slot."""
    slot, logits = engine.start_sequence(prompt)
    toks = [int(np.argmax(logits))]
    for _ in range(n - 1):
        out = engine.decode_step({slot: toks[-1]})
        toks.append(int(np.argmax(out[slot])))
    engine.free_sequence(slot)
    return toks


def _bits(values, dtype):
    """``values`` as ``dtype``, their bit patterns as int32."""
    return np.atleast_1d(np.asarray(values, dtype)).view(np.int32)


def pack_slot_feed(tokens, positions, tables, actives, temps, top_ks,
                   top_ps, seeds):
    """A decode tick's feed (``tokens`` [B]) or a verify window's
    ([B, W], ``positions`` the windows' starts) from the arrays the
    program cuts it into: a slot's table row, position, active, the
    sampler's four (floats as their bits), token(s)."""
    tokens = np.asarray(tokens, np.int32)
    tokens = tokens.reshape(tokens.shape[0], -1)
    tables = np.asarray(tables, np.int32)
    columns = [_bits(a, dt)[:, None] for a, dt in (
        (positions, np.int32), (actives, np.int32), (temps, np.float32),
        (top_ks, np.int32), (top_ps, np.float32), (seeds, np.int32))]
    feed = np.concatenate([tables, *columns, tokens], axis=1)
    assert feed.shape == E.slot_feed_shape(
        tokens.shape[0], tables.shape[1], tokens.shape[1])
    return feed


def pack_rung_feed(tokens, length, prefix_len, table_row, slot, temp, top_k,
                   top_p, seed):
    """A prefill rung's feed from what its program cuts it into: the
    slot's table row, length, prefix_len, slot, the sampler's four, the
    padded suffix (``tokens`` [1, bucket])."""
    table_row = np.asarray(table_row, np.int32)
    tokens = np.asarray(tokens, np.int32).reshape(-1)
    feed = np.concatenate(
        [table_row,
         *(_bits(a, dt) for a, dt in (
             (length, np.int32), (prefix_len, np.int32), (slot, np.int32),
             (temp, np.float32), (top_k, np.int32), (top_p, np.float32),
             (seed, np.int32))),
         tokens])
    assert feed.shape == (E.rung_feed_len(len(table_row), len(tokens)),)
    return feed


def greedy_knobs(batch):
    """(temps, top_ks, top_ps, seeds) of ``batch`` greedy lanes."""
    return (np.zeros((batch,), np.float32), np.zeros((batch,), np.int32),
            np.ones((batch,), np.float32), np.zeros((batch,), np.int32))
