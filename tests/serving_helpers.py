"""Oracles the serving tests share: greedy decoding through the engine's
cache-free float32 full forward (``DecodeEngine.reference_logits``), and
greedy decoding through the engine under test."""
import numpy as np

import jax

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
