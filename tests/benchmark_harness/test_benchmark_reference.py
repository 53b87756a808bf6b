"""The plain GPT-2 reference against the program, float32, tiny size, CPU:
forward, loss and gradients; serving's prefill-then-decode logits against
the reference's full forward."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402

GPT2 = harness.load_module(os.path.join(ROOT, "benchmark", "families",
                                        "gpt2.py"))
CONFIG = {"n_embd": 64, "n_layer": 2, "n_head": 4, "n_inner": 128,
          "vocab_size": 256, "n_positions": 64}
SEED = 2 ** 31 + 17
# float32 on the CPU, two layers: differences are rounding in another order
TOL = 2e-5


@pytest.fixture(scope="module")
def weights():
    return GPT2.init_weights(SEED, CONFIG)


@pytest.fixture(scope="module")
def program():
    from paddle_tpu.models import gpt as G

    cfg = GPT2._gpt_config(CONFIG, {"remat": False}, jnp.float32)
    return G, cfg, GPT2.program_weights(SEED, CONFIG, jnp.float32)


def test_reference_imports_nothing_of_the_program():
    src = open(os.path.join(ROOT, "benchmark", "families", "gpt2.py")).read()
    ref = src[src.index("# the plain reference"):]
    assert "paddle_tpu" not in ref and "import" not in ref.replace(
        "imports nothing", "")


def test_same_seed_same_weights_in_both_layouts(weights, program):
    _, _, tree = program
    np.testing.assert_array_equal(
        np.asarray(tree["blocks"]["w_qkv"]).reshape(2, 64, 192),
        np.asarray(weights["c_attn_w"]))
    np.testing.assert_array_equal(np.asarray(tree["lm_head"]),
                                  np.asarray(weights["lm_head"]))
    other = GPT2.init_weights(SEED + 1, CONFIG)
    assert not np.array_equal(np.asarray(other["wte"]),
                              np.asarray(weights["wte"]))
    # biases and gains are drawn too, so that their paths are compared
    assert float(jnp.abs(weights["c_fc_b"]).max()) > 0
    assert float(jnp.abs(weights["ln_1_g"] - 1).max()) > 0


def test_forward_agrees(weights, program):
    G, cfg, tree = program
    tokens = np.random.default_rng(0).integers(0, 256, (2, 48), np.int32)
    got = np.asarray(G.forward(tree, tokens, cfg))
    for row in range(2):
        want = np.asarray(GPT2.forward(weights, tokens[row], 4))
        np.testing.assert_allclose(got[row], want, atol=TOL, rtol=TOL)


def test_loss_and_gradients_agree(weights, program):
    G, cfg, tree = program
    rng = np.random.default_rng(1)
    tokens = rng.integers(0, 256, (3, 32), np.int32)
    labels = rng.integers(0, 256, (3, 32), np.int32)
    loss_p, grads_p = jax.value_and_grad(G.loss_fn)(tree, tokens, labels,
                                                    cfg)

    def ref_loss(w):
        return sum(GPT2.sequence_loss_sum(w, tokens[i], labels[i], 4)
                   for i in range(3)) / tokens.size

    loss_r, grads_r = jax.value_and_grad(ref_loss)(weights)
    assert float(loss_p) == pytest.approx(float(loss_r), rel=1e-6)
    norms_p = {k: float(v) for k, v in
               GPT2._leaf_norms_program(grads_p).items()}
    for name, g in grads_r.items():
        assert norms_p[name] == pytest.approx(
            float(jnp.linalg.norm(g.ravel())), rel=1e-4, abs=1e-7), name
    # and element by element on the leaf with the longest path to the loss
    np.testing.assert_allclose(
        np.asarray(grads_p["blocks"]["w_qkv"]).reshape(2, 64, 192),
        np.asarray(grads_r["c_attn_w"]), atol=1e-6, rtol=1e-3)


def test_remat_and_lower_precisions_of_the_reference(weights):
    tokens = np.arange(40, dtype=np.int32) % 256
    labels = (tokens * 7 + 3) % 256
    plain = GPT2.sequence_loss_sum(weights, tokens, labels, 4)
    remat = GPT2.sequence_loss_sum(weights, tokens, labels, 4, "f32", True)
    assert float(plain) == pytest.approx(float(remat), rel=1e-6)
    exact = np.asarray(GPT2.forward(weights, tokens, 4))
    errs = {p: float(np.abs(np.asarray(GPT2.forward(weights, tokens, 4, p))
                            - exact).max())
            for p in ("bf16", "fp8")}
    # each rung down the ladder moves the logits further
    assert 0 < errs["bf16"] < errs["fp8"]
    with pytest.raises(ValueError):
        GPT2.forward(weights, tokens, 4, "fp4")


def test_prefill_then_decode_agrees_with_the_full_forward(weights):
    """Through the paged cache: prefill 20 tokens, then feed the true
    stream one token at a time; every step's logits against the
    reference's forward over the whole sequence."""
    from paddle_tpu import serving

    cfg = GPT2._gpt_config(CONFIG, {}, jnp.float32)
    tree = GPT2.program_weights(SEED, CONFIG, jnp.float32)
    eng = serving.DecodeEngine(tree, cfg, serving.EngineConfig(
        max_batch=2, max_seq=64, kv_layout="paged", weight_dtype="f32"))
    seq = np.random.default_rng(2).integers(0, 256, 44).astype(np.int32)
    want = np.asarray(GPT2.forward(weights, seq, 4))
    slot, logits = eng.start_sequence(seq[:20].tolist())
    np.testing.assert_allclose(logits, want[19], atol=TOL, rtol=TOL)
    for i in range(20, 44):
        logits = eng.decode_step({slot: int(seq[i])})[slot]
        np.testing.assert_allclose(logits, want[i], atol=TOL, rtol=TOL)


def test_reference_serve_reads_the_gap_of_each_served_token(weights):
    prompt = [5, 9, 200, 31, 7]
    logits = np.asarray(GPT2.forward(
        weights, np.asarray(prompt + [0, 0], np.int32), 4))
    first = int(logits[4].argmax())
    nxt = np.asarray(GPT2.forward(
        weights, np.asarray(prompt + [first, 0], np.int32), 4))
    second = int(nxt[5].argsort()[-2])          # the runner-up, on purpose
    out = GPT2.reference(CONFIG, "serve", SEED,
                         samples=[(prompt, [first, second])],
                         pads=[32, 8, 16], rows=4, columns=[3, 250])
    assert set(out["gaps"]) == {"served"}
    assert set(out["logits"]) == {"reference"}
    np.testing.assert_allclose(out["logits"]["reference"][0],
                               [logits[4][[3, 250]], nxt[5][[3, 250]]],
                               atol=1e-6)
    gaps = out["gaps"]["served"]
    assert len(gaps) == 1 and gaps[0].shape == (2,)
    assert gaps[0][0] == 0.0
    assert gaps[0][1] == pytest.approx(
        float(nxt[5].max() - nxt[5][second]), abs=1e-6)
    with pytest.raises(ValueError):
        GPT2.reference(CONFIG, "serve", SEED, samples=[(prompt, [1] * 5)],
                       pads=[16], rows=4, columns=[0])
    with pytest.raises(ValueError):
        GPT2.reference(CONFIG, "serve", SEED, samples=[(prompt, [1] * 4)],
                       pads=[8], rows=4, columns=[0])


@pytest.mark.parametrize("precision,worst", [("bf16w", 2.0 ** -8),
                                             ("int8w", 1.0 / 127),
                                             ("fp8w", 2.0 ** -4)])
def test_round_weights_keeps_every_value_within_its_format(weights,
                                                           precision, worst):
    rounded = GPT2.round_weights(dict(weights), precision)   # in place
    assert set(rounded) == set(weights)
    for name, x in weights.items():
        err = float(jnp.abs(rounded[name] - x).max())
        # int8w: half a step of the chunk's own largest value
        assert 0 < err <= worst * float(jnp.abs(x).max()), name
        assert rounded[name].shape == x.shape
    with pytest.raises(ValueError):
        GPT2.round_weights(dict(weights), "int3w")


def test_the_control_reads_the_lower_precisions_first_token(weights):
    prompt = list(range(3, 23))
    served = [7, 7, 7, 7]                    # what was served is not read
    ref = GPT2.reference(CONFIG, "serve", SEED, samples=[(prompt, served)],
                         pads=[32], rows=4, columns=[1, 2, 255],
                         chosen_by=["int8w", "int8w+bf16"])
    out = ref["gaps"]
    assert set(out) == {"served", "int8w", "int8w+bf16"}
    assert set(ref["logits"]) == {"reference", "int8w", "int8w+bf16"}
    tokens = np.asarray(prompt + served, np.int32)
    full = np.asarray(GPT2.forward(weights, tokens, 4))[19:23]
    rounded = GPT2.round_weights(dict(weights), "int8w")
    for name, compute in (("int8w", "f32"), ("int8w+bf16", "bf16")):
        low = np.asarray(GPT2.forward(rounded, tokens, 4, compute))[19:23]
        want = full.max(axis=-1) - full[np.arange(4), low.argmax(axis=-1)]
        np.testing.assert_allclose(out[name][0], want, atol=1e-6)
        assert (out[name][0] >= 0).all()
        np.testing.assert_allclose(ref["logits"][name][0],
                                   low[:, [1, 2, 255]], atol=1e-6)
    np.testing.assert_allclose(ref["logits"]["reference"][0],
                               full[:, [1, 2, 255]], atol=1e-6)
    np.testing.assert_allclose(
        out["served"][0], full.max(axis=-1) - full[:, 7], atol=1e-6)
