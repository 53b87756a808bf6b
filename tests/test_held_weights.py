"""How the GPT serving engine holds its weights (docs/serving.md): the QKV
weight and bias in the layout the programs contract, ``[L, d, 3·nh·hd]``
and ``[L, 3·nh·hd]``, a row-major reshape of the stored
``[L, d, 3, nh, hd]`` / ``[L, 3, nh, hd]`` made once in
``GPTServing.hold``; the tensor-parallel engine keeps the stored layout.
The copy this removes from the compiled programs is held by
``tests/test_chip_compile.py``; here, on the CPU at tiny sizes: the
shapes, the bytes, the int8 quantiser's groups, and the tokens of every
program against the cache-free float32 forward.
"""
import numpy as np
import pytest

import jax

from paddle_tpu import serving
from paddle_tpu.models import gpt
from paddle_tpu.serving import quant as squant
from paddle_tpu.models.gpt_serving import GPTServing, qkv_heads

from serving_helpers import greedy_engine, greedy_reference

WEIGHT_DTYPES = ("f32", "bf16", "int8")


@pytest.fixture(scope="module")
def tiny_model():
    cfg = gpt.GPT_TINY.scaled(num_layers=2, max_seq_len=64)
    return cfg, gpt.init_params(jax.random.PRNGKey(0), cfg)


def make_engine(tiny_model, **kw):
    cfg, params = tiny_model
    kw.setdefault("max_batch", 4)
    kw.setdefault("max_seq", 32)
    kw.setdefault("prefill_buckets", (8, 16))
    kw.setdefault("page_size", 8)
    return serving.DecodeEngine(params, cfg, serving.EngineConfig(**kw))


@pytest.fixture(scope="module")
def engines(tiny_model):
    """One engine a weight dtype, each with a verify window of 4."""
    return {wd: make_engine(tiny_model, weight_dtype=wd, verify_window=4)
            for wd in WEIGHT_DTYPES}


def _flat_shapes(cfg):
    L, d, n = cfg.num_layers, cfg.d_model, 3 * cfg.num_heads * cfg.head_dim
    return {"blocks/w_qkv": (L, d, n), "blocks/b_qkv": (L, n)}


@pytest.mark.parametrize("wd", WEIGHT_DTYPES)
def test_engine_holds_qkv_flat(tiny_model, engines, wd):
    """The held leaves' shapes, as the engine reports them and as the
    programs get them; every other leaf keeps its stored shape."""
    cfg, params = tiny_model
    eng = engines[wd]
    assert eng.held_shapes == _flat_shapes(cfg)
    blocks = eng.qparams["blocks"]
    assert tuple(blocks["w_qkv"].shape) == _flat_shapes(cfg)["blocks/w_qkv"]
    assert tuple(blocks["b_qkv"].shape) == _flat_shapes(cfg)["blocks/b_qkv"]
    for name in ("w_proj", "w_fc", "w_out", "ln1_scale"):
        assert tuple(blocks[name].shape) == params["blocks"][name].shape
    # the stored parameters (training, checkpoints, the parity forward)
    # are the caller's and are not touched
    assert params["blocks"]["w_qkv"].shape == (
        cfg.num_layers, cfg.d_model, 3, cfg.num_heads, cfg.head_dim)
    assert eng._ref_params is params


@pytest.mark.parametrize("wd", WEIGHT_DTYPES)
def test_held_weights_are_the_stored_layouts_bytes(tiny_model, engines, wd):
    """``weight_nbytes`` is what holding the stored layout takes, and each
    held leaf is bit for bit the stored leaf's serving storage: the int8
    payload and scales are those of quantising the stored layout (a
    row-major reshape moves no element, so the flat chunks are the same
    groups; heads of 16 x 4 are no multiple of the chunk of 256 here, so
    a split into three leaves would not have kept them)."""
    cfg, params = tiny_model
    eng = engines[wd]
    stored = squant.quantize_params(params, wd, eng.ecfg.quant_chunk)
    assert eng.weight_nbytes == squant.quantized_nbytes(stored)
    for name in ("w_qkv", "b_qkv"):
        held, was = eng.qparams["blocks"][name], stored["blocks"][name]
        if wd == "int8":
            assert held.pad == was.pad and held.chunk == was.chunk
            np.testing.assert_array_equal(np.asarray(held.payload),
                                          np.asarray(was.payload))
            np.testing.assert_array_equal(np.asarray(held.scales),
                                          np.asarray(was.scales))
        else:
            assert held.dtype == was.dtype
            np.testing.assert_array_equal(
                np.asarray(held, np.float32),
                np.asarray(was, np.float32).reshape(held.shape))


@pytest.mark.parametrize("wd", WEIGHT_DTYPES)
def test_prefill_tick_and_verify_tokens_equal_the_reference(
        tiny_model, engines, wd):
    """Greedy tokens of a prefill rung, of the ticks after it and of the
    verify window equal greedy decoding of ``reference_logits``, the
    cache-free forward over the stored float32 parameters."""
    cfg, _ = tiny_model
    eng = engines[wd]
    rng = np.random.RandomState(32)
    prompt = rng.randint(0, cfg.vocab_size, size=11).tolist()
    want = greedy_reference(eng, prompt, 6)
    assert greedy_engine(eng, prompt, 6) == want
    # the verify program over the same continuation: window position w
    # holds the model's token after prompt + want[:w + 1]
    slot, logits = eng.start_sequence(prompt)
    assert int(np.argmax(logits)) == want[0]
    (_, target), = eng.verify_step({slot: want[:4]}).values()
    assert [int(t) for t in target] == want[1:5]
    eng.free_sequence(slot)


def test_qkv_heads_is_one_product_in_either_layout(tiny_model):
    """The helper the three programs share gives the same q, k, v from the
    held leaves as from the stored ones (the tensor-parallel engine's), for
    decode rows ``[B, d]`` and for a rung or window ``[B, T, d]``."""
    cfg, params = tiny_model

    def first_layer(tree):
        return jax.tree_util.tree_map(lambda x: x[0], tree["blocks"])

    held = first_layer(GPTServing(cfg).hold(params, "f32", 256))
    stored = first_layer(
        GPTServing(cfg).hold(params, "f32", 256, sharded=True))
    assert held["w_qkv"].ndim == 2 and stored["w_qkv"].ndim == 4
    rng = np.random.RandomState(0)
    for shape in ((4, cfg.d_model), (2, 8, cfg.d_model)):
        h = rng.standard_normal(shape).astype(np.float32)
        for a, b in zip(qkv_heads(h, held, cfg),
                        qkv_heads(h, stored, cfg)):
            assert a.shape == (*shape[:-1], cfg.num_heads, cfg.head_dim)
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


def test_tensor_parallel_engine_keeps_the_stored_layout(tiny_model,
                                                        engines):
    """``sharding="tp"`` shards ``w_qkv`` on its head axis
    (``sharding/plan.py``), which the flat axis no longer shows: that
    engine holds the stored shapes, and serves the one-chip engine's
    tokens."""
    cfg, params = tiny_model
    tp = make_engine(tiny_model, sharding="tp", tp=2)
    assert tp.held_shapes == {}
    assert tp.qparams["blocks"]["w_qkv"].shape == \
        params["blocks"]["w_qkv"].shape
    assert tp.weight_nbytes == engines["f32"].weight_nbytes
    rng = np.random.RandomState(5)
    for n in (3, 13):
        prompt = rng.randint(0, cfg.vocab_size, size=n).tolist()
        got = greedy_engine(tp, prompt, 5)
        assert got == greedy_engine(engines["f32"], prompt, 5)
        assert got == greedy_reference(tp, prompt, 5)
