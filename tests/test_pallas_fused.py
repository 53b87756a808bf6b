"""ISSUE 16 megakernel gates (docs/kernels.md), interpret mode on CPU:

* fused layernorm+residual(+dropout) block kernel — forward parity,
  custom_vjp gradcheck, exact model-level equivalence behind
  ``cfg.fused_ln`` in both flagship models;
* the optimizer megakernel — kernel-level bit-parity against the JITTED
  unfused expressions, fluid engine parity under
  ``FLAGS_fuse_optimizer_pallas``, flat-moment bit-parity + checkpoint
  resume, and the ``make_train_step(fused_opt_pallas=...)`` lever;
* the one-launch decode step — the page-table kernel's parity against
  the unfused update-then-gather-then-attend pipeline (dead lanes write
  the scratch page alone: tests/test_paged_serving.py), and greedy-token
  EXACTNESS through a real ``fused_decode=True`` engine.

Parity methodology: the references are JITTED. The production unfused
paths (fluid executor programs, the parallelize train step, the serving
decode fn) all run under jit, and XLA's FMA contraction means an EAGER
reference can differ from the same jitted expression by 1 ulp — bitwise
asserts against eager references would test the wrong thing.
"""
import dataclasses
import functools
import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu.framework import unique_name
from paddle_tpu.framework.core import get_flag, set_flags
from paddle_tpu.ops import decode_attention as DA
from paddle_tpu.ops import pallas_kernels as PK


# ---------------------------------------------------------------------------
# (a) fused layernorm block kernel
# ---------------------------------------------------------------------------


def _ref_ln(x, scale, bias, eps):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32)
            + bias.astype(jnp.float32)).astype(x.dtype)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5),
                                       (jnp.bfloat16, 2e-2)],
                         ids=["f32", "bf16"])
def test_fused_ln_forward_parity(dtype, tol):
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((5, 7, 96)), dtype)
    res = jnp.asarray(rng.standard_normal((5, 7, 96)), dtype)
    scale = jnp.asarray(1.0 + 0.1 * rng.standard_normal(96), jnp.float32)
    bias = jnp.asarray(0.1 * rng.standard_normal(96), jnp.float32)
    badd = jnp.asarray(0.1 * rng.standard_normal(96), dtype)

    ref = jax.jit(lambda x: _ref_ln(x, scale, bias, 1e-5))
    np.testing.assert_allclose(
        np.asarray(PK.fused_ln(x, scale, bias, eps=1e-5), jnp.float32),
        np.asarray(ref(x), jnp.float32), atol=tol, rtol=tol)

    # residual + bias-add + return_residual: s must be the models' exact
    # pre-norm stream (residual + x) + b, computed in x.dtype
    ref_rs = jax.jit(lambda x, r, b: (res + x) + b)
    y, s = PK.fused_ln(x, scale, bias, residual=res, bias_add=badd,
                       eps=1e-5, return_residual=True)
    s_ref = ref_rs(x, res, badd)
    np.testing.assert_array_equal(np.asarray(s, jnp.float32),
                                  np.asarray(s_ref, jnp.float32))
    np.testing.assert_allclose(
        np.asarray(y, jnp.float32),
        np.asarray(ref(s_ref), jnp.float32), atol=tol, rtol=tol)


def test_fused_ln_forward_dropout_parity():
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((33, 64)), jnp.float32)
    res = jnp.asarray(rng.standard_normal((33, 64)), jnp.float32)
    scale = jnp.asarray(1.0 + 0.1 * rng.standard_normal(64), jnp.float32)
    bias = jnp.asarray(0.1 * rng.standard_normal(64), jnp.float32)
    key = jax.random.PRNGKey(3)
    keep = 0.9

    def ref(x, res):
        mask = jax.random.bernoulli(key, keep, x.shape)
        s = x * mask.astype(x.dtype) * jnp.asarray(1.0 / keep, x.dtype)
        s = res + s
        return _ref_ln(s, scale, bias, 1e-5), s

    y, s = PK.fused_ln(x, scale, bias, residual=res, eps=1e-5,
                       dropout_rate=1.0 - keep, dropout_key=key,
                       return_residual=True)
    ry, rs = jax.jit(ref)(x, res)
    np.testing.assert_array_equal(np.asarray(s), np.asarray(rs))
    np.testing.assert_allclose(np.asarray(y), np.asarray(ry), atol=1e-5)


def test_fused_ln_gradcheck():
    """custom_vjp vs jax.grad of the jitted unfused expression — every
    differentiable operand (x, scale, bias, residual, bias_add)."""
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.standard_normal((150, 80)), jnp.float32)
    res = jnp.asarray(rng.standard_normal((150, 80)), jnp.float32)
    scale = jnp.asarray(1.0 + 0.1 * rng.standard_normal(80), jnp.float32)
    bias = jnp.asarray(0.1 * rng.standard_normal(80), jnp.float32)
    badd = jnp.asarray(0.1 * rng.standard_normal(80), jnp.float32)
    w = jnp.asarray(rng.standard_normal((150, 80)), jnp.float32)
    w2 = jnp.asarray(rng.standard_normal((150, 80)), jnp.float32)

    def fused(x, scale, bias, res, badd):
        y, s = PK.fused_ln(x, scale, bias, residual=res, bias_add=badd,
                           eps=1e-5, return_residual=True,
                           block_rows=64)   # non-divisible: 3 blocks pad
        return jnp.sum(y * w) + jnp.sum(s * w2)

    def ref(x, scale, bias, res, badd):
        s = (res + x) + badd
        return jnp.sum(_ref_ln(s, scale, bias, 1e-5) * w) \
            + jnp.sum(s * w2)

    gf = jax.jit(jax.grad(fused, argnums=(0, 1, 2, 3, 4)))(
        x, scale, bias, res, badd)
    gr = jax.jit(jax.grad(ref, argnums=(0, 1, 2, 3, 4)))(
        x, scale, bias, res, badd)
    for a, b, name in zip(gf, gr, ("x", "scale", "bias", "res", "badd")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-5, rtol=1e-4, err_msg=name)


def test_fused_ln_gradcheck_dropout():
    # the bernoulli mask operand carries a float0 cotangent — grads must
    # still flow through the masked x path
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((16, 64)), jnp.float32)
    scale = jnp.ones((64,), jnp.float32)
    bias = jnp.zeros((64,), jnp.float32)
    key = jax.random.PRNGKey(9)
    keep = 0.8

    def fused(x):
        return jnp.sum(PK.fused_ln(x, scale, bias, eps=1e-5,
                                   dropout_rate=1.0 - keep,
                                   dropout_key=key) ** 2)

    def ref(x):
        mask = jax.random.bernoulli(key, keep, x.shape)
        s = x * mask.astype(x.dtype) * jnp.asarray(1.0 / keep, x.dtype)
        return jnp.sum(_ref_ln(s, scale, bias, 1e-5) ** 2)

    np.testing.assert_allclose(np.asarray(jax.jit(jax.grad(fused))(x)),
                               np.asarray(jax.jit(jax.grad(ref))(x)),
                               atol=2e-5, rtol=1e-4)


def test_gpt_fused_ln_model_parity():
    """cfg.fused_ln flips every block + final layernorm to the kernel;
    loss and logits must match the unfused model exactly."""
    from paddle_tpu.models import gpt as G

    cfg = G.GPT_TINY.scaled(num_layers=2, max_seq_len=32)
    params = G.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 32)),
                         jnp.int32)
    labels = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 32)),
                         jnp.int32)
    fcfg = dataclasses.replace(cfg, fused_ln=True)
    base_logits = jax.jit(lambda p, t: G.forward(p, t, cfg))(
        params, tokens)
    fused_logits = jax.jit(lambda p, t: G.forward(p, t, fcfg))(
        params, tokens)
    np.testing.assert_allclose(np.asarray(fused_logits),
                               np.asarray(base_logits), atol=2e-5,
                               rtol=1e-5)
    base_loss = float(jax.jit(
        lambda p: G.loss_fn(p, tokens, labels, cfg))(params))
    fused_loss = float(jax.jit(
        lambda p: G.loss_fn(p, tokens, labels, fcfg))(params))
    assert abs(fused_loss - base_loss) < 1e-6, (fused_loss, base_loss)
    # and gradients flow through the custom_vjp inside the real model
    g = jax.jit(jax.grad(lambda p: G.loss_fn(p, tokens, labels, fcfg)))(
        params)
    gr = jax.jit(jax.grad(lambda p: G.loss_fn(p, tokens, labels, cfg)))(
        params)
    flat_g = jax.tree_util.tree_leaves(g)
    flat_r = jax.tree_util.tree_leaves(gr)
    assert all(bool(jnp.isfinite(x).all()) for x in flat_g)
    for a, b in zip(flat_g, flat_r):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=3e-4, rtol=1e-3)


def test_ernie_fused_ln_model_parity():
    from paddle_tpu.models import ernie as E

    cfg = E.ERNIE_TINY
    params = E.init_params(jax.random.PRNGKey(1), cfg)
    rng = np.random.default_rng(1)
    B, T = 2, 24
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, T)),
                         jnp.int32)
    seg = jnp.zeros((B, T), jnp.int32)
    pad = jnp.ones((B, T), jnp.float32)
    fcfg = dataclasses.replace(cfg, fused_ln=True)
    base = jax.jit(lambda p: E.encode(p, tokens, seg, pad, cfg))(params)
    fused = jax.jit(lambda p: E.encode(p, tokens, seg, pad, fcfg))(params)
    np.testing.assert_allclose(np.asarray(fused), np.asarray(base),
                               atol=2e-5, rtol=1e-5)


# ---------------------------------------------------------------------------
# (b) optimizer megakernel
# ---------------------------------------------------------------------------


def _flat(rng, n, dtype=jnp.float32):
    return jnp.asarray(rng.standard_normal(n), dtype)


def test_megakernel_sgd_bitwise():
    rng = np.random.default_rng(0)
    p, g = _flat(rng, 1000), _flat(rng, 1000)
    lr = jnp.asarray(0.01, jnp.float32)
    ref = jax.jit(lambda p, g, lr: p - lr.astype(p.dtype) * g)
    np.testing.assert_array_equal(np.asarray(PK.megakernel_sgd(p, g, lr)),
                                  np.asarray(ref(p, g, lr)))


@pytest.mark.parametrize("nesterov", [False, True])
def test_megakernel_momentum_parity(nesterov):
    rng = np.random.default_rng(1)
    p, g, v = _flat(rng, 777), _flat(rng, 777), _flat(rng, 777)
    lr, mu = jnp.asarray(0.01, jnp.float32), 0.9

    @jax.jit
    def ref(p, g, v, lr):
        v_new = mu * v + g
        if nesterov:
            p_new = p - (g + mu * v_new) * lr
        else:
            p_new = p - lr * v_new
        return p_new, v_new

    p2, v2 = PK.megakernel_momentum(p, g, v, lr, mu=mu, nesterov=nesterov)
    rp, rv = ref(p, g, v, lr)
    # FMA contraction across the two-term expression can split 1 ulp
    np.testing.assert_allclose(np.asarray(p2), np.asarray(rp), atol=1e-6,
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(v2), np.asarray(rv), atol=1e-6,
                               rtol=1e-6)


@pytest.mark.parametrize("coeff", [0.0, 0.01], ids=["adam", "adamw"])
def test_megakernel_adam_bitwise(coeff):
    rng = np.random.default_rng(2)
    p, g = _flat(rng, 1000), _flat(rng, 1000)
    m, v = _flat(rng, 1000) * 0.1, jnp.abs(_flat(rng, 1000)) * 0.01
    lr = jnp.asarray(1e-3, jnp.float32)
    b1p, b2p = jnp.asarray(0.9, jnp.float32), jnp.asarray(0.999,
                                                          jnp.float32)
    b1, b2, eps = 0.9, 0.999, 1e-8

    @jax.jit
    def ref(p, g, m, v, lr, b1p, b2p):
        m_new = b1 * m + (1 - b1) * g
        v_new = b2 * v + (1 - b2) * g * g
        lr_t = lr * jnp.sqrt(1 - b2p * b2) / (1 - b1p * b1)
        p_new = p - lr_t * m_new / (jnp.sqrt(v_new) + eps)
        if coeff:
            p_new = p_new - lr * coeff * p
        return p_new, m_new, v_new

    outs = PK.megakernel_adam(p, g, m, v, lr, b1p, b2p, b1=b1, b2=b2,
                              eps=eps, coeff=coeff)
    wants = ref(p, g, m, v, lr, b1p, b2p)
    # moments are single-expression — bitwise; the param update chains
    # mul/div/sub so XLA may contract the hand-written ref differently
    # than the kernel body by 1 ulp (bitwise parity vs the PRODUCTION
    # unfused path is asserted in test_fluid_optimizer_megakernel_parity)
    np.testing.assert_allclose(np.asarray(outs[0]), np.asarray(wants[0]),
                               atol=1e-8, rtol=1e-7, err_msg="p")
    for got, want, name in zip(outs[1:], wants[1:], ("m", "v")):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want),
                                      err_msg=name)


@pytest.mark.parametrize("mdt", [jnp.float32, jnp.bfloat16],
                         ids=["f32_moments", "bf16_moments"])
def test_megakernel_adamw_flat_parity(mdt):
    """parallelize's flat AdamW sweep: BITWISE at f32 moments (the
    acceptance bar); bf16 moment storage converts split XLA's fusion
    clusters so contraction nondeterminism allows 1 ulp on the params."""
    rng = np.random.default_rng(3)
    n = 1000
    p, g = _flat(rng, n), _flat(rng, n)
    m = _flat(rng, n, mdt) * jnp.asarray(0.1, mdt)
    v = (jnp.abs(_flat(rng, n)) * 0.01).astype(mdt)
    wd_mask = jnp.asarray(rng.integers(0, 2, n), jnp.float32)
    lr = jnp.asarray(1e-3, jnp.float32)
    scale = jnp.asarray(0.7, jnp.float32)
    c1, c2 = jnp.asarray(0.4, jnp.float32), jnp.asarray(0.2, jnp.float32)
    b1, b2, eps, wd = 0.9, 0.95, 1e-8, 0.1

    @jax.jit
    def ref(p, g, m, v, wd_mask, lr, scale, c1, c2):
        gf = g * scale
        mf = b1 * m.astype(jnp.float32) + (1 - b1) * gf
        vf = b2 * v.astype(jnp.float32) + (1 - b2) * gf * gf
        u = (mf / c1) / (jnp.sqrt(vf / c2) + eps)
        p_new = p - lr * (u + wd * wd_mask * p)
        return p_new, mf.astype(mdt), vf.astype(mdt)

    outs = PK.megakernel_adamw_flat(p, g, m, v, wd_mask, lr, scale, c1,
                                    c2, b1=b1, b2=b2, eps=eps,
                                    weight_decay=wd)
    wants = ref(p, g, m, v, wd_mask, lr, scale, c1, c2)
    if mdt is jnp.float32:
        for got, want, name in zip(outs, wants, ("p", "m", "v")):
            np.testing.assert_array_equal(np.asarray(got),
                                          np.asarray(want), err_msg=name)
    else:
        np.testing.assert_allclose(np.asarray(outs[0]),
                                   np.asarray(wants[0]), atol=2e-7,
                                   rtol=2e-7)
        for got, want in zip(outs[1:], wants[1:]):
            np.testing.assert_array_equal(
                np.asarray(got, jnp.float32), np.asarray(want, jnp.float32))


def test_use_opt_megakernel_resolution():
    assert PK.use_opt_megakernel(True) is True
    assert PK.use_opt_megakernel(False) is False
    assert PK.use_opt_megakernel(None) == (jax.default_backend() == "tpu")


def _run_fluid_mlp(opt_factory, pallas, steps=5, seed=7):
    """Train the memory-levers MLP with the flat fused sweep on and the
    Pallas megakernel forced on/off; returns (loss, {param: value})."""
    prev = get_flag("FLAGS_fuse_optimizer_pallas")
    set_flags({"FLAGS_fuse_optimizer_pallas": pallas})
    try:
        with unique_name.guard():
            main, startup = fluid.Program(), fluid.Program()
            main.random_seed = startup.random_seed = seed
            with fluid.program_guard(main, startup):
                x = fluid.layers.data(name="x", shape=[8],
                                      dtype="float32")
                h = fluid.layers.fc(x, size=16, act="relu")
                y = fluid.layers.fc(h, size=1)
                label = fluid.layers.data(name="y", shape=[1],
                                          dtype="float32")
                loss = fluid.layers.reduce_mean(
                    fluid.layers.square(y - label))
                opt_factory().minimize(loss)
        rng = np.random.default_rng(0)
        feed = {"x": rng.standard_normal((4, 8)).astype(np.float32),
                "y": rng.standard_normal((4, 1)).astype(np.float32)}
        exe = fluid.Executor(fluid.XLAPlace(0))
        scope = fluid.Scope()
        exe.run(startup, scope=scope)
        for _ in range(steps):
            lv, = exe.run(main, feed=feed, fetch_list=[loss], scope=scope)
        params = {p.name: np.asarray(scope.find_var(p.name))
                  for p in main.global_block().all_parameters()}
        return float(np.asarray(lv).ravel()[0]), params
    finally:
        set_flags({"FLAGS_fuse_optimizer_pallas": prev})


@pytest.mark.parametrize("opt_factory,exact", [
    (lambda: fluid.optimizer.SGD(0.05, fuse=True), True),
    (lambda: fluid.optimizer.Momentum(0.05, 0.9, fuse=True), False),
    (lambda: fluid.optimizer.Adam(0.01, fuse=True), True),
    (lambda: fluid.optimizer.AdamW(0.01, weight_decay=0.1, fuse=True),
     True),
], ids=["sgd", "momentum", "adam", "adamw"])
def test_fluid_optimizer_megakernel_parity(opt_factory, exact):
    """FLAGS_fuse_optimizer_pallas must not change a single bit of the
    trained parameters (momentum's two-term update is the one expression
    XLA contracts differently — 1 ulp band there)."""
    l_xla, p_xla = _run_fluid_mlp(opt_factory, pallas=False)
    l_pal, p_pal = _run_fluid_mlp(opt_factory, pallas=True)
    assert abs(l_pal - l_xla) < 1e-6
    assert set(p_pal) == set(p_xla)
    for name in p_xla:
        if exact:
            np.testing.assert_array_equal(p_pal[name], p_xla[name],
                                          err_msg=name)
        else:
            np.testing.assert_allclose(p_pal[name], p_xla[name],
                                       atol=5e-8, rtol=5e-8,
                                       err_msg=name)


def test_fluid_megakernel_checkpoint_resume(tmp_path):
    """Flat moments trained through the Pallas megakernel round-trip
    through save/load_persistables and resume bit-identically."""
    prev = get_flag("FLAGS_fuse_optimizer_pallas")
    set_flags({"FLAGS_fuse_optimizer_pallas": True})
    try:
        with unique_name.guard():
            main, startup = fluid.Program(), fluid.Program()
            main.random_seed = startup.random_seed = 7
            with fluid.program_guard(main, startup):
                x = fluid.layers.data(name="x", shape=[8],
                                      dtype="float32")
                h = fluid.layers.fc(x, size=16, act="relu")
                y = fluid.layers.fc(h, size=1)
                label = fluid.layers.data(name="y", shape=[1],
                                          dtype="float32")
                loss = fluid.layers.reduce_mean(
                    fluid.layers.square(y - label))
                fluid.optimizer.Adam(0.01, fuse=True).minimize(loss)
        flat_names = [n for n in main.global_block().vars
                      if n.startswith("fused_adam_")]
        assert any("moment1" in n for n in flat_names), flat_names
        rng = np.random.default_rng(1)
        feed = {"x": rng.standard_normal((4, 8)).astype(np.float32),
                "y": rng.standard_normal((4, 1)).astype(np.float32)}
        exe = fluid.Executor(fluid.XLAPlace(0))
        ckpt = str(tmp_path / "ckpt")
        scope = fluid.Scope()
        exe.run(startup, scope=scope)
        for _ in range(3):
            exe.run(main, feed=feed, fetch_list=[loss], scope=scope)
        with fluid.framework.executor.scope_guard(scope):
            fluid.io.save_persistables(exe, ckpt, main_program=main)
        for _ in range(2):
            exe.run(main, feed=feed, fetch_list=[loss], scope=scope)
        expect = {p.name: np.asarray(scope.find_var(p.name))
                  for p in main.global_block().all_parameters()}
        scope2 = fluid.Scope()
        exe.run(startup, scope=scope2)
        with fluid.framework.executor.scope_guard(scope2):
            fluid.io.load_persistables(exe, ckpt, main_program=main)
        for _ in range(2):
            exe.run(main, feed=feed, fetch_list=[loss], scope=scope2)
        for name, want in expect.items():
            got = np.asarray(scope2.find_var(name))
            np.testing.assert_array_equal(got, want, err_msg=name)
    finally:
        set_flags({"FLAGS_fuse_optimizer_pallas": prev})


def test_train_step_fused_opt_pallas_bitwise():
    """make_train_step(fused_opt=True, fused_opt_pallas=True): params
    AND the flat f32 moment megabuffers match the XLA flat sweep
    bit-for-bit over multiple steps."""
    from paddle_tpu.models import gpt as G
    from paddle_tpu.parallel import parallelize as PZ

    cfg = G.GPT_TINY.scaled(num_layers=2)
    pcfg = PZ.ParallelConfig(dp=1, pp=1, tp=1, microbatches=1)
    mesh = PZ.build_mesh(pcfg, devices=[jax.devices()[0]])
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, cfg.vocab_size, (1, 4, 32), dtype=np.int32)
    labels = rng.integers(0, cfg.vocab_size, (1, 4, 32), dtype=np.int32)
    out = {}
    for pallas in (False, True):
        params, opt = PZ.init_sharded(jax.random.PRNGKey(0), cfg, pcfg,
                                      mesh, fused_opt=True)
        step = PZ.make_train_step(cfg, pcfg, mesh, lr=1e-3,
                                  fused_opt=True,
                                  fused_opt_pallas=pallas)
        for _ in range(3):
            params, opt, loss, _ = step(params, opt, tokens, labels)
        out[pallas] = (float(loss), params, opt)
    assert out[True][0] == out[False][0], (out[True][0], out[False][0])
    for a, b in zip(jax.tree_util.tree_leaves(out[True][1]),
                    jax.tree_util.tree_leaves(out[False][1])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for key in ("m", "v"):
        np.testing.assert_array_equal(np.asarray(out[True][2][key]),
                                      np.asarray(out[False][2][key]),
                                      err_msg=key)


# ---------------------------------------------------------------------------
# (c) one-launch decode step
# ---------------------------------------------------------------------------


# bfloat16 pools: the kernel rounds the query to the pool's dtype (the
# tests hand it one that is bfloat16 already) and carries the
# probabilities as e_hi + e_lo, 2^-17 of a probability; against the
# float32 softmax over the same bfloat16 keys and values that is 2^-17 x
# max|v| (N(0, 1) draws: under 4.5) = 3.4e-5 at worst, 5e-6 as measured.
# Rounding the probabilities to bfloat16 would miss it by 2^8.
PAGED_TOL = {jnp.float32: 3e-6, jnp.bfloat16: 3.4e-5}


def _as_pool_dtype(x, cdt):
    return jnp.asarray(x, cdt).astype(jnp.float32)


@pytest.mark.parametrize("cdt", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_fused_paged_decode_parity(cdt):
    """Disjoint page tables (the only layout the engine's allocator ever
    produces for live slots — pages are owned exclusively; only the
    never-read-back scratch page 0 is shared by dead lanes). Flat rows:
    a token's heads side by side in the lanes."""
    rng = np.random.default_rng(2)
    B, M, page, nh, hd = 3, 4, 8, 2, 64
    P = 1 + B * M                            # page 0 = scratch
    kp = jnp.asarray(rng.standard_normal((P, page, nh * hd)), cdt)
    vp = jnp.asarray(rng.standard_normal((P, page, nh * hd)), cdt)
    q = _as_pool_dtype(rng.standard_normal((B, nh, hd)), cdt)
    nk = jnp.asarray(rng.standard_normal((B, nh, hd)), jnp.float32)
    nv = jnp.asarray(rng.standard_normal((B, nh, hd)), jnp.float32)
    # slot b owns pages [1 + b*M, 1 + (b+1)*M) — disjoint by construction
    tables = jnp.asarray(
        [[1 + b * M + m for m in range(M)] for b in range(B)], jnp.int32)
    positions = jnp.asarray([5, 0, 30], jnp.int32)

    @jax.jit
    def ref(q, kp, vp, nk, nv):
        phys = tables[jnp.arange(B), positions // page]
        rows = positions % page
        kp2 = DA.paged_cache_update(kp, nk.reshape(B, -1), phys, rows)
        vp2 = DA.paged_cache_update(vp, nv.reshape(B, -1), phys, rows)
        gk = DA.paged_gather(kp2, tables, heads=(nh, hd))
        gv = DA.paged_gather(vp2, tables, heads=(nh, hd))
        return DA.decode_attention(q, gk, gv, positions + 1), kp2, vp2

    out, kp2, vp2 = PK.fused_paged_decode_attention(
        q, kp, vp, nk, nv, tables, positions)
    r_out, r_kp, r_vp = ref(q, kp, vp, nk, nv)
    np.testing.assert_array_equal(np.asarray(kp2, jnp.float32),
                                  np.asarray(r_kp, jnp.float32))
    np.testing.assert_array_equal(np.asarray(vp2, jnp.float32),
                                  np.asarray(r_vp, jnp.float32))
    tol = {jnp.float32: 2e-6, jnp.bfloat16: PAGED_TOL[cdt]}[cdt]
    np.testing.assert_allclose(np.asarray(out), np.asarray(r_out),
                               atol=tol, rtol=tol)


@pytest.mark.parametrize("cdt", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_paged_decode_layer_indexed_parity(cdt, monkeypatch):
    """The layer-indexed kernel over the engine's whole
    [L, P, page, nh * hd] pool against paged_gather + decode_attention, at
    every layer, with ragged lengths: a slot of one token, a slot ending
    on a page edge, one a row past it, a full 2048-token slot (eight
    chunks at the megabyte a chunk shrunk to 64 KB), and dead lanes
    (all-zero tables, position 0). The pools come back bit for bit the
    reference's: the written rows and nothing else."""
    monkeypatch.setattr(PK, "_PAGED_CHUNK_BYTES", 1 << 17)
    rng = np.random.default_rng(4)
    L, B, M, page, nh, hd = 3, 6, 128, 16, 2, 64
    own = [1, 2, 3, M]                       # pages each live slot owns
    P = 1 + sum(own)                         # page 0 = scratch
    kp = jnp.asarray(rng.standard_normal((L, P, page, nh * hd)), cdt)
    vp = jnp.asarray(rng.standard_normal((L, P, page, nh * hd)), cdt)
    q = _as_pool_dtype(rng.standard_normal((B, nh, hd)), cdt)
    nk = jnp.asarray(rng.standard_normal((B, nh, hd)), jnp.float32)
    nv = jnp.asarray(rng.standard_normal((B, nh, hd)), jnp.float32)
    tables = np.zeros((B, M), np.int32)      # slots 1 and 4: dead lanes
    first = 1
    for slot, n in zip((0, 2, 3, 5), own):
        tables[slot, :n] = first + np.arange(n)
        first += n
    tables = jnp.asarray(tables)
    positions = jnp.asarray([0, 0, 2 * page - 1, 2 * page, 0,
                             M * page - 1], jnp.int32)
    live = [0, 2, 3, 5]

    @jax.jit
    def ref(q, kp, vp, nk, nv, layer):
        phys = tables[jnp.arange(B), positions // page]
        rows = positions % page
        kp2 = DA.paged_cache_update(kp, nk.reshape(B, -1), phys, rows,
                                    layer=layer)
        vp2 = DA.paged_cache_update(vp, nv.reshape(B, -1), phys, rows,
                                    layer=layer)
        gk = DA.paged_gather(kp2, tables, layer=layer, heads=(nh, hd))
        gv = DA.paged_gather(vp2, tables, layer=layer, heads=(nh, hd))
        return (DA.decode_attention(q, gk, gv, positions + 1), kp2, vp2,
                phys, rows)

    fused = jax.jit(lambda q, kp, vp, nk, nv, layer:
                    PK.fused_paged_decode_attention(
                        q, kp, vp, nk, nv, tables, positions, layer=layer))
    for layer in range(L):
        out, kp2, vp2 = fused(q, kp, vp, nk, nv, jnp.int32(layer))
        r_out, r_kp, r_vp, phys, rows = ref(q, kp, vp, nk, nv,
                                            jnp.int32(layer))
        np.testing.assert_allclose(np.asarray(out)[live],
                                   np.asarray(r_out)[live],
                                   atol=PAGED_TOL[cdt], rtol=PAGED_TOL[cdt])
        for got, want, before in ((kp2, r_kp, kp), (vp2, r_vp, vp)):
            got, want, before = (np.asarray(a, np.float32)
                                 for a in (got, want, before))
            np.testing.assert_array_equal(got, want)
            # outside the written rows and the scratch page: untouched
            mask = np.ones(got.shape[:3], bool)
            mask[:, 0] = False
            mask[layer, np.asarray(phys), np.asarray(rows)] = False
            np.testing.assert_array_equal(got[mask], before[mask])


# where a slot may end, in pages of 16 rows and chunks of two pages
ENDS = {"one_token": 0, "page_last_row": 47, "chunk_last_row": 63,
        "row_past_a_chunk": 64, "mid_page": 70}


@pytest.mark.parametrize("end", sorted(ENDS))
@pytest.mark.parametrize("cdt", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("nh", [16, 30])
def test_paged_decode_masks_the_last_chunk_alone(nh, cdt, end, monkeypatch):
    """Equal heads at any count, 16 or 30 lane tiles of 128, through the
    grouped kernel at a group of one. Only the chunk that holds a slot's
    position is masked, so everything the mask has to hide is planted as
    NaN: the rows of the slot's last page past its position, and the tail
    of its last chunk, which no copy writes (a slot of NaN rides in front
    of it and leaves both VMEM buffers full of them). A dead lane rides
    between the two."""
    page, M, hd, L = 16, 8, 128, 2
    pos = ENDS[end]
    # two pages a chunk: 32 rows
    monkeypatch.setattr(PK, "_PAGED_CHUNK_BYTES",
                        2 * page * nh * hd * jnp.dtype(cdt).itemsize)
    rng = np.random.default_rng(nh + pos)
    B, P = 3, 1 + 2 * M
    tables = np.zeros((B, M), np.int32)
    tables[0] = 1 + np.arange(M)             # the slot of NaN
    tables[2] = 1 + M + np.arange(M)
    positions = jnp.asarray([M * page - 1, 0, pos], jnp.int32)
    clean = rng.standard_normal((2, L, P, page, nh * hd)).astype(np.float32)
    poisoned = clean.copy()
    poisoned[:, :, 1:1 + M] = np.nan
    flat = poisoned[:, :, 1 + M:].reshape(2, L, M * page, nh * hd)
    flat[:, :, pos + 1:] = np.nan            # a view: written in place
    q = _as_pool_dtype(rng.standard_normal((B, nh, hd)), cdt)
    tables = jnp.asarray(tables)
    for layer in range(L):
        got = PK.paged_decode_attention(
            q, jnp.asarray(poisoned[0], cdt), jnp.asarray(poisoned[1], cdt),
            jnp.int32(layer), tables, positions)
        want = DA.decode_attention(
            q, *(DA.paged_gather(jnp.asarray(x, cdt), tables, layer,
                                 heads=(nh, hd)) for x in clean),
            positions + 1)
        assert got.shape == (B, nh, hd)
        np.testing.assert_allclose(np.asarray(got)[2], np.asarray(want)[2],
                                   atol=PAGED_TOL[cdt], rtol=PAGED_TOL[cdt])


def test_paged_decode_probabilities_keep_float32():
    """What ``split`` is for: over bfloat16 pools the probabilities go
    into the product with the values as e_hi + e_lo and hold PAGED_TOL;
    rounded to bfloat16 alone they miss it 25 times over and more."""
    rng = np.random.default_rng(9)
    B, M, page, nh, hd, tile = 2, 8, 16, 16, 128, 16
    kp, vp = (jnp.asarray(rng.standard_normal((1, 1 + B * M, page, nh * hd)),
                          jnp.bfloat16) for _ in range(2))
    q = _as_pool_dtype(rng.standard_normal((B, nh, hd)), jnp.bfloat16)
    tables = jnp.asarray(1 + np.arange(B * M).reshape(B, M), jnp.int32)
    positions = jnp.asarray([M * page - 1, 40], jnp.int32)
    want = DA.decode_attention(
        q, DA.paged_gather(kp, tables, 0, heads=(nh, hd)),
        DA.paged_gather(vp, tables, 0, heads=(nh, hd)), positions + 1)
    rows = jnp.broadcast_to(q[:, :, None], (B, nh, tile, hd))
    err = {}
    for split in (True, False):
        got = PK._paged_attention_call(
            "paged_decode_attention", rows.reshape(B, nh * tile, hd), kp, vp,
            jnp.int32(0), tables, positions, kv_heads=nh, split=split)
        err[split] = float(np.abs(
            np.asarray(got).reshape(B, nh, tile, hd)[:, :, 0]
            - np.asarray(want)).max())
    tol = PAGED_TOL[jnp.bfloat16]
    assert err[True] < tol and err[False] > 25 * tol, err


def test_fused_logits_head_parity():
    rng = np.random.default_rng(3)
    B, d, V = 4, 64, 300                     # V not a multiple of block_v
    x = jnp.asarray(rng.standard_normal((B, d)), jnp.float32)
    scale = jnp.asarray(1.0 + 0.1 * rng.standard_normal(d), jnp.float32)
    bias = jnp.asarray(0.1 * rng.standard_normal(d), jnp.float32)
    head = jnp.asarray(rng.standard_normal((d, V)) * 0.05, jnp.float32)

    @jax.jit
    def ref(x):
        return (_ref_ln(x, scale, bias, 1e-5) @ head)

    got = PK.fused_logits_head(x, scale, bias, head, eps=1e-5,
                               block_v=128)
    want = ref(x)
    assert got.shape == (B, V)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=1e-5)
    assert (np.argmax(np.asarray(got), -1)
            == np.argmax(np.asarray(want), -1)).all()


def _greedy(engine, prompt, n):
    slot, logits = engine.start_sequence(prompt)
    tok = int(np.argmax(logits))
    toks = [tok]
    for _ in range(n - 1):
        out = engine.decode_step({slot: tok})
        tok = int(np.argmax(out[slot]))
        toks.append(tok)
    engine.free_sequence(slot)
    return toks


def test_engine_greedy_tokens_exact_fused_decode():
    """EngineConfig(fused_decode=True) (fused layernorms and head, and
    off the TPU the page-table kernel in interpret mode) must emit the
    EXACT same greedy tokens as the unfused engine, multiple prompts."""
    from paddle_tpu import serving
    from paddle_tpu.models import gpt

    cfg = gpt.GPT_TINY.scaled(num_layers=2, max_seq_len=64)
    params = gpt.init_params(jax.random.PRNGKey(7), cfg)
    ekw = dict(max_batch=4, max_seq=32, prefill_buckets=(8, 16),
               page_size=8)
    base = serving.DecodeEngine(params, cfg, serving.EngineConfig(**ekw))
    fused = serving.DecodeEngine(
        params, cfg, serving.EngineConfig(fused_decode=True, **ekw))
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, cfg.vocab_size, size=int(n)).tolist()
               for n in (3, 6, 11)]
    assert (base.kv_path, fused.kv_path) == ("xla_gather", "pallas_paged")
    for prompt in prompts:
        want = _greedy(base, prompt, 12)
        got = _greedy(fused, prompt, 12)
        assert got == want, (prompt, got, want)


def test_fused_decode_engine_partial_batch_isolation():
    """A fused-decode engine stepping a PARTIAL batch (live slot rides
    next to masked lanes) must not perturb the parked slot's cache: park
    one sequence, decode another, then resume the first — its
    continuation must match an engine that never interleaved."""
    from paddle_tpu import serving
    from paddle_tpu.models import gpt

    cfg = gpt.GPT_TINY.scaled(num_layers=2, max_seq_len=64)
    params = gpt.init_params(jax.random.PRNGKey(3), cfg)
    ekw = dict(max_batch=4, max_seq=32, prefill_buckets=(8, 16),
               page_size=8, fused_decode=True)
    eng = serving.DecodeEngine(params, cfg, serving.EngineConfig(**ekw))
    ref_eng = serving.DecodeEngine(params, cfg,
                                   serving.EngineConfig(**ekw))
    pa, pb = [5, 9, 2], [7, 7, 7, 1]

    want = _greedy(ref_eng, pa, 8)
    slot_a, la = eng.start_sequence(pa)
    ta = int(np.argmax(la))
    got = [ta]
    for _ in range(3):                      # a alone
        out = eng.decode_step({slot_a: ta})
        ta = int(np.argmax(out[slot_a]))
        got.append(ta)
    slot_b, lb = eng.start_sequence(pb)     # b joins mid-stream
    tb = int(np.argmax(lb))
    for _ in range(4):                      # a and b share the batch
        out = eng.decode_step({slot_a: ta, slot_b: tb})
        ta = int(np.argmax(out[slot_a]))
        tb = int(np.argmax(out[slot_b]))
        got.append(ta)
    eng.free_sequence(slot_a)
    eng.free_sequence(slot_b)
    assert got == want, (got, want)


def test_megakernel_launch_counter_labels():
    """paddle_megakernel_launches_total{kernel} ticks at trace time with
    the documented label per family."""
    from paddle_tpu.observability import default_registry

    def counts():
        s = default_registry().snapshot().get(
            "paddle_megakernel_launches_total", {}).get("series", [])
        return {tuple(x["labels"])[0]: x["value"] for x in s}

    before = counts()
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((4, 64)), jnp.float32)
    one = jnp.ones((64,), jnp.float32)
    PK.fused_ln(x, one, one, eps=1e-5)
    p = jnp.zeros((130,), jnp.float32)
    PK.megakernel_sgd(p, p, jnp.asarray(0.1, jnp.float32))
    after = counts()
    assert after.get("fused_ln", 0) - before.get("fused_ln", 0) == 1
    assert after.get("opt_sgd", 0) - before.get("opt_sgd", 0) == 1
