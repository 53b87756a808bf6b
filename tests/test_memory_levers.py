"""Whole-step HBM-traffic levers (docs/memory_levers.md): chunked
vocab-projection CE, the fused flat-buffer optimizer sweep, the remat-policy
API, and the ParallelExecutor scalar-feed fix."""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu.framework import unique_name
from paddle_tpu.ops import pallas_kernels as PK


# ---------------------------------------------------------------------------
# chunked vocab-projection CE
# ---------------------------------------------------------------------------


def _ref_ce(x, head, labels):
    logits = (x @ head).astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, -1)
    gold = jnp.take_along_axis(logits, labels[:, None], -1)[:, 0]
    return jnp.sum(lse - gold)


@pytest.mark.parametrize("V", [1000, 50257])
def test_chunked_lm_loss_parity_and_grads(V):
    rng = np.random.default_rng(0)
    n, D = (16 if V > 10000 else 33), 16
    x = jnp.asarray(rng.standard_normal((n, D)), jnp.float32)
    head = jnp.asarray(rng.standard_normal((D, V)) * 0.05, jnp.float32)
    labels = jnp.asarray(rng.integers(0, V, n), jnp.int32)
    r, (rgx, rgh) = jax.value_and_grad(_ref_ce, argnums=(0, 1))(
        x, head, labels)
    # chunk sizes that do and do not divide V, plus chunk == V
    for vc in (128, 1024, V):
        f = lambda x, h: PK.chunked_lm_loss(x, h, labels, vocab_chunk=vc,
                                            row_chunk=8)
        c, (cgx, cgh) = jax.value_and_grad(f, argnums=(0, 1))(x, head)
        assert abs(float(c - r)) / max(1.0, abs(float(r))) < 1e-5, vc
        np.testing.assert_allclose(cgx, rgx, atol=1e-5, rtol=1e-4)
        np.testing.assert_allclose(cgh, rgh, atol=1e-5, rtol=1e-4)


def test_chunked_lm_loss_pallas_interpreter_matches_lax():
    rng = np.random.default_rng(1)
    n, D, V = 32, 8, 512
    x = jnp.asarray(rng.standard_normal((n, D)), jnp.float32)
    head = jnp.asarray(rng.standard_normal((D, V)) * 0.1, jnp.float32)
    labels = jnp.asarray(rng.integers(0, V, n), jnp.int32)
    # lane-aligned chunk exercises the Pallas kernel in interpret mode
    a = PK.chunked_lm_loss(x, head, labels, vocab_chunk=128, use_pallas=True)
    b = PK.chunked_lm_loss(x, head, labels, vocab_chunk=128, use_pallas=False)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               atol=1e-4, rtol=1e-6)


def test_chunked_lm_loss_vd_layout_bias_valid():
    rng = np.random.default_rng(2)
    n, D, V = 21, 12, 301
    x = jnp.asarray(rng.standard_normal((n, D)), jnp.float32)
    headT = jnp.asarray(rng.standard_normal((V, D)) * 0.1, jnp.float32)
    bias = jnp.asarray(rng.standard_normal(V) * 0.1, jnp.float32)
    labels = jnp.asarray(rng.integers(0, V, n), jnp.int32)
    valid = jnp.asarray(rng.integers(0, 2, n), bool)

    def ref(x, hT, b):
        logits = (x @ hT.T + b).astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, -1)
        gold = jnp.take_along_axis(logits, labels[:, None], -1)[:, 0]
        return jnp.sum(jnp.where(valid, lse - gold, 0.0))

    r, rg = jax.value_and_grad(ref, argnums=(0, 1, 2))(x, headT, bias)
    f = lambda x, hT, b: PK.chunked_lm_loss(
        x, hT, labels, bias=b, valid=valid, vocab_chunk=96, row_chunk=10,
        head_layout="vd")
    c, cg = jax.value_and_grad(f, argnums=(0, 1, 2))(x, headT, bias)
    assert abs(float(c - r)) < 1e-4
    for a, b in zip(cg, rg):
        np.testing.assert_allclose(a, b, atol=1e-5, rtol=1e-4)


def test_chunked_ce_eliminates_full_logits_buffer():
    """The compiled chunked loss+grad must not hold a [rows, V] f32 buffer;
    the unchunked reference must (it is the buffer being eliminated)."""
    n, D, V, vc = 64, 32, 50257, 1024
    vp = V + ((-V) % vc)
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((n, D)), jnp.float32)
    head = jnp.asarray(rng.standard_normal((D, V)) * 0.05, jnp.float32)
    labels = jnp.asarray(rng.integers(0, V, n), jnp.int32)

    def unchunked(x, head):
        return _ref_ce(x, head, labels)

    def chunked(x, head):
        return PK.chunked_lm_loss(x, head, labels, vocab_chunk=vc,
                                  row_chunk=16)

    def compiled(f):
        return jax.jit(jax.grad(f, argnums=(0, 1))).lower(x, head).compile()

    cu, cc = compiled(unchunked), compiled(chunked)
    full_shapes = [f"f32[{n},{V}]", f"f32[{n},{vp}]"]
    cc_text = cc.as_text()
    for s in full_shapes:
        assert s not in cc_text, f"chunked HLO still holds {s}"
    assert any(s in cu.as_text() for s in full_shapes)
    # when this backend reports buffer sizes, the chunked peak temp must sit
    # below the unchunked one (which carries the [rows, V] f32 logits +
    # dlogits pair)
    try:
        mem_c = cc.memory_analysis()
        mem_u = cu.memory_analysis()
        if mem_c is not None and mem_u is not None:
            assert mem_c.temp_size_in_bytes < mem_u.temp_size_in_bytes
    except (AttributeError, NotImplementedError, jax.errors.JaxRuntimeError):
        pass  # HLO-text assertion above already covers the criterion


def test_softmax_with_cross_entropy_vocab_chunk_op():
    """Fluid op variant: loss parity AND Logits-grad parity (via one SGD
    step on an fc feeding the loss) across chunk sizes."""
    rng = np.random.default_rng(4)
    V = 301
    xs = rng.standard_normal((6, 9)).astype(np.float32)
    ys = rng.integers(0, V, (6, 1)).astype(np.int64)

    def run(vocab_chunk):
        with unique_name.guard():
            main, startup = fluid.Program(), fluid.Program()
            main.random_seed = startup.random_seed = 11
            with fluid.program_guard(main, startup):
                x = fluid.layers.data(name="x", shape=[9], dtype="float32")
                label = fluid.layers.data(name="y", shape=[1], dtype="int64")
                logits = fluid.layers.fc(x, size=V)
                loss = fluid.layers.reduce_mean(
                    fluid.layers.softmax_with_cross_entropy(
                        logits, label, vocab_chunk=vocab_chunk))
                fluid.optimizer.SGD(0.1).minimize(loss)
            exe = fluid.Executor(fluid.XLAPlace(0))
            scope = fluid.Scope()
            exe.run(startup, scope=scope)
            lv, = exe.run(main, feed={"x": xs, "y": ys},
                          fetch_list=[loss], scope=scope)
            w = np.asarray(scope.find_var(
                main.global_block().all_parameters()[0].name))
            return np.asarray(lv), w

    l0, w0 = run(0)
    for vc in (128, 1024, V):
        l1, w1 = run(vc)
        np.testing.assert_allclose(l1, l0, atol=1e-5)
        np.testing.assert_allclose(w1, w0, atol=1e-5)


def test_gpt_ce_vocab_chunk_matches_unchunked():
    from paddle_tpu.models import gpt as G

    cfg = G.GPT_TINY.scaled(num_layers=1)
    cfgc = cfg.scaled(ce_vocab_chunk=96, ce_chunk=32)
    params = G.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(5)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 16)), jnp.int32)
    labels = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 16)), jnp.int32)
    a = G.loss_fn(params, tokens, labels, cfg)
    b = G.loss_fn(params, tokens, labels, cfgc)
    assert abs(float(a) - float(b)) < 1e-5


def test_ernie_ce_vocab_chunk_matches_unchunked():
    from paddle_tpu.models import ernie as E

    cfg = E.ERNIE_TINY
    cfgc = cfg.scaled(ce_vocab_chunk=48)
    params = E.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(6)
    B, T, M = 2, 16, cfg.max_masked
    batch = {
        "tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (B, T)),
                              jnp.int32),
        "seg_ids": jnp.asarray(rng.integers(0, 2, (B, T)), jnp.int32),
        "pad_mask": jnp.ones((B, T), bool),
        "mlm_pos": jnp.asarray(rng.integers(0, T, (B, M)), jnp.int32),
        "mlm_ids": jnp.asarray(rng.integers(0, cfg.vocab_size, (B, M)),
                               jnp.int32),
        "mlm_valid": jnp.asarray(rng.integers(0, 2, (B, M)), bool),
        "nsp_label": jnp.asarray(rng.integers(0, 2, (B,)), jnp.int32),
    }
    a, _ = E.pretrain_loss(params, batch, cfg)
    b, _ = E.pretrain_loss(params, batch, cfgc)
    assert abs(float(a) - float(b)) < 1e-4


# ---------------------------------------------------------------------------
# fused flat-buffer optimizer sweep
# ---------------------------------------------------------------------------


def _build_mlp(fuse, opt_factory, seed=7):
    with unique_name.guard():
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = startup.random_seed = seed
        with fluid.program_guard(main, startup):
            x = fluid.layers.data(name="x", shape=[8], dtype="float32")
            h = fluid.layers.fc(x, size=16, act="relu")
            h = fluid.layers.fc(h, size=16, act="relu")
            y = fluid.layers.fc(h, size=1)
            label = fluid.layers.data(name="y", shape=[1], dtype="float32")
            loss = fluid.layers.reduce_mean(fluid.layers.square(y - label))
            opt_factory(fuse).minimize(loss)
    return main, startup, loss


def _optimize_op_count(program):
    return sum(1 for op in program.global_block().ops
               if int(op.attr("op_role", 0) or 0)
               & fluid.Program.OP_ROLE_OPTIMIZE)


def test_fused_adam_50_params_single_optimize_op():
    """Acceptance: a 50-param Adam program's optimize segment collapses to
    <= #(dtype, hparam) groups."""
    with unique_name.guard():
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            parts = [fluid.layers.create_parameter([4], "float32")
                     for _ in range(50)]
            loss = parts[0]
            for p in parts[1:]:
                loss = loss + p
            loss = fluid.layers.reduce_sum(loss)
            opt = fluid.optimizer.Adam(0.01, fuse=True)
            opt.minimize(loss)
    assert len(main.global_block().all_parameters()) == 50
    assert _optimize_op_count(main) == 1  # one (float32, lr_mult=1.0) group


def test_fused_groups_split_by_lr_mult():
    from paddle_tpu.framework.param_attr import ParamAttr

    with unique_name.guard():
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            a = fluid.layers.create_parameter([4], "float32")
            b = fluid.layers.create_parameter(
                [4], "float32", attr=ParamAttr(learning_rate=0.5))
            loss = fluid.layers.reduce_sum(a + b)
            fluid.optimizer.Adam(0.01, fuse=True).minimize(loss)
    assert _optimize_op_count(main) == 2


@pytest.mark.parametrize("opt_factory", [
    lambda fuse: fluid.optimizer.Adam(0.01, fuse=fuse),
    lambda fuse: fluid.optimizer.AdamW(0.01, weight_decay=0.1, fuse=fuse),
    lambda fuse: fluid.optimizer.AdamW(
        0.01, weight_decay=0.1, fuse=fuse,
        apply_decay_param_fun=lambda n: "fc_0" in n),
    lambda fuse: fluid.optimizer.Momentum(0.01, 0.9, fuse=fuse),
], ids=["adam", "adamw", "adamw_decay_fn", "momentum"])
def test_fused_optimizer_numeric_parity(opt_factory):
    rng = np.random.default_rng(0)
    feed = {"x": rng.standard_normal((4, 8)).astype(np.float32),
            "y": rng.standard_normal((4, 1)).astype(np.float32)}
    results = {}
    for fuse in (False, True):
        main, startup, loss = _build_mlp(fuse, opt_factory)
        exe = fluid.Executor(fluid.XLAPlace(0))
        scope = fluid.Scope()
        exe.run(startup, scope=scope)
        for _ in range(5):
            lv, = exe.run(main, feed=feed, fetch_list=[loss], scope=scope)
        params = {p.name: np.asarray(scope.find_var(p.name))
                  for p in main.global_block().all_parameters()}
        results[fuse] = (np.asarray(lv), params)
    l0, p0 = results[False]
    l1, p1 = results[True]
    np.testing.assert_allclose(l1, l0, atol=1e-6)
    assert _optimize_op_count(main) <= 2   # decay_fn splits into 2 groups
    for name in p0:
        np.testing.assert_allclose(p1[name], p0[name], atol=1e-6,
                                   err_msg=name)


def test_fused_adam_checkpoint_resume_flat_moments(tmp_path):
    """Flat moment megabuffers round-trip through save/load_persistables
    and the resumed run continues bit-identically."""
    rng = np.random.default_rng(1)
    feed = {"x": rng.standard_normal((4, 8)).astype(np.float32),
            "y": rng.standard_normal((4, 1)).astype(np.float32)}
    main, startup, loss = _build_mlp(
        True, lambda fuse: fluid.optimizer.Adam(0.01, fuse=fuse))
    # the flat moment buffers exist as persistables
    flat_names = [n for n in main.global_block().vars
                  if n.startswith("fused_adam_")]
    assert any("moment1" in n for n in flat_names)
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
        np.testing.assert_allclose(got, want, atol=0, err_msg=name)


def test_fused_flat_adamw_engine_parity():
    """parallelize.make_train_step(fused_opt=True): flat megabuffer sweep
    matches the per-leaf update (the mfu_sweep --fused-opt axis)."""
    from paddle_tpu.models import gpt as G
    from paddle_tpu.parallel import parallelize as PZ

    cfg = G.GPT_TINY.scaled(num_layers=2)
    pcfg = PZ.ParallelConfig(dp=1, pp=1, tp=1, microbatches=1)
    mesh = PZ.build_mesh(pcfg, devices=[jax.devices()[0]])
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, cfg.vocab_size, (1, 4, 32), dtype=np.int32)
    labels = rng.integers(0, cfg.vocab_size, (1, 4, 32), dtype=np.int32)
    out = {}
    for fused in (False, True):
        params, opt = PZ.init_sharded(jax.random.PRNGKey(0), cfg, pcfg,
                                      mesh, fused_opt=fused)
        if fused:
            assert opt["m"].ndim == 1   # ONE flat megabuffer
        step = PZ.make_train_step(cfg, pcfg, mesh, lr=1e-3, fused_opt=fused)
        for _ in range(3):
            params, opt, loss, gnorm = step(params, opt, tokens, labels)
        out[fused] = (float(loss), float(gnorm), params)
    assert abs(out[True][0] - out[False][0]) < 1e-5
    assert abs(out[True][1] - out[False][1]) < 1e-4
    for a, b in zip(jax.tree_util.tree_leaves(out[True][2]),
                    jax.tree_util.tree_leaves(out[False][2])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_fused_opt_rejects_multi_device_mesh():
    from paddle_tpu.models import gpt as G
    from paddle_tpu.parallel import parallelize as PZ

    pcfg = PZ.ParallelConfig(dp=2, pp=1, tp=1)
    with pytest.raises(NotImplementedError):
        PZ.make_train_step(G.GPT_TINY, pcfg, mesh=None, fused_opt=True)


# ---------------------------------------------------------------------------
# remat-policy API
# ---------------------------------------------------------------------------


def test_remat_policy_names_and_aliases():
    from paddle_tpu.parallel import remat

    assert remat.resolve("dots").name == "dots"
    assert remat.resolve("save_only_flash").name == "save_only_flash"
    # old spellings stay valid
    assert remat.resolve(None, remat=False).name == "none"
    assert remat.resolve(None, remat=True).name == "full"
    assert remat.resolve("full", remat=False).name == "none"
    assert remat.resolve("dots_with_no_batch_dims_saveable").name == "dots"
    with pytest.raises(ValueError):
        remat.resolve("everything_but_the_kitchen_sink")


def test_remat_policy_wrap_preserves_grads():
    from paddle_tpu.parallel import remat

    def f(x):
        y = remat.checkpoint_name(jnp.sin(x), remat.ATTN_CHECKPOINT_NAME)
        return jnp.sum(jnp.tanh(y) ** 2)

    x = jnp.asarray(np.linspace(-1, 1, 12), jnp.float32)
    g0 = jax.grad(f)(x)
    for name in ("none", "full", "dots", "save_only_flash"):
        g = jax.grad(remat.resolve(name).wrap(f))(x)
        np.testing.assert_allclose(np.asarray(g), np.asarray(g0), atol=1e-6)


@pytest.mark.parametrize("policy", ["none", "full", "dots",
                                    "save_only_flash"])
def test_gpt_config_accepts_named_policies(policy):
    from paddle_tpu.models import gpt as G

    cfg = G.GPT_TINY.scaled(num_layers=1, remat=True, remat_policy=policy)
    params = G.init_params(jax.random.PRNGKey(0), cfg)
    tokens = jnp.zeros((1, 8), jnp.int32)
    loss, grads = jax.value_and_grad(G.loss_fn)(params, tokens, tokens, cfg)
    assert np.isfinite(float(loss))
    assert all(np.all(np.isfinite(np.asarray(g)))
               for g in jax.tree_util.tree_leaves(grads))


def test_gpt_config_rejects_unknown_policy():
    from paddle_tpu.models import gpt as G

    with pytest.raises(ValueError):
        G.GPT_TINY.scaled(remat_policy="sometimes")


def test_pipeline_optimizer_accepts_remat_policy():
    """Stage-level remat via PipelineOptimizer(remat_policy=...) trains to
    the same loss as the unrematted pipeline."""
    def build(remat_policy):
        with unique_name.guard():
            main, startup = fluid.Program(), fluid.Program()
            main.random_seed = startup.random_seed = 3
            with fluid.program_guard(main, startup):
                x = fluid.layers.data(name="x", shape=[8], dtype="float32")
                h = fluid.layers.fc(x, size=8, act="relu")
                h = fluid.layers.fc(h, size=8, act="relu")
                y = fluid.layers.fc(h, size=1)
                label = fluid.layers.data(name="y", shape=[1],
                                          dtype="float32")
                loss = fluid.layers.reduce_mean(
                    fluid.layers.square(y - label))
                opt = fluid.optimizer.PipelineOptimizer(
                    fluid.optimizer.SGD(0.05), num_stages=1,
                    num_microbatches=2, remat_policy=remat_policy)
                opt.minimize(loss)
        assert main._annotations["pipeline"]["remat"] == \
            (remat_policy or "none")
        return main, startup, loss

    rng = np.random.default_rng(2)
    feed = {"x": rng.standard_normal((4, 8)).astype(np.float32),
            "y": rng.standard_normal((4, 1)).astype(np.float32)}
    losses = {}
    for policy in (None, "full"):
        main, startup, loss = build(policy)
        exe = fluid.Executor(fluid.XLAPlace(0))
        scope = fluid.Scope()
        exe.run(startup, scope=scope)
        for _ in range(3):
            lv, = exe.run(main, feed=feed, fetch_list=[loss], scope=scope)
        losses[policy] = float(np.asarray(lv).ravel()[0])
    assert abs(losses[None] - losses["full"]) < 1e-5


def test_grad_merge_accepts_remat_policy():
    def run(remat_policy):
        with unique_name.guard():
            main, startup = fluid.Program(), fluid.Program()
            main.random_seed = startup.random_seed = 5
            with fluid.program_guard(main, startup):
                x = fluid.layers.data(name="x", shape=[6], dtype="float32")
                y = fluid.layers.fc(x, size=1)
                label = fluid.layers.data(name="y", shape=[1],
                                          dtype="float32")
                loss = fluid.layers.reduce_mean(
                    fluid.layers.square(y - label))
                opt = fluid.optimizer.GradientMergeOptimizer(
                    fluid.optimizer.SGD(0.05), k_steps=2,
                    remat_policy=remat_policy)
                opt.minimize(loss)
        rng = np.random.default_rng(0)
        feed = {"x": rng.standard_normal((4, 6)).astype(np.float32),
                "y": rng.standard_normal((4, 1)).astype(np.float32)}
        exe = fluid.Executor(fluid.XLAPlace(0))
        scope = fluid.Scope()
        exe.run(startup, scope=scope)
        for _ in range(2):
            lv, = exe.run(main, feed=feed, fetch_list=[loss], scope=scope)
        return float(np.asarray(lv).ravel()[0])

    assert abs(run(None) - run("full")) < 1e-6


# ---------------------------------------------------------------------------
# satellites: ParallelExecutor scalar feed, bench stamping, sweep axes
# ---------------------------------------------------------------------------


def test_parallel_executor_scalar_feed_passthrough():
    """0-d feeds (a fed learning rate) must pass through the per-device
    merge unsplit instead of crashing np.concatenate."""
    with unique_name.guard():
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = fluid.layers.data(name="x", shape=[3], dtype="float32")
            s = fluid.layers.data(name="s", shape=[], dtype="float32",
                                  append_batch_size=False)
            out = x * s
    exe = fluid.Executor(fluid.XLAPlace(0))
    scope = fluid.Scope()
    exe.run(startup, scope=scope)
    with fluid.framework.executor.scope_guard(scope):
        pe = fluid.ParallelExecutor(use_cuda=False, main_program=main,
                                    scope=scope)
        xs = np.arange(6, dtype=np.float32).reshape(2, 3)
        lr = np.float32(0.5)
        # per-device feed list with a batched entry and a 0-d scalar
        res, = pe.run(fetch_list=[out],
                      feed=[{"x": xs[:1], "s": lr}, {"x": xs[1:], "s": lr}])
        np.testing.assert_allclose(res, xs * 0.5)
        # mismatched scalars across devices must fail loudly
        with pytest.raises(ValueError):
            pe.run(fetch_list=[out],
                   feed=[{"x": xs[:1], "s": np.float32(0.5)},
                         {"x": xs[1:], "s": np.float32(0.25)}])


def test_mfu_sweep_builds_lever_axes():
    import importlib.util as _ilu
    import sys as _sys

    spec = _ilu.spec_from_file_location(
        "mfu_sweep", os.path.join(os.path.dirname(__file__), "..",
                                  "tools", "mfu_sweep.py"))
    mod = _ilu.module_from_spec(spec)
    spec.loader.exec_module(mod)
    argv = _sys.argv
    try:
        _sys.argv = ["mfu_sweep.py", "--base", "d=64,L=2,b=4",
                     "--ce-chunk", "0,64", "--fused-opt", "0,1"]
        specs = mod.build_specs()
    finally:
        _sys.argv = argv
    assert len(specs) == 4
    assert any("vchunk=64" in s and "fused=1" in s for s in specs)
    assert all(s.startswith("d=64,L=2,b=4") for s in specs)


def test_hw_tables_refuse_unknown_accelerator():
    """A device kind in no table is an error, never a default; the CPU row
    stays as the CPU lane's placeholder."""
    from types import SimpleNamespace

    from paddle_tpu.observability import hw

    v5e = SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    assert hw.peak_bf16_flops(v5e) == 197e12
    assert hw.peak_hbm_bytes_per_s(v5e) == 819e9
    assert hw.hbm_capacity_bytes(v5e) == 16e9
    unknown = SimpleNamespace(platform="tpu", device_kind="TPU v99x")
    for fn in (hw.peak_bf16_flops, hw.peak_hbm_bytes_per_s,
               hw.hbm_capacity_bytes, hw.ridge_intensity):
        with pytest.raises(ValueError, match="v99x"):
            fn(unknown)
    cpu = jax.devices()[0]
    assert cpu.platform == "cpu"
    assert hw.peak_bf16_flops(cpu) == hw._CPU_PLACEHOLDER_FLOPS
    assert hw.peak_hbm_bytes_per_s(cpu) == hw._CPU_PLACEHOLDER_HBM_BPS
    assert hw.hbm_capacity_bytes(cpu) is None


def test_bench_refuses_to_run_without_tpu():
    """bench.py measures the chip and nothing else: on the CPU backend it
    exits non-zero from the first worker and prints no result line."""
    import subprocess
    import sys as _sys

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [_sys.executable, os.path.join(os.path.dirname(__file__), "..",
                                       "bench.py")],
        env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""          # no JSON, no tokens/s
    assert "not a TPU" in proc.stderr
