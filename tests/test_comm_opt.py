"""Communication-optimization layer (docs/comm_opt.md): reduce-scatter
gradient path, quantized collectives, double-buffered pipeline tick, wire
byte accounting, and the XLA perf-flag preset — on the 8-virtual-device
CPU mesh (conftest forces it)."""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

import paddle_tpu as fluid
from paddle_tpu.models import gpt as G
from paddle_tpu.parallel import comm_opt, parallelize as PZ
from paddle_tpu.parallel.comm_opt import CommConfig


def _mesh1d(n=8, name="dp"):
    return Mesh(np.array(jax.devices()[:n]).reshape(n), (name,))


def _shard_map(f, mesh, in_specs, out_specs):
    return jax.jit(PZ.shard_map_compat(f, mesh, in_specs=in_specs,
                                       out_specs=out_specs))


def _data(cfg, m, b, T=32, seed=0):
    rng = np.random.default_rng(seed)
    tokens = rng.integers(0, cfg.vocab_size, (m, b, T), dtype=np.int32)
    labels = rng.integers(0, cfg.vocab_size, (m, b, T), dtype=np.int32)
    return tokens, labels


def _train(cfg, pcfg, mesh, tokens, labels, steps=5, **kw):
    init_kw = {k: v for k, v in kw.items()
               if k in ("grad_reduce", "bucket_mb", "error_feedback",
                        "grad_allreduce_dtype", "comm")}
    params, opt = PZ.init_sharded(jax.random.PRNGKey(0), cfg, pcfg, mesh,
                                  **init_kw)
    step = PZ.make_train_step(cfg, pcfg, mesh, lr=1e-2, **kw)
    losses = []
    for _ in range(steps):
        params, opt, loss, gnorm = step(params, opt, tokens, labels)
        losses.append(float(loss))
    return losses, params, opt


# ---------------------------------------------------------------------------
# Tentpole 1: reduce-scatter gradient path + sharded optimizer state
# ---------------------------------------------------------------------------

def test_reduce_scatter_bit_identical_dp8():
    """f32-comm reduce-scatter vs the psum baseline on a pure dp=8 mesh:
    5 steps, bit-identical losses AND params (grad_clip=None on both so
    the clip scale's reduction order — the one float-association
    difference between the paths — is excluded; with clipping on the
    losses still match bit-for-bit, tested below)."""
    cfg = G.GPT_TINY
    pcfg = PZ.ParallelConfig(dp=8, pp=1, tp=1, microbatches=1)
    mesh = PZ.build_mesh(pcfg)
    tokens, labels = _data(cfg, 1, 16)
    l0, p0, _ = _train(cfg, pcfg, mesh, tokens, labels, grad_clip=None)
    # small bucket cap forces multiple buckets — the concat/pad/unflatten
    # round-trip is exercised, not just the single-bucket fast case
    l1, p1, opt1 = _train(cfg, pcfg, mesh, tokens, labels, grad_clip=None,
                          grad_reduce="reduce_scatter", bucket_mb=0.05)
    assert l0 == l1, (l0, l1)
    for a, b in zip(jax.tree_util.tree_leaves(p0),
                    jax.tree_util.tree_leaves(p1)):
        assert (np.asarray(a) == np.asarray(b)).all()
    # sharded flat optimizer state: dp x smaller than the replicated
    # per-leaf layout would be
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(p1))
    assert opt1["m"].ndim == 1
    assert opt1["m"].size < 1.01 * n_params  # flat total == params (+pad)


def test_reduce_scatter_losses_bit_identical_with_clip():
    cfg = G.GPT_TINY
    pcfg = PZ.ParallelConfig(dp=8, pp=1, tp=1, microbatches=1)
    mesh = PZ.build_mesh(pcfg)
    tokens, labels = _data(cfg, 1, 16)
    l0, _, _ = _train(cfg, pcfg, mesh, tokens, labels)
    l1, _, _ = _train(cfg, pcfg, mesh, tokens, labels,
                      grad_reduce="reduce_scatter")
    assert l0 == l1, (l0, l1)


def test_reduce_scatter_mixed_mesh_close():
    """dp2 x pp2 x tp2: the pp/tp psum happens before the dp scatter, so
    float association differs from the single 3-axis psum — values agree
    to tolerance."""
    cfg = G.GPT_TINY
    pcfg = PZ.ParallelConfig(dp=2, pp=2, tp=2, microbatches=2)
    mesh = PZ.build_mesh(pcfg)
    tokens, labels = _data(cfg, 2, 8)
    l0, p0, _ = _train(cfg, pcfg, mesh, tokens, labels, steps=3)
    l1, p1, _ = _train(cfg, pcfg, mesh, tokens, labels, steps=3,
                       grad_reduce="reduce_scatter")
    np.testing.assert_allclose(l0, l1, rtol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(p0),
                    jax.tree_util.tree_leaves(p1)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=2e-4, atol=2e-5)


# ---------------------------------------------------------------------------
# Tentpole 2: quantized collectives
# ---------------------------------------------------------------------------

def test_bf16_comm_convergence_bar():
    """bf16 wire payload (f32 accumulation): the 5-step loss trajectory
    tracks the f32-comm run closely and ends within the bar."""
    cfg = G.GPT_TINY
    pcfg = PZ.ParallelConfig(dp=8, pp=1, tp=1, microbatches=1)
    mesh = PZ.build_mesh(pcfg)
    tokens, labels = _data(cfg, 1, 16)
    l_f32, _, _ = _train(cfg, pcfg, mesh, tokens, labels,
                         grad_reduce="reduce_scatter")
    l_bf16, _, _ = _train(cfg, pcfg, mesh, tokens, labels,
                          grad_reduce="reduce_scatter",
                          grad_allreduce_dtype="bf16")
    assert np.isfinite(l_bf16).all()
    np.testing.assert_allclose(l_bf16, l_f32, rtol=0.02)
    assert l_bf16[-1] < l_bf16[0] - 0.2  # still learning


def test_int8_comm_with_error_feedback_converges():
    cfg = G.GPT_TINY
    pcfg = PZ.ParallelConfig(dp=8, pp=1, tp=1, microbatches=1)
    mesh = PZ.build_mesh(pcfg)
    tokens, labels = _data(cfg, 1, 16)
    l_f32, _, _ = _train(cfg, pcfg, mesh, tokens, labels,
                         grad_reduce="reduce_scatter")
    l_int8, _, opt = _train(cfg, pcfg, mesh, tokens, labels,
                            grad_reduce="reduce_scatter",
                            grad_allreduce_dtype="int8",
                            error_feedback=True)
    assert np.isfinite(l_int8).all()
    np.testing.assert_allclose(l_int8, l_f32, rtol=0.05)
    assert l_int8[-1] < l_int8[0] - 0.2
    # the residual actually carries state
    assert "ef" in opt and float(jnp.abs(opt["ef"]).max()) > 0


def test_quantized_allreduce_parity():
    mesh = _mesh1d()
    rng = np.random.default_rng(1)
    xs = (rng.standard_normal((8, 512)) * 3).astype(np.float32)

    def f(x):
        exact = jax.lax.psum(x, "dp")
        bf16 = comm_opt.quantized_allreduce(x, "dp", "bf16")
        i8 = comm_opt.quantized_allreduce(x, "dp", "int8", quant_chunk=64)
        return exact, bf16, i8

    exact, bf16, i8 = _shard_map(f, mesh, P("dp"), (P("dp"),) * 3)(
        xs.reshape(-1))
    exact = np.asarray(exact)
    np.testing.assert_allclose(np.asarray(bf16), exact,
                               rtol=0.02, atol=0.05)
    np.testing.assert_allclose(np.asarray(i8), exact, rtol=0.1, atol=0.3)


def test_quantize_roundtrip_int8():
    x = np.linspace(-4, 4, 256).astype(np.float32)
    q, s = comm_opt.quantize_chunked(jnp.asarray(x), "int8", 64)
    back = comm_opt.dequantize_chunked(q, s, "int8", 64)
    np.testing.assert_allclose(np.asarray(back), x, atol=4 / 127 + 1e-6)
    # all-zero chunks stay exact (scale guard)
    q0, s0 = comm_opt.quantize_chunked(jnp.zeros((64,)), "int8", 64)
    assert (np.asarray(comm_opt.dequantize_chunked(
        q0, s0, "int8", 64)) == 0).all()


# ---------------------------------------------------------------------------
# Tentpole 3: comm/compute overlap plumbing
# ---------------------------------------------------------------------------

def test_double_buffered_pipeline_same_loss_trajectory():
    """The double-buffered tick (ppermute at the head of the next tick, on
    the carried un-permuted activation) must produce the same 5-step loss
    trajectory as the serial permute-at-tail schedule."""
    cfg = G.GPT_TINY
    pcfg = PZ.ParallelConfig(dp=1, pp=4, tp=1, microbatches=4)
    mesh = PZ.build_mesh(pcfg)
    tokens, labels = _data(cfg, 4, 4)
    serial = CommConfig(pipeline_double_buffer=False)
    db = CommConfig(pipeline_double_buffer=True)
    l0, p0, _ = _train(cfg, pcfg, mesh, tokens, labels, comm=serial)
    l1, p1, _ = _train(cfg, pcfg, mesh, tokens, labels, comm=db)
    np.testing.assert_allclose(l0, l1, rtol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(p0),
                    jax.tree_util.tree_leaves(p1)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=1e-5, atol=1e-6)


def test_rs_bucketed_reduce_same_loss_as_serial_pipeline():
    """Satellite: double-buffered tick + bucketed reduce together vs the
    fully serial psum path — same loss trajectory (5-step CPU-mesh run)."""
    cfg = G.GPT_TINY
    pcfg = PZ.ParallelConfig(dp=2, pp=2, tp=1, microbatches=2)
    mesh = PZ.build_mesh(pcfg)
    tokens, labels = _data(cfg, 2, 8)
    l0, _, _ = _train(cfg, pcfg, mesh, tokens, labels,
                      comm=CommConfig(pipeline_double_buffer=False))
    l1, _, _ = _train(cfg, pcfg, mesh, tokens, labels,
                      comm=CommConfig(grad_reduce="reduce_scatter",
                                      pipeline_double_buffer=True))
    np.testing.assert_allclose(l0, l1, rtol=1e-5)


def test_measure_overlap_fraction_from_trace(tmp_path):
    """A profiled psum step yields a labeled overlap measurement (host
    thread lines off-TPU -> source=cpu_thread_emulation)."""
    mesh = _mesh1d()

    f = _shard_map(lambda x: jax.lax.psum(jnp.sin(x) * x, "dp"), mesh,
                   P("dp"), P("dp"))
    xs = np.ones((8 * 4096,), np.float32)
    f(xs)  # compile outside the capture
    tdir = str(tmp_path / "trace")
    with jax.profiler.trace(tdir):
        np.asarray(f(xs))
    res = comm_opt.measure_overlap_fraction(tdir)
    assert res is not None
    assert 0.0 <= res["overlap_fraction"] <= 1.0
    assert res["collective_ms"] > 0
    assert res["source"] in ("device_plane", "cpu_thread_emulation")


def test_axis_size_under_shard_map():
    """comm_opt.axis_size is jax.lax.axis_size: a static python int inside
    shard_map, per axis and as a product over several."""
    mesh = PZ.build_mesh(PZ.ParallelConfig(dp=4, pp=1, tp=2))
    seen = {}

    def f(x):
        seen["dp"] = comm_opt.axis_size("dp")
        seen["tp"] = comm_opt.axis_size("tp")
        seen["both"] = comm_opt._axes_size(("dp", "tp"))
        return x * seen["both"]

    out = _shard_map(f, mesh, P("dp"), P("dp"))(jnp.ones((8,)))
    assert seen == {"dp": 4, "tp": 2, "both": 8}
    assert all(type(v) is int for v in seen.values())
    np.testing.assert_array_equal(np.asarray(out), np.full((8,), 8.0))


def test_tpu_perf_flags_use_libtpu_channel():
    from paddle_tpu.sysconfig import TPU_PERF_XLA_FLAGS, tpu_perf_flags

    env = {"XLA_FLAGS": "--existing=1", "LIBTPU_INIT_ARGS": "--mine=2"}
    preset = tpu_perf_flags(env=env)
    assert "latency_hiding_scheduler" in preset
    # jaxlib's own parser aborts on --xla_tpu_* flags: XLA_FLAGS untouched
    assert env["XLA_FLAGS"] == "--existing=1"
    for f in TPU_PERF_XLA_FLAGS:
        assert f in env["LIBTPU_INIT_ARGS"]
    assert "--mine=2" in env["LIBTPU_INIT_ARGS"]
    # idempotent: re-applying does not duplicate
    once = env["LIBTPU_INIT_ARGS"]
    tpu_perf_flags(env=env)
    assert env["LIBTPU_INIT_ARGS"] == once


def test_named_scope_buckets_lowered():
    """The per-bucket collective named scopes land in the lowered HLO
    metadata (the merged trace reads overlap off these spans)."""
    cfg = G.GPT_TINY
    pcfg = PZ.ParallelConfig(dp=8, pp=1, tp=1, microbatches=1)
    mesh = PZ.build_mesh(pcfg)
    specs = G.param_specs(cfg)
    ccfg = CommConfig(grad_reduce="reduce_scatter", bucket_mb=0.05)
    params, opt = PZ.init_sharded(jax.random.PRNGKey(0), cfg, pcfg, mesh,
                                  comm=ccfg)
    step = PZ.make_train_step(cfg, pcfg, mesh, comm=ccfg)
    tokens, labels = _data(cfg, 1, 16)
    params, opt, loss, _ = step(params, opt, tokens, labels)
    # the AOT-kept executable's HLO carries the scope names
    from paddle_tpu.observability import program_report as prep

    reports = [r for r in prep.recent_reports()
               if "_rs" in r.get("program", "")]
    assert reports, "no program report for the rs step"


# ---------------------------------------------------------------------------
# Bucket layout unit tests
# ---------------------------------------------------------------------------

def test_bucket_layout_cap_pad_roundtrip():
    shapes = [((64, 64), np.float32), ((64,), np.float32),
              ((7, 5), np.float32), ((3,), np.float32)]
    layout = comm_opt.build_bucket_layout(shapes, ranks=8,
                                          cap_bytes=64 * 64 * 4)
    assert len(layout.buckets) >= 2          # cap forces a split
    assert layout.total_len % 8 == 0
    for b in layout.buckets:
        assert b.size % 8 == 0               # padded to the rank multiple
    covered = sorted(i for b in layout.buckets for i, _, _ in b.entries)
    assert covered == [0, 1, 2, 3]           # every leaf exactly once

    rng = np.random.default_rng(0)
    leaves = [jnp.asarray(rng.standard_normal(s).astype(d))
              for s, d in shapes]
    rebuilt = {}
    for b in layout.buckets:
        vec = comm_opt.flatten_bucket(leaves, b)
        assert vec.shape == (b.size,)
        rebuilt.update(comm_opt.unflatten_bucket(vec, b))
    for i, leaf in enumerate(leaves):
        np.testing.assert_array_equal(np.asarray(rebuilt[i]),
                                      np.asarray(leaf))


def test_bucket_layout_int8_chunk_alignment():
    shapes = [((100,), np.float32)]
    layout = comm_opt.build_bucket_layout(shapes, ranks=4,
                                          cap_bytes=1 << 20,
                                          pad_multiple=64)
    assert layout.buckets[0].size % (4 * 64) == 0


def test_wd_mask_rule():
    shapes = [((4, 4), np.float32), ((4,), np.float32)]
    layout = comm_opt.build_bucket_layout(shapes, ranks=1, cap_bytes=1 << 20)
    mask = comm_opt.bucket_wd_mask(layout.buckets[0])
    assert mask[:16].sum() == 16             # 2-D leaf decays
    assert mask[16:20].sum() == 0            # 1-D leaf does not


def test_wire_bytes_model():
    assert comm_opt.wire_bytes("psum", 800, 8) == 1400       # 2*(7/8)*800
    assert comm_opt.wire_bytes("psum_scatter", 800, 8) == 700
    assert comm_opt.wire_bytes("all_gather", 800, 8) == 700
    assert comm_opt.wire_bytes("ppermute", 800, 8) == 800
    assert comm_opt.wire_bytes("psum", 800, 1) == 0


def test_wire_byte_counter_halves_for_reduce_scatter():
    """Satellite (CI/tooling): the paddle_collective_bytes_total{op,dtype}
    counter records ~half the gradient-reduction bytes for the rs path."""
    from paddle_tpu.observability import metrics as M

    def grad_bytes(**kw):
        cfg = G.GPT_TINY
        pcfg = PZ.ParallelConfig(dp=8, pp=1, tp=1, microbatches=1)
        mesh = PZ.build_mesh(pcfg)
        tokens, labels = _data(cfg, 1, 16)

        def snap():
            s = M.default_registry().snapshot().get(
                "paddle_collective_bytes_total", {}).get("series", [])
            return {tuple(x["labels"]): x["value"] for x in s}

        before = snap()
        _train(cfg, pcfg, mesh, tokens, labels, steps=1, **kw)
        after = snap()
        return sum(v - before.get(k, 0) for k, v in after.items()
                   if k[0] in ("psum", "psum_scatter", "all_to_all"))

    base = grad_bytes()
    rs = grad_bytes(grad_reduce="reduce_scatter")
    assert base > 0 and rs > 0
    assert base / rs > 1.9, (base, rs)


# ---------------------------------------------------------------------------
# Satellite: fluid c_reducescatter / c_allgather interpret-mode parity
# ---------------------------------------------------------------------------

def _run_collective_program(layer_fn, x, ring_axes={0: "dp"}, fetch=None):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        xv = fluid.layers.data("x", list(x.shape[1:]), dtype="float32")
        out = layer_fn(xv)
    main._annotations["mesh"] = {
        "mode": "shard_map", "axes": [("dp", 8)], "data_axis": "dp",
        "ring_axes": dict(ring_axes),
    }
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    exe.run(startup, scope=scope)
    (res,) = exe.run(main, feed={"x": x}, fetch_list=[out], scope=scope)
    return np.asarray(res)


def test_c_reducescatter_parity_8way():
    """Each rank feeds [8, 4]; reduce-scatter leaves rank r with the
    rank-sum of row block r — capability parity with
    operators/collective/c_reducescatter_op. (This lowering previously
    called a nonexistent lax.axis_size and could not trace at all.)"""
    from paddle_tpu.layers.collective import _c_reducescatter

    x = np.arange(8 * 8 * 4, dtype="float32").reshape(64, 4)
    res = _run_collective_program(
        lambda v: _c_reducescatter(v, nranks=8), x)
    # per-rank local [8,4] -> [1,4] shard; fetches concat over ranks ->
    # [8, 4]; rank r's shard = sum over ranks of their local row r
    local = x.reshape(8, 8, 4)
    expect = local.sum(axis=0)
    np.testing.assert_allclose(res, expect, rtol=1e-6)


def test_c_allgather_parity_8way():
    from paddle_tpu.layers.collective import _c_allgather

    x = np.arange(8 * 2 * 3, dtype="float32").reshape(16, 3)
    res = _run_collective_program(
        lambda v: _c_allgather(v, nranks=8), x)
    # every rank ends with the concat of all local [2,3] blocks ([16,3]);
    # fetch-merge concats the 8 identical copies -> [128, 3]
    assert res.shape == (128, 3)
    for r in range(8):
        np.testing.assert_allclose(res[r * 16:(r + 1) * 16], x, rtol=1e-6)


def test_c_allreduce_sum_quantized_flag():
    """FLAGS_collective_comm_dtype reroutes c_allreduce_sum through the
    chunk-scaled quantized exchange — values match full-precision psum to
    quantization tolerance, wire dtype shows up in the byte counter."""
    from paddle_tpu.framework.core import get_flag, set_flags
    from paddle_tpu.layers.collective import _c_allreduce
    from paddle_tpu.observability import metrics as M

    x = np.linspace(-2, 2, 8 * 4).astype("float32").reshape(8, 4)
    ref = _run_collective_program(
        lambda v: _c_allreduce(v, reduce_type="sum"), x)
    prev = get_flag("FLAGS_collective_comm_dtype")
    set_flags({"FLAGS_collective_comm_dtype": "bf16"})
    try:
        res = _run_collective_program(
            lambda v: _c_allreduce(v, reduce_type="sum"), x)
    finally:
        set_flags({"FLAGS_collective_comm_dtype": prev})
    np.testing.assert_allclose(res, ref, rtol=0.02, atol=0.05)
    snap = M.default_registry().snapshot()
    series = snap["paddle_collective_bytes_total"]["series"]
    assert any(s["labels"][1] == "bfloat16" for s in series)


# ---------------------------------------------------------------------------
# Satellite: grad-merge accumulator dtype
# ---------------------------------------------------------------------------

def _gm_build(acc_dtype, seed=11):
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = seed
    startup.random_seed = seed
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.layers.data("x", [8], dtype="float32")
        y = fluid.layers.data("y", [1], dtype="int64")
        h = fluid.layers.fc(x, 16, act="relu")
        logits = fluid.layers.fc(h, 4)
        loss = fluid.layers.reduce_mean(
            fluid.layers.softmax_with_cross_entropy(logits, y))
        sgd = fluid.optimizer.MomentumOptimizer(0.1, 0.9)
        fluid.optimizer.GradientMergeOptimizer(
            sgd, k_steps=4, acc_dtype=acc_dtype).minimize(loss)
    return main, startup, loss


def _gm_train(acc_dtype, steps=4):
    main, startup, loss = _gm_build(acc_dtype)
    assert main._annotations["grad_merge"]["acc_dtype"] == acc_dtype
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.XLAPlace(0))
    rng = np.random.RandomState(3)
    xb = rng.rand(32, 8).astype("float32")
    yb = xb[:, :4].argmax(1).astype("int64").reshape(-1, 1)
    with fluid.scope_guard(scope):
        exe.run(startup, scope=scope)
        losses = [float(np.asarray(exe.run(
            main, feed={"x": xb, "y": yb}, fetch_list=[loss],
            scope=scope)[0]).ravel()[0]) for _ in range(steps)]
        w = np.asarray(scope.find_var("fc_0.w_0"))
    return losses, w


def test_grad_merge_acc_dtype_default_f32():
    """Default stays f32 (annotation records it); bf16 opt-in runs but
    accumulates in reduced precision — the weights drift measurably from
    the f32-accumulated run, which is exactly why f32 is the default."""
    l32, w32 = _gm_train("float32")
    lbf, wbf = _gm_train("bfloat16")
    assert np.isfinite(lbf).all()
    # same program, same data: trajectories agree only coarsely
    np.testing.assert_allclose(lbf, l32, rtol=0.05)
    assert not np.array_equal(w32, wbf), \
        "bf16 accumulation should not be bit-identical to f32"


def test_grad_merge_acc_dtype_rejects_unknown():
    with pytest.raises(ValueError, match="acc_dtype"):
        fluid.optimizer.GradientMergeOptimizer(
            fluid.optimizer.SGD(0.1), k_steps=2, acc_dtype="int8")


# ---------------------------------------------------------------------------
# Monitor schema + CommConfig validation
# ---------------------------------------------------------------------------

def test_monitor_rows_carry_overlap_fraction(tmp_path):
    from paddle_tpu.observability import TrainMonitor

    p = str(tmp_path / "mon.jsonl")
    mon = TrainMonitor(path=p, examples_per_step=4, sample_hbm=False)
    mon.record_step(10.0, loss=1.0)
    mon.record_step(10.0, loss=0.9, overlap_fraction=0.42)
    mon.close()
    import json

    rows = [json.loads(ln) for ln in open(p)]
    assert rows[0]["overlap_fraction"] == 0.0
    assert rows[1]["overlap_fraction"] == 0.42


def test_comm_config_validation():
    with pytest.raises(ValueError, match="grad_reduce"):
        CommConfig(grad_reduce="ring")
    with pytest.raises(ValueError, match="comm dtype"):
        CommConfig(comm_dtype="fp8")
    with pytest.raises(ValueError, match="error_feedback"):
        CommConfig(error_feedback=True)
    assert CommConfig(comm_dtype="bfloat16").comm_dtype == "bf16"
    assert CommConfig(comm_dtype="float32").comm_dtype is None
    with pytest.raises(NotImplementedError, match="error_feedback"):
        cfg = G.GPT_TINY
        pcfg = PZ.ParallelConfig(dp=2, pp=1, tp=1, microbatches=1)
        mesh = PZ.build_mesh(pcfg)
        PZ.make_train_step(cfg, pcfg, mesh, grad_allreduce_dtype="int8",
                           error_feedback=True)
