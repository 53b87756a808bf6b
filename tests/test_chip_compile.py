"""Compiles for a described, unattached TPU v5e (2x2) — no chip needed.

The TPU's compiler is installed in the CPU sandbox and compiles for a chip
that is described, not attached (on-chip-measurement guide, section 2).
Every ``pallas_call`` on the train and decode paths is compiled here at
gpt_wide widths through Mosaic — interpret mode accepts block shapes and
VMEM budgets the chip's compiler refuses — and so is the whole gpt_wide
train step and the serving engine's decode tick, from ``jax.eval_shape``
shapes (there is no device to hold an array). A compile that passes is not
a chip run: nothing here is a timing or a result.

The topology is described inside a module-scoped fixture, never at import:
only one process may hold libtpu, and every xdist worker imports this file.
Keep these tests in this one file for the same reason.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from paddle_tpu.ops import pallas_kernels as PK

# gpt_wide (bench.gpt_wide_config): b=16, T=1024, 16 heads of 128, d=2048
B, T, NH, HD, D, V = 16, 1024, 16, 128, 2048, 50304
SERVE_B, SERVE_S, PAGE = 8, 1024, 16
BF16, F32 = jnp.bfloat16, jnp.float32


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu here, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def mosaic_not_interpreter(monkeypatch):
    """The process's backend is the CPU, so the kernels' own backend
    question would lower the interpreter and every compile would pass
    vacuously. Also: a compile for a described chip is written to the
    persistent cache but cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    monkeypatch.setattr(PK, "_on_tpu", lambda: True)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compile(fn, sharding, *shapes):
    """Lower ``fn`` for the described chip from (shape, dtype) pairs, check
    the lowering went through Mosaic, and compile."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    lowered = jax.jit(fn).lower(*args)
    assert "tpu_custom_call" in lowered.as_text(), \
        "lowered without a Mosaic kernel (interpret mode?)"
    return lowered.compile()


QKV = ((B, T, NH, HD), BF16)


def test_flash_attention_fwd(one_chip):
    _compile(lambda q, k, v: PK.flash_attention(q, k, v, causal=True),
             one_chip, QKV, QKV, QKV)


def test_flash_attention_fwd_bwd(one_chip):
    def loss(q, k, v):
        return PK.flash_attention(q, k, v, causal=True).astype(F32).sum()

    _compile(jax.grad(loss, argnums=(0, 1, 2)), one_chip, QKV, QKV, QKV)


CE_ARGS = (((B * T, D), BF16), ((D, V), BF16), ((B * T,), jnp.int32))


def _ce(x, head, labels):
    return PK.chunked_lm_loss(x, head, labels, vocab_chunk=1024,
                              row_chunk=2048, use_pallas=True)


def test_chunked_ce_fwd(one_chip):
    _compile(_ce, one_chip, *CE_ARGS)


def test_chunked_ce_fwd_bwd(one_chip):
    _compile(jax.grad(_ce, argnums=(0, 1)), one_chip, *CE_ARGS)


# models/gpt.py block_fn: f32 scale/bias, residual and bias-add in cfg.dtype
LN_ARGS = (((B * T, D), BF16), ((D,), F32), ((D,), F32),
           ((B * T, D), BF16), ((D,), BF16))


def _ln(x, scale, bias, residual, badd):
    y, s = PK.fused_ln(x, scale, bias, residual, badd, return_residual=True)
    return y.astype(F32).sum() + s.astype(F32).sum()


def test_fused_ln_fwd(one_chip):
    _compile(_ln, one_chip, *LN_ARGS)


def test_fused_ln_fwd_bwd(one_chip):
    _compile(jax.grad(_ln, argnums=(0, 1, 2, 3, 4)), one_chip, *LN_ARGS)


def test_fused_ln_dropout_fwd_bwd(one_chip):
    def loss(x, scale, bias, residual, key):
        return PK.fused_ln(x, scale, bias, residual, dropout_rate=0.1,
                           dropout_key=key).astype(F32).sum()

    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    _compile(jax.grad(loss, argnums=(0, 1, 2, 3)), one_chip, *LN_ARGS[:4],
             (key.shape, key.dtype))


ROW = ((SERVE_B, NH, HD), BF16)


def test_fused_decode_slab(one_chip):
    cache = ((SERVE_B, SERVE_S, NH, HD), BF16)
    _compile(PK.fused_decode_attention, one_chip, ROW, cache, cache, ROW,
             ROW, ((SERVE_B,), jnp.int32), ((SERVE_B,), jnp.int32))


def test_fused_decode_paged(one_chip):
    m = SERVE_S // PAGE
    pool = ((1 + SERVE_B * m, PAGE, NH, HD), BF16)
    _compile(PK.fused_paged_decode_attention, one_chip, ROW, pool, pool,
             ROW, ROW, ((SERVE_B, m), jnp.int32), ((SERVE_B,), jnp.int32))


def test_fused_logits_head(one_chip):
    _compile(PK.fused_logits_head, one_chip, ((SERVE_B, D), BF16),
             ((D,), F32), ((D,), F32), ((D, V), BF16))


def test_adamw_megakernel(one_chip):
    n = 64 * 1024 * 1024
    flat = ((n,), F32)

    def sweep(p, g, m, v, mask):
        return PK.megakernel_adamw_flat(p, g, m, v, mask, 1e-4, 1.0, 0.1,
                                        0.05)

    _compile(sweep, one_chip, flat, flat, ((n,), BF16), ((n,), BF16), flat)


# ---------------------------------------------------------------------------
# whole programs, from eval_shape shapes
# ---------------------------------------------------------------------------

def _gpt_wide():
    import bench                  # repo root: light, never imports jax

    return bench.gpt_wide_config(use_flash=True)


def _lower_train_step(topo, dp, tp):
    """gpt_wide make_train_step lowered for dp*tp described chips: shapes
    from eval_shape, shardings from the step's own param specs."""
    from paddle_tpu.models import gpt as G
    from paddle_tpu.parallel import parallelize as PZ

    cfg = _gpt_wide()
    pcfg = PZ.ParallelConfig(dp=dp, pp=1, tp=tp)
    mesh = PZ.build_mesh(pcfg, devices=topo.devices[:dp * tp])
    step = PZ.make_train_step(cfg, pcfg, mesh, lr=1e-4)
    specs = G.param_specs(cfg, pp=pcfg.axis_names[1], tp=pcfg.axis_names[2])

    def placed(shapes):
        return jax.tree_util.tree_map(
            lambda a, sp: jax.ShapeDtypeStruct(
                a.shape, a.dtype, sharding=NamedSharding(mesh, sp)),
            shapes, specs)

    params = jax.eval_shape(
        lambda: G.init_params(jax.random.PRNGKey(0), cfg))
    opt = jax.eval_shape(
        lambda p: PZ.init_adamw_state(p, moment_dtype=BF16), params)
    opt_s = {"m": placed(opt["m"]), "v": placed(opt["v"]),
             "step": jax.ShapeDtypeStruct(
                 (), jnp.int32, sharding=NamedSharding(mesh, P()))}
    batch = jax.ShapeDtypeStruct(
        (1, B, T), jnp.int32,
        sharding=NamedSharding(mesh, P(None, pcfg.axis_names[0], None)))
    return step.lower(placed(params), opt_s, batch, batch)


def test_gpt_wide_train_step_one_chip(topo):
    lowered = _lower_train_step(topo, dp=1, tp=1)
    assert "tpu_custom_call" in lowered.as_text()     # Mosaic flash
    mem = lowered.compile().memory_analysis()
    resident = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    print(f"gpt_wide b={B} T={T} one-chip step: args "
          f"{mem.argument_size_in_bytes / 2**30:.2f} GiB, temps "
          f"{mem.temp_size_in_bytes / 2**30:.2f} GiB, resident "
          f"{resident / 2**30:.2f} GiB of 16")
    # params + state + activations, with room left for the batch and the
    # runtime's own reservations
    assert resident < 14.5 * 2**30


def test_gpt_wide_train_step_dp2_tp2(topo):
    from paddle_tpu.models import gpt as G

    lowered = _lower_train_step(topo, dp=2, tp=2)
    assert "tpu_custom_call" in lowered.as_text()
    compiled = lowered.compile()
    hlo = compiled.as_text()
    assert "all-reduce" in hlo                        # dp grads, tp sums
    assert "reduce-scatter" in hlo or "all-gather" in hlo   # tp sequence
    # tp=2 halves the sharded weights and their state on every device:
    # unsharded, params (f32) + two bf16 moments are 8 bytes a parameter
    shapes = jax.eval_shape(
        lambda: G.init_params(jax.random.PRNGKey(0), _gpt_wide()))
    whole = 8 * sum(int(np.prod(a.shape))
                    for a in jax.tree_util.tree_leaves(shapes))
    assert compiled.memory_analysis().argument_size_in_bytes < 0.75 * whole


def test_ernie_base_pretrain_step_fits(one_chip):
    """bench.py's ERNIE-base lane (b=32, T=512, no remat, flash with the
    additive padding bias): the compiler refused b=48 for HBM, on the chip
    and here alike."""
    import bench
    from paddle_tpu.models import ernie as E

    cfg, batch, T = bench.ernie_base_config(), 32, 512
    M = cfg.max_masked

    def s(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def placed(tree):
        return jax.tree_util.tree_map(lambda a: s(a.shape, a.dtype), tree)

    params = jax.eval_shape(
        lambda: E.init_params(jax.random.PRNGKey(0), cfg))
    opt = jax.eval_shape(E.init_opt, params)
    feed = {"tokens": s((batch, T), jnp.int32),
            "seg_ids": s((batch, T), jnp.int32),
            "pad_mask": s((batch, T), jnp.bool_),
            "mlm_pos": s((batch, M), jnp.int32),
            "mlm_ids": s((batch, M), jnp.int32),
            "mlm_valid": s((batch, M), jnp.bool_),
            "nsp_label": s((batch,), jnp.int32)}
    lowered = E.make_pretrain_step(cfg).lower(placed(params), placed(opt),
                                              feed)
    assert "tpu_custom_call" in lowered.as_text()
    mem = lowered.compile().memory_analysis()
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            < 14 * 2**30)


@pytest.mark.parametrize("kv_layout", ["slab", "paged"])
@pytest.mark.parametrize("fused", [False, True], ids=["xla", "fused"])
def test_gpt_wide_decode_tick(one_chip, kv_layout, fused):
    """The serving engine's decode tick at gpt_wide widths (depth 1: the
    layers are one scanned body), default and fused_decode paths."""
    from paddle_tpu import serving
    from paddle_tpu.models import gpt as G

    cfg = _gpt_wide().scaled(num_layers=1)
    shapes = jax.eval_shape(
        lambda: G.init_params(jax.random.PRNGKey(0), cfg))
    params = jax.tree_util.tree_map(      # calloc'd: never touched
        lambda a: np.zeros(a.shape, a.dtype), shapes)
    eng = serving.DecodeEngine(params, cfg, serving.EngineConfig(
        max_seq=SERVE_S, max_batch=SERVE_B, kv_layout=kv_layout,
        page_size=PAGE, weight_dtype="bf16", fused_decode=fused))
    fn, example = eng._decode_program()
    args = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), np.asarray(a).dtype
                                       if not hasattr(a, "dtype")
                                       else a.dtype, sharding=one_chip),
        example)
    lowered = jax.jit(fn, donate_argnums=(1, 2)).lower(*args)
    assert ("tpu_custom_call" in lowered.as_text()) == fused
    lowered.compile()
