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
import re

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
# serve_cgpt1p3b_closed14 (benchmark/configs/cerebras-gpt-1.3b.json): 16
# slots of 2048 tokens over 1793 pages; depth 2 keeps the layer loop real
CELL_B, CELL_S, CELL_PAGES, CELL_L = 16, 2048, 1793, 2
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


def test_fused_decode_paged(one_chip):
    m = SERVE_S // PAGE
    pool = ((1 + SERVE_B * m, PAGE, NH * HD), BF16)
    _compile(PK.fused_paged_decode_attention, one_chip, ROW, pool, pool,
             ROW, ROW, ((SERVE_B, m), jnp.int32), ((SERVE_B,), jnp.int32))


def test_paged_decode_attention_layer_indexed(one_chip):
    """The kernel of the paged tick at the serving cell's widths: the
    whole [L, P, page, nh * hd] pool in HBM, layer index, page tables and
    positions as scalar prefetch."""
    pool = ((CELL_L, CELL_PAGES, PAGE, NH * HD), BF16)
    row = ((CELL_B, NH, HD), BF16)
    _compile(PK.paged_decode_attention, one_chip, row, pool, pool,
             ((), jnp.int32), ((CELL_B, CELL_S // PAGE), jnp.int32),
             ((CELL_B,), jnp.int32))


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


def _engine(num_layers, **ecfg):
    """A bf16 serving engine at gpt_wide widths over calloc'd weights."""
    from paddle_tpu import serving
    from paddle_tpu.models import gpt as G

    cfg = _gpt_wide().scaled(num_layers=num_layers, max_seq_len=CELL_S)
    shapes = jax.eval_shape(
        lambda: G.init_params(jax.random.PRNGKey(0), cfg))
    params = jax.tree_util.tree_map(      # calloc'd: never touched
        lambda a: np.zeros(a.shape, a.dtype), shapes)
    return serving.DecodeEngine(params, cfg, serving.EngineConfig(
        page_size=PAGE, weight_dtype="bf16", **ecfg))


def _lower_donated(fn, example, sharding):
    """``fn`` lowered for the described chip from its example arguments'
    shapes, the cache arguments donated as the engine does (the prefill
    and decode programs' one tuple of arrays at 1)."""
    args = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), np.asarray(a).dtype
                                       if not hasattr(a, "dtype")
                                       else a.dtype, sharding=sharding),
        example)
    return jax.jit(fn, donate_argnums=(1,)).lower(*args)


@pytest.mark.parametrize("fused", [False, True], ids=["xla", "fused"])
def test_gpt_wide_decode_tick(one_chip, fused):
    """The serving engine's decode tick at gpt_wide widths (depth 1),
    default and fused_decode paths. The engine reads its cache through
    the Pallas kernel on a TPU whatever fused_decode says."""
    eng = _engine(1, max_seq=SERVE_S, max_batch=SERVE_B,
                  fused_decode=fused)
    assert eng.kv_path == "pallas_paged"
    lowered = _lower_donated(*eng._decode_program(), one_chip)
    text = lowered.as_text()
    assert "paged_decode_attention" in text and "tpu_custom_call" in text
    # fused_decode adds its own launches (layernorms, the logits head)
    assert text.count("tpu_custom_call") > (2 if fused else 0)
    lowered.compile()


def test_paged_engine_gathers_where_mosaic_refuses_the_page(one_chip):
    """Heads of 16 (GPT_TINY): a head is no whole lane tile of a page's
    rows, so Mosaic refuses the kernel's slice of one; the engine sees it
    from the shapes (``paged_decode_tiles``) and its tick gathers
    instead."""
    from paddle_tpu import serving
    from paddle_tpu.models import gpt as G

    cfg = G.GPT_TINY.scaled(num_layers=2, max_seq_len=64, dtype=BF16)
    assert not PK.paged_decode_tiles(cfg.num_heads, cfg.head_dim)
    assert PK.paged_decode_tiles(NH, HD)
    eng = serving.DecodeEngine(
        G.init_params(jax.random.PRNGKey(0), cfg), cfg,
        serving.EngineConfig(max_batch=4, max_seq=32,
                             prefill_buckets=(8, 16), page_size=8,
                             weight_dtype="bf16"))
    assert eng.kv_path == "xla_gather"
    lowered = _lower_donated(*eng._decode_program(), one_chip)
    assert "tpu_custom_call" not in lowered.as_text()
    lowered.compile()
    with pytest.raises(Exception, match="aligned to tiling"):
        _compile(PK.paged_decode_attention, one_chip,
                 ((4, 4, 16), BF16), ((2, 17, 8, 4 * 16), BF16),
                 ((2, 17, 8, 4 * 16), BF16), ((), jnp.int32),
                 ((4, 4), jnp.int32), ((4,), jnp.int32))


@pytest.mark.parametrize("T", [256, 2048])
def test_selective_scan_fwd(one_chip, T):
    """The prefill scan at the cell's widths (d_inner 5120, d_state 16):
    blocks of 64 tokens by the whole width, the state in VMEM."""
    from paddle_tpu.ops import selective_scan as SS

    DI, N = 5120, 16
    _compile(SS.selective_scan, one_chip,
             ((T, DI), BF16), ((T, DI), F32), ((N, DI), F32), ((T, N), BF16),
             ((T, N), BF16), ((DI,), F32), ((T, DI), BF16), ((), jnp.int32))


def _jamba_cut_engine():
    """The hybrid model at the cell's widths and 64 slots, cut to one
    Mamba and one attention layer, over calloc'd weights."""
    from paddle_tpu import serving
    from paddle_tpu.models import jamba as J

    cfg = J.JambaConfig(num_hidden_layers=2, attn_layer_period=2,
                        attn_layer_offset=1)
    shapes = J.leaf_shapes(cfg)
    params = jax.tree_util.tree_map(      # calloc'd: never touched
        lambda s: np.zeros(s, np.float32), shapes,
        is_leaf=lambda s: isinstance(s, tuple))
    return serving.DecodeEngine(params, cfg, serving.EngineConfig(
        max_batch=64, max_seq=CELL_S, page_size=PAGE,
        weight_dtype="bf16", prefix_cache=False,
        prefill_buckets=(256, 2048)))


_TICKS = {}
_CELL_ENGINES = {}
_LOWERED = {}


def _cell_engine(cell):
    """The GPT serving cell's engine at depth 2, or the two-layer cut of
    the hybrid cell's: built once a process."""
    if cell not in _CELL_ENGINES:
        _CELL_ENGINES[cell] = (
            _jamba_cut_engine() if cell == "jamba_cut" else
            _engine(CELL_L, max_seq=CELL_S, max_batch=CELL_B,
                    num_pages=CELL_PAGES))
    return _CELL_ENGINES[cell]


def _lowered(cell, program, sharding, uncut=False):
    """``decode`` or ``prefill_b<rung>`` of a cell (``gpt_cell``,
    ``jamba_cut``, ``kimi``, ``olmo_cut``, ``cohere_cut``, ``solar``)
    lowered for the described chip, once a process. Called from inside a test (the autouse fixture has to be in
    force), never from a fixture of wider scope. ``uncut``: the program
    behind the feed's cut (:func:`_uncut`)."""
    key = (cell, program) + (("uncut",) if uncut else ())
    if key not in _LOWERED:
        if cell == "kimi":
            _LOWERED[key] = _lower_kimi_cut(program, sharding, uncut)
        elif cell == "olmo_cut":
            _LOWERED[key] = _lower_olmo_cut(program, sharding)[0]
        elif cell == "cohere_cut":
            _LOWERED[key] = _lower_cohere_cut(program, sharding)
        elif cell == "solar":
            _LOWERED[key] = _lower_solar(program, sharding)[0]
        else:
            eng = _cell_engine(cell)
            fn, (held, caches, feed) = (
                eng._decode_program() if program == "decode" else
                eng._prefill_program(int(program.split("_b")[1])))
            if uncut:
                feed = _uncut(feed, eng.table_width)
            _LOWERED[key] = _lower_donated(fn, (held, caches, feed),
                                           sharding)
    return _LOWERED[key]


class _Column:
    """Stands where a program reads ``tokens[:, 0]`` of the feed's token
    columns: the [B] vector the tick took before there was a feed."""

    def __init__(self, vector):
        self.vector = vector

    def __getitem__(self, at):
        return self.vector


def _uncut(feed, M):
    """What PR 35's programs took where today's take ``feed``: the eight
    arrays of a tick or the nine of a rung, as shapes, in the order of
    their arguments, as ONE pytree. With :func:`_cuts_handed_through` in
    force the engine's program functions lower from it to the program
    behind the cut, and that is PR 35's program, text for text."""
    i32, f32 = np.int32, np.float32

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype)

    if len(feed.shape) == 2:
        B = feed.shape[0]
        return (arr((B,), i32), arr((B,), i32), arr((B, M), i32),
                arr((B,), i32), (arr((B,), f32), arr((B,), i32),
                                 arr((B,), f32), arr((B,), i32)))
    from paddle_tpu.serving import engine as E

    bucket = feed.shape[0] - E.rung_feed_len(M, 0)
    return (arr((1, bucket), i32), arr((), i32), arr((), i32),
            arr((M,), i32), arr((), i32),
            (arr((), f32), arr((), i32), arr((), f32), arr((), i32)))


@pytest.fixture
def _cuts_handed_through(monkeypatch):
    """The feed's two cuts return what they are given (:func:`_uncut`'s
    arrays): the engine's program functions then trace what lies behind
    the cut and nothing of the cut."""
    from paddle_tpu.serving import engine as E

    monkeypatch.setattr(E, "cut_rung_feed", lambda feed, M: feed)
    monkeypatch.setattr(
        E, "cut_slot_feed",
        lambda feed, M: (_Column(feed[0]),) + tuple(feed[1:]))


def _compiled(cell, program, sharding):
    """A cell's ``decode`` tick or ``prefill_b<rung>`` compiled for the
    described chip: a minute or more each, so compiled once a process
    for the tests that read it."""
    key = (cell, program)
    if key not in _TICKS:
        _TICKS[key] = _lowered(cell, program, sharding).compile()
    return _TICKS[key]


def _compiled_tick(cell, sharding):
    """(engine, its compiled decode tick) of the GPT serving cell at
    depth 2 or of the two-layer cut of the hybrid cell."""
    return _cell_engine(cell), _compiled(cell, "decode", sharding)


def _compiled_gpt_program(program, sharding):
    """(the GPT cell's engine, its compiled ``decode`` tick or
    ``prefill_b<rung>`` program)."""
    return (_cell_engine("gpt_cell"),
            _compiled("gpt_cell", program, sharding))


def test_jamba_decode_tick_two_layer_cut(one_chip):
    """The hybrid model's decode tick at the cell's widths and 64 slots,
    cut to one Mamba and one attention layer: both state arrays and both
    pools are carried in place (aliased whole), and the tick's temporaries
    stay under one layer's scan state."""
    eng, compiled = _compiled_tick("jamba_cut", one_chip)
    assert eng.kv_path == "xla_gather"        # 1 KV head: no page shape
    mem = compiled.memory_analysis()
    caches = sum(a.size * a.dtype.itemsize for a in eng.cache.arrays())
    assert caches == eng.cache.nbytes
    assert mem.alias_size_in_bytes >= caches
    assert mem.temp_size_in_bytes < eng.cache.ssm.nbytes
    # and a prefill rung goes through Mosaic (the scan kernel)
    lowered = _lower_donated(*eng._prefill_program(256), one_chip)
    assert "selective_scan_fwd" in lowered.as_text()
    lowered.compile()


_POOL_SIZED = ("copy", "convert", "dynamic-slice", "dynamic-update-slice")


def _elements(shape_text):
    """Elements of the largest array in an HLO result type such as
    ``bf16[2,1793,16,16,128]{...}`` or a tuple of them."""
    return max((int(np.prod([int(d) for d in dims.split(",")]))
                for dims in re.findall(r"\w+\[([\d,]+)\]", shape_text)),
               default=0)


_LINE = re.compile(r"^\s*(?:ROOT )?%(\S+) = (.*?) ([\w-]+)\((.*)$")
_CALLED = re.compile(
    r"\b(?:calls|to_apply|body|condition|branch_computations|"
    r"true_computation|false_computation)=\{?(%[\w.-]+(?:, %[\w.-]+)*)")


def _callees(rest):
    """Computations an instruction's attributes name, in order."""
    return [c.lstrip("%") for group in _CALLED.findall(rest)
            for c in group.split(", ")]


def _computations(hlo):
    """A compiled module's text as {computation: [(name, result type,
    opcode, rest of the line)]}, and the entry computation's name."""
    bodies, current, entry = {}, None, None
    for line in hlo.splitlines():
        head = re.match(r"^(ENTRY )?%(\S+) \(.*\) -> .* \{$", line)
        if head:
            current = head.group(2)
            bodies[current] = []
            if head.group(1):
                entry = current
            continue
        m = _LINE.match(line)
        if m and current is not None:
            bodies[current].append(m.groups())
    return bodies, entry


def _pool_sized_moves(hlo, at_least):
    """Instructions of the compiled module that materialise an array of
    ``at_least`` elements or more by a copy, a convert, a dynamic-slice or
    a dynamic-update-slice: such an instruction on its own, or a fusion
    whose result is that large and whose body holds one that large (the
    body of a fusion with a small result materialises nothing)."""
    bodies, _entry = _computations(hlo)
    fused = {_callees(rest)[0]
             for instrs in bodies.values()
             for _n, _sh, op, rest in instrs if op == "fusion"}

    def big(body):
        return [f"{n} = {sh} {op}" for n, sh, op, _ in bodies.get(body, ())
                if op in _POOL_SIZED and _elements(sh) >= at_least]

    found = []
    for body, instrs in bodies.items():
        if body in fused:
            continue
        found += big(body)
        for name, shape, op, rest in instrs:
            if op == "fusion" and _elements(shape) >= at_least:
                inner = big(_callees(rest)[0])
                found += [f"{name} = {shape} fusion of {i}" for i in inner]
    return found


@pytest.mark.parametrize("program", ["decode", "prefill_b16",
                                     "prefill_b512"])
def test_paged_programs_touch_only_live_pages(one_chip, program):
    """The serving cell's paged decode tick and two prefill rungs, the
    one-page rung (XLA rewrites a one-index scatter as a
    dynamic-update-slice and re-lays the pool for it) and a common one
    (16 slots, max_seq 2048, 1793 pages of 16 tokens, 16 heads of 128;
    depth 2):
    the KV pools are the layer loop's carry, updated in place. The
    donated pools alias the outputs, the program's temporaries are
    smaller than one pool (a scan's xs/ys held a second copy of both),
    and nothing of a layer's pool size is copied, converted, sliced out
    or written back."""
    eng, compiled = _compiled_gpt_program(program, one_chip)
    pool = eng.cache.k.size * eng.cache.k.dtype.itemsize
    mem = compiled.memory_analysis()
    print(f"{program}: arguments {mem.argument_size_in_bytes / 2**20:.0f} "
          f"MiB, aliased {mem.alias_size_in_bytes / 2**20:.0f}, "
          f"temporaries {mem.temp_size_in_bytes / 2**20:.0f}, one pool "
          f"{pool / 2**20:.0f}")
    assert mem.alias_size_in_bytes >= 2 * pool
    assert mem.temp_size_in_bytes < pool
    moves = _pool_sized_moves(compiled.as_text(),
                              at_least=eng.cache.k[0].size)
    assert not moves, "\n".join(moves)


def _weight_sized_relayouts(hlo, at_least):
    """``copy`` and ``transpose`` instructions of the compiled module whose
    result has ``at_least`` elements or more, wherever they stand (in the
    entry, a loop's body or a fusion's), with the tiling they write."""
    bodies, _entry = _computations(hlo)
    return [f"{name} = {shape} {op}"
            for instrs in bodies.values() for name, shape, op, _ in instrs
            if op in ("copy", "transpose") and _elements(shape) >= at_least]


@pytest.mark.parametrize("program", [
    "decode", "prefill_b16", "prefill_b512",
    pytest.param("prefill_b2048", marks=pytest.mark.xfail(
        strict=True, reason="the 2048 rung alone still copies w_proj "
        "[L, nh, hd, d] into the tiling {2,3,1,0} (hd minor), as the "
        "parent's did beside its copy of w_qkv: 0.6 ms of a 60 ms rung "
        "by its bytes (PERF.md section 7)"))])
def test_serving_programs_relay_no_weight(one_chip, program):
    """The engine holds ``w_qkv`` in the layout its programs contract
    (``GPTServing.hold``: ``[L, d, 3·nh·hd]``, where the stored
    ``[L, d, 3, nh, hd]`` put the tiles on ``(nh, hd)``): the serving
    cell's decode tick and its prefill rungs, compiled for the described
    chip, hold no ``copy`` or ``transpose`` the size of the smallest
    stacked block matrix (``w_proj``, a third of ``w_qkv``) or larger
    (compiled by hand, PR 32: nor do the rungs 32 to 1024). At
    24 layers such a copy of ``w_qkv`` was 1.83 ms of every 8.3 ms tick
    (PERF.md section 6, PR 32). XLA decides the tiling from the product's
    spelling (``models/gpt_serving.py:qkv_heads``): a reshape to heads straight
    after the product brings the copy back, in the tiling ``{1,2,0}``."""
    eng, compiled = _compiled_gpt_program(program, one_chip)
    held = eng.held_shapes["blocks/w_qkv"]
    assert held == (CELL_L, D, 3 * NH * HD)
    moves = _weight_sized_relayouts(compiled.as_text(),
                                    at_least=CELL_L * D * NH * HD)
    assert not moves, "\n".join(moves)


# the GPT cell's programs with eight and nine host arrays (the parent of
# the one feed array, PR 35's tree): their temporaries by this compile
_TEMP_BEFORE_THE_FEED = {"decode": 4_300_288, "prefill_b512": 1_285_632}


@pytest.mark.parametrize("program", ["decode", "prefill_b512"])
def test_the_one_feed_array_is_cut_apart_for_nothing(one_chip, program):
    """A call's host arguments are one int32 array that the program cuts
    apart (``serving/engine.py``, "the feed"): on the chip's own compile of
    the GPT cell's tick and its 512 rung the entry takes ONE integer
    parameter behind the weights and the two pools, the cut costs no
    re-laid copy of it (the table row comes first so that its slice starts
    at lane 0; the compiler may prefetch the array's few KB into fast
    memory, a ``copy-start`` in the same tiling, which is no relayout),
    and the program's temporaries are within 1 MB of what they were with
    eight and nine arrays (described compile of the parent: 4.10 and 1.23
    MiB). That no weight is re-laid either is
    ``test_serving_programs_relay_no_weight``'s."""
    eng, compiled = _compiled_gpt_program(program, one_chip)
    _fn, example = (eng._decode_program() if program == "decode" else
                    eng._prefill_program(512))
    feed = example[-1]
    assert len(example) == 3 and feed.dtype == np.int32
    bodies, entry = _computations(compiled.as_text())
    shape = "s32[%s]" % ",".join(map(str, feed.shape))
    params = [sh for _n, sh, op, _r in bodies[entry] if op == "parameter"]
    (integers,) = [sh for sh in params if sh.startswith("s32")]
    assert integers.startswith(shape), params
    assert not [sh for sh in params if sh.startswith("f32[]")]
    relaid = [f"{n} = {sh} {op}" for instrs in bodies.values()
              for n, sh, op, _r in instrs
              if op in ("copy", "transpose") and sh.startswith(shape)]
    assert not relaid, relaid
    temp = compiled.memory_analysis().temp_size_in_bytes
    print(f"{program}: temporaries {temp} B, with eight or nine arrays "
          f"{_TEMP_BEFORE_THE_FEED[program]} B")
    assert abs(temp - _TEMP_BEFORE_THE_FEED[program]) < 2 ** 20


def _reached(bodies, roots, through_conditionals):
    """Computations reached from ``roots`` along the instructions' calls;
    with ``through_conditionals`` false a ``conditional``'s branches are
    not entered."""
    seen, todo = set(), list(roots)
    while todo:
        body = todo.pop()
        if body in seen:
            continue
        seen.add(body)
        for _name, _shape, op, rest in bodies[body]:
            if op != "conditional" or through_conditionals:
                todo += _callees(rest)
    return seen


@pytest.mark.parametrize("cell", ["gpt_cell", "jamba_cut"])
def test_decode_tick_sorts_the_vocabulary_under_a_conditional(one_chip,
                                                              cell):
    """The sampler's work is a branch of the tick, not a part of it
    (serving/sampling.py): on the chip's own compile of the GPT cell's
    tick (16 slots) and of the hybrid cell's (64 slots, vocabulary
    65,536) every sort of ``[slots, vocabulary]`` lies in a computation
    that only a ``conditional`` reaches (XLA may flatten a cond into a
    select that computes every side: then the sort is back in every
    tick), and the branch a greedy batch takes holds neither a sort nor
    an exponential of that size."""
    eng, compiled = _compiled_tick(cell, one_chip)
    rows = eng.ecfg.max_batch * eng.cfg.vocab_size
    bodies, entry = _computations(compiled.as_text())

    def dear(body, ops):
        return [f"{body}: {n} = {sh} {op}" for n, sh, op, _ in bodies[body]
                if op in ops and _elements(sh) >= rows]

    sorts = [body for body in bodies if dear(body, ("sort",))]
    assert sorts, "no sort of the vocabulary anywhere: is the filtered " \
                  "path still in the executable?"
    always = _reached(bodies, [entry], through_conditionals=False)
    assert not set(sorts) & always, sorts
    switches = [
        _callees(rest) for body in always
        for _n, _sh, op, rest in bodies[body] if op == "conditional"
        and set(sorts) & _reached(bodies, _callees(rest), True)]
    (branches,) = switches            # the one switch that holds the sort
    assert len(branches) == 3         # greedy, temperature, filtered
    greedy, temperature, filtered = (
        _reached(bodies, [b], True) for b in branches)
    for body in greedy:
        assert not dear(body, ("sort", "exponential"))
    assert not set(sorts) & temperature and set(sorts) <= filtered


# ---------------------------------------------------------------------------
# sparse experts and a latent cache (models/kimi_k2.py, ops/moe.py)
# ---------------------------------------------------------------------------

KIMI_YARN = {"type": "yarn", "factor": 64.0, "beta_fast": 32.0,
             "beta_slow": 1.0, "mscale": 1.0, "mscale_all_dim": 1.0,
             "original_max_position_embeddings": 4096}


def _kimi_cut_config():
    from paddle_tpu.models import kimi_k2 as KK

    return KK.KimiK2Config(vocab_size=20480, num_hidden_layers=3,
                           experts_held=12, rope_scaling=KIMI_YARN)


def _kimi_cut_program(program, sharding):
    """(config, the cell's decode tick or a prefill rung compiled for the
    described chip)."""
    return _kimi_cut_config(), _compiled("kimi", program, sharding)


def _lower_kimi_cut(program, sharding, uncut=False):
    """The decode tick or a prefill rung, lowered for the described chip,
    of the expert-parallel cell at its published widths,
    128 slots of 3072 tokens, 12 held experts of 384, an eighth of the
    vocabulary, cut to layer 0 (dense) and two expert layers (so that the
    expert layers' loop is real). Compiled from SHAPES alone: the engine's
    pure functions on an engine that was never built (its weights would be
    4 GB of host memory that no compile reads)."""
    from paddle_tpu import serving
    from paddle_tpu.models import kimi_k2 as KK
    from paddle_tpu.serving import engine as E

    B, S = 128, 3072
    cfg = _kimi_cut_config()
    eng = object.__new__(E.DecodeEngine)
    eng.model, eng.cfg = KK.KimiK2Serving(cfg), cfg
    eng.ecfg = serving.EngineConfig(
        max_batch=B, max_seq=S, page_size=PAGE, weight_dtype="bf16",
        prefix_cache=False)
    eng.kv_path = "pallas_paged"
    assert eng.model.kernel_takes_pages(PAGE, BF16)

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    stored = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s, F32), KK.leaf_shapes(cfg),
        is_leaf=lambda s: isinstance(s, tuple))
    held = jax.tree_util.tree_map(
        lambda a: arg(a.shape, a.dtype),
        jax.eval_shape(lambda p: KK.hold(p, cfg, "bf16"), stored))
    pool = (arg((3, B * S // PAGE + 1, PAGE, cfg.cache_width), BF16),)
    # the one feed array of a call (serving/engine.py, "the feed")
    if program == "decode":
        fn, feed = eng._decode_fn_paged, arg(
            E.slot_feed_shape(B, S // PAGE), jnp.int32)
    else:
        T = int(program.split("_b")[1])
        fn, feed = eng._prefill_fn_paged, arg(
            (E.rung_feed_len(S // PAGE, T),), jnp.int32)
    if uncut:
        feed = jax.tree_util.tree_map(
            lambda a: arg(a.shape, a.dtype), _uncut(feed, S // PAGE))
    return jax.jit(fn, donate_argnums=(1,)).lower(held, pool, feed)


# What ``_mla_decode_kernel`` may hold, counted in the kernel's jaxpr at the
# cell's shapes (each ``pl.when`` and loop body once): copies started, and
# products on the MXU (two a fold body). PR 50 ships 63 starts (32 of a
# whole chunk and 16 + 8 + 4 + 2 + 1 of a tail, at ONE site) and ONE fold
# body of 512 rows. A larger program is paid for by EVERY process that
# serves the model, warm compile cache or not: PR 49's kernel (254 starts at
# two sites, five fold bodies of up to 1,024 rows) was 6 % faster end to end
# and cost 13.4 s more ``setup_s`` in the expert-parallel cell, which
# refused it (ledger, PR 49; the table of variant, program size, first call
# and warm set-up is PERF.md section 6, PR 50). Raise these only with that
# table's columns measured anew. (Two products: an unmasked second fold
# body read the same time a launch in the cell and is not shipped.)
MLA_KERNEL_DMA_STARTS = 63
MLA_KERNEL_DMA_WAITS = 6
MLA_KERNEL_DOTS = 2


def _kernel_primitive_counts(fn, *args):
    """{primitive: count} over the jaxpr of the one ``pallas_call`` that
    ``fn`` traces to, the bodies of its conditionals and loops once each."""
    from collections import Counter

    def subjaxprs(eqn):
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield inner

    def walk(jaxpr, count):
        for eqn in jaxpr.eqns:
            count[eqn.primitive.name] += 1
            for inner in subjaxprs(eqn):
                walk(inner, count)
        return count

    def kernels(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                yield eqn.params["jaxpr"]
            else:
                for inner in subjaxprs(eqn):
                    yield from kernels(inner)

    (kernel,) = kernels(jax.make_jaxpr(fn)(*args).jaxpr)
    return walk(kernel, Counter())


def test_mla_paged_decode_attention(one_chip):
    """Mosaic takes the latent decode kernel at the expert-parallel cell's
    shapes (128 slots, 64 heads, rows of 640 lanes of which 512 are
    values, pages of 16 rows, a table of 192 entries), its copies
    unchecked; and the kernel's program is no larger than PR 50 shipped
    it."""
    B, H, W, RANK, M = 128, 64, 640, 512, 192

    def fn(q, pool, rows, tables, positions, layer):
        return PK.mla_paged_decode_attention(
            q, pool, rows, tables, positions, layer, RANK, 0.1447)

    shapes = (((B, H, W), BF16), ((3, B * M + 1, PAGE, W), BF16),
              ((B, W), BF16), ((B, M), jnp.int32), ((B,), jnp.int32),
              ((), jnp.int32))
    compiled = _compile(fn, one_chip, *shapes)
    assert re.search(r"%mla_paged_decode[\w.]* = ", compiled.as_text())
    count = _kernel_primitive_counts(
        fn, *[jax.ShapeDtypeStruct(s, d) for s, d in shapes])
    assert count["dma_start"] <= MLA_KERNEL_DMA_STARTS, count["dma_start"]
    assert count["dot_general"] <= MLA_KERNEL_DOTS, count["dot_general"]
    assert count["dma_wait"] <= MLA_KERNEL_DMA_WAITS, count["dma_wait"]


@pytest.mark.parametrize("program", ["decode", "prefill_b512"])
def test_kimi_programs_copy_no_expert_leaf(one_chip, program):
    """The expert-parallel cell's tick and a prefill rung on the chip's
    own compile: both kernels are there by name (``moe_grouped_matmul``
    takes the stacked experts with the layer as a prefetched scalar,
    ``mla_paged_decode`` the pool where it lies), the donated pool is
    aliased whole, and nothing the size of ONE expert's smallest leaf
    (``w_down [F, D]``, 14.7 M elements: the held leaves are 12 and 24
    times that a layer) is copied, transposed, converted or sliced out:
    the experts are held as the grouped product contracts them
    (``KimiK2Serving.hold``). The tick never materialises the padded ``[128,
    3072, 640]`` view of the pool either (the gather lowering's): its
    temporaries stay under a tenth of one layer's pool."""
    cfg, compiled = _kimi_cut_program(program, one_chip)
    hlo = compiled.as_text()
    assert re.search(r"%moe_grouped_matmul[\w.]* = ", hlo)
    assert bool(re.search(r"%mla_paged_decode[\w.]* = ", hlo)) == (
        program == "decode")
    one_expert_leaf = cfg.moe_intermediate_size * cfg.hidden_size
    moves = (_weight_sized_relayouts(hlo, at_least=one_expert_leaf)
             + _pool_sized_moves(hlo, at_least=one_expert_leaf))
    # a rung's own activations are that large ([T, H, 192] queries);
    # weights and pool are told from them by their leading sizes
    weights = [m for m in moves if re.search(
        r"\[(?:\d+,)*(?:12,7168,4096|12,2048,7168|7168,4096|2048,7168|"
        r"24577,16,640|128,3072,640|393216,640)\]", m)]
    assert not weights, "\n".join(weights)
    mem = compiled.memory_analysis()
    layer_pool = (128 * 3072 + PAGE) * cfg.cache_width * 2
    assert mem.alias_size_in_bytes >= 3 * layer_pool
    if program == "decode":
        assert not moves, "\n".join(moves)
        assert mem.temp_size_in_bytes < layer_pool // 10


# ---------------------------------------------------------------------------
# the serving cells' programs, as text: a change to the host's side of the
# tick (PR 34: early dispatch) must leave every one of them as it was
# ---------------------------------------------------------------------------

# sha256 of ``lowered.as_text()`` (StableHLO, no source locations) of each
# cell's decode tick and of every prefill rung, lowered for the described
# chip from the shapes above, the Mosaic kernels' bodies masked (serialized
# MLIR that carries the checkout's path in its locations; the kernels have
# tests of their own above). Read at PR 33's tree and the same at PR 34's;
# read anew at PR 38's, which changed every program's signature on purpose
# (one feed array behind the caches in place of eight or nine, cut apart
# first thing: the layers' and the sampler's text is the parent's).
# Read anew for ``gpt_cell/*`` at PR 42's, in both tables: the pools' rows
# are flat (``bf16[2,1793,16,2048]``), the tick's custom call is the grouped
# kernel at a group of one, a rung writes pages without a reshape to heads
# and attends the transposed flat view; ``jamba_cut/*`` and ``kimi/*`` are
# PR 38's still, which is the proof that those cells' programs are the
# parent's.
# Read anew for ``kimi/decode`` at PR 50's, in both tables: the latent
# kernel takes its page table flat (a reshape of the feed's columns before
# the call, ``s32[24576]`` where ``s32[128,192]`` was) and its custom call
# says ``disable_bounds_checks``; ``kimi/prefill_b512`` is PR 38's still.
# ``olmo_cut/*``, ``cohere_cut/*`` and ``solar/*`` (the tick and one rung of
# the delta-rule, the window-and-global and the gate-a-channel cell's
# described compiles below) were read at PR 50's tree, before PR 51 moved a
# line of ``models/``: all twenty are held through that refactoring.
# A PR that changes a program on purpose reads the new digests off the
# failure's message, puts them here and says in PERF.md which program
# changed and why; one that meant to leave the device's work alone has not.
PROGRAM_TEXT_SHA256 = {
    "gpt_cell/decode":
        "3ee90c509bff70535104c388223face1456a21d13323c4f4201f894c3beb84ef",
    "gpt_cell/prefill_b16":
        "408ef5622e62ff4832805c68cfadc6e82b96d142fcea5b00b6765cf60c171063",
    "gpt_cell/prefill_b32":
        "4e32b12c09151de0af2335b7fa73cf27469c682d355b14e400f9aa338062ff05",
    "gpt_cell/prefill_b64":
        "7e962ea13598348c182b4959b39f1b4f7fb50aacc213f689480361237cb1525f",
    "gpt_cell/prefill_b128":
        "8d00e9bab9137fc7f5003ca8d5b0bf3b55334cc4b4ce924f5dc12492bb7c5f45",
    "gpt_cell/prefill_b256":
        "0893180a845729f4e19f517d7a770656874d9c7c8aa023d1223e931ef2fd7fda",
    "gpt_cell/prefill_b512":
        "7ade07ef7e8086f04e88dfeee17eaee842995a49f0faab45d4a87c8d9a735a65",
    "gpt_cell/prefill_b1024":
        "547883a9b66441ec745b6c23f1a45c15d117cf6994ad9552c7c015dcc37a6e48",
    "gpt_cell/prefill_b2048":
        "0b4c8f8ef2de232b8d11a719009a2f1827186eefad472b8860b7dc6fe4ba0bb7",
    "jamba_cut/decode":
        "89820486ab01c4a292d3a3960cdfb639c76c5aaba51a7ec086b14adacb4c61c8",
    "jamba_cut/prefill_b256":
        "457166bf9e26c2b84b33ca299a15c06ad80b60b8f584eb1060153a33fbd5a3b7",
    "jamba_cut/prefill_b2048":
        "4236a86f1728454259195c681b48841cb57449fe7651ad4e8ed25460b3dc2c33",
    "kimi/decode":
        "6c84ea7d634ae5574ae38a702d86fcc3c189f557c609fd17c94aa6eb6a8fc6cd",
    "kimi/prefill_b512":
        "abe8eea28687e68d7baa85e1ba7304e6e712b5fc6c0a4367049da51ae1e778e1",
    "olmo_cut/decode":
        "be1c1cf6969393b0bab42fa8c324e2a41754836bf439525b9f60a225ecb09321",
    "olmo_cut/prefill_b4096":
        "c3351c41216d2d153d3937c5725bab4064dc1f5386d8716671bc6ef407647081",
    "cohere_cut/decode":
        "aed9f81e687b4be2d623e3b518170e4784c1cdd92cbd465613f8824323466c0d",
    "cohere_cut/prefill_b2048":
        "ff7ac6851107f5b3b98e1393faba8d47a0008182be0f5313cd51d3da9f78d735",
    "solar/decode":
        "85014dbbac8dc5e6eafa6a02567b377ae1e286bf6248dab790fa47362697822b",
    "solar/prefill_b8192":
        "99648828307f4572821c7d517b7547f12bf209fcb9ef542b6d65ab7c6de12d77",
}


# The same programs behind the feed's cut: PR 35's digests, of the programs
# that took eight and nine host arrays. PR 38 packed those into the feed and
# changed nothing else, and this holds it to that: what the layers, the head
# and the sampler are traced from, and in what order, is PR 35's, text for
# text. A PR that changes a program on purpose re-pins both tables.
BEHIND_THE_CUT_SHA256 = {
    "gpt_cell/decode":
        "1c149076c8ec3571dd878631f40bda7f9d6e2bd7c521a1afd8fb9ff8ad512f62",
    "gpt_cell/prefill_b16":
        "a1d81af7e79f6fbc6b59b809784c2f4579588d2f34941dbbbd05b96f7c1eff76",
    "gpt_cell/prefill_b32":
        "44fe4d4b13ed08b00c99202e4e8ecb7461621445e6b2530e8169a7e6c99ab96c",
    "gpt_cell/prefill_b64":
        "68a3ea564ca2ae09d29e8ee7537e736406f9141653fed8ba3b17da051375873b",
    "gpt_cell/prefill_b128":
        "22b7069a2320d3b2304af935f44d6273155cc2445685c17728edd29c3ee0e753",
    "gpt_cell/prefill_b256":
        "e6d8d19a3c2022a1d3baf606e4d0296dc88e9be400bcedf5d9fbeaee13e1a7f6",
    "gpt_cell/prefill_b512":
        "358969e7b8b084f99e0a276a200f42de00d6348fe990fe3bfeec4f37466f4d56",
    "gpt_cell/prefill_b1024":
        "55402336a2b328a3d58ef943b63f2aa0ba03d788b59f617ba279218a972ca742",
    "gpt_cell/prefill_b2048":
        "54c5311a652ee06a12e49083e71ce56ecbe38f076bf18a34eaaff00cbccdc202",
    "jamba_cut/decode":
        "a7bd90bf1b15a58fb8bcd784ca92a38d038cd1fbb271e50cc506bfce64f5c874",
    "jamba_cut/prefill_b256":
        "765bfeb613a9f29e4c21536b79246b660e47c1ece682f1b4a0ee6ef323ddeec0",
    "jamba_cut/prefill_b2048":
        "6c4346064d7e51b44def091e3c8ef4e160b304ee37389623122cfd2b0d719b6a",
    "kimi/decode":
        "522f60c4ac8926985a3609a4761425cce7bc7cbc153148f4a0b6d10daffd97b2",
    "kimi/prefill_b512":
        "a83f6981bf07fd7f8b7fff087feb600adbd4c04fd18b55f9419b06080794d73e",
}


def _text_digest(lowered):
    import hashlib

    return hashlib.sha256(re.sub(
        r'\\22body\\22: \\22[A-Za-z0-9+/=]*\\22', "body",
        lowered.as_text()).encode()).hexdigest()


def _program_names(cell):
    if cell == "gpt_cell":
        rungs = (16, 32, 64, 128, 256, 512, 1024, 2048)
    elif cell == "jamba_cut":
        rungs = (256, 2048)
    else:
        rungs = {"kimi": (512,), "olmo_cut": (4096,), "cohere_cut": (2048,),
                 "solar": (8192,)}[cell]
    return ["decode"] + [f"prefill_b{r}" for r in rungs]


@pytest.mark.parametrize("cell", ["gpt_cell", "jamba_cut", "kimi",
                                  "olmo_cut", "cohere_cut", "solar"])
def test_serving_programs_lower_to_the_text_they_had(one_chip, cell):
    if cell in ("gpt_cell", "jamba_cut"):
        assert _program_names(cell)[1:] == [
            f"prefill_b{b}" for b in _cell_engine(cell).buckets]
    got = {f"{cell}/{program}": _text_digest(
        _lowered(cell, program, one_chip)) for program in _program_names(cell)}
    want = {k: v for k, v in PROGRAM_TEXT_SHA256.items()
            if k.startswith(cell + "/")}
    assert got == want, f"the programs' digests now:\n{got!r}"


@pytest.mark.parametrize("cell", ["gpt_cell", "jamba_cut", "kimi"])
def test_serving_programs_behind_the_cut_are_what_they_were(
        one_chip, cell, _cuts_handed_through):
    got = {f"{cell}/{program}": _text_digest(
        _lowered(cell, program, one_chip, uncut=True))
        for program in _program_names(cell)}
    want = {k: v for k, v in BEHIND_THE_CUT_SHA256.items()
            if k.startswith(cell + "/")}
    assert got == want, f"the digests behind the cut now:\n{got!r}"


# ---------------------------------------------------------------------------
# serve_olmo_hybrid_7b_l16_closed32 (benchmark/configs/
# olmo-hybrid-7b-l16.json): 48 slots of 4608 tokens over 3585 pages, 30
# heads of 128 in the full layers, 30 heads of 96 x 192 in the linear ones
# ---------------------------------------------------------------------------

GDN_H, GDN_DK, GDN_DV = 30, 96, 192
OLMO_B, OLMO_S, OLMO_PAGES = 48, 4608, 3585


# temp_size_in_bytes of the chunk kernel's program (the transposes and the
# running sums around the call) at the lowest rung, the median first
# token's and the top one, as PR 44's tree compiled them: the blocked
# inverse holds its tile in values and drops the [C, C] scratch, so none
# may grow
GDN_CHUNK_TEMP = {256: 0, 1408: 0, 4096: 63_301_632}


@pytest.mark.parametrize("T", sorted(GDN_CHUNK_TEMP))
def test_gated_delta_chunk_fwd(one_chip, T):
    """The chunkwise delta rule at the cell's head sizes, its lowest rung,
    the 1408 of a median first token and its highest: blocks of 64 tokens
    of one head, the triangle inverted in two blocks of 32 (masked lane
    sums, static slices of 8 to 32 rows, one product) and transposed, the
    state in VMEM."""
    from paddle_tpu.ops import gated_delta as GD

    compiled = _compile(
        GD.gated_delta_chunked, one_chip,
        ((T, GDN_H, GDN_DK), BF16), ((T, GDN_H, GDN_DK), BF16),
        ((T, GDN_H, GDN_DV), BF16), ((T, GDN_H), F32),
        ((T, GDN_H), F32), ((), jnp.int32))
    assert re.search(r"%gated_delta_chunk_fwd[\w.]* = ", compiled.as_text())
    assert compiled.memory_analysis().temp_size_in_bytes <= GDN_CHUNK_TEMP[T]


def test_gated_delta_update_rows(one_chip):
    """The one-token delta rule over all 12 linear layers' state rows of
    48 slots: the array stays in HBM and is aliased whole (no copy of it
    is made), a rider's row of 15 x 96 x 384 float32 is what a DMA
    moves."""
    from paddle_tpu.ops import gated_delta as GD

    assert GD.state_fold(GDN_H, GDN_DV) == 2
    state = (12, OLMO_B, GDN_H // 2, GDN_DK, 2 * GDN_DV)
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in (
        (state, F32), ((OLMO_B, GDN_H, GDN_DK), BF16),
        ((OLMO_B, GDN_H, GDN_DK), BF16), ((OLMO_B, GDN_H, GDN_DV), BF16),
        ((OLMO_B, GDN_H), F32), ((OLMO_B, GDN_H), F32),
        ((OLMO_B,), jnp.int32), ((), jnp.int32))]
    lowered = jax.jit(
        lambda S, q, k, v, a, b, slots, layer: GD.gated_delta_update(
            S, q, k, v, a, b, slots, layer=layer),
        donate_argnums=(0,)).lower(*args)
    assert "gated_delta_update_rows" in lowered.as_text()
    mem = lowered.compile().memory_analysis()
    nbytes = int(np.prod(state)) * 4
    assert mem.alias_size_in_bytes >= nbytes
    assert mem.temp_size_in_bytes < nbytes // (12 * OLMO_B) * 4


def test_thirty_heads_lie_flat_in_3840_lanes(one_chip):
    """30 key/value heads of 128 are 30 lane tiles of a page's rows: the
    model's pools hold a token's heads flat, 3,840 lanes and no padded
    row (as head rows, 30 had to be padded to 32: Mosaic copies a page's
    head axis in eights), and the page-table kernel takes them as it
    takes any count of equal heads of whole lanes. At a megabyte a chunk
    such a row gives chunks of 128 rows, eight pages."""
    from paddle_tpu.models import olmo_hybrid as O

    cfg = O.OlmoHybridConfig()
    model = O.OlmoHybridServing(cfg)
    assert cfg.num_key_value_heads == 30
    assert model.cache_pools["rows"] == ((30 * HD,),) * 2
    assert PK.paged_decode_tiles(30, HD)
    assert model.kernel_takes_pages(PAGE, BF16)
    assert PK._chunk_pages(OLMO_S // PAGE, PAGE, 30 * HD, BF16) == 8
    pool = ((4, OLMO_PAGES, PAGE, 30 * HD), BF16)
    _compile(PK.paged_decode_attention, one_chip,
             ((OLMO_B, 30, HD), BF16), pool, pool, ((), jnp.int32),
             ((OLMO_B, OLMO_S // PAGE), jnp.int32), ((OLMO_B,), jnp.int32))


def _lower_olmo_cut(program, sharding):
    """The decode tick or a prefill rung, lowered for the described chip,
    of the delta-rule cell at its published widths, 48 slots of 4608
    tokens over 3585 pages, cut to one period of its pattern (three linear
    layers, so that their loop is real, and one full layer). Compiled from
    SHAPES alone: the engine's pure functions on an engine that was never
    built (its weights would be 2.8 GB of host memory that no compile
    reads)."""
    from paddle_tpu import serving
    from paddle_tpu.models import olmo_hybrid as O
    from paddle_tpu.serving import engine as E

    B, S = OLMO_B, OLMO_S
    cfg = O.OlmoHybridConfig(num_hidden_layers=4, layer_types=O._PERIOD)
    eng = object.__new__(E.DecodeEngine)
    eng.model, eng.cfg = O.OlmoHybridServing(cfg), cfg
    eng.ecfg = serving.EngineConfig(
        max_batch=B, max_seq=S, page_size=PAGE, weight_dtype="bf16",
        prefix_cache=False)
    eng.kv_path = "pallas_paged"

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    stored = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s, F32), O.leaf_shapes(cfg),
        is_leaf=lambda s: isinstance(s, tuple))
    held = jax.tree_util.tree_map(
        lambda a: arg(a.shape, a.dtype),
        jax.eval_shape(lambda p: eng.model.hold(p, "bf16", 256), stored))
    geometry = eng.model.state_geometry
    pool = arg((1, OLMO_PAGES, PAGE) + eng.model.cache_pools["rows"][0],
               BF16)
    caches = (pool, pool, arg((3, B) + geometry["conv"], BF16),
              arg((3, B) + geometry["ssm"], F32))
    # the one feed array of a call (serving/engine.py, "the feed")
    if program == "decode":
        fn, feed = eng._decode_fn_paged, arg(
            E.slot_feed_shape(B, S // PAGE), jnp.int32)
    else:
        T = int(program.split("_b")[1])
        fn, feed = eng._prefill_fn_paged, arg(
            (E.rung_feed_len(S // PAGE, T),), jnp.int32)
    return (jax.jit(fn, donate_argnums=(1,)).lower(held, caches, feed),
            caches)


def test_olmo_hybrid_tick_moves_the_riders_rows_and_relays_no_weight(
        one_chip):
    """The tick of one period at the cell's widths: pages read through the
    page-table kernel, the state rows advanced in place by the update
    kernel (every cache array aliased whole, the temporaries under one
    slot's state of a layer times the slots: no copy of a layer's rows),
    and no weight re-laid: applied on heads, the output gate had XLA copy
    all layers' ``w_g`` on every tick (0.53 GB at 12 layers, described
    compile, PR 35)."""
    lowered, caches = _lower_olmo_cut("decode", one_chip)
    text = lowered.as_text()
    assert "paged_decode_attention" in text
    assert "gated_delta_update_rows" in text
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    nbytes = sum(int(np.prod(a.shape)) * a.dtype.itemsize for a in caches)
    assert mem.alias_size_in_bytes >= nbytes
    layer_rows = OLMO_B * GDN_H * GDN_DK * GDN_DV * 4
    assert mem.temp_size_in_bytes < layer_rows
    moves = _weight_sized_relayouts(compiled.as_text(),
                                    at_least=3 * 3840 * 3840)
    assert not moves, "\n".join(moves)


def test_olmo_hybrid_rung_goes_through_both_kernels(one_chip):
    """A rung of 1408 tokens (the cycle's median prompt): the chunked
    delta rule and the flash kernel (no ``[30, T, T]`` scores), and a
    slot's state written into the carried arrays in place."""
    lowered, caches = _lower_olmo_cut("prefill_b1408", one_chip)
    text = lowered.as_text()
    assert "gated_delta_chunk_fwd" in text and "flash_fwd" in text
    mem = lowered.compile().memory_analysis()
    nbytes = sum(int(np.prod(a.shape)) * a.dtype.itemsize for a in caches)
    assert mem.alias_size_in_bytes >= nbytes
    assert mem.temp_size_in_bytes < 30 * 1408 * 1408 * 4


# ---------------------------------------------------------------------------
# window and global layers over two page groups (PR 41): the cohere2_moe
# cell's kernels, tick and rungs at published widths, from shapes alone
# ---------------------------------------------------------------------------

COHERE_B, COHERE_S, COHERE_PAGE, COHERE_PAGES = 32, 17408, 64, 5001


def _cohere_cut_config():
    from paddle_tpu.models import cohere2_moe as CM

    return CM.Cohere2MoeConfig(
        vocab_size=32768, num_hidden_layers=4,
        layer_types=(CM.SLIDING,) * 3 + (CM.FULL,), experts_held=16)


def _lower_cohere_cut(program, sharding):
    """The decode tick or a prefill rung of ``command-a-plus-ep8-l4`` as the
    cell runs it (one period of the layer pattern, 16 held experts of 128,
    an eighth of the vocabulary, 32 slots of 17,408 tokens in pages of 64),
    lowered for the described chip from SHAPES alone (its weights would be
    9.5 GB of host memory that no compile reads)."""
    from paddle_tpu import serving
    from paddle_tpu.models import cohere2_moe as CM
    from paddle_tpu.serving import engine as E
    from paddle_tpu.serving.paged_kv import table_width

    B, S, PG = COHERE_B, COHERE_S, COHERE_PAGE
    cfg = _cohere_cut_config()
    eng = object.__new__(E.DecodeEngine)
    eng.model, eng.cfg = CM.Cohere2MoeServing(cfg), cfg
    eng.ecfg = serving.EngineConfig(
        max_batch=B, max_seq=S, page_size=PG, num_pages=COHERE_PAGES,
        weight_dtype="bf16", prefix_cache=False)
    eng.kv_path = "pallas_paged"
    assert eng.model.kernel_takes_pages(PG, BF16)
    assert eng.table_widths == (S // PG, 4096 // PG + 1)

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    stored = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s, F32), CM.leaf_shapes(cfg),
        is_leaf=lambda s: isinstance(s, tuple))
    held = jax.tree_util.tree_map(
        lambda a: arg(a.shape, a.dtype),
        jax.eval_shape(lambda p: CM.hold(p, cfg, "bf16"), stored))
    ring = table_width(4096, S, PG)
    pools = tuple(
        arg((layers, pages, PG, cfg.kv_width), BF16)
        for layers, pages in ((1, COHERE_PAGES), (3, B * ring + 1))
        for _ in range(2))
    if program == "decode":
        fn, feed = eng._decode_fn_paged, arg(
            E.slot_feed_shape(B, eng.table_width), jnp.int32)
    else:
        T = int(program.split("_b")[1])
        fn, feed = eng._prefill_fn_paged, arg(
            (E.rung_feed_len(eng.table_width, T),), jnp.int32)
    return jax.jit(fn, donate_argnums=(1,)).lower(held, pools, feed)


@pytest.mark.parametrize("window", [None, 4096], ids=["full", "window"])
def test_gqa_paged_decode_attention(one_chip, window):
    """Mosaic takes the grouped-query paged decode kernel at the cell's
    shapes: 128 query heads over 8 key/value heads of 128, pages of 64 rows
    of 1,024 lanes, a full table of 272 entries and a ring of 65."""
    B, H, KVH, PG = COHERE_B, 128, 8, COHERE_PAGE
    M = COHERE_S // PG if window is None else 4096 // PG + 1

    def fn(q, kp, vp, nk, nv, tables, positions, layer):
        return PK.gqa_paged_decode_attention(
            q, kp, vp, nk, nv, tables, positions, layer, KVH,
            window=window, ring=window is not None)

    pool = ((3, 2081, PG, KVH * HD), BF16)
    row = ((B, KVH * HD), BF16)
    compiled = _compile(fn, one_chip, ((B, H, HD), BF16), pool, pool, row,
                        row, ((B, M), jnp.int32), ((B,), jnp.int32),
                        ((), jnp.int32))
    assert re.search(r"%gqa_paged_decode[\w.]* = ", compiled.as_text())


@pytest.mark.parametrize("window", [None, 4096], ids=["causal", "band"])
@pytest.mark.parametrize("T", [2048, 16384])
def test_band_flash_attention(one_chip, T, window):
    """Mosaic takes the grouped, windowed flash kernel on the flat ``[1, T,
    heads x 128]`` arrays of the lowest and the top rung; no transposed
    copy of q, k, v or the output is made (no temporaries at all)."""
    compiled = _compile(
        lambda q, k, v: PK.band_flash_attention(q, k, v, 128, 8,
                                                window=window),
        one_chip, ((1, T, 128 * HD), BF16), ((1, T, 8 * HD), BF16),
        ((1, T, 8 * HD), BF16))
    assert re.search(r"%window_flash_fwd[\w.]* = ", compiled.as_text())
    assert compiled.memory_analysis().temp_size_in_bytes == 0


def test_gpt_flash_call_is_the_kernel_it_was(one_chip):
    """``flash_attention`` with equal heads and no window lowers to the
    kernel it lowered to before it learned of either (``flash_fwd`` on
    ``[BH, T, hd]``, a three-axis grid): the training cell's path."""
    text = jax.jit(lambda q, k, v: PK.flash_attention(q, k, v)).lower(
        *[jax.ShapeDtypeStruct(s, d, sharding=one_chip)
          for s, d in (QKV, QKV, QKV)]).as_text()
    assert "flash_fwd" in text and "window_flash_fwd" not in text


_COHERE_RESIDENT = 15.75 * 2 ** 30


@pytest.mark.parametrize("program", ["decode", "prefill_b2048",
                                     "prefill_b16384"])
def test_cohere_programs_fit_and_relay_no_weight(one_chip, program):
    """The window-and-global cell's tick, lowest and top rung on the chip's
    own compile: the kernels are there by name (``gqa_paged_decode`` in the
    tick, ``window_flash_fwd`` in a rung, ``moe_grouped_matmul`` in both),
    both page groups' donated pools are aliased whole, nothing the size of
    one expert's smallest leaf (``w_down [F, D]``, 16.8 M elements) is
    copied, transposed, converted or sliced out of a weight or a pool, and
    arguments plus temporaries stay under the chip's 15.75 GiB."""
    cfg = _cohere_cut_config()
    compiled = _lower_cohere_cut(program, one_chip).compile()
    hlo = compiled.as_text()
    assert re.search(r"%moe_grouped_matmul[\w.]* = ", hlo)
    assert bool(re.search(r"%gqa_paged_decode[\w.]* = ", hlo)) == (
        program == "decode")
    assert bool(re.search(r"%window_flash_fwd[\w.]* = ", hlo)) == (
        program != "decode")
    one_expert_leaf = cfg.intermediate_size * cfg.hidden_size
    moves = (_weight_sized_relayouts(hlo, at_least=one_expert_leaf)
             + _pool_sized_moves(hlo, at_least=one_expert_leaf))
    # a rung's own activations are that large; weights and pools are told
    # from them by their leading sizes (``w_o [16384, 4096]`` and the
    # table ``[32768, 4096]`` are the shapes of the experts' full-size row
    # buffers of the 2048 and the 16384 rung too, so those two are held by
    # the tick alone, below)
    weights = [m for m in moves if re.search(
        r"\[(?:\d+,)*(?:16,4096,8192|16,4096,4096|4096,18432|4096,32768|"
        r"4096,8192|5001,64,1024|2081,64,1024)\]", m)]
    assert not weights, "\n".join(weights)
    mem = compiled.memory_analysis()
    pools = 2 * (COHERE_PAGES + 3 * 2081) * COHERE_PAGE * cfg.kv_width * 2
    assert mem.alias_size_in_bytes >= pools
    resident = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    print(f"{program}: arguments {mem.argument_size_in_bytes / 2**30:.2f} "
          f"GiB, temporaries {mem.temp_size_in_bytes / 2**30:.3f} GiB")
    assert resident < _COHERE_RESIDENT, resident
    if program == "decode":
        assert not moves, "\n".join(moves)
        assert mem.temp_size_in_bytes < 256 << 20


# ---------------------------------------------------------------------------
# serve_solar_open2_ep8_closed64 (benchmark/configs/solar-open2-ep8-l4.json):
# 80 slots of 11,264 tokens over 14,081 pages of 64, 64 query heads over 8
# key/value heads of 128 in the GQA layer (a group of EIGHT), 64 heads of
# 128 x 128 with a gate a channel in the KDA layers, 40 held experts of 1280
# ---------------------------------------------------------------------------

KDA_H, KDA_D = 64, 128
KDA_CHUNK_TEMP = {256: 0, 1408: 47_266_304, 8192: 671_249_920}  # as GDN's
SOLAR_B, SOLAR_S, SOLAR_PAGE, SOLAR_PAGES = 80, 11264, 64, 14081


@pytest.mark.parametrize("T", sorted(KDA_CHUNK_TEMP))
def test_kda_chunk_fwd(one_chip, T):
    """The chunkwise delta rule with a gate a channel at the cell's head
    sizes, its lowest rung, the 1408 of a median first token and its top
    rung: blocks of 64 tokens of one head, the sub-blocks' selects on full
    tiles, rows read back from VMEM one by one, the blocked inverse, the
    state in VMEM."""
    from paddle_tpu.ops import gated_delta as GD

    compiled = _compile(
        GD.kda_chunked, one_chip, ((T, KDA_H, KDA_D), BF16),
        ((T, KDA_H, KDA_D), BF16), ((T, KDA_H, KDA_D), BF16),
        ((T, KDA_H, KDA_D), F32), ((T, KDA_H), F32), ((), jnp.int32))
    assert re.search(r"%kda_chunk_fwd[\w.]* = ", compiled.as_text())
    assert compiled.memory_analysis().temp_size_in_bytes <= KDA_CHUNK_TEMP[T]


def test_kda_update_rows(one_chip):
    """The one-token delta rule with a gate a channel over the three KDA
    layers' state rows of 80 slots: the array stays in HBM and is aliased
    whole, a rider's row of 64 x 128 x 128 float32 (4.19 MB) is what a DMA
    moves, and the riders' inputs come eight a grid step (all 80 at once
    with the two row buffers pass the kernel's 48 MB)."""
    from paddle_tpu.ops import gated_delta as GD

    assert GD.state_fold(KDA_H, KDA_D) == 1
    assert GD._rider_block(SOLAR_B, KDA_H, KDA_D, KDA_D,
                           2 * KDA_H * KDA_D * KDA_D * 4, True) == 8
    # the delta-rule cell's 48 riders still come in one step
    assert GD._rider_block(OLMO_B, GDN_H, GDN_DK, GDN_DV,
                           2 * 15 * GDN_DK * 2 * GDN_DV * 4, False) is None
    state = (3, SOLAR_B, KDA_H, KDA_D, KDA_D)
    head = ((SOLAR_B, KDA_H, KDA_D), BF16)
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in (
        (state, F32), head, head, head, ((SOLAR_B, KDA_H, KDA_D), F32),
        ((SOLAR_B, KDA_H), F32), ((SOLAR_B,), jnp.int32), ((), jnp.int32))]
    lowered = jax.jit(
        lambda S, q, k, v, a, b, slots, layer: GD.kda_update(
            S, q, k, v, a, b, slots, layer=layer),
        donate_argnums=(0,)).lower(*args)
    assert "kda_update_rows" in lowered.as_text()
    mem = lowered.compile().memory_analysis()
    nbytes = int(np.prod(state)) * 4
    assert mem.alias_size_in_bytes >= nbytes
    assert mem.temp_size_in_bytes < nbytes // (3 * SOLAR_B) * 8


def test_gqa_paged_decode_attention_at_a_group_of_eight(one_chip):
    """Mosaic takes the grouped-query paged decode kernel at 64 query heads
    over 8 key/value heads of 128: a group of EIGHT query rows, half a
    packed bfloat16 tile; pages of 64 rows of 1,024 lanes, a table of 176
    entries."""
    B, H, KVH, PG = SOLAR_B, 64, 8, SOLAR_PAGE
    assert PK.paged_decode_kernel(H, KVH, HD) == "gqa_paged_decode_attention"

    def fn(q, kp, vp, nk, nv, tables, positions, layer):
        return PK.gqa_paged_decode_attention(
            q, kp, vp, nk, nv, tables, positions, layer, KVH)

    pool = ((1, SOLAR_PAGES, PG, KVH * HD), BF16)
    row = ((B, KVH * HD), BF16)
    compiled = _compile(fn, one_chip, ((B, H, HD), BF16), pool, pool, row,
                        row, ((B, SOLAR_S // PG), jnp.int32),
                        ((B,), jnp.int32), ((), jnp.int32))
    assert re.search(r"%gqa_paged_decode[\w.]* = ", compiled.as_text())


@pytest.mark.parametrize("T", [256, 1408, 8192])
def test_band_flash_attention_at_a_group_of_eight(one_chip, T):
    """The grouped flash kernel on the flat ``[1, T, heads x 128]`` arrays
    of the lowest, the median and the top rung at 64 over 8; no transposed
    copy is made."""
    compiled = _compile(
        lambda q, k, v: PK.band_flash_attention(q, k, v, 64, 8),
        one_chip, ((1, T, 64 * HD), BF16), ((1, T, 8 * HD), BF16),
        ((1, T, 8 * HD), BF16))
    assert re.search(r"%window_flash_fwd[\w.]* = ", compiled.as_text())
    assert compiled.memory_analysis().temp_size_in_bytes == 0


def _solar_config():
    from paddle_tpu.models import solar_open2 as SO

    return SO.SolarOpen2Config(vocab_size=24576, num_hidden_layers=4,
                               gqa_layers=(0,), experts_held=40)


def _lower_solar(program, sharding):
    """The decode tick or a prefill rung of ``solar-open2-ep8-l4`` as the
    cell runs it (one period: a GQA layer and three KDA layers, 40 held
    experts of 320, an eighth of the vocabulary, 80 slots of 11,264 tokens
    in pages of 64), lowered for the described chip from SHAPES alone (its
    weights would be 6.6 GB of host memory that no compile reads)."""
    from paddle_tpu import serving
    from paddle_tpu.models import solar_open2 as SO
    from paddle_tpu.serving import engine as E

    B, S, PG = SOLAR_B, SOLAR_S, SOLAR_PAGE
    cfg = _solar_config()
    eng = object.__new__(E.DecodeEngine)
    eng.model, eng.cfg = SO.SolarOpen2Serving(cfg), cfg
    eng.ecfg = serving.EngineConfig(
        max_batch=B, max_seq=S, page_size=PG, num_pages=SOLAR_PAGES,
        weight_dtype="bf16", prefix_cache=False)
    eng.kv_path = "pallas_paged"
    assert eng.model.kernel_takes_pages(PG, BF16)

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    stored = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s, F32), SO.leaf_shapes(cfg),
        is_leaf=lambda s: isinstance(s, tuple))
    held = jax.tree_util.tree_map(
        lambda a: arg(a.shape, a.dtype),
        jax.eval_shape(lambda p: SO.hold(p, cfg, "bf16"), stored))
    geometry = eng.model.state_geometry
    pool = arg((1, SOLAR_PAGES, PG, cfg.kv_width), BF16)
    caches = (pool, pool, arg((3, B) + geometry["conv"], BF16),
              arg((3, B) + geometry["ssm"], F32))
    if program == "decode":
        fn, feed = eng._decode_fn_paged, arg(
            E.slot_feed_shape(B, S // PG), jnp.int32)
    else:
        T = int(program.split("_b")[1])
        fn, feed = eng._prefill_fn_paged, arg(
            (E.rung_feed_len(S // PG, T),), jnp.int32)
    return (jax.jit(fn, donate_argnums=(1,)).lower(held, caches, feed),
            caches)


@pytest.mark.parametrize("program", ["decode", "prefill_b8192"])
def test_solar_programs_fit_move_the_riders_rows_and_relay_no_weight(
        one_chip, program):
    """The KDA-and-experts cell's tick and top rung on the chip's own
    compile (the 1408 rung, the cycle's median, compiled to 0.26 GiB of
    temporaries by hand: a minute of this file, which the suite's limit
    does not have to spare): the kernels are there by name
    (``kda_update_rows`` and ``gqa_paged_decode`` in the tick,
    ``kda_chunk_fwd`` and ``window_flash_fwd`` in a rung,
    ``moe_grouped_matmul`` in both), every
    donated cache array (pools, conv rows, matrix states) is aliased whole,
    nothing the size of one expert's smallest leaf (``w_down [F, D]``, 5.2 M
    elements) is copied, transposed, converted or sliced out of a weight in
    the tick, its temporaries stay under one layer's riders' states, and
    arguments plus temporaries stay under the chip's 15.75 GiB."""
    cfg = _solar_config()
    lowered, caches = _lower_solar(program, one_chip)
    compiled = lowered.compile()
    hlo = compiled.as_text()
    tick = program == "decode"
    assert re.search(r"%moe_grouped_matmul[\w.]* = ", hlo)
    for name in ("kda_update_rows", "gqa_paged_decode"):
        assert bool(re.search(rf"%{name}[\w.]* = ", hlo)) == tick, name
    for name in ("kda_chunk_fwd", "window_flash_fwd"):
        assert bool(re.search(rf"%{name}[\w.]* = ", hlo)) == (not tick), name
    mem = compiled.memory_analysis()
    nbytes = sum(int(np.prod(a.shape)) * a.dtype.itemsize for a in caches)
    assert mem.alias_size_in_bytes >= nbytes
    resident = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    print(f"{program}: arguments {mem.argument_size_in_bytes / 2**30:.2f} "
          f"GiB, temporaries {mem.temp_size_in_bytes / 2**30:.3f} GiB")
    assert resident < _COHERE_RESIDENT, resident
    if tick:
        one_expert_leaf = cfg.moe_intermediate_size * cfg.hidden_size
        moves = (_weight_sized_relayouts(hlo, at_least=one_expert_leaf)
                 + _pool_sized_moves(hlo, at_least=one_expert_leaf))
        # a layer's conv rows of all 80 lanes (5.9 M values, written in
        # place every tick: the riders' new taps) are that large too
        moves = [m for m in moves if "80,73728]" not in m]
        assert not moves, "\n".join(moves)
        layer_rows = SOLAR_B * KDA_H * KDA_D * KDA_D * 4
        assert mem.temp_size_in_bytes < layer_rows
