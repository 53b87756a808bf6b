"""The Cohere2-MoE block (Command A+) on the CPU at tiny widths: the parallel
block with window and global layers through the paged engine's two page
groups, against its own forward pass and against the benchmark's plain
reference (``benchmark/families/cohere2_moe.py``, which imports nothing of
the program); the two new kernels in interpret mode; the shares of an
expert-parallel group; the configuration's record of its source."""
import json
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402
from paddle_tpu import serving  # noqa: E402
from paddle_tpu.models import blocks, cohere2_moe as C  # noqa: E402
from paddle_tpu.ops import moe, rope  # noqa: E402
from paddle_tpu.ops import pallas_kernels as PK  # noqa: E402
from paddle_tpu.ops.decode_attention import (_grouped_attention,  # noqa
                                             band_prefill_attention,
                                             paged_gather,
                                             sliding_decode_attention)
from paddle_tpu.serving import metrics as smetrics  # noqa: E402

CELL = "serve_command_a_plus_ep8_closed24"
FAMILY = harness.load_module(os.path.join(
    ROOT, "benchmark", "families", "cohere2_moe.py"))
# a window of 8 over pages of 4: a ring of 3 entries
SHARE = C.COHERE2_MOE_TINY.scaled(experts_held=4, first_expert=4)


def _engine(cfg=SHARE, **kw):
    params = C.init_params(jax.random.PRNGKey(0), cfg)
    ecfg = dict(max_batch=4, max_seq=64, page_size=4,
                prefill_buckets=(8, 16, 32), prefix_cache=False)
    ecfg.update(kw)
    return serving.DecodeEngine(params, cfg, serving.EngineConfig(**ecfg))


def _family_config(cfg, **over):
    """The benchmark family's configuration of a program config."""
    return {"hidden_size": cfg.hidden_size,
            "intermediate_size": cfg.intermediate_size,
            "num_hidden_layers": cfg.num_hidden_layers,
            "layer_types": list(cfg.layer_types),
            "num_attention_heads": cfg.num_attention_heads,
            "num_key_value_heads": cfg.num_key_value_heads,
            "head_dim": cfg.head_dim, "sliding_window": cfg.sliding_window,
            "num_experts": cfg.experts_held,
            "first_expert": cfg.first_expert,
            "published": {"num_experts": cfg.num_experts_published},
            "num_experts_per_tok": cfg.num_experts_per_tok,
            "num_shared_experts": cfg.num_shared_experts,
            "layer_norm_eps": cfg.layer_norm_eps,
            "rope_theta": cfg.rope_theta, "logit_scale": cfg.logit_scale,
            "vocab_size": cfg.vocab_size, **over}


# ---------------------------------------------------------------------------
# the two kernels, interpret mode, against the grouped attention in XLA
# ---------------------------------------------------------------------------

def _band_by_hand(q, k, v, window):
    """``_grouped_attention`` over the whole ``[T, T]`` mask."""
    T, nh, hd = q.shape
    kvh = k.shape[1]
    i, j = np.arange(T)[:, None], np.arange(T)[None, :]
    seen = j <= i
    if window is not None:
        seen &= i - j < window
    return _grouped_attention(
        q.reshape(1, T, kvh, nh // kvh, hd), k[None], v[None],
        jnp.asarray(seen)[None, None, None], hd ** -0.5
    ).reshape(T, nh, hd)


@pytest.mark.parametrize("window", [None, 8, 24, 200])
@pytest.mark.parametrize("T, block", [(64, 16), (48, 16), (32, 32)])
def test_band_flash_attention_against_the_masked_product(T, block, window):
    """Grouped heads (8 over 2) inside the band, blocks of 16 (several
    key blocks a query block, some skipped, some on the band's edge) and
    one block; a window wider than the rung is plain causal attention."""
    rng = np.random.default_rng(T + (window or 0))
    q = jnp.asarray(rng.normal(size=(T, 8, 16)), jnp.float32)
    k, v = (jnp.asarray(rng.normal(size=(T, 2, 16)), jnp.float32)
            for _ in range(2))
    want = _band_by_hand(q, k, v, window)
    got = PK.band_flash_attention(
        q.reshape(1, T, -1), k.reshape(1, T, -1), v.reshape(1, T, -1), 8, 2,
        window=window, block_q=block, block_k=block)[0].reshape(T, 8, 16)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-6)
    # the public entry: grouped heads or a window take this kernel
    via = PK.flash_attention(q[None], k[None], v[None], window=window,
                             block_q=block, block_k=block)[0]
    np.testing.assert_allclose(np.asarray(via), np.asarray(want), atol=2e-6)
    # and the XLA lowering of the rungs off the TPU is the same numbers
    np.testing.assert_allclose(
        np.asarray(band_prefill_attention(q, k, v, window)),
        np.asarray(want), atol=2e-6)
    if window is not None:
        assert PK.band_blocks(T, block, block, window) <= T // block


def test_flash_attention_with_equal_heads_and_no_window_is_untouched():
    """The GPT call: the kernel on ``[BH, T, hd]`` it was (``flash_fwd``),
    and its numbers the band kernel's at a window that bounds nothing."""
    rng = np.random.default_rng(1)
    q, k, v = (jnp.asarray(rng.normal(size=(1, 32, 4, 16)), jnp.float32)
               for _ in range(3))
    text = str(jax.make_jaxpr(PK.flash_attention)(q, k, v))
    assert "flash_fwd" in text and "window_flash" not in text
    assert "window_flash_fwd" in str(jax.make_jaxpr(
        lambda q, k, v: PK.flash_attention(q, k, v, window=8))(q, k, v))
    np.testing.assert_allclose(
        np.asarray(PK.flash_attention(q, k, v)),
        np.asarray(PK.flash_attention(q, k, v, window=32)), atol=2e-6)
    with pytest.raises(ValueError, match="causal and take no bias"):
        PK.flash_attention(q, k, v, window=8, causal=False)


@pytest.mark.parametrize("window, ring", [(None, False), (8, True),
                                          (12, True), (8, False)])
def test_gqa_paged_decode_attention_against_the_gathered_view(window, ring):
    """Slots at positions before, at and past the window, at page
    boundaries and past a ring's first turn; a dead lane. The kernel
    (interpret mode) against gather + masked grouped attention, and both
    against the rows laid out by hand."""
    B, H, KVH, hd, page, P = 5, 8, 2, 16, 4, 40
    M = 16 if not ring else -(-window // page) + 1
    rng = np.random.default_rng(3 + (window or 0))
    positions = np.array([0, 5, 11, 16, 37], np.int32)
    kp, vp = (jnp.asarray(rng.normal(size=(2, P, page, KVH * hd)),
                          jnp.float32) for _ in range(2))
    q = jnp.asarray(rng.normal(size=(B, H, hd)), jnp.float32)
    new_k, new_v = (jnp.asarray(rng.normal(size=(B, KVH * hd)), jnp.float32)
                    for _ in range(2))
    # a table a slot: every logical page up to its position, or the ring's
    # newest; lane 0 is dead (all zero, position 0)
    tables = np.zeros((B, M), np.int32)
    free = iter(rng.permutation(np.arange(1, P)))
    logical = {}
    for b in range(1, B):
        for j in range(positions[b] // page + 1):
            page_id = int(next(free)) if j >= positions[b] // page - M + 1 \
                or not ring else 0
            if page_id:
                tables[b, j % M if ring else j] = page_id
                logical[b, j] = page_id
    layer = jnp.int32(1)
    got, kp2, vp2 = PK.gqa_paged_decode_attention(
        q, kp, vp, new_k, new_v, jnp.asarray(tables),
        jnp.asarray(positions), layer, KVH, window=window, ring=ring)
    # the row of this tick went where the table says
    for b in range(1, B):
        phys = logical[b, positions[b] // page]
        np.testing.assert_array_equal(
            np.asarray(kp2[1, phys, positions[b] % page]),
            np.asarray(new_k[b]))
    want = sliding_decode_attention(
        q, paged_gather(kp2, jnp.asarray(tables), layer),
        paged_gather(vp2, jnp.asarray(tables), layer),
        jnp.asarray(positions), KVH, page, window=window, ring=ring)
    np.testing.assert_allclose(np.asarray(got[1:]), np.asarray(want[1:]),
                               atol=2e-6)
    # by hand: the span's rows, in order, through the grouped product
    for b in range(1, B):
        lo = 0 if window is None else max(0, positions[b] - window + 1)
        rows = [(logical[b, t // page], t % page)
                for t in range(lo, positions[b] + 1)]
        k = jnp.stack([kp2[1, p, r] for p, r in rows]).reshape(-1, KVH, hd)
        v = jnp.stack([vp2[1, p, r] for p, r in rows]).reshape(-1, KVH, hd)
        by_hand = _grouped_attention(
            q[b].reshape(1, 1, KVH, H // KVH, hd), k[None], v[None],
            jnp.ones((1, 1, 1, 1, len(rows)), bool), hd ** -0.5)
        np.testing.assert_allclose(np.asarray(got[b]),
                                   np.asarray(by_hand.reshape(H, hd)),
                                   atol=2e-6)


@pytest.mark.parametrize("heads, kv_heads, head_dim, kernel", [
    (16, 16, 128, "paged_decode_attention"),      # the GPT cells
    (30, 30, 128, "paged_decode_attention"),      # the delta-rule hybrid
    (12, 12, 128, "paged_decode_attention"),      # equal heads: any count
    (128, 8, 128, "gqa_paged_decode_attention"),  # this model
    (20, 1, 128, None),                           # the Mamba hybrid
    (16, 16, 64, None),                           # a head is half a tile
    (16, 4, 128, None)])                          # a group of 4: no tile
def test_one_rule_says_which_heads_take_which_decode_kernel(
        heads, kv_heads, head_dim, kernel):
    assert PK.paged_decode_kernel(heads, kv_heads, head_dim) == kernel
    if heads == kv_heads:
        assert PK.paged_decode_tiles(heads, head_dim) == (kernel is not None)


# ---------------------------------------------------------------------------
# the program through the engine's two page groups
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("fused", [False, True], ids=["gather", "kernels"])
def test_prefill_then_decode_through_both_page_groups(fused):
    """Prefill (a prompt inside the window, one past it on a rung whose
    pages outnumber the ring, one exactly at a page boundary), then decode
    across the window and across pages that leave it, three requests
    interleaved, against the full forward pass; ``fused_decode`` drives
    the grouped-query paged kernel in interpret mode."""
    eng = _engine(fused_decode=fused)
    assert eng.kv_path == ("pallas_paged" if fused else "xla_gather")
    assert eng.table_widths == (16, 3) and eng.table_width == 19
    assert [a.shape for a in eng.cache.arrays()] == [
        (1, 65, 4, 16), (1, 65, 4, 16), (3, 13, 4, 16), (3, 13, 4, 16)]
    released0 = smetrics.m_window_released.value
    rng = np.random.default_rng(4)
    streams, served = {}, {}
    for n in (5, 16, 27):
        prompt = rng.integers(0, SHARE.vocab_size, n).tolist()
        slot, logits, tok = eng.start_sequence_sampled(
            prompt, serving.sampling.GREEDY)
        streams[slot], served[slot] = prompt + [tok], [logits]
        held = eng.cache.pages_held(slot)
        assert held == {"full": -(-n // 4), "window": min(-(-n // 4), 3)}
    for _ in range(14):
        out = eng.decode_step_sampled(
            {s: t[-1] for s, t in streams.items()}, None)
        for slot, (tok, logits) in out.items():
            served[slot].append(logits)
            streams[slot].append(tok)
            # never more than the window and two pages of tokens a slot
            assert eng.cache.pages_held(slot)["window"] * 4 <= 8 + 2 * 4
    # every rider crossed pages while decoding; those past the window gave
    # the pages that left it back
    assert smetrics.m_window_released.value - released0 >= 3 + 4 + 3
    assert eng.cache.group("window").released >= 10
    # one causal forward pass a stream holds every step's reference
    for slot, stream in streams.items():
        want = eng.reference_logits(stream[:-1])[-len(served[slot]):]
        np.testing.assert_allclose(np.stack(served[slot]), want, atol=3e-6)
        assert stream[-len(served[slot]):] == list(want.argmax(-1))
    assert eng.cache.held_over_one_table() < 0.7
    assert eng.last_expert_load["expert_tokens"] >= 0
    for slot in list(streams):
        eng.free_sequence(slot)
    assert [g.held_pages() for g in eng.cache.groups] == [0, 0]


def test_scheduler_serves_through_both_groups_and_records_their_rows():
    """The normal path: ``Scheduler`` over the engine, requests that run
    past the window; the tick record carries the rows each group has live
    (the window's clipped) and the share of one table's pages held."""
    from paddle_tpu.observability import spans

    eng = _engine()
    eng.warmup()
    sched = serving.Scheduler(eng, serving.SchedulerConfig(max_queue=8))
    rng = np.random.default_rng(9)
    prompts = [rng.integers(0, SHARE.vocab_size, n).tolist()
               for n in (6, 21, 30)]
    reqs = [sched.submit(p, max_new_tokens=12) for p in prompts]
    for _ in range(200):
        if all(r.finished.is_set() for r in reqs):
            break
        sched.step()
    assert [r.state for r in reqs] == ["done"] * 3
    for p, r in zip(prompts, reqs):
        stream = p + r.tokens
        want = eng.reference_logits(stream[:-1])[-len(r.tokens):]
        assert r.tokens == list(want.argmax(-1))
    ticks = [r["attrs"] for r in spans.default_tracer().spans()
             if r["name"] == "serve/decode_tick"
             and "rows_window" in r.get("attrs", {})]
    assert ticks
    for t in ticks:
        assert t["rows_window"] <= min(t["rows_full"], 8 * t["batch"])
        assert 0 < t["held_over_one_table"] <= 1
    assert any(t["rows_window"] < t["rows_full"] for t in ticks)
    prefills = [r["attrs"] for r in spans.default_tracer().spans()
                if r["name"] == "serve/prefill"
                and "pages_window" in r.get("attrs", {})]
    assert {(a["prompt_len"], a["pages_full"], a["pages_window"])
            for a in prefills} >= {(6, 2, 2), (21, 6, 3), (30, 8, 3)}


@pytest.mark.parametrize("kw, named", [
    (dict(prefix_cache=True), "the prefix cache"),
    (dict(verify_window=4), "the verify window"),
    (dict(sharding="tp", tp=2), "the tensor-parallel engine"),
    (dict(weight_dtype="int8"), "weight_dtype 'int8'"),
    (dict(role="prefill"), "role 'prefill'")])
def test_what_cannot_carry_two_page_groups_is_refused_by_name(kw, named):
    with pytest.raises(ValueError) as e:
        _engine(**{"prefix_cache": False, **kw})
    assert "Cohere2MoeServing has several page groups" in str(e.value)
    assert named in str(e.value) and "a table a group" in str(e.value)


def test_page_contents_and_hand_off_refuse_two_page_groups():
    eng = _engine()
    for call in (lambda: eng.cache.read_pages([1]),
                 lambda: eng.cache.adopt_slot(8, [1]),
                 lambda: eng.export_request_kv(0)):
        with pytest.raises(ValueError, match="page groups|key and value"):
            call()
    assert "kv_transfer" not in eng.warmup()
    assert eng.cache.nbytes == 2 * (65 + 3 * 13) * 4 * 16 * 4


def test_a_model_of_one_kind_of_layer_has_one_group():
    full = C.COHERE2_MOE_TINY.scaled(layer_types=(C.FULL,) * 4)
    assert [g["name"] for g in C.Cohere2MoeServing(full).cache_pools[
        "groups"]] == ["full"]
    with pytest.raises(ValueError, match="layer_types"):
        C.COHERE2_MOE_TINY.scaled(num_hidden_layers=3)
    with pytest.raises(ValueError, match="unknown kinds"):
        C.COHERE2_MOE_TINY.scaled(layer_types=("linear_attention",) * 4)


def test_held_tree_is_the_stored_one_relaid():
    """``hold``: one flat QKV product with q's and k's columns in halves,
    the shared experts as one gated MLP; a rung's chunks of the experts
    give what one call over the rung gives."""
    cfg = SHARE
    params = C.init_params(jax.random.PRNGKey(2), cfg)
    held = C.hold(params, cfg, "f32")
    L, D = cfg.num_hidden_layers, cfg.hidden_size
    assert held["layers"]["w_qkv"].shape == (
        L, D, cfg.q_width + 2 * cfg.kv_width)
    assert held["layers"]["shared_gate_up"].shape == (
        L, D, 2 * cfg.shared_width)
    assert held["layers"]["shared_down"].shape == (L, cfg.shared_width, D)
    halves = rope.halves_from_interleaved(cfg.head_dim)
    np.testing.assert_array_equal(
        np.asarray(held["layers"]["w_qkv"][1, :, :cfg.head_dim]),
        np.asarray(params["layers"]["w_q"][1, :, :cfg.head_dim][:, halves]))
    np.testing.assert_array_equal(
        np.asarray(held["layers"]["w_qkv"][2, :, -cfg.kv_width:]),
        np.asarray(params["layers"]["w_v"][2]))
    u = jax.random.normal(jax.random.PRNGKey(3), (256, D), jnp.float32)
    valid = jnp.arange(256) < 200
    whole, r_whole = C._ffn_rows(u, valid, held["layers"], 1, cfg, False)
    old = blocks._FFN_ROWS
    try:
        blocks._FFN_ROWS = 128
        assert blocks.ffn_chunk(256) == 128 and blocks.ffn_chunk(384) == 128
        parts, r_parts = C._ffn(u, valid, held, 1, cfg, False)
    finally:
        blocks._FFN_ROWS = old
    np.testing.assert_allclose(np.asarray(parts), np.asarray(whole),
                               atol=2e-6)
    np.testing.assert_array_equal(np.asarray(r_parts), np.asarray(r_whole))
    assert blocks.ffn_chunk(16384) == 2048 and blocks.ffn_chunk(7168) == 1792


# ---------------------------------------------------------------------------
# against the benchmark's plain reference
# ---------------------------------------------------------------------------

def test_program_forward_against_the_plain_reference():
    """The same seeded weights through ``models/cohere2_moe.py:forward``
    and through the benchmark's reference, which shares no code with it."""
    config = _family_config(SHARE)
    params = FAMILY.program_weights(7, config, jnp.float32)
    assert (jax.tree_util.tree_map(lambda a: a.shape, params)
            == C.leaf_shapes(SHARE))
    tokens = np.random.default_rng(5).integers(0, SHARE.vocab_size, 40)
    got = C.forward(params, jnp.asarray(tokens, jnp.int32), SHARE)
    want = FAMILY.forward(config, 7, tokens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-6)
    # (the tied table is drawn N(0, 0.002): logits of a few hundredths)
    assert float(np.abs(np.asarray(want)).max()) > 0.01


def _served_against(reference_kw):
    """Engine prefill + decode of one request past the window (27 + 14
    tokens, window 8, pages of 4) against the family's forward pass with
    ``reference_kw``: (largest gap below the reference's best, relative
    root mean square of the logits)."""
    config = _family_config(SHARE)
    params = FAMILY.program_weights(7, config, jnp.float32)
    eng = serving.DecodeEngine(params, SHARE, serving.EngineConfig(
        max_batch=2, max_seq=64, page_size=4, prefill_buckets=(8, 16, 32),
        prefix_cache=False))
    prompt = np.random.default_rng(6).integers(
        0, SHARE.vocab_size, 27).tolist()
    slot, logits, tok = eng.start_sequence_sampled(
        prompt, serving.sampling.GREEDY)
    stream, served = prompt + [tok], [logits]
    for _ in range(13):
        tok, logits = eng.decode_step_sampled({slot: stream[-1]}, None)[slot]
        served.append(logits)
        stream.append(tok)
    assert eng.cache.group("window").released >= 3
    want = np.asarray(FAMILY.forward(config, 7, stream[:-1],
                                     **reference_kw))[-len(served):]
    got = np.stack(served)
    picked = np.take_along_axis(
        want, np.asarray(stream[-len(served):])[:, None], axis=1)[:, 0]
    d = got - want
    d -= d.mean(axis=1, keepdims=True)
    w = want - want.mean(axis=1, keepdims=True)
    return (float((want.max(axis=1) - picked).max()),
            float(np.sqrt(np.square(d).sum() / np.square(w).sum())))


def test_engine_against_the_plain_reference_across_the_window():
    """What the cell's ``correct`` compares, at rehearsal size: prefill and
    decode through both page groups, across the window and across released
    pages, inside the rehearsal's limits."""
    limits = harness.Cell(ROOT, CELL, rehearsal=True).limits
    gap, rms = _served_against({})
    assert gap <= limits["served_logit_gap_max"]
    assert rms <= limits["served_logits_rel_rms"]


@pytest.mark.parametrize("broken", [dict(band=False), dict(rotary=True)],
                         ids=["no_band", "rotary_on_full_layers"])
def test_the_comparison_sees_the_mechanism(broken):
    """A reference WITHOUT the band (sliding layers see the whole context)
    or WITH rotary positions on the full layers is another model: the
    program lies far outside the rehearsal's limits of it (a sound run
    reads 2e-7 there; these read 1e-3 and more)."""
    limits = harness.Cell(ROOT, CELL, rehearsal=True).limits
    _gap, rms = _served_against(broken)
    assert rms > 50 * limits["served_logits_rel_rms"]


def test_the_shares_add_up_to_the_uncut_layer():
    """The guide's share test, on the reference: the routed parts of the 8
    shares (2 of 16 experts each) plus the shared experts' mean and the
    attention ONCE are the uncut layer's output; and the program's share
    is the reference's share of the same rank."""
    whole = _family_config(C.COHERE2_MOE_TINY)
    s_whole = FAMILY.dims(whole)
    w = FAMILY.layer_weights(FAMILY._key(11), whole, 1)
    x = jax.random.normal(jax.random.PRNGKey(12), (20, 64), jnp.float32)
    mm = FAMILY._mm("f32")
    freq = jnp.asarray(FAMILY.inv_freq(whole))
    full, _ = FAMILY._layer(x, w, s_whole, mm, FAMILY.SLIDING, freq)
    u = FAMILY._layer_norm(x, w["input_layernorm"], s_whole["eps"])
    attention = FAMILY._attention(u, w, s_whole, mm, FAMILY.SLIDING, freq)
    shared = sum(FAMILY._gated(u, w["shared_gate_proj"][j],
                               w["shared_up_proj"][j],
                               w["shared_down_proj"][j], mm)
                 for j in range(s_whole["S"])) / s_whole["S"]
    routed = jnp.zeros_like(x)
    for rank in range(8):
        s = {**s_whole, "G": 2, "first": 2 * rank}
        mine = {**w, **{k: w[k][2 * rank:2 * rank + 2] for k in (
            "experts_gate_proj", "experts_up_proj", "experts_down_proj")}}
        share, chose = FAMILY._ffn(u, mine, s, mm)
        routed = routed + (share - shared)
        assert chose.shape == (20, 2)
        # the program's share of this rank, from the same leaves
        y, report = moe.expert_share(
            u, jnp.ones((20,), bool), *moe.route(
                u, w["gate"], jnp.zeros((16,)), 4, 1.0),
            jnp.concatenate([mine["experts_gate_proj"],
                             mine["experts_up_proj"]], -1),
            mine["experts_down_proj"], first_expert=2 * rank)
        np.testing.assert_allclose(np.asarray(y),
                                   np.asarray(share - shared), atol=2e-6)
        assert int(report[:-1].sum()) == int(chose.sum())
    np.testing.assert_allclose(
        np.asarray(x + attention + routed + shared), np.asarray(full),
        atol=2e-6)


# ---------------------------------------------------------------------------
# the configuration's record of its source, and the family's counts
# ---------------------------------------------------------------------------

# command-a-plus-05-2026's config.json, the sizes that shape the program
PINNED = {"hidden_size": 4096, "num_attention_heads": 128,
          "num_key_value_heads": 8, "head_dim": 128,
          "intermediate_size": 4096, "num_experts": 128,
          "num_experts_per_tok": 8, "num_shared_experts": 4,
          "num_hidden_layers": 32, "sliding_window": 4096,
          "vocab_size": 262144, "first_k_dense_replace": 0,
          "layer_norm_eps": 1e-5, "rope_theta": 50000, "logit_scale": 1,
          "max_position_embeddings": 200000, "rotary_pct": 1,
          "shared_expert_combination_strategy": "average",
          "expert_selection_fn": "sigmoid", "norm_topk_prob": True,
          "use_parallel_block": True, "use_qk_norm": False,
          "tie_word_embeddings": True,
          "position_embedding_type": "rope_gptj"}


def test_published_is_the_sources_own():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "command-a-plus-ep8-l4.json")) as f:
        doc = json.load(f)
    assert {k: doc["published"][k] for k in PINNED} == PINNED
    assert doc["published"]["layer_types"] == (
        ["sliding_attention"] * 3 + ["full_attention"]) * 8
    assert doc["layer_types"] == doc["published"]["layer_types"][:4]
    assert doc["reduced"] == ["num_hidden_layers", "layer_types",
                              "num_experts", "vocab_size"]
    assert (doc["num_hidden_layers"], doc["num_experts"],
            doc["vocab_size"]) == (4, 16, 32768)
    # every other key of the source is as published
    for key, value in doc["published"].items():
        if key not in doc["reduced"]:
            assert doc[key] == value, key
    assert set(FAMILY.WIDTH_KEYS).isdisjoint(doc["reduced"])


def test_the_familys_counts_at_published_widths():
    config = harness.Cell(ROOT, CELL).config
    assert FAMILY.expert_params(config) == 3 * 4096 * 4096
    # a layer: gain, attention, router, four shared and sixteen held experts
    layer = 4096 + 142_606_336 + 524_288 + 4 * 50_331_648 + 16 * 50_331_648
    assert FAMILY.param_count(config) == 4 * layer + 32768 * 4096 + 4096
    assert round(2 * FAMILY.param_count(config) / 1e9, 2) == 9.47
    assert FAMILY.kv_bytes_per_row(config) == 4096
    assert FAMILY.layers_of(config) == {"full": 1, "window": 3}
    # a rider of 16,384 tokens: one layer whole, three inside the window
    assert FAMILY.kv_bytes_per_decode_step(config, 16384, 4096) == \
        4096 * (16384 + 3 * 4096)
    # 100 tokens: the band bounds nothing; 10,000: 4096 keys a query
    assert FAMILY.band_attention_flops(config, 100) == \
        4 * 128 * 128 * 4 * (100 * 101 // 2)
    tail = 4096 * 4097 // 2 + (10000 - 4096) * 4096
    assert FAMILY.band_attention_flops(config, 10000) == \
        4 * 128 * 128 * (10000 * 10001 // 2 + 3 * tail)
    nbytes, flops = FAMILY.grouped_matmul_work(config, 24, 13)
    assert flops == 2 * 24 * 3 * 4096 * 4096
    assert nbytes == 13 * 3 * 4096 * 4096 * 2 + 24 * 5 * 4096 * 2
    # the program's own description agrees on the cache's geometry
    cfg = FAMILY.ServeProgram.__init__.__globals__["dims"](config)
    model = C.Cohere2MoeServing(C.Cohere2MoeConfig(
        vocab_size=cfg["V"], num_hidden_layers=cfg["L"],
        layer_types=tuple(cfg["kinds"]), experts_held=cfg["G"]))
    assert [(g["name"], g["layers"], g["window"], g["rows"])
            for g in model.cache_pools["groups"]] == [
        ("full", 1, None, ((1024,),) * 2),
        ("window", 3, 4096, ((1024,),) * 2)]
    assert model.kernel_takes_pages(64, jnp.bfloat16)
    assert not model.kernel_takes_pages(8, jnp.bfloat16)
