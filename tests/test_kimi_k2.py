"""The Kimi-K2 block on the CPU at tiny widths: yarn rotary, the router,
the chip's share of an expert layer, latent attention absorbed and expanded,
both lowerings of the latent decode, and the program through the paged
engine against its own forward pass and against the benchmark's plain
reference (``benchmark/families/kimi_k2.py``, which imports nothing of the
program)."""
import math
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402
from paddle_tpu import serving  # noqa: E402
from paddle_tpu.models import gpt as G  # noqa: E402
from paddle_tpu.models import jamba as J  # noqa: E402
from paddle_tpu.models import kimi_k2 as K  # noqa: E402
from paddle_tpu.ops import moe, rope  # noqa: E402
from paddle_tpu.ops import pallas_kernels as PK  # noqa: E402
from paddle_tpu.ops.decode_attention import (latent_decode_attention,  # noqa
                                             paged_gather)
from paddle_tpu.serving import metrics as smetrics  # noqa: E402

PUBLISHED_YARN = {"type": "yarn", "factor": 64, "beta_fast": 32,
                  "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1,
                  "original_max_position_embeddings": 4096}
FAMILY = harness.load_module(os.path.join(
    ROOT, "benchmark", "families", "kimi_k2.py"))


# ---------------------------------------------------------------------------
# rotary
# ---------------------------------------------------------------------------

def test_yarn_against_hand_worked_values_for_the_published_settings():
    # dim ln(orig / (beta 2 pi)) / (2 ln theta): 64 ln(4096 / (32 x 6.2832))
    # / (2 x 10.8198) = 64 x 3.0142 / 21.640 = 8.91 -> floor 8;
    # 64 ln(4096 / 6.2832) / 21.640 = 64 x 6.4799 / 21.640 = 19.16 -> ceil 20
    assert rope.yarn_correction_range(64, 50000.0, 4096, 32, 1) == (8, 20)
    f = rope.yarn_inv_freq(64, 50000.0, PUBLISHED_YARN)
    extra = 50000.0 ** (-2.0 * np.arange(32) / 64)
    assert f.shape == (32,) and f.dtype == np.float32
    np.testing.assert_allclose(f[:9], extra[:9], rtol=1e-6)    # fast: kept
    np.testing.assert_allclose(f[20:], extra[20:] / 64, rtol=1e-6)
    # half way up the ramp, pair 14: (extra / 64 + extra) / 2
    np.testing.assert_allclose(f[14], extra[14] * (1 + 1 / 64) / 2,
                               rtol=1e-6)
    # 192^-0.5 (0.1 ln 64 + 1)^2 = 0.0721688 x 1.4158883^2 = 0.144680
    s = rope.yarn_softmax_scale(192, PUBLISHED_YARN)
    assert s == pytest.approx(0.144680, rel=1e-5)
    assert K.KimiK2Config(rope_scaling=PUBLISHED_YARN).softmax_scale == s
    # the reference's own copy of the arithmetic agrees
    cfg = harness.Cell(ROOT, "serve_kimi_k2p5_ep32_closed96").config
    assert FAMILY.yarn_correction_range(cfg) == (8, 20)
    np.testing.assert_allclose(FAMILY.yarn_inv_freq(cfg), f, rtol=1e-6)
    assert FAMILY.softmax_scale(cfg) == pytest.approx(s)


def test_rotating_halves_of_permuted_columns_changes_no_score():
    rng = np.random.default_rng(0)
    q, k = rng.normal(size=(2, 5, 16)).astype(np.float32)
    cos, sin = rope.angles(jnp.arange(5) + 3,
                           rope.yarn_inv_freq(16, 10000.0))
    perm = rope.halves_from_interleaved(16)
    # the source's pairing is the reference's own rotation
    a = (FAMILY._rotate_interleaved(q, cos, sin)
         * FAMILY._rotate_interleaved(k, cos, sin)).sum(-1)
    b = (rope.rotate(q[:, perm], cos, sin)
         * rope.rotate(k[:, perm], cos, sin)).sum(-1)
    np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)
    # and a rotation moves no norm
    np.testing.assert_allclose(
        np.linalg.norm(rope.rotate(q, cos, sin), axis=-1),
        np.linalg.norm(q, axis=-1), rtol=1e-5)


# ---------------------------------------------------------------------------
# router and experts
# ---------------------------------------------------------------------------

def _experts(T=40, D=32, F=48, E=16, G=4, k=4, layers=2, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    return dict(
        x=jax.random.normal(ks[0], (T, D), jnp.float32),
        w_g=jax.random.normal(ks[1], (D, E)) * 0.2,
        bias=jax.random.normal(ks[2], (E,)) * 0.05,
        w_gu=jax.random.normal(ks[3], (layers, G, D, 2 * F)) * 0.1,
        w_d=jax.random.normal(ks[4], (layers, G, F, D)) * 0.1, k=k, F=F)


def _loop_over_experts(p, experts, weights, valid, first, layer):
    """The share as a plain loop: every (token, choice) on a held expert."""
    x, F = np.asarray(p["x"]), p["F"]
    G = p["w_d"].shape[1]
    y, counts = np.zeros_like(x), np.zeros(G, int)
    for t in np.flatnonzero(np.asarray(valid)):
        for e, w in zip(np.asarray(experts[t]), np.asarray(weights[t])):
            g = int(e) - first
            if 0 <= g < G:
                h = x[t] @ np.asarray(p["w_gu"][layer, g])
                a = np.asarray(jax.nn.silu(h[:F])) * h[F:]
                y[t] += w * (a @ np.asarray(p["w_d"][layer, g]))
                counts[g] += 1
    return y, counts


def test_bias_moves_selection_and_not_weights():
    p = _experts()
    experts, w = moe.route(p["x"], p["w_g"], p["bias"], p["k"], 2.827)
    np.testing.assert_allclose(np.asarray(w).sum(1), 2.827, rtol=1e-5)
    score = jax.nn.sigmoid(p["x"] @ p["w_g"])
    # weights are the scores WITHOUT the bias at the chosen experts
    picked = np.take_along_axis(np.asarray(score), np.asarray(experts), 1)
    np.testing.assert_allclose(
        np.asarray(w), picked / picked.sum(1, keepdims=True) * 2.827,
        rtol=1e-5)
    # a large bias on one expert puts it in every token's choice ...
    pushed = p["bias"].at[7].set(10.0)
    experts2, w2 = moe.route(p["x"], p["w_g"], pushed, p["k"], 2.827)
    assert np.all(np.any(np.asarray(experts2) == 7, axis=1))
    # ... at its unbiased score's weight
    at = np.asarray(experts2) == 7
    others = np.where(at, 0, np.take_along_axis(
        np.asarray(score), np.asarray(experts2), 1)).sum(1)
    s7 = np.asarray(score)[:, 7]
    np.testing.assert_allclose(np.asarray(w2)[at],
                               s7 / (s7 + others) * 2.827, rtol=1e-5)


@pytest.mark.parametrize("use_pallas", [False, True], ids=["xla", "pallas"])
@pytest.mark.parametrize("first", [0, 8])
def test_expert_share_against_a_loop_over_experts(first, use_pallas):
    p = _experts()
    experts, w = moe.route(p["x"], p["w_g"], p["bias"], p["k"], 2.5)
    valid = jnp.arange(p["x"].shape[0]) < 33
    y, report = jax.jit(lambda x: moe.expert_share(
        x, valid, experts, w, p["w_gu"], p["w_d"], first_expert=first,
        layer=jnp.int32(1), use_pallas=use_pallas))(p["x"])
    want, counts = _loop_over_experts(p, experts, w, valid, first, 1)
    np.testing.assert_allclose(np.asarray(y), want, rtol=2e-4, atol=2e-5)
    assert list(report[:-1]) == list(counts) and int(report[-1]) == 0


@pytest.mark.parametrize("use_pallas", [False, True], ids=["xla", "pallas"])
def test_every_token_on_one_held_expert_loses_none(use_pallas):
    """The worst case the buffer is sized for: every choice of every token
    falls on this chip (16 x 8 = 128 pairs on 8 held experts, one of them
    taking a pair of every token): the full buffer's branch runs and no
    pair is dropped."""
    p = _experts(T=16, E=8, G=8, k=8)
    experts = jnp.tile(jnp.arange(8, dtype=jnp.int32), (16, 1))
    w = jnp.full((16, 8), 0.25, jnp.float32)
    valid = jnp.ones((16,), bool)
    y, report = jax.jit(lambda x: moe.expert_share(
        x, valid, experts, w, p["w_gu"], p["w_d"], first_expert=0,
        layer=jnp.int32(0), use_pallas=use_pallas))(p["x"])
    want, counts = _loop_over_experts(p, experts, w, valid, 0, 0)
    assert list(report[:-1]) == [16] * 8 and int(report[-1]) == 0
    np.testing.assert_allclose(np.asarray(y), want, rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("use_pallas", [False, True], ids=["xla", "pallas"])
def test_grouped_product_against_a_loop_over_groups(use_pallas):
    rng = np.random.default_rng(1)
    sizes = np.array([5, 0, 130, 17], np.int32)       # ragged, one empty
    lhs = rng.normal(size=(256, 64)).astype(np.float32)
    rhs = rng.normal(size=(3, 4, 64, 128)).astype(np.float32)
    out = np.asarray(jax.jit(lambda a, b: moe.grouped_matmul(
        a, b, jnp.asarray(sizes), layer=jnp.int32(2),
        use_pallas=use_pallas))(lhs, rhs))
    at = 0
    for g, n in enumerate(sizes):
        np.testing.assert_allclose(out[at:at + n], lhs[at:at + n] @ rhs[2, g],
                                   rtol=2e-4, atol=2e-4)
        at += n


# ---------------------------------------------------------------------------
# latent attention
# ---------------------------------------------------------------------------

def test_absorbed_attention_is_the_expanded_one():
    """Scores through ``W_uk`` on the query side and values through
    ``W_uv`` after the sum are the expanded keys' and values' numbers."""
    rng = np.random.default_rng(2)
    H, dn, dr, dv, R, S = 4, 8, 8, 8, 16, 11
    q_nope = rng.normal(size=(1, H, dn)).astype(np.float32)
    q_rope = rng.normal(size=(1, H, dr)).astype(np.float32)
    ckv = rng.normal(size=(S, R)).astype(np.float32)
    k_rope = rng.normal(size=(S, dr)).astype(np.float32)
    w_uk = rng.normal(size=(H, R, dn)).astype(np.float32)
    w_uv = rng.normal(size=(H, R, dv)).astype(np.float32)
    scale = 0.3
    k_nope = np.einsum("sc,hcd->shd", ckv, w_uk)
    v = np.einsum("sc,hcd->shd", ckv, w_uv)
    s = (np.einsum("bhd,shd->bhs", q_nope, k_nope)
         + np.einsum("bhd,sd->bhs", q_rope, k_rope)) * scale
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    expanded = np.einsum("bhs,shd->bhd", p, v)
    q_lat = np.concatenate(
        [np.einsum("bhd,hcd->bhc", q_nope, w_uk), q_rope], -1)
    rows = np.concatenate([ckv, k_rope], -1)[None]
    o_lat = latent_decode_attention(jnp.asarray(q_lat), jnp.asarray(rows),
                                    jnp.asarray([S]), R, scale)
    absorbed = np.einsum("bhc,hcd->bhd", np.asarray(o_lat), w_uv)
    np.testing.assert_allclose(absorbed, expanded, rtol=1e-4, atol=1e-5)


# (id, page, chunk rows, table entries, positions a lane; None: the lane
# idles). A chunk holds ``G = chunk rows / page`` pages; a rider at position
# ``p`` has ``p // page + 1`` live pages: whole chunks of ``G`` and a tail
# of ``left`` pages, which the kernel starts and awaits in pieces of 2^k
# pages, one a set bit of ``left``.
LATENT_DECODE_CASES = [
    # the one case this test was before PR 50: a table of one chunk
    ("positions_19_and_8_and_an_idle_lane", 8, 512, 4, [19, 8, None]),
    # the span ends on a chunk's last row (no tail at all), and one row on
    ("ends_on_a_chunks_last_row", 8, 32, 12, [31, 63, None]),
    ("one_row_into_the_next_chunk", 8, 32, 12, [32, None, 64]),
    ("on_a_pages_first_and_last_row", 8, 32, 12, [8, 15, 40, 47]),
    ("in_the_first_page", 8, 32, 12, [0, 3, 7]),
    ("every_page_of_the_table_live", 8, 32, 12, [95, None, 95]),
    ("one_and_three_whole_chunks_then_a_tail", 8, 32, 16, [37, 100, 127]),
    # G = 8: tails of 1..7 pages, every piece (4, 2, 1) alone and together
    ("tails_of_1_2_3_4_pages", 8, 64, 16, [64, 75, 87, 31]),
    ("tails_of_5_6_7_pages", 8, 64, 16, [39, 111, 119, 55]),
    ("idle_lanes_first_last_and_between", 8, 32, 12,
     [None, 19, None, None, 70, None]),
    ("one_lane", 8, 32, 12, [45]),
    ("one_idle_lane", 8, 32, 12, [None]),
    ("a_page_a_chunk", 8, 8, 8, [0, 7, 8, None, 63]),
    ("pages_of_16_four_a_chunk", 16, 64, 8, [15, 16, 63, 64, 127, None]),
    ("pages_of_16_a_table_smaller_than_a_chunk", 16, 512, 6, [95, 17]),
]


@pytest.mark.parametrize(
    "page, chunk_rows, M, positions",
    [c[1:] for c in LATENT_DECODE_CASES],
    ids=[c[0] for c in LATENT_DECODE_CASES])
def test_both_lowerings_of_the_latent_decode_agree(monkeypatch, page,
                                                   chunk_rows, M, positions):
    """``mla_paged_decode_attention`` (interpret mode: the same code the
    chip runs) against ``latent_decode_attention`` over ``paged_gather``,
    every lane: an idle one reads the scratch page's first row. Rows past a
    rider's position in its last page are NaN in the kernel's pool: the
    copies bring them in, and only the masks keep them out of the scores
    and of the values. The interpreter performs a copy when its bytes are
    AWAITED and leaves unwritten VMEM NaN, so a wait that counts too few
    pages, or the wrong piece's, reads NaN here (plain interpret mode
    completes a copy at its start and cannot tell)."""
    monkeypatch.setattr(PK, "_MLA_CHUNK_ROWS", chunk_rows)
    monkeypatch.setattr(PK, "_interpret", lambda: pltpu.InterpretParams(
        dma_execution_mode="on_wait", uninitialized_memory="nan"))
    rng = np.random.default_rng(3)
    L, W, R, H, B = 2, 128, 96, 4, len(positions)
    riders = [b for b, p in enumerate(positions) if p is not None]
    pos = np.array([p or 0 for p in positions], np.int32)
    need = [0 if p is None else p // page + 1 for p in positions]
    P = sum(need) + 3
    assert max(need, default=0) <= M
    free = rng.permutation(np.arange(1, P)).tolist()
    tables = np.zeros((B, M), np.int32)
    for b in riders:
        tables[b, :need[b]] = [free.pop() for _ in range(need[b])]
    pool = rng.normal(size=(L, P, page, W)).astype(np.float32)
    poisoned = pool.copy()
    for b in riders:
        poisoned[:, tables[b, need[b] - 1], pos[b] % page + 1:] = np.nan
    q = jnp.asarray(rng.normal(size=(B, H, W)), jnp.float32)
    new = jnp.asarray(rng.normal(size=(B, W)), jnp.float32)
    tables, pos = jnp.asarray(tables), jnp.asarray(pos)
    out, pool2 = PK.mla_paged_decode_attention(
        q, jnp.asarray(poisoned), new, tables, pos, jnp.int32(1), R, 0.2)
    # the rows went where the table says, in the one layer
    for b in riders:
        np.testing.assert_array_equal(
            np.asarray(pool2[1, tables[b, need[b] - 1], pos[b] % page]),
            np.asarray(new[b]))
    np.testing.assert_array_equal(np.asarray(pool2[0]), poisoned[0])
    clean = jnp.where(jnp.isnan(pool2), pool, pool2)
    want = latent_decode_attention(q, paged_gather(clean, tables, 1),
                                   pos + 1, R, 0.2)
    assert np.isfinite(np.asarray(out)).all()
    # float32 both sides; the kernel sums a span chunk by chunk under a
    # running maximum, the reference in one pass: 1.8e-6 apart at most, on
    # the spans of 128 rows (atol was 1e-6 when the longest span was 20)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=2e-6)


# ---------------------------------------------------------------------------
# the program: forward, the engine, the reference
# ---------------------------------------------------------------------------

SHARE = K.KIMI_K2_TINY.scaled(experts_held=4, first_expert=4)


def _engine(cfg=SHARE, **kw):
    params = K.init_params(jax.random.PRNGKey(0), cfg)
    ecfg = dict(max_batch=4, max_seq=64, page_size=8,
                prefill_buckets=(16, 32), prefix_cache=False)
    ecfg.update(kw)
    return serving.DecodeEngine(params, cfg, serving.EngineConfig(**ecfg))


@pytest.mark.parametrize("fused", [False, True], ids=["gather", "kernels"])
def test_prefill_then_decode_through_the_latent_pool(fused):
    """Prefill (expanded, on its rung), then decode (absorbed, through the
    page table) against the full forward pass, two requests interleaved;
    ``fused_decode`` drives both Pallas kernels in interpret mode."""
    eng = _engine(fused_decode=fused)
    assert eng.kv_path == ("pallas_paged" if fused else "xla_gather")
    assert [a.shape for a in eng.cache.arrays()] == [(3, 33, 8, 128)]
    dropped0 = smetrics.m_moe_dropped.value
    rng = np.random.default_rng(4)
    streams, served = {}, {}
    for n in (11, 19):
        prompt = rng.integers(0, SHARE.vocab_size, n).tolist()
        slot, logits, tok = eng.start_sequence_sampled(
            prompt, serving.sampling.GREEDY)
        streams[slot], served[slot] = prompt + [tok], [logits]
        assert eng.last_expert_load["expert_tokens"] > 0
    for _ in range(10):
        out = eng.decode_step_sampled(
            {s: t[-1] for s, t in streams.items()}, None)
        for slot, (tok, logits) in out.items():
            served[slot].append(logits)
            streams[slot].append(tok)
        load = eng.last_expert_load
        # two riders, 4 choices each, 2 expert layers: at most 16 here
        assert 0 <= load["expert_tokens"] <= 16
        assert load["experts_hit"] <= min(8, load["expert_tokens"])
        assert load["expert_load_max"] <= 2
    # one causal forward pass a stream holds every step's reference
    for slot, stream in streams.items():
        want = eng.reference_logits(stream[:-1])[-len(served[slot]):]
        np.testing.assert_allclose(np.stack(served[slot]), want, atol=2e-6)
        assert stream[-len(served[slot]):] == list(want.argmax(-1))
    assert eng.latent_token_bytes == (16 + 8) * 4 * 3
    assert smetrics.m_moe_dropped.value == dropped0 == 0
    assert smetrics.m_moe_routed.labels("elsewhere").value > \
        smetrics.m_moe_routed.labels("here").value > 0


def test_the_latent_tick_is_handed_pages_of_its_pool_and_zeros():
    """What ``mla_paged_decode_attention`` rests on since its page copies
    go unchecked (``disable_bounds_checks``): every entry of every table
    row a tick is fed names a page of the pool, ``[0, P)``; a lane that
    does not ride has the all-zero row (the scratch page) and position 0;
    a rider's live pages ``[0, position // page]`` are its own, none the
    scratch page and none another rider's. Through admit, grow over page
    ends, evict, and reuse of the slot and of its pages, in a pool too
    small to hold every slot at full length."""
    eng = _engine(num_pages=13)
    P, page, M = eng.cache.arrays()[0].shape[1], 8, eng.table_width
    assert (P, M) == (13, 8)
    fed = []
    tick_args = eng._tick_args

    def recorded(slot_tokens, params_by_slot):
        feed, sampler = tick_args(slot_tokens, params_by_slot)
        fed.append((feed.copy(), sorted(slot_tokens)))
        return feed, sampler

    eng._tick_args = recorded
    rng = np.random.default_rng(6)
    streams = {}

    def admit(n):
        prompt = rng.integers(0, SHARE.vocab_size, n).tolist()
        slot, _, tok = eng.start_sequence_sampled(
            prompt, serving.sampling.GREEDY)
        streams[slot] = tok

    def tick(n=1):
        for _ in range(n):
            out = eng.decode_step_sampled(dict(streams), None)
            for slot, (tok, _) in out.items():
                streams[slot] = tok

    admit(11), admit(19), admit(7)            # 2, 3 and 1 pages
    tick(10)                                  # each grows over a page end
    gone = min(streams)
    eng.free_sequence(gone)                   # evict: its pages go back
    del streams[gone]
    tick(2)                                   # its lane idles among riders
    admit(31)               # 4 of the 5 free pages: the slot AND pages reused
    assert gone in streams
    tick(1)
    for slot in list(streams):
        eng.free_sequence(slot)
    assert len(fed) == 13
    was = set(fed[9][0][gone, :M].tolist()) - {0}
    assert len(was & set(fed[-1][0][gone, :M].tolist())) >= 2
    for feed, riders in fed:
        tables, positions = feed[:, :M], feed[:, M]
        assert tables.min() >= 0 and tables.max() < P
        idle = [s for s in range(feed.shape[0]) if s not in riders]
        assert idle, "a lane idles in every tick of this test"
        assert not tables[idle].any() and not positions[idle].any()
        live = [tables[s, :positions[s] // page + 1] for s in riders]
        held = np.concatenate(live)
        assert (held > 0).all() and len(set(held.tolist())) == held.size


def _family_config(cfg, **over):
    """The benchmark family's configuration of a program config."""
    return {"hidden_size": cfg.hidden_size,
            "intermediate_size": cfg.intermediate_size,
            "moe_intermediate_size": cfg.moe_intermediate_size,
            "num_hidden_layers": cfg.num_hidden_layers,
            "first_k_dense_replace": cfg.first_k_dense_replace,
            "num_attention_heads": cfg.num_attention_heads,
            "q_lora_rank": cfg.q_lora_rank, "kv_lora_rank": cfg.kv_lora_rank,
            "qk_nope_head_dim": cfg.qk_nope_head_dim,
            "qk_rope_head_dim": cfg.qk_rope_head_dim,
            "v_head_dim": cfg.v_head_dim,
            "n_routed_experts": cfg.experts_held,
            "first_expert": cfg.first_expert,
            "published": {"n_routed_experts":
                          cfg.n_routed_experts_published},
            "num_experts_per_tok": cfg.num_experts_per_tok,
            "n_shared_experts": cfg.n_shared_experts,
            "routed_scaling_factor": cfg.routed_scaling_factor,
            "rms_norm_eps": cfg.rms_norm_eps, "rope_theta": cfg.rope_theta,
            "rope_scaling": cfg.rope_scaling, "vocab_size": cfg.vocab_size,
            **over}


def test_program_forward_against_the_plain_reference():
    """The same seeded weights through ``models/kimi_k2.py:forward`` and
    through the benchmark's reference, which shares no code with it."""
    config = _family_config(SHARE)
    params = FAMILY.program_weights(7, config, jnp.float32)
    assert (jax.tree_util.tree_map(lambda a: a.shape, params)
            == K.leaf_shapes(SHARE))
    tokens = np.random.default_rng(5).integers(0, SHARE.vocab_size, 24)
    got = K.forward(params, jnp.asarray(tokens, jnp.int32), SHARE)
    want = FAMILY.forward(config, 7, tokens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-6)
    assert float(np.abs(np.asarray(want)).max()) > 0.1


def test_the_shares_add_up_to_the_uncut_layer():
    """The guide's share test, on the reference: the routed parts of the 4
    shares (4 of 16 experts each) plus the shared expert ONCE are the
    uncut expert layer's output; and the program's share is the
    reference's share of the same rank."""
    whole = _family_config(K.KIMI_K2_TINY)
    s_whole = FAMILY.dims(whole)
    w = FAMILY.layer_weights(FAMILY._key(11), whole, "moe", 1)
    h = jax.random.normal(jax.random.PRNGKey(12), (20, 64), jnp.float32)
    mm = FAMILY._mm("f32")
    full, _ = FAMILY._moe_ffn(h, w, s_whole, mm)
    u = FAMILY._rms(h, w["post_attention_layernorm"], s_whole["eps"])
    shared = FAMILY._gated(u, w["shared_gate_proj"], w["shared_up_proj"],
                           w["shared_down_proj"], mm)
    routed = jnp.zeros_like(h)
    for rank in range(4):
        s = {**s_whole, "G": 4, "first": 4 * rank}
        mine = {**w, **{k: w[k][4 * rank:4 * rank + 4] for k in (
            "experts_gate_proj", "experts_up_proj", "experts_down_proj")}}
        share, chose = FAMILY._moe_ffn(h, mine, s, mm)
        routed = routed + (share - h - shared)
        assert chose.shape == (20, 4)
        # the program's share of this rank, from the same leaves
        y, report = moe.expert_share(
            u, jnp.ones((20,), bool), *moe.route(
                u, w["gate"], w["e_score_correction_bias"], 4, 2.5),
            jnp.concatenate([mine["experts_gate_proj"],
                             mine["experts_up_proj"]], -1),
            mine["experts_down_proj"], first_expert=4 * rank)
        np.testing.assert_allclose(np.asarray(y),
                                   np.asarray(share - h - shared),
                                   atol=2e-6)
        assert int(report[:-1].sum()) == int(chose.sum())
    np.testing.assert_allclose(np.asarray(h + routed + shared),
                               np.asarray(full), atol=2e-6)


def test_gpt_and_jamba_pools_are_what_they_were():
    """Shape and dtype of the arrays the two older families carry (the
    GPT pools' rows flat since PR 42: a token's heads side by side)."""
    g = serving.DecodeEngine(
        G.init_params(jax.random.PRNGKey(0), G.GPT_TINY), G.GPT_TINY,
        serving.EngineConfig(max_batch=2, max_seq=32, page_size=8))
    nh, hd = G.GPT_TINY.num_heads, G.GPT_TINY.head_dim
    assert [(a.shape, a.dtype) for a in g.cache.arrays()] == [
        ((G.GPT_TINY.num_layers, 9, 8, nh * hd), jnp.float32)] * 2
    assert g.cache.k is g.cache.arrays()[0]
    assert g.cache.v is g.cache.arrays()[1] and g.cache.keys_and_values
    assert g.latent_token_bytes == 0
    j = serving.DecodeEngine(
        J.init_params(jax.random.PRNGKey(0), J.JAMBA_TINY), J.JAMBA_TINY,
        serving.EngineConfig(max_batch=2, max_seq=32, page_size=8,
                             prefix_cache=False, prefill_buckets=(8, 16)))
    c = J.JAMBA_TINY
    assert [(a.shape, a.dtype) for a in j.cache.arrays()] == [
        ((1, 9, 8, 1, 16), jnp.float32), ((1, 9, 8, 1, 16), jnp.float32),
        ((3, 2, 3 * c.d_inner), jnp.float32),
        ((3, 2, c.mamba_d_state, c.d_inner), jnp.float32)]


@pytest.mark.parametrize("kw, named", [
    (dict(prefix_cache=True), "the prefix cache"),
    (dict(verify_window=4), "the verify window"),
    (dict(sharding="tp", tp=2), "the tensor-parallel engine"),
    (dict(weight_dtype="int8"), "weight_dtype 'int8'"),
    (dict(role="prefill"), "role 'prefill'")])
def test_what_cannot_carry_latent_rows_is_refused_by_name(kw, named):
    with pytest.raises(ValueError) as e:
        _engine(**{"prefix_cache": False, **kw})
    assert "KimiK2Serving has a latent cache" in str(e.value)
    assert named in str(e.value) and "latent rows" in str(e.value)


def test_page_contents_and_hand_off_refuse_a_latent_pool():
    eng = _engine()
    for call in (lambda: eng.cache.read_pages([1]),
                 lambda: eng.cache.adopt_slot(8, [1]),
                 lambda: eng.export_request_kv(0)):
        with pytest.raises(ValueError, match="latent"):
            call()
    assert "kv_transfer" not in eng.warmup()
    assert eng.model.max_positions is None
    assert math.isclose(eng.cache.nbytes, 3 * 33 * 8 * 128 * 4)
