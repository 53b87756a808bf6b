"""The delta rule with a gate a channel (``ops/gated_delta.py``:
``kda_chunked``, ``kda_update``) and a model of KDA layers, a gated
grouped-query layer and sparse experts (``models/solar_open2.py``) through
``DecodeEngine`` + ``Scheduler`` at the cell's rehearsal sizes on the CPU:
the chunked form (both lowerings), the one-token form and the plain
reference's recurrence (``benchmark/families/solar_open2.py``, which imports
nothing of the program) against each other, a fast-decaying channel among
the draws; prefill then decoding through the caches against the reference's
full forward pass on the same seeded weights, logits and not tokens; the
eight shares that add up to the uncut layer; a tick's record with the
state's and the experts' attributes together; and every refusal."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import serving
from paddle_tpu.models import solar_open2 as SO
from paddle_tpu.ops import gated_delta as GD
from paddle_tpu.ops import moe
from paddle_tpu.serving.sampling import GREEDY

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402

CELL = "serve_solar_open2_ep8_closed64"
SEED = 2 ** 31 + 44
# float32 program against a float32 reference that sums in another order (a
# chunked delta rule in sub-blocks against a token-by-token one, a sorted
# grouped product against every expert dense, a cache between the tokens):
# the largest difference of a logit reads 1e-6 at logits of order 0.6 (my
# CPU runs, PR 44); bfloat16 weights read 1e-2 and more. 2e-5 absolute is
# some 300 float32 roundings of such a logit
LOGIT_TOL = dict(rtol=0, atol=2e-5)


@pytest.fixture(scope="module")
def cell():
    return harness.Cell(ROOT, CELL, rehearsal=True)


@pytest.fixture(scope="module")
def family(cell):
    return cell.family


def _cfg(cell, **kw):
    s = cell.family.dims(cell.config)
    c = cell.config
    return SO.SolarOpen2Config(
        vocab_size=s["V"], hidden_size=s["D"], num_hidden_layers=s["L"],
        gqa_layers=tuple(c["gqa_layers"]), num_attention_heads=s["H"],
        num_key_value_heads=s["KVH"], head_dim=s["hd"],
        linear_num_heads=s["Hk"], linear_head_dim=s["dk"],
        short_conv_kernel_size=s["K"], moe_intermediate_size=s["F"],
        n_routed_experts_published=s["E"], experts_held=s["G"],
        first_expert=s["first"], num_experts_per_tok=s["k"],
        n_shared_experts=s["S"], routed_scaling_factor=s["scale"],
        rms_norm_eps=s["eps"], dtype=jnp.float32).scaled(**kw)


_WEIGHTS = []


def _weights(cell):
    if not _WEIGHTS:                 # one draw serves every engine here
        _WEIGHTS.append(cell.family.program_weights(SEED, cell.config,
                                                    jnp.float32))
    return _WEIGHTS[0]


def _engine(cell, **kw):
    ecfg = dict(max_batch=4, max_seq=160, page_size=8, prefix_cache=False,
                prefill_buckets=(8, 16, 32, 128))
    ecfg.update(kw)
    return serving.DecodeEngine(_weights(cell), _cfg(cell),
                                serving.EngineConfig(**ecfg))


@pytest.fixture(scope="module")
def engine(cell):
    return _engine(cell)


def _decode(engine, prompt, n_new):
    """Prefill then ``n_new`` greedy ticks: (slot, tokens fed, [logits])."""
    slot, logits, tok = engine.start_sequence_sampled(prompt, GREEDY)
    fed, rows = list(prompt), [logits]
    for _ in range(n_new):
        fed.append(tok)
        tok, logits = engine.decode_step_sampled({slot: tok}, None)[slot]
        rows.append(logits)
    return slot, fed, np.stack(rows)


def _prompt(n, seed=0):
    return np.random.default_rng(seed).integers(0, 2048, n).tolist()


# ---------------------------------------------------------------------------
# the delta rule with a gate a channel: three forms
# ---------------------------------------------------------------------------

def _kda_inputs(T, H=3, dk=16, dv=32, seed=0):
    """Normalised q and k, a log-gate A CHANNEL from a thousandth to 1.6
    nats a token, and two channels that decay FAST: 3 and 8 nats a token,
    192 and 512 across a chunk of 64, where ``exp(r - G[j])`` about one
    reference a chunk has no float32; beta over the whole of (0, 2)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (T, H, dk))) / np.sqrt(dk)
    k = unit(jax.random.normal(ks[1], (T, H, dk)))
    v = jax.random.normal(ks[2], (T, H, dv))
    alpha_log = -jnp.exp(jax.random.uniform(ks[3], (T, H, dk), minval=-7.0,
                                            maxval=0.5))
    alpha_log = alpha_log.at[:, 0, 1].set(-3.0).at[:, 1, 2].set(-8.0)
    beta = 2 * jax.nn.sigmoid(2 * jax.random.normal(ks[4], (T, H)))
    assert float(beta.max()) > 1.5 and float(beta.min()) < 0.5
    return q, k, v, alpha_log, beta


@pytest.mark.parametrize("use_pallas", [False, True], ids=["xla", "pallas"])
@pytest.mark.parametrize("length", [128, 100, 64, 17])
def test_kda_chunked_is_the_references_recurrence(family, length,
                                                  use_pallas):
    """Two chunks of 64 in sub-blocks of 16, whole, part padding and less
    than a chunk, float32, against the token-by-token recurrence of the
    plain reference (in the source's form, ``S [dk, dv]``) and against
    ``gated_delta_recurrence`` with a gate a channel: outputs before
    ``length`` and the state after ``length - 1``, to rounding, the fast
    channels finite."""
    q, k, v, alpha_log, beta = _kda_inputs(128)
    want_o, want_S = family.kda_rule(
        q[:length], k[:length], v[:length], jnp.exp(alpha_log[:length]),
        beta[:length])
    o, St = GD.kda_chunked(q, k, v, alpha_log, beta, jnp.int32(length),
                           use_pallas=use_pallas)
    assert np.isfinite(np.asarray(o)).all()
    np.testing.assert_allclose(o[:length], want_o, rtol=0, atol=2e-6)
    np.testing.assert_allclose(St, want_S, rtol=0, atol=5e-6)
    own_o, own_S = GD.gated_delta_recurrence(q, k, v, alpha_log, beta,
                                             length)
    np.testing.assert_allclose(own_o[:length], want_o, rtol=0, atol=2e-6)
    np.testing.assert_allclose(own_S, St, rtol=0, atol=5e-6)


@pytest.mark.parametrize("use_pallas", [False, True], ids=["xla", "pallas"])
def test_padding_past_length_leaves_the_state_bit_for_bit(use_pallas):
    """What lies at or past ``length`` moves nothing: other tokens there,
    or a chunk less of them, give the same state bit for bit and the same
    outputs before ``length``."""
    q, k, v, alpha_log, beta = _kda_inputs(192, seed=1)
    L = jnp.int32(70)
    o, St = GD.kda_chunked(q, k, v, alpha_log, beta, L,
                           use_pallas=use_pallas)
    q2, k2, v2, a2, b2 = _kda_inputs(192, seed=2)
    mix = lambda x, y: jnp.concatenate([x[:70], y[70:]])
    o2, St2 = GD.kda_chunked(mix(q, q2), mix(k, k2), mix(v, v2),
                             mix(alpha_log, a2), mix(beta, b2), L,
                             use_pallas=use_pallas)
    np.testing.assert_array_equal(np.asarray(St), np.asarray(St2))
    np.testing.assert_array_equal(np.asarray(o[:70]), np.asarray(o2[:70]))
    o3, St3 = GD.kda_chunked(q[:128], k[:128], v[:128], alpha_log[:128],
                             beta[:128], L, use_pallas=use_pallas)
    np.testing.assert_array_equal(np.asarray(St), np.asarray(St3))
    np.testing.assert_array_equal(np.asarray(o[:70]), np.asarray(o3[:70]))


@pytest.mark.parametrize("lanes,block", [(6, None), (16, 8)],
                         ids=["one-step", "blocks-of-eight"])
@pytest.mark.parametrize("use_pallas", [False, True], ids=["xla", "pallas"])
def test_kda_update_advances_the_riders_rows_alone(use_pallas, lanes, block,
                                                   monkeypatch):
    """Lanes over state rows of two layers, some lanes that do not ride:
    every row that no rider names, and the whole other layer, is what it
    was bit for bit; a rider's row and output are one step of the
    reference's recurrence from that row. With the riders' inputs in
    blocks of eight (what 80 riders at the cell's widths force) the row
    DMA's double buffer runs on across the grid's steps."""
    if block:
        monkeypatch.setattr(GD, "_UPDATE_VMEM", 0)
    H, dk, dv, rows = 4, 16, 32, lanes + 4
    assert GD._rider_block(lanes, H, dk, dv, 0, True) == block
    q, k, v, alpha_log, beta = _kda_inputs(lanes, H=H, dk=dk, dv=dv, seed=3)
    S = GD.fold_state(jax.random.normal(jax.random.PRNGKey(5),
                                        (2, rows, H, dk, dv)))
    slots = np.full((lanes,), -1, np.int32)
    riders = [0, 2, 3, 5] + list(range(6, lanes, 2))
    slots[riders] = np.random.default_rng(1).permutation(rows)[:len(riders)]
    o, new = GD.kda_update(S, q, k, v, jnp.exp(alpha_log), beta,
                           jnp.asarray(slots), layer=jnp.int32(1),
                           use_pallas=use_pallas)
    idle = sorted(set(range(rows)) - set(slots[riders].tolist()))
    np.testing.assert_array_equal(np.asarray(new[0]), np.asarray(S[0]))
    np.testing.assert_array_equal(np.asarray(new[1, idle]),
                                  np.asarray(S[1, idle]))
    assert not np.asarray(o)[slots < 0].any()
    before, after = GD.unfold_state(S, dv), GD.unfold_state(new, dv)
    for lane in riders:
        # the source's step from this row, S [dk, dv]
        St = before[1, slots[lane]]
        a, b = jnp.exp(alpha_log[lane]), beta[lane]
        Sa = a[:, :, None] * St
        kS = jnp.einsum("hk,hkv->hv", k[lane], Sa)
        want = Sa - b[:, None, None] * k[lane][:, :, None] * kS[:, None, :] \
            + b[:, None, None] * k[lane][:, :, None] * v[lane][:, None, :]
        np.testing.assert_allclose(after[1, slots[lane]], want, rtol=0,
                                   atol=5e-6)
        np.testing.assert_allclose(
            o[lane], jnp.einsum("hk,hkv->hv", q[lane], want), rtol=0,
            atol=3e-5 if use_pallas else 5e-6)


def test_chunked_prefill_then_one_token_steps_are_one_recurrence(family):
    """40 tokens chunked, then 9 one at a time from the state the chunked
    form left, against the reference's recurrence over all 49."""
    q, k, v, alpha_log, beta = _kda_inputs(64, H=4, seed=7)
    want_o, _ = family.kda_rule(q[:49], k[:49], v[:49],
                                jnp.exp(alpha_log[:49]), beta[:49])
    _, St = GD.kda_chunked(q, k, v, alpha_log, beta, jnp.int32(40))
    S = GD.fold_state(St)[None]                       # one state row
    for t in range(40, 49):
        o, S = GD.kda_update(
            S, q[t:t + 1], k[t:t + 1], v[t:t + 1],
            jnp.exp(alpha_log[t:t + 1]), beta[t:t + 1],
            jnp.zeros((1,), jnp.int32))
        np.testing.assert_allclose(o[0], want_o[t], rtol=0, atol=2e-6)


@pytest.mark.parametrize("use_pallas", [False, True], ids=["xla", "pallas"])
def test_scalar_gate_entries_are_what_they_were(use_pallas):
    """Olmo's path: ``gated_delta_chunked`` and ``gated_delta_update`` keep
    their signatures and still equal the recurrence, and a gate a channel
    whose channels all agree is the scalar gate; each entry refuses the
    other's gate by its rank."""
    q, k, v, a3, beta = _kda_inputs(128, seed=9)
    alpha_log = a3[:, :, 5]                         # one number a head
    wide = jnp.broadcast_to(alpha_log[:, :, None], a3.shape)
    L = jnp.int32(100)
    want_o, want_S = GD.gated_delta_recurrence(q, k, v, alpha_log, beta, L)
    o, St = GD.gated_delta_chunked(q, k, v, alpha_log, beta, L, chunk=64,
                                   use_pallas=use_pallas)
    np.testing.assert_allclose(o[:100], want_o[:100], rtol=0, atol=2e-6)
    np.testing.assert_allclose(St, want_S, rtol=0, atol=5e-6)
    o2, St2 = GD.kda_chunked(q, k, v, wide, beta, L, use_pallas=use_pallas)
    np.testing.assert_allclose(o2[:100], o[:100], rtol=0, atol=2e-6)
    np.testing.assert_allclose(St2, St, rtol=0, atol=5e-6)
    S = GD.fold_state(jax.random.normal(jax.random.PRNGKey(2),
                                        (5, 3, 16, 32)))
    slots = jnp.asarray([4, -1, 1], jnp.int32)
    args = (q[:3], k[:3], v[:3])
    o3, S3 = GD.gated_delta_update(S, *args, jnp.exp(alpha_log[:3]),
                                   beta[:3], slots, use_pallas=use_pallas)
    o4, S4 = GD.kda_update(S, *args, jnp.exp(wide[:3]), beta[:3], slots,
                           use_pallas=use_pallas)
    np.testing.assert_allclose(o4, o3, rtol=0, atol=3e-5)
    np.testing.assert_allclose(S4, S3, rtol=0, atol=5e-6)
    with pytest.raises(ValueError, match="a gate a head"):
        GD.gated_delta_update(S, *args, jnp.exp(wide[:3]), beta[:3], slots)
    with pytest.raises(ValueError, match="a gate a channel"):
        GD.kda_update(S, *args, jnp.exp(alpha_log[:3]), beta[:3], slots)


# ---------------------------------------------------------------------------
# the model through the engine
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n_prompt,fused", [(13, False), (100, False),
                                            (13, True)],
                         ids=["rung16", "rung128-two-chunks", "kernels"])
def test_prefill_then_decode_against_the_references_forward_pass(
        cell, family, engine, n_prompt, fused):
    """13 tokens through a rung of 16 and 100 through a rung of 128 (two
    chunks of the delta rule, the second part padding), then 7 ticks:
    every row of logits the engine handed out against the plain model's
    row at that position; the reference with ONE gate a head, or without
    the GQA layer's output gate, or with bfloat16 weights fails the
    tolerance, so it would catch either mechanism left out and the
    precision below. ``fused_decode`` drives ``kda_update_rows``, the
    grouped paged kernel and the grouped product in interpret mode."""
    eng = _engine(cell, fused_decode=True) if fused else engine
    assert eng.kv_path == ("pallas_paged" if fused else "xla_gather")
    slot, fed, rows = _decode(eng, _prompt(n_prompt, n_prompt), 7)
    want = np.asarray(family.forward(cell.config, SEED,
                                     fed))[n_prompt - 1:]
    np.testing.assert_allclose(rows, want, **LOGIT_TOL)
    assert np.abs(want).max() > 0.1
    assert eng.cache.length(slot) == n_prompt + 7
    eng.free_sequence(slot)
    if fused:
        return
    for broken in (dict(held="bf16w"), dict(scalar_gate=True),
                   dict(gated=False)):
        low = np.asarray(family.forward(cell.config, SEED, fed,
                                        **broken))[n_prompt - 1:]
        assert np.abs(low - want).max() > 20 * LOGIT_TOL["atol"], broken


def test_the_programs_plain_forward_is_the_references(cell, family):
    tokens = _prompt(70, 8)             # more than a chunk, not whole chunks
    params = _weights(cell)
    assert (jax.tree_util.tree_map(lambda a: a.shape, params)
            == SO.leaf_shapes(_cfg(cell)))
    got = SO.forward(params, jnp.asarray(tokens, jnp.int32), _cfg(cell))
    want = family.forward(cell.config, SEED, tokens)
    np.testing.assert_allclose(got, want, **LOGIT_TOL)


def test_the_eight_shares_add_up_to_the_uncut_layer(cell, family):
    """The guide's share test, on the reference: the routed parts of the 8
    shares (2 of 16 experts each) plus the shared expert ONCE are the
    uncut expert layer's output; and the program's share of a rank is the
    reference's share of that rank, from the same leaves."""
    whole = harness._merge(cell.config, {"n_routed_experts": 16,
                                         "first_expert": 0})
    s_whole = family.dims(whole)
    assert (s_whole["G"], s_whole["E"], s_whole["k"]) == (16, 16, 4)
    w = family.layer_weights(family._key(11), whole, "kda", 1)
    u = jax.random.normal(jax.random.PRNGKey(12), (20, 64), jnp.float32)
    mm = family._mm("f32")
    full, _ = family._moe(u, w, s_whole, mm)
    shared = family._gated(u, w["shared_gate_proj"], w["shared_up_proj"],
                           w["shared_down_proj"], mm)
    routed = jnp.zeros_like(u)
    names = ("experts_gate_proj", "experts_up_proj", "experts_down_proj")
    for rank in range(8):
        s = {**s_whole, "G": 2, "first": 2 * rank}
        mine = {**w, **{k: w[k][2 * rank:2 * rank + 2] for k in names}}
        share, chose = family._moe(u, mine, s, mm)
        routed = routed + (share - shared)
        assert chose.shape == (20, 2)
        y, report = moe.expert_share(
            u, jnp.ones((20,), bool), *moe.route(
                u, w["gate"], w["e_score_correction_bias"], 4, 1.0),
            jnp.concatenate([mine["experts_gate_proj"],
                             mine["experts_up_proj"]], -1),
            mine["experts_down_proj"], first_expert=2 * rank)
        np.testing.assert_allclose(np.asarray(y),
                                   np.asarray(share - shared), atol=2e-6)
        assert int(report[:-1].sum()) == int(chose.sum())
    np.testing.assert_allclose(np.asarray(routed + shared),
                               np.asarray(full), atol=2e-6)
    assert float(np.abs(np.asarray(routed)).max()) > 1e-3


def test_a_slot_that_sits_out_ticks_keeps_its_state_bit_for_bit(engine):
    a, _, tok_a = engine.start_sequence_sampled(_prompt(9, 1), GREEDY)
    b, _, tok_b = engine.start_sequence_sampled(_prompt(5, 2), GREEDY)
    conv0, ssm0 = (np.asarray(engine.cache.conv[:, a]),
                   np.asarray(engine.cache.ssm[:, a]))
    assert np.abs(ssm0).max() > 0
    for _ in range(3):                  # a is live and does not ride
        tok_b = engine.decode_step_sampled({b: tok_b}, None)[b][0]
    np.testing.assert_array_equal(np.asarray(engine.cache.conv[:, a]), conv0)
    np.testing.assert_array_equal(np.asarray(engine.cache.ssm[:, a]), ssm0)
    assert engine.cache.length(a) == 9 and engine.cache.length(b) == 8
    # and when it rides, it goes on as if the others' ticks had not been
    solo = engine.decode_step_sampled({a: tok_a}, None)[a][1]
    engine.free_sequence(a)
    engine.free_sequence(b)
    fresh, _, tok = engine.start_sequence_sampled(_prompt(9, 1), GREEDY)
    assert tok == tok_a
    again = engine.decode_step_sampled({fresh: tok}, None)[fresh][1]
    np.testing.assert_allclose(solo, again, **LOGIT_TOL)
    engine.free_sequence(fresh)


def test_state_geometry_is_the_models_rows_and_bytes(cell, engine):
    """A slot's rows as the model states them: the conv's last three
    inputs over q, k and v side by side in the cache's dtype, the matrix
    states float32, a head a row (four heads of 16 fill no lane tile and
    are not folded; at the published 128 a head is whole lane tiles)."""
    s = cell.family.dims(cell.config)
    channels = 3 * s["Hk"] * s["dk"]
    cache = engine.cache
    assert cache.conv.shape == (3, 4, 3 * channels)
    assert cache.ssm.shape == (3, 4, s["Hk"], s["dk"], s["dk"])
    assert cache.ssm.dtype == jnp.float32
    per_slot = 3 * (3 * channels * 4 + s["Hk"] * s["dk"] * s["dk"] * 4)
    assert cache.state_bytes_per_slot == per_slot
    assert cache.state_bytes_per_slot == cell.family.state_bytes_per_sequence(
        cell.config, conv_bytes=4)
    assert len(cache.pools) == 2 and cache.pools[0].shape[0] == 1
    assert cache.pools[0].shape[-1] == s["KVH"] * s["hd"]
    full = SO.SolarOpen2Serving(SO.SolarOpen2Config())
    assert full.state_geometry == {"layers": 36, "conv": (3 * 24576,),
                                   "ssm": (64, 128, 128)}
    assert full.cache_pools == {"layers": 12, "rows": ((1024,),) * 2}
    assert full.kernel_takes_pages(64, jnp.bfloat16)
    assert not full.kernel_takes_pages(8, jnp.bfloat16)


def test_a_ticks_record_carries_state_and_experts_together(cell):
    """Nothing new in kind in scheduler, engine or cache: one
    ``serve/decode_tick`` record names the riders' state rows AND what the
    expert layers reported, one ``serve/prefill`` record the chunks of the
    delta rule AND the experts' load."""
    from paddle_tpu.observability import spans
    from paddle_tpu.serving import metrics as smetrics

    eng = _engine(cell)
    tracer = spans.default_tracer()
    tracer.clear()
    born = smetrics.m_state_resets.value
    sched = serving.Scheduler(eng)
    reqs = [sched.submit(_prompt(n, n), max_new_tokens=m)
            for n, m in ((7, 5), (70, 3), (3, 6))]
    for _ in range(40):
        sched.step()
    assert [r.state for r in reqs] == ["done"] * 3
    per_slot = eng.cache.state_bytes_per_slot
    ticks = [s["attrs"] for s in tracer.spans()
             if s["name"] == "serve/decode_tick"]
    assert ticks and all(
        t["state_slots"] == t["batch"]
        and t["state_bytes"] == t["batch"] * per_slot
        and t["kv_path"] == "xla_gather"
        and {"expert_tokens", "experts_hit", "expert_load_max",
             "cached_tokens"} <= set(t)
        and "latent_bytes" not in t and "rows_full" not in t
        for t in ticks)
    # 4 of 16 experts held, 4 a token, 4 layers: a rider expects 4 pairs
    assert sum(t["expert_tokens"] for t in ticks) > 0
    assert all(t["experts_hit"] <= 16 and t["expert_load_max"] <= t["batch"]
               for t in ticks)
    prefills = {s["attrs"]["prompt_len"]: s["attrs"] for s in tracer.spans()
                if s["name"] == "serve/prefill"}
    assert {n: (a["scan_tokens"], a["delta_chunks"])
            for n, a in prefills.items()} == {7: (7, 1), 70: (70, 2),
                                              3: (3, 1)}
    assert all({"expert_tokens", "experts_hit"} <= set(a)
               for a in prefills.values())
    assert smetrics.m_state_resets.value == born + 3   # state rows born
    # each request against the plain model, greedy token by token
    for r in reqs[:2]:
        want = np.asarray(cell.family.forward(
            cell.config, SEED, list(r.prompt) + list(r.tokens)))
        picks = want[len(r.prompt) - 1:-1].argmax(axis=-1)
        assert list(picks) == list(r.tokens)
    assert eng.cache.live_state_bytes() == 0


REFUSALS = [
    (dict(prefix_cache=True), "prefix cache"),
    (dict(verify_window=3), "verify window"),
    (dict(sharding="tp", tp=2), "tensor-parallel"),
    (dict(weight_dtype="int8"), "int8"),
    (dict(role="prefill"), "kv_transfer"),
]


@pytest.mark.parametrize("kw,mechanism", REFUSALS,
                         ids=[m for _, m in REFUSALS])
def test_what_carries_neither_state_nor_experts_is_refused_by_name(
        cell, kw, mechanism):
    """The same rule, stated once (``_refuse_what_cannot_carry_state``),
    in the same words a Mamba hybrid is refused by."""
    from paddle_tpu.models import jamba as J

    ecfg = dict(prefix_cache=False)
    ecfg.update(kw)
    with pytest.raises(ValueError, match="recurrent") as e:
        serving.DecodeEngine({}, _cfg(cell), serving.EngineConfig(**ecfg))
    assert mechanism in str(e.value)
    with pytest.raises(ValueError) as mamba:
        serving.DecodeEngine({}, J.JAMBA_TINY, serving.EngineConfig(**ecfg))
    assert (str(e.value).replace("SolarOpen2Serving", "JambaServing")
            == str(mamba.value))


def test_config_derives_the_pattern_and_refuses_what_is_not_built():
    cfg = SO.SolarOpen2Config()
    assert cfg.layer_types == (("gqa",) + ("kda",) * 3) * 12
    assert (cfg.num_kda_layers, cfg.kv_width, cfg.q_width, cfg.kda_width,
            cfg.gate_rank, cfg.shared_width) == (36, 1024, 8192, 8192, 128,
                                                 1280)
    # the pattern is the list's, whatever it is: no period is assumed
    odd = SO.SOLAR_OPEN2_TINY.scaled(num_hidden_layers=5, gqa_layers=(1, 4))
    assert odd.layer_types == ("kda", "gqa", "kda", "kda", "gqa")
    with pytest.raises(ValueError, match="gqa_layers"):
        SO.SolarOpen2Config(num_hidden_layers=4)
    for bad in (dict(use_rope=True), dict(kda_use_full_proj=True),
                dict(use_gqa_gate=False), dict(first_k_dense_replace=1),
                dict(norm_topk_prob=False)):
        with pytest.raises(ValueError, match="is built"):
            SO.SOLAR_OPEN2_TINY.scaled(**bad)
    assert isinstance(serving.model.describe(SO.SOLAR_OPEN2_TINY),
                      SO.SolarOpen2Serving)


def test_init_params_are_the_leaf_shapes_and_run():
    cfg = SO.SOLAR_OPEN2_TINY.scaled(experts_held=4, first_expert=8)
    params = SO.init_params(jax.random.PRNGKey(0), cfg)
    shapes = jax.tree_util.tree_map(lambda a: a.shape, params)
    assert shapes == SO.leaf_shapes(cfg)
    # a leaf from its own (key, layer, leaf): two layers' leaves differ
    a, b = params["layers"][1], params["layers"][2]
    assert float(jnp.abs(a["w_q"] - b["w_q"]).max()) > 0
    assert float(jnp.abs(a["w_q"] - a["w_k"]).max()) > 0
    held = SO.hold(params, cfg, "bf16")
    assert held["layers"][0]["w_qkvg"].shape == (64, 2 * 64 + 2 * 16)
    assert held["layers"][1]["w_qkv"].shape == (64, 3 * 64)
    assert held["layers"][1]["w_low"].shape == (64, 2 * 16 + 4)
    assert held["layers"][1]["conv_w"].dtype == jnp.float32
    assert held["layers"][1]["router"].dtype == jnp.float32
    assert held["layers"][1]["w_gate_up"].dtype == jnp.bfloat16
    logits = SO.forward(params, jnp.arange(10, dtype=jnp.int32), cfg)
    assert logits.shape == (10, cfg.vocab_size)
    assert np.isfinite(np.asarray(logits)).all()
