"""The gated delta rule (``ops/gated_delta.py``) and a model of linear and
full attention layers (``models/olmo_hybrid.py``) through ``DecodeEngine``
+ ``Scheduler`` at the cell's rehearsal sizes on the CPU: the chunked form,
the one-token form and the plain reference's recurrence
(``benchmark/families/olmo_hybrid.py``, which imports nothing of the
program) against each other; prefill then decoding through the caches
against the reference's full forward pass on the same seeded weights,
logits and not tokens; what a slot's state does when the slot sits out or
is handed out again; and every refusal, by the words a Mamba hybrid gets."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import serving
from paddle_tpu.models import olmo_hybrid as O
from paddle_tpu.ops import gated_delta as GD
from paddle_tpu.serving.sampling import GREEDY

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402

SEED = 2 ** 31 + 29
# float32 program against a float32 reference that sums in another order
# (a chunked delta rule against a token-by-token one, a transposed and
# folded state, stacked layers, a cache between the tokens): the largest
# difference of a logit reads 4e-6 at logits of order 0.7 (my CPU runs, PR
# 35); bfloat16 weights read 4e-2 to 6e-2. 2e-5 absolute is some 300
# float32 roundings of such a logit and 1/2000 of bfloat16's difference
LOGIT_TOL = dict(rtol=0, atol=2e-5)


@pytest.fixture(scope="module")
def cell():
    return harness.Cell(ROOT, "serve_olmo_hybrid_7b_l16_closed32",
                        rehearsal=True)


@pytest.fixture(scope="module")
def family(cell):
    return cell.family


def _cfg(cell):
    s = cell.family.dims(cell.config)
    return O.OlmoHybridConfig(
        vocab_size=s["V"], hidden_size=s["D"], intermediate_size=s["F"],
        num_hidden_layers=s["L"], num_attention_heads=s["H"],
        num_key_value_heads=s["KVH"], head_dim=s["hd"],
        layer_types=tuple(cell.config["layer_types"]),
        linear_num_key_heads=s["Hk"], linear_num_value_heads=s["Hv"],
        linear_key_head_dim=s["dk"], linear_value_head_dim=s["dv"],
        dtype=jnp.float32)


_WEIGHTS = []


def _engine(cell, **kw):
    ecfg = dict(max_batch=4, max_seq=160, page_size=8, prefix_cache=False,
                prefill_buckets=(8, 16, 32, 128))
    ecfg.update(kw)
    if not _WEIGHTS:                 # one draw serves every engine here
        _WEIGHTS.append(cell.family.program_weights(SEED, cell.config,
                                                    jnp.float32))
    return serving.DecodeEngine(_WEIGHTS[0], _cfg(cell),
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
# the delta rule's three forms
# ---------------------------------------------------------------------------

def _delta_inputs(T, H=3, dk=16, dv=32, seed=0):
    """Normalised q and k, decays from a thousandth to 1.6 nats a token,
    beta over the whole of (0, 2): above 1 the transition has a negative
    eigenvalue."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (T, H, dk))) / np.sqrt(dk)
    k = unit(jax.random.normal(ks[1], (T, H, dk)))
    v = jax.random.normal(ks[2], (T, H, dv))
    alpha_log = -jnp.exp(jax.random.uniform(ks[3], (T, H), minval=-7.0,
                                            maxval=0.5))
    beta = 2 * jax.nn.sigmoid(2 * jax.random.normal(ks[4], (T, H)))
    assert float(beta.max()) > 1.5 and float(beta.min()) < 0.5
    return q, k, v, alpha_log, beta


@pytest.mark.parametrize("use_pallas", [False, True], ids=["xla", "pallas"])
@pytest.mark.parametrize("length", [128, 100, 64, 17])
def test_chunked_form_is_the_references_recurrence(family, length,
                                                   use_pallas):
    """Lengths that fill their chunks of 32 and lengths that do not, in
    float32, against the token-by-token recurrence of the plain reference:
    outputs before ``length`` and the state after ``length - 1``, to
    rounding (states of order 3, outputs of order 0.5)."""
    q, k, v, alpha_log, beta = _delta_inputs(128)
    want_o, want_S = family.delta_rule(
        q[:length], k[:length], v[:length],
        jnp.exp(alpha_log[:length]), beta[:length])
    o, St = GD.gated_delta_chunked(q, k, v, alpha_log, beta,
                                   jnp.int32(length), chunk=32,
                                   use_pallas=use_pallas)
    np.testing.assert_allclose(o[:length], want_o, rtol=0, atol=2e-6)
    np.testing.assert_allclose(St, jnp.swapaxes(want_S, -1, -2), rtol=0,
                               atol=5e-6)
    own_o, own_S = GD.gated_delta_recurrence(q, k, v, alpha_log, beta,
                                             length)
    np.testing.assert_allclose(own_o[:length], want_o, rtol=0, atol=2e-6)
    np.testing.assert_allclose(own_S, St, rtol=0, atol=5e-6)


@pytest.mark.parametrize("use_pallas", [False, True], ids=["xla", "pallas"])
def test_one_token_form_advances_the_riders_rows_alone(family, use_pallas):
    """Six lanes over eight state rows of two layers, two lanes that do
    not ride: every row that no rider names, and the whole other layer,
    is what it was bit for bit; a rider's row and output are one step of
    the reference's recurrence from that row."""
    H, dk, dv, rows = 4, 16, 32, 8
    q, k, v, alpha_log, beta = _delta_inputs(6, H=H, dk=dk, dv=dv, seed=3)
    S = GD.fold_state(jax.random.normal(jax.random.PRNGKey(5),
                                        (2, rows, H, dk, dv)))
    assert S.shape == (2, rows, 1, dk, 4 * dv)      # four heads a lane row
    slots = jnp.asarray([3, -1, 0, 7, -1, 5], jnp.int32)
    o, new = GD.gated_delta_update(S, q, k, v, jnp.exp(alpha_log), beta,
                                   slots, layer=jnp.int32(1),
                                   use_pallas=use_pallas)
    idle = [1, 2, 4, 6]
    np.testing.assert_array_equal(np.asarray(new[0]), np.asarray(S[0]))
    np.testing.assert_array_equal(np.asarray(new[1, idle]),
                                  np.asarray(S[1, idle]))
    assert not np.asarray(o[1]).any() and not np.asarray(o[4]).any()
    before, after = GD.unfold_state(S, dv), GD.unfold_state(new, dv)
    for lane, slot in enumerate(np.asarray(slots)):
        if slot < 0:
            continue
        St = before[1, slot]
        Sk = jnp.einsum("hkv,hk->hv", St, k[lane])
        a, b = jnp.exp(alpha_log[lane]), beta[lane]
        want = a[:, None, None] * (
            St - b[:, None, None] * k[lane][:, :, None] * Sk[:, None, :]) \
            + b[:, None, None] * k[lane][:, :, None] * v[lane][:, None, :]
        np.testing.assert_allclose(after[1, slot], want, rtol=0, atol=2e-6)
        np.testing.assert_allclose(
            o[lane], jnp.einsum("hkv,hk->hv", want, q[lane]), rtol=0,
            atol=2e-6)


def test_chunked_prefill_then_one_token_steps_are_one_recurrence(family):
    """40 tokens chunked, then 9 one at a time from the state the chunked
    form left, against the reference's recurrence over all 49."""
    q, k, v, alpha_log, beta = _delta_inputs(64, H=4, seed=7)
    want_o, _ = family.delta_rule(q[:49], k[:49], v[:49],
                                  jnp.exp(alpha_log[:49]), beta[:49])
    _, St = GD.gated_delta_chunked(q, k, v, alpha_log, beta, jnp.int32(40),
                                   chunk=16)
    S = GD.fold_state(St)[None]                       # one state row
    for t in range(40, 49):
        o, S = GD.gated_delta_update(
            S, q[t:t + 1], k[t:t + 1], v[t:t + 1],
            jnp.exp(alpha_log[t:t + 1]), beta[t:t + 1],
            jnp.zeros((1,), jnp.int32))
        np.testing.assert_allclose(o[0], want_o[t], rtol=0, atol=2e-6)


def test_stored_state_fills_whole_lane_tiles():
    """Two heads of 192 side by side are 384 lanes; one of 128 needs no
    neighbour; an odd number of heads of 192 stays as it is."""
    assert GD.state_fold(30, 192) == 2 and GD.state_fold(8, 128) == 1
    assert GD.state_fold(3, 192) == 1 and GD.state_fold(4, 32) == 4
    St = jax.random.normal(jax.random.PRNGKey(0), (5, 30, 96, 192))
    S = GD.fold_state(St)
    assert S.shape == (5, 15, 96, 384)
    np.testing.assert_array_equal(np.asarray(GD.unfold_state(S, 192)),
                                  np.asarray(St))
    np.testing.assert_array_equal(np.asarray(S[2, 4, :, 192:]),
                                  np.asarray(St[2, 9]))


# ---------------------------------------------------------------------------
# the model through the engine
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n_prompt,fused", [(13, False), (100, False),
                                            (13, True)],
                         ids=["rung16", "rung128-two-chunks", "paged-kernel"])
def test_prefill_then_decode_against_the_references_forward_pass(
        cell, family, engine, n_prompt, fused):
    """13 tokens through a rung of 16 and 100 through a rung of 128 (two
    chunks of the delta rule, the second part padding), then 7 ticks:
    every row of logits the engine handed out against the plain model's
    row at that position; and bfloat16 weights fail the tolerance (the
    plain model with its weights rounded, the rehearsal's control), so it
    would catch the precision below. ``fused_decode`` drives the
    page-table kernel over the pool's padded head rows in interpret
    mode."""
    eng = _engine(cell, fused_decode=True) if fused else engine
    assert eng.kv_path == ("pallas_paged" if fused else "xla_gather")
    slot, fed, rows = _decode(eng, _prompt(n_prompt, n_prompt), 7)
    want = np.asarray(family.forward(cell.config, SEED,
                                     fed))[n_prompt - 1:]
    np.testing.assert_allclose(rows, want, **LOGIT_TOL)
    assert eng.cache.length(slot) == n_prompt + 7
    eng.free_sequence(slot)
    low = np.asarray(family.forward(cell.config, SEED, fed,
                                    held="bf16w"))[n_prompt - 1:]
    assert np.abs(low - want).max() > 20 * LOGIT_TOL["atol"]


def test_the_programs_plain_forward_is_the_references(cell, family):
    tokens = _prompt(70, 8)             # more than a chunk, not whole chunks
    got = O.forward(_WEIGHTS[0] if _WEIGHTS else family.program_weights(
        SEED, cell.config, jnp.float32), jnp.asarray(tokens, jnp.int32),
        _cfg(cell))
    want = family.forward(cell.config, SEED, tokens)
    np.testing.assert_allclose(got, want, **LOGIT_TOL)


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


def test_a_slot_reused_after_a_longer_sequence_starts_from_zero(cell):
    """The slot's rows and pages hold the last owner's when it is handed
    out again; the prefill writes the new state from an empty history."""
    eng = _engine(cell)                 # its state rows are all zero
    first, _, want = _decode(eng, _prompt(6, 4), 2)
    eng.free_sequence(first)
    dirty, _, _ = _decode(eng, _prompt(90, 3), 5)
    resets = eng.cache.state_resets
    eng.free_sequence(dirty)
    assert dirty == first and eng.cache.live_state_bytes() == 0
    assert np.abs(np.asarray(eng.cache.ssm[:, first])).max() > 0
    again, _, rows = _decode(eng, _prompt(6, 4), 2)
    assert again == first and eng.cache.state_resets == resets + 1
    assert (eng.cache.live_state_bytes()
            == eng.cache.state_bytes_per_slot > 0)
    np.testing.assert_array_equal(rows, want)


def test_state_geometry_is_the_models_rows_and_bytes(cell, engine):
    """A slot's rows as the model states them: the conv's last three
    inputs over q, k and v side by side in the cache's dtype, the matrix
    states float32 with heads folded into whole lane tiles."""
    s = cell.family.dims(cell.config)
    channels = 2 * s["Hk"] * s["dk"] + s["Hv"] * s["dv"]
    cache = engine.cache
    assert cache.conv.shape == (3, 4, 3 * channels)
    assert cache.ssm.shape == (3, 4, 1, s["dk"], s["Hv"] * s["dv"])
    assert cache.ssm.dtype == jnp.float32
    per_slot = 3 * (3 * channels * 4 + s["Hv"] * s["dk"] * s["dv"] * 4)
    assert cache.state_bytes_per_slot == per_slot
    assert cache.state_bytes_per_slot == cell.family.state_bytes_per_sequence(
        cell.config, conv_bytes=4)
    pools = sum(int(p.size) * 4 for p in cache.pools)
    assert cache.nbytes == pools + 4 * per_slot
    # the pool holds a token's heads flat in the lanes, none padded
    assert cache.pools[0].shape[-1] == s["KVH"] * s["hd"]
    assert engine.state_bytes([0, 2]) == 2 * per_slot


def test_scheduler_batches_the_model_and_the_spans_say_so(cell):
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
    assert ticks and all(t["state_slots"] == t["batch"]
                         and t["state_bytes"] == t["batch"] * per_slot
                         and t["kv_path"] == "xla_gather" for t in ticks)
    prefills = {s["attrs"]["prompt_len"]: s["attrs"] for s in tracer.spans()
                if s["name"] == "serve/prefill"}
    assert {n: (a["scan_tokens"], a["delta_chunks"])
            for n, a in prefills.items()} == {7: (7, 1), 70: (70, 2),
                                              3: (3, 1)}
    assert smetrics.m_state_resets.value == born + 3   # state rows born
    # each request against the plain model, greedy token by token
    for r in reqs[:2]:
        want = np.asarray(cell.family.forward(
            cell.config, SEED, list(r.prompt) + list(r.tokens)))
        picks = want[len(r.prompt) - 1:-1].argmax(axis=-1)
        assert list(picks) == list(r.tokens)
    assert eng.cache.live_state_bytes() == 0
    assert smetrics.m_state_bytes.value == 0


REFUSALS = [
    (dict(prefix_cache=True), "prefix cache"),
    (dict(verify_window=3), "verify window"),
    (dict(sharding="tp", tp=2), "tensor-parallel"),
    (dict(weight_dtype="int8"), "int8"),
    (dict(role="prefill"), "kv_transfer"),
]


@pytest.mark.parametrize("kw,mechanism", REFUSALS,
                         ids=[m for _, m in REFUSALS])
def test_what_cannot_carry_a_matrix_state_is_refused_as_for_mamba(
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
    assert (str(e.value).replace("OlmoHybridServing", "JambaServing")
            == str(mamba.value))


def test_hand_off_and_speculation_refuse_the_engine(engine):
    with pytest.raises(ValueError, match="speculative wrapper"):
        serving.SpecDecodeEngine(engine, engine)
    slot, _, _ = engine.start_sequence_sampled(_prompt(4, 9), GREEDY)
    with pytest.raises(ValueError, match="kv_transfer"):
        engine.export_request_kv(slot)
    engine.free_sequence(slot)
    with pytest.raises(ValueError, match="prefix_cache=False"):
        serving.DecodeEngine({}, O.OLMO_HYBRID_TINY, serving.EngineConfig())


def test_init_params_are_the_leaf_shapes_and_run():
    cfg = O.OLMO_HYBRID_TINY
    params = O.init_params(jax.random.PRNGKey(0), cfg)
    shapes = jax.tree_util.tree_map(lambda a: a.shape, params)
    assert shapes == O.leaf_shapes(cfg)
    assert cfg.segments() == [("linear", 0, 3), ("full", 0, 1)]
    assert O.OlmoHybridConfig().segments()[:3] == [
        ("linear", 0, 3), ("full", 0, 1), ("linear", 3, 3)]
    assert O.OlmoHybridServing(O.OlmoHybridConfig()).cache_pools["rows"] \
        == ((30 * 128,),) * 2            # flat: no padded head row
    logits = O.forward(params, jnp.arange(10, dtype=jnp.int32), cfg)
    assert logits.shape == (10, cfg.vocab_size)
    assert np.isfinite(np.asarray(logits)).all()
