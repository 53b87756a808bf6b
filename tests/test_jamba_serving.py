"""A hybrid model through ``DecodeEngine`` + ``Scheduler`` at the cell's
rehearsal sizes on the CPU: prefill then decoding through the caches against
the plain reference's full forward pass (``benchmark/families/jamba.py``,
which imports nothing of the program) on the same seeded weights, logits
and not tokens; what a slot's recurrent state does when the slot sits out,
is freed, or is preempted; and every refusal, by the mechanism's name."""
import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import serving
from paddle_tpu.models import jamba as J
from paddle_tpu.serving.sampling import GREEDY

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402

SEED = 2 ** 31 + 29
# float32 program against a float32 reference that sums in another order
# (stacked layers, a transposed state, a cache between the tokens): the
# logits' root-mean-square difference over their own spread reads 1.5e-7
# (my CPU runs, PR 29); bfloat16 weights read 3e-3. Per element, logits of
# order 0.3: 2e-5 absolute is 60 float32 roundings and 1/50 of bfloat16's
LOGIT_TOL = dict(rtol=0, atol=2e-5)


@pytest.fixture(scope="module")
def cell():
    return harness.Cell(ROOT, "serve_jamba2_3b_closed48", rehearsal=True)


@pytest.fixture(scope="module")
def family(cell):
    return cell.family


def _cfg(cell):
    s = cell.family.dims(cell.config)
    return J.JambaConfig(
        vocab_size=s["V"], hidden_size=s["D"], intermediate_size=s["F"],
        num_hidden_layers=s["L"], num_attention_heads=s["H"],
        num_key_value_heads=s["KVH"], head_dim=s["hd"],
        attn_layer_period=cell.config["attn_layer_period"],
        attn_layer_offset=cell.config["attn_layer_offset"],
        mamba_d_state=s["N"], mamba_d_conv=s["K"],
        mamba_expand=cell.config["mamba_expand"], mamba_dt_rank=s["R"],
        dtype=jnp.float32)


_WEIGHTS = []


def _engine(cell, **kw):
    ecfg = dict(max_batch=4, max_seq=64, page_size=8,
                prefix_cache=False, prefill_buckets=(8, 16, 32))
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


def test_prefill_then_decode_against_the_references_forward_pass(
        cell, family, engine):
    """13 tokens through a rung of 16 (padding), then 7 ticks: every row
    of logits the engine handed out against the plain model's row at that
    position; and bfloat16 weights fail the tolerance (the plain model
    with its weights rounded, the rehearsal's control), so it would catch
    the precision below."""
    slot, fed, rows = _decode(engine, _prompt(13), 7)
    want = np.asarray(family.forward(cell.config, SEED, fed))[12:]
    np.testing.assert_allclose(rows, want, **LOGIT_TOL)
    assert engine.cache.length(slot) == 20
    engine.free_sequence(slot)
    low = np.asarray(family.forward(cell.config, SEED, fed,
                                    held="bf16w"))[12:]
    assert np.abs(low - want).max() > 20 * LOGIT_TOL["atol"]


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


def test_a_freed_and_reallocated_slot_starts_from_zero(cell, engine):
    """The slot's rows hold the last owner's state when it is handed out
    again; the prefill writes the new state from an empty history."""
    eng = _engine(cell)                 # its state rows are all zero
    first, _, want = _decode(eng, _prompt(6, 4), 2)
    eng.free_sequence(first)
    dirty, _, _ = _decode(eng, _prompt(11, 3), 2)
    resets = eng.cache.state_resets
    eng.free_sequence(dirty)
    assert dirty == first and eng.cache.live_state_bytes() == 0
    assert np.abs(np.asarray(eng.cache.ssm[:, first])).max() > 0
    again, _, rows = _decode(eng, _prompt(6, 4), 2)
    assert again == first and eng.cache.state_resets == resets + 1
    assert (eng.cache.live_state_bytes()
            == eng.cache.state_bytes_per_slot > 0)
    np.testing.assert_array_equal(rows, want)


def test_a_preempted_request_resumes_to_the_same_logits(cell, engine):
    """``resume_sequence_sampled`` re-prefills prompt plus generated
    tokens from nothing, also past the ladder's top rung (the tail then
    replays through the tick, which advances the state)."""
    slot, fed, rows = _decode(engine, _prompt(30, 5), 6)
    engine.free_sequence(slot)                     # preempted
    resumed, logits, tok = engine.resume_sequence_sampled(fed, GREEDY)
    assert len(fed) == 36 > engine.buckets[-1]
    np.testing.assert_allclose(logits, rows[-1], **LOGIT_TOL)
    assert tok == int(np.argmax(rows[-1]))
    engine.free_sequence(resumed)


def test_scheduler_batches_a_hybrid_model_and_the_spans_say_so(cell):
    from paddle_tpu.observability import spans

    eng = _engine(cell)
    tracer = spans.default_tracer()
    tracer.clear()
    sched = serving.Scheduler(eng)
    reqs = [sched.submit(_prompt(n, n), max_new_tokens=m)
            for n, m in ((7, 5), (12, 3), (3, 6))]  # 12 + 3 = 7 + 5
    for _ in range(32):
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
    assert {n: a["scan_tokens"] for n, a in prefills.items()} == {
        7: 7, 12: 12, 3: 3}
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
def test_what_cannot_carry_recurrent_state_is_refused_by_name(
        cell, kw, mechanism):
    ecfg = dict(prefix_cache=False)
    ecfg.update(kw)
    with pytest.raises(ValueError, match="recurrent") as e:
        serving.DecodeEngine({}, _cfg(cell), serving.EngineConfig(**ecfg))
    assert mechanism in str(e.value)


def test_speculative_wrapper_and_kv_transfer_refuse_a_hybrid_engine(engine):
    with pytest.raises(ValueError, match="speculative wrapper"):
        serving.SpecDecodeEngine(engine, engine)
    slot, _, _ = engine.start_sequence_sampled(_prompt(4, 9), GREEDY)
    with pytest.raises(ValueError, match="kv_transfer"):
        engine.export_request_kv(slot)
    with pytest.raises(ValueError, match="kv_transfer"):
        engine.adopt_request_kv({})
    with pytest.raises(ValueError, match="kv_transfer"):
        engine.cache.adopt_slot(8, [1])
    engine.free_sequence(slot)
    # the default engine (prefix_cache=True) is refused, not quietly fixed
    with pytest.raises(ValueError, match="prefix_cache=False"):
        serving.DecodeEngine({}, J.JAMBA_TINY, serving.EngineConfig())
