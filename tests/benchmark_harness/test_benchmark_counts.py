"""The yardstick's arithmetic against hand-worked cases."""
import os
import statistics
import sys
import types

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import harness, stats  # noqa: E402

GPT2 = harness.load_module(os.path.join(ROOT, "benchmark", "families",
                                        "gpt2.py"))
CLOSED = harness.load_module(os.path.join(
    ROOT, "benchmark", "traffic_kinds", "serve_closed_loop.py"))
TRAIN = harness.load_module(os.path.join(
    ROOT, "benchmark", "traffic_kinds", "train_fixed_batch.py"))


@pytest.mark.parametrize("values,q,want", [
    ([1, 2, 3, 4, 5], 50, 3.0),
    ([1, 2, 3, 4], 50, 2.5),
    ([10, 20, 30, 40, 50], 95, 48.0),      # pos 3.8: 40 + 0.8 * 10
    ([5, 1, 3], 0, 1.0),
    ([5, 1, 3], 100, 5.0),
    ([7], 95, 7.0),
])
def test_percentile(values, q, want):
    assert stats.percentile(values, q) == pytest.approx(want)
    assert stats.percentile(values, q) == pytest.approx(
        np.percentile(values, q))


def test_percentile_refuses_nothing_and_nonsense():
    with pytest.raises(ValueError):
        stats.percentile([], 50)
    with pytest.raises(ValueError):
        stats.percentile([1], 101)


def test_quartile_spread_is_the_contracts():
    values = [100, 101, 102, 103, 104, 110]
    q1, _, q3 = statistics.quantiles(values, n=4)
    assert stats.quartile_spread(values) == pytest.approx(
        (q3 - q1) / 102.5)
    # numpy's quartiles lie closer together: not what the contract uses
    assert (np.percentile(values, 75) - np.percentile(values, 25)) < q3 - q1


def test_worst_leaf_gap_measures_against_the_larger_of_leaf_and_median():
    ref = {"a": 10.0, "b": 1.0, "c": 1e-9}
    prog = {"a": 10.5, "b": 1.2, "c": 0.1}
    gap, leaf = stats.worst_leaf_gap(prog, ref)
    # median leaf norm is 1.0: c's gap is 0.1/1.0, b's 0.2/1.0, a's 0.05
    assert leaf == "b" and gap == pytest.approx(0.2)


# -- counts from the shapes -------------------------------------------------

TINY = {"n_embd": 8, "n_layer": 2, "n_head": 2, "n_inner": 32,
        "vocab_size": 50, "n_positions": 16}


def test_parameter_counts_by_hand():
    d, f, v, p, layers = 8, 32, 50, 16, 2
    block = (d * 3 * d + 3 * d) + (d * d + d) + (d * f + f) + (f * d + d) \
        + 4 * d
    assert GPT2.param_count(TINY) == layers * block + v * d + p * d \
        + d * v + 2 * d
    matmul = layers * (3 * d * d + d * d + 2 * d * f) + d * v
    assert GPT2.matmul_param_count(TINY) == matmul


def test_flops_per_token_by_hand():
    d, layers, t = 8, 2, 16
    matmul = GPT2.matmul_param_count(TINY)
    # causal attention: QK^T and AV are 2*d*T each at the full square,
    # half of it on average, three times that with the backward pass
    attn_fwd = layers * 2 * d * t
    assert GPT2.attention_flops_per_token(TINY, t) == attn_fwd
    assert GPT2.flops_per_token(TINY, t) == 6 * matmul + 3 * attn_fwd


def test_cerebras_1p3b_counts():
    cfg = harness.load_json(os.path.join(
        ROOT, "benchmark", "configs", "cerebras-gpt-1.3b.json"))
    # 24 * 12 * 2048^2 in the blocks' matrices, 2048 * 50257 in lm_head
    assert GPT2.matmul_param_count(cfg) == 24 * 12 * 2048 ** 2 \
        + 2048 * 50257
    assert 1.41e9 < GPT2.param_count(cfg) < 1.42e9   # untied lm_head


def test_bytes_per_decode_step_by_hand():
    matmul = GPT2.matmul_param_count(TINY)
    # two live slots of 3 and 5 tokens: keys and values, 2 layers, d=8
    cache = (3 + 5) * 2 * 2 * 8 * 2
    assert GPT2.bytes_per_decode_step(TINY, [3, 5]) == matmul * 2 + cache
    assert GPT2.bytes_per_decode_step(TINY, [], weight_bytes=1) == matmul


# -- traffic ------------------------------------------------------------------

TRAFFIC = {"pool": 32, "pairing_stride": 7,
           "prompt_len": {"median": 320, "sigma": 0.7, "min": 64,
                          "max": 1536},
           "output_len": {"median": 64, "sigma": 0.6, "min": 16,
                          "max": 256}}


def test_pool_is_the_same_for_every_seed_and_within_its_clips():
    pool = CLOSED.make_pool(TRAFFIC)
    assert pool == CLOSED.make_pool(TRAFFIC) and len(pool) == 32
    prompts = sorted(p for p, _ in pool)
    outputs = sorted(o for _, o in pool)
    assert 64 <= prompts[0] and prompts[-1] <= 1536
    assert 16 <= outputs[0] and outputs[-1] <= 256
    assert abs(statistics.median(prompts) - 320) < 10
    assert abs(statistics.median(outputs) - 64) < 3
    # evenly mixed: every quarter of the cycle carries a like share
    quarters = [sum(p for p, _ in pool[i:i + 8]) for i in range(0, 32, 8)]
    assert max(quarters) < 1.5 * min(quarters)
    with pytest.raises(ValueError):
        CLOSED.make_pool(dict(TRAFFIC, pool=24))
    with pytest.raises(ValueError):
        CLOSED.make_pool(dict(TRAFFIC, pairing_stride=6))


def test_plans_of_two_seeds_send_the_same_cycle_from_places_of_their_own():
    a = CLOSED.Plan(TRAFFIC, 1, 1000)
    b = CLOSED.Plan(TRAFFIC, 2 ** 31 + 11, 1000)
    assert 0 <= a.start < 32 and 0 <= b.start < 32
    sent_a = [a.next() for _ in range(40)]
    sent_b = [b.next() for _ in range(40)]
    for plan, sent in ((a, sent_a), (b, sent_b)):
        sizes = [(len(p), n) for p, n in sent]
        turned = plan.pool[plan.start:] + plan.pool[:plan.start]
        assert sizes[:32] == turned and sizes[32:] == turned[:8]  # a cycle
        assert sorted(sizes[:32]) == sorted(a.pool)     # the same set
    assert sent_a[0][0] != sent_b[0][0]                 # other tokens
    again = CLOSED.Plan(TRAFFIC, 1, 1000)
    assert [again.next() for _ in range(40)] == sent_a
    assert all(0 <= t < 1000 for p, _ in sent_a for t in p)
    # the seeds between them enter the cycle in many places
    starts = {CLOSED.Plan(TRAFFIC, seed, 1000).start
              for seed in range(2 ** 31, 2 ** 31 + 64)}
    assert len(starts) > 16


def _req(submitted, times, state="done", tokens=None):
    return types.SimpleNamespace(
        submitted=submitted, token_times=list(times), state=state,
        tokens=list(tokens if tokens is not None else [1] * len(times)))


def test_closed_loop_bookkeeping_by_hand():
    # window [10, 20)
    records = [
        # sent before the window, still answering inside it: its tokens
        # inside count, its first token and itself do not
        (_req(8.0, [9.0, 10.5, 11.0]), 3),
        # sent inside, done inside
        (_req(12.0, [12.2, 12.3, 12.5]), 3),
        # sent inside, last token after the close: a drained request
        (_req(19.0, [19.5, 20.5]), 2),
        # sent inside, failed
        (_req(15.0, [15.1], state="failed"), 4),
        # sent inside, a token outside the vocabulary
        (_req(16.0, [16.1, 16.2], tokens=[1, 99]), 2),
    ]
    s = CLOSED.summarise(records, 10.0, 20.0, vocab_size=50)
    assert s["attempted"] == 4 and s["failed"] == 2
    assert s["tokens_in_window"] == 2 + 3 + 1 + 1 + 2
    assert sorted(round(x) for x in s["ttft_ms"]) == [100, 100, 200, 500]
    # gaps that end inside the window: 1500, 500 | 100, 200 | 100
    assert sorted(round(x) for x in s["gap_ms"]) == [100, 100, 200, 500,
                                                     1500]


class _Engine:
    """The three entries the tap wraps, handing out recognisable logits."""

    def __init__(self, hand_out=True):
        self.hand_out, self.calls = hand_out, 0

    def _logits(self, mark):
        return np.full((10,), mark, np.float32) if self.hand_out else None

    def start_sequence_sampled(self, tokens, params):
        self.calls += 1
        return len(tokens) % 4, self._logits(100 * len(tokens)), 7

    def resume_sequence_sampled(self, tokens, params):
        return len(tokens) % 4, self._logits(-1), 7

    def decode_step_sampled(self, slot_tokens, params_by_slot):
        self.calls += 1
        return {s: (7, self._logits(self.calls)) for s in slot_tokens}


def test_logit_tap_follows_each_request_through_the_engines_calls():
    eng = _Engine()
    tap = CLOSED.LogitTap(eng, [2, 5])
    a = types.SimpleNamespace(prompt=[1] * 5, tokens=[7, 7, 7])   # slot 1
    b = types.SimpleNamespace(prompt=[2] * 6, tokens=[7, 7])      # slot 2
    c = types.SimpleNamespace(prompt=[3] * 9, tokens=[7, 7])      # slot 1 too
    eng.start_sequence_sampled(a.prompt, None)          # call 1
    eng.decode_step_sampled({1: 7}, None)               # call 2
    eng.start_sequence_sampled(b.prompt, None)          # call 3
    eng.decode_step_sampled({1: 7, 2: 7}, None)         # call 4: a is done
    eng.start_sequence_sampled(c.prompt, None)          # call 5, a's slot
    eng.decode_step_sampled({1: 7}, None)               # call 6
    assert tap.of(a).tolist() == [[500, 500], [2, 2], [4, 4]]
    assert tap.of(b).tolist() == [[600, 600], [4, 4]]
    assert tap.of(c).tolist() == [[900, 900], [6, 6]]
    assert tap.of(a).dtype == np.float32
    # not one row a token: nothing to compare for that request
    assert tap.of(types.SimpleNamespace(prompt=b.prompt, tokens=[7])) is None
    assert tap.of(types.SimpleNamespace(prompt=[9], tokens=[7])) is None
    # a resumed request is let go
    eng.resume_sequence_sampled(c.prompt + [7], None)   # 10 tokens: slot 2
    eng.decode_step_sampled({2: 7}, None)
    assert tap.dropped == 1 and len(tap.streams[tap.key(b.prompt)]) == 2
    # an engine that hands out no logits leaves nothing to compare
    quiet = _Engine(hand_out=False)
    tap = CLOSED.LogitTap(quiet, [2, 5])
    quiet.start_sequence_sampled(a.prompt, None)
    quiet.decode_step_sampled({1: 7}, None)
    assert tap.of(types.SimpleNamespace(prompt=a.prompt,
                                        tokens=[7, 7])) is None


def test_logits_rel_rms_by_hand():
    want = np.array([[1.0, -1.0], [3.0, 1.0]])
    assert CLOSED.logits_rel_rms(want.astype(np.float32), want) == 0.0
    # a whole row shifted moves no token and counts for nothing
    assert CLOSED.logits_rel_rms(want + [[5.0], [0.0]], want) == 0.0
    got = want + [[0.1, -0.1], [0.0, 0.0]]
    # difference about its row mean: 0.1, -0.1, 0, 0; want about its: 1 x 4
    assert CLOSED.logits_rel_rms(got, want) == pytest.approx(
        (0.02 / 4) ** 0.5)


def test_train_batches_differ_row_by_row_and_follow_the_seed():
    tr = {"batches": 4, "batch": 3, "seq_len": 8}
    a = TRAIN.make_batches(2 ** 31 + 5, tr, 100)
    b = TRAIN.make_batches(2 ** 31 + 5, tr, 100)
    c = TRAIN.make_batches(6, tr, 100)
    assert len(a) == 4 and a[0][0].shape == (1, 3, 8)
    assert all((x[0] == y[0]).all() and (x[1] == y[1]).all()
               for x, y in zip(a, b))
    assert not (a[0][0] == c[0][0]).all()
    tokens, labels = a[0]
    assert (tokens[..., 1:] == labels[..., :-1]).all()   # next token
    rows = {tuple(r) for t, _ in a for r in t[0]}
    assert len(rows) == 12


def test_sweep_report_arithmetic(capsys):
    sweep = harness.load_module(os.path.join(ROOT, "benchmark", "sweep.py"))
    a = [100.0, 101.0, 102.0, 103.0, 104.0, 120.0]
    assert sweep.spread(a) == pytest.approx(stats.quartile_spread(a))
    assert sweep.trimmed(a) == a[:5]            # the far-off run goes
    rows = [{"set": k, "seed": i, "result": {"metrics": {
        "rate": {"value": v + k, "unit": "x/s"}}}}
        for k in (0, 1) for i, v in enumerate(a)]
    sweep.report(rows)
    line = capsys.readouterr().out.strip()
    widest = max(sweep.spread(a), sweep.spread([v + 1 for v in a]))
    assert f"rule 5x widest = {500 * widest:.2f}%" in line
    tight = (sweep.spread(a[:5]) + sweep.spread([v + 1 for v in a[:5]])) / 2
    assert f"admitted {200 * tight:.2f}%..{800 * widest:.2f}%" in line
    assert "set1 vs set0 +0.976%" in line       # medians 102.5 -> 103.5
