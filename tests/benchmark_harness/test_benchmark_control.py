"""``correct`` has to be able to fail. Two ways it is shown to, at a size a
test run can hold (the rehearsal sizes, float32, whose limits were read on
the CPU as the workload files say):

* the control: the precision below the stated one, switched on, comes out
  not correct in both cells;
* the timed path broken underneath: a train step that returns its state
  unchanged, one that steps a tenth too far, a batch with rows left out, a
  served token altered where it is produced.
"""
import io
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402

TRAIN, SERVE = "train_cgpt1p3b_l6_1chip", "serve_cgpt1p3b_closed14"


def _run(cell, seed, control=False, patch=None, seconds=1.0):
    """The rest of a run after the harness's look for a chip."""
    out = io.StringIO()
    real = harness.Cell

    def patched(*a, **k):
        c = real(*a, **k)
        if patch is not None:
            patch(c)
        return c

    harness.Cell = patched
    try:
        result = harness.run_cell(ROOT, cell, seed, seconds, 0,
                                  rehearsal=True, control=control, out=out)
    finally:
        harness.Cell = real
    failed = [x for x in out.getvalue().splitlines() if "FAILED" in x]
    return result, failed


@pytest.mark.parametrize("seed", [11, 12, 2 ** 31 + 13])
@pytest.mark.parametrize("cell", [TRAIN, SERVE])
def test_control_comes_out_not_correct(cell, seed):
    # the serving number moves only where the lower precision flips a
    # greedy token, about one in a hundred at this size: give the window
    # the seconds that some hundreds of served tokens take on a busy CPU
    result, failed = _run(cell, seed, control=True,
                          seconds=5.0 if cell == SERVE else 1.0)
    assert result["correct"] is False
    assert failed, "no compared number was over its limit"


@pytest.mark.parametrize("cell", [TRAIN, SERVE])
def test_sound_run_is_correct(cell):
    result, failed = _run(cell, 14)
    assert result["correct"] is True and not failed


def test_train_step_that_returns_its_state_unchanged_is_caught():
    def patch(cell):
        build = cell.family.build

        def broken(*a, **k):
            program = build(*a, **k)
            inner = program._step

            def frozen(params, opt, tokens, labels):
                import jax

                keep = jax.tree_util.tree_map(lambda x: x + 0, params)
                _, new_opt, loss, gnorm = inner(params, opt, tokens, labels)
                return keep, new_opt, loss, gnorm

            program._step = frozen
            return program

        cell.family.build = broken

    result, failed = _run(TRAIN, 15, patch=patch)
    assert result["correct"] is False
    assert any("change_leaf_gap" in x for x in failed)


def test_train_step_with_a_learning_rate_a_tenth_off_is_caught():
    # every leaf then moves a tenth too far: the worst-leaf rule may not
    # hide that behind a leaf whose own rounding is as large (the key bias
    # read 0.10 in every sound run on the chip, and is left out for it)
    def patch(cell):
        build = cell.family.build

        def broken(config, *a, **k):
            training = dict(config["training"],
                            lr=1.1 * config["training"]["lr"])
            return build(dict(config, training=training), *a, **k)

        cell.family.build = broken

    result, failed = _run(TRAIN, 18, patch=patch)
    assert result["correct"] is False
    assert any("change_leaf_gap" in x for x in failed)
    assert not any("loss_rel_gap.step1" in x for x in failed)


def test_the_key_bias_is_left_out_of_the_compared_norms():
    import numpy as np

    fam = harness.Cell(ROOT, TRAIN).family
    b = np.arange(2 * 3 * 4, dtype=np.float32).reshape(2, 12)
    kept = np.asarray(fam.compared_part("c_attn_b", b))
    assert kept.shape == (2, 2, 4)
    assert kept[0].tolist() == [[0, 1, 2, 3], [8, 9, 10, 11]]   # q and v
    same = np.asarray(fam.compared_part(
        "c_attn_b", b.reshape(2, 3, 2, 2)))           # the program's layout
    assert (same == kept).all()
    assert fam.compared_part("c_fc_b", b) is b


def test_part_of_the_batch_left_out_is_caught():
    def patch(cell):
        build = cell.family.build

        def broken(*a, **k):
            program = build(*a, **k)
            inner = program._step

            def half(params, opt, tokens, labels):
                n = tokens.shape[1] // 2
                return inner(params, opt, tokens[:, :n], labels[:, :n])

            program._step = half
            return program

        cell.family.build = broken

    result, failed = _run(TRAIN, 16, patch=patch)
    assert result["correct"] is False
    assert any("loss_rel_gap" in x or "first_grad" in x for x in failed)


def test_served_token_altered_where_it_is_produced_is_caught():
    def patch(cell):
        build = cell.family.build

        def broken(*a, **k):
            program = build(*a, **k)
            eng = program.engine
            inner = eng.decode_step_sampled
            vocab = program.vocab_size

            def decode(slot_tokens, params_by_slot):
                out = inner(slot_tokens, params_by_slot)
                return {s: ((tok + 1) % vocab, logits)
                        for s, (tok, logits) in out.items()}

            eng.decode_step_sampled = decode
            return program

        cell.family.build = broken

    result, failed = _run(SERVE, 17, patch=patch)
    assert result["correct"] is False
    assert any("served_logit_gap" in x for x in failed)
    assert result["failed"] == 0        # the requests themselves went well


def test_logits_computed_in_a_lower_precision_are_caught_by_the_tap():
    # the tokens stay what they were (the gaps read 0): only the logits the
    # timed path hands out show the rounding
    def patch(cell):
        build = cell.family.build

        def broken(*a, **k):
            import jax.numpy as jnp
            import numpy as np

            program = build(*a, **k)
            eng = program.engine
            inner = eng.decode_step_sampled

            def decode(slot_tokens, params_by_slot):
                out = inner(slot_tokens, params_by_slot)
                return {s: (tok, np.asarray(jnp.asarray(logits).astype(
                    jnp.bfloat16).astype(jnp.float32)))
                    for s, (tok, logits) in out.items()}

            eng.decode_step_sampled = decode
            return program

        cell.family.build = broken

    result, failed = _run(SERVE, 19, patch=patch)
    assert result["correct"] is False
    assert [x for x in failed if "served_logits_rel_rms" in x]
    assert not [x for x in failed if "served_logit_gap" in x]


def test_limits_are_written_with_the_readings_they_were_set_from():
    for cell in (TRAIN, SERVE):
        spec = harness.load_json(os.path.join(ROOT, "benchmark",
                                              "workloads", cell + ".json"))
        assert spec["limits_from"] and spec["rehearsal"]["limits_from"]
        assert set(spec["limits"]) == set(spec["rehearsal"]["limits"])
