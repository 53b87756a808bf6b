"""The trace reducer: hand-worked intervals, and the v5e recording kept
under benchmark/fixtures/ with the values written beside it."""
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import trace_reduce as T  # noqa: E402

FIX = os.path.join(ROOT, "benchmark", "fixtures")


def test_merge_intervals():
    assert T.merge_intervals([(5, 7), (1, 3), (2, 4), (7, 8), (9, 9)]) == \
        [(1, 4), (5, 8)]


def test_short_name_keeps_name_opcode_target_and_first_shape():
    text = ('%checkpoint.19 = (bf16[128,2048,128]{2,1,0:T(8,128)(2,1)}, '
            'bf16[128,2048,128]{2,1,0}) custom-call(bf16[128,2048,128]'
            '{2,1,0:T(8,128)(2,1)} %bitcast.522), '
            'custom_call_target="tpu_custom_call", frontend_attributes={}')
    assert T.short_name(text) == \
        "checkpoint.19 custom-call/tpu_custom_call bf16[128,2048,128]"
    assert T.short_name("%while.118 = (s32[]{:T(128)}, bf16[8,2048]{1,0}) "
                        "while((s32[]{:T(128)}) %tuple.352), condition=%c"
                        ) == "while.118 while s32[]"
    assert T.short_name("no instruction") == "no instruction"


def _profile():
    # one plane; a while of 10..50 holding two operations, then a gap,
    # then one more operation. Spans: step 0..55, fetch 55..100.
    ops = [("while.1 while", 10, 40), ("fusion.1 fusion", 10, 15),
           ("fusion.2 fusion", 30, 20), ("copy.1 copy", 70, 10)]
    ops.sort(key=lambda e: (e[1], -e[2]))
    return T.Profile({"/device:TPU:0": ops},
                     {"/device:TPU:0": [("jit_step(1)", 10, 70)]},
                     [("step", 0, 55), ("fetch", 55, 45)])


def test_busy_union_and_idle_share_by_hand():
    p = _profile()
    assert T.window(p) == (0, 100)
    assert T.busy_intervals(p, "/device:TPU:0") == [(10, 50), (70, 80)]
    busy, window = T.busy_seconds(p, chips=1)
    assert busy == pytest.approx(50e-9) and window == pytest.approx(100e-9)
    assert T.idle_share(p, 1) == pytest.approx(50.0)
    with pytest.raises(ValueError):
        T.busy_seconds(p, chips=4)


def test_self_times_take_a_loops_body_out_of_the_loop():
    by = T.durations_by_name(_profile())
    assert by["while.1 while"] == pytest.approx(5e-9)     # 40 - 15 - 20
    assert by["fusion.1 fusion"] == pytest.approx(15e-9)
    assert sum(by.values()) == pytest.approx(50e-9)       # = busy
    assert T.seconds_matching(_profile(), "fusion") == \
        (pytest.approx(35e-9), 2)


def test_idle_gaps_are_divided_over_the_spans_that_cover_them():
    gaps = T.idle_gaps(_profile())
    # 0..10 under step; 50..70: 5 under step, 15 under fetch; 80..100 fetch
    assert gaps == [("step", 0, 10), ("step", 50, 5), ("fetch", 55, 15),
                    ("fetch", 80, 20)]
    b = T.breakdown(_profile())
    assert b["idle_gaps"] == [["fetch", pytest.approx(35e-9)],
                              ["step", pytest.approx(15e-9)]]
    assert b["device_ops"][0] == ["fusion.2 fusion", pytest.approx(20e-9)]
    no_span = T.Profile({"/device:TPU:0": [("a", 0, 1), ("b", 5, 1)]},
                        {}, [])
    assert T.idle_gaps(no_span) == [(T.OUTSIDE, 1, 4)]
    # a span that covers the middle of a gap leaves both ends outside
    part = T.Profile({"/device:TPU:0": [("a", 0, 1), ("b", 9, 1)]}, {},
                     [("s", 3, 4)])
    assert T.idle_gaps(part) == [(T.OUTSIDE, 1, 2), ("s", 3, 4),
                                 (T.OUTSIDE, 7, 2)]


@pytest.mark.parametrize("spans,want", [
    # nested: the inner one owns its stretch, the outer one the rest
    ([("outer", 0, 100), ("inner", 20, 30)],
     [(0, 20, "outer"), (20, 50, "inner"), (50, 100, "outer")]),
    # three deep, the innermost ending with its parent
    ([("a", 0, 100), ("b", 10, 80), ("c", 40, 50)],
     [(0, 10, "a"), (10, 40, "b"), (40, 90, "c"), (90, 100, "a")]),
    # two threads' spans that overlap without nesting: the shorter wins
    # where both cover
    ([("long", 0, 60), ("short", 50, 30)],
     [(0, 50, "long"), (50, 80, "short")]),
    # neighbours of one name that touch are one stretch; a hole stays one
    ([("t", 0, 10), ("t", 10, 10), ("u", 30, 5)],
     [(0, 20, "t"), (30, 35, "u")]),
    ([("empty", 5, 0)], []),
], ids=["nested", "three_deep", "overlap", "neighbours", "empty"])
def test_innermost_timeline(spans, want):
    assert T.innermost_timeline(spans) == want


def test_seconds_matching_by_the_head_of_the_name():
    ops = [("flash_fwd.17 custom-call/tpu_custom_call bf16[128,2048,128]",
            0, 10),
           ("scan_kernel.3 custom-call/tpu_custom_call bf16[128,2048,128]",
            10, 7),
           ("flash_bwd_dq.2 custom-call/tpu_custom_call bf16[128,2048,128]",
            20, 5),
           ("not_flash_.1 fusion bf16[8]", 30, 1)]
    p = T.Profile({"/device:TPU:0": ops}, {}, [])
    call = "custom-call/tpu_custom_call"
    assert T.seconds_matching(p, call) == (pytest.approx(22e-9), 3)
    assert T.seconds_matching(p, call, head="flash_") == (
        pytest.approx(15e-9), 2)
    assert T.seconds_matching(p, head="flash_bwd") == (
        pytest.approx(5e-9), 1)


def test_busy_over_the_window_is_refused():
    # two operations that overlap on one line are merged, never summed
    p = T.Profile({"/device:TPU:0": [("a", 0, 10), ("b", 5, 10)]}, {}, [])
    busy, window = T.busy_seconds(p)
    assert busy == window == pytest.approx(15e-9)


def test_recorded_v5e_trace_reproduces_the_values_beside_it():
    p = T.Profile.from_file(os.path.join(FIX, "v5e_train_two_steps.json.gz"))
    with open(os.path.join(FIX, "v5e_train_two_steps.expected.json")) as f:
        want = json.load(f)
    busy, window = T.busy_seconds(p, chips=1)
    assert busy <= window
    assert busy == pytest.approx(want["busy_s"], rel=1e-9)
    assert window == pytest.approx(want["window_s"], rel=1e-9)
    assert T.idle_share(p, 1) == pytest.approx(want["idle_share_percent"])
    flash, n = T.seconds_matching(p, "custom-call/tpu_custom_call",
                                  "[128,2048,128]")
    assert flash == pytest.approx(want["flash_kernel_seconds"], rel=1e-9)
    # 6 layers x (forward, recomputed forward, two backward kernels) x 2
    assert n == want["flash_kernel_events"] == 48
    by = T.durations_by_name(p)
    assert sum(by.values()) == pytest.approx(busy, rel=1e-9)
    top = sorted(by.items(), key=lambda kv: -kv[1])[:3]
    assert [k for k, _ in top] == [k for k, _ in want["top_operations"]]
    assert [m[2] / 1e9 for m in p.modules["/device:TPU:0"]] == \
        pytest.approx(want["module_seconds"])
    assert len(p.devices["/device:TPU:0"]) == want["events"]
    # no gap of this recording straddles two spans, so the division over
    # the spans that cover a gap gives what the span at its middle got
    assert T.breakdown(p)["idle_gaps"] == [
        [name, pytest.approx(s)] for name, s in want["idle_gaps_by_span"]]
