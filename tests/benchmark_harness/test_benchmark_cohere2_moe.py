"""The fifth family's cell, ``serve_command_a_plus_ep8_closed24``: the cell
as the issue names it, the ladder against the cycle's sizes, the cell's
rehearsal in process with its controls, the manifest's checks on the tree,
and the four readers this cell brought, on planted records. (The source's
sizes are pinned in ``tests/test_cohere2_moe.py``.)"""
import io
import json
import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import checks, harness  # noqa: E402

CELL = "serve_command_a_plus_ep8_closed24"
NEW = ("swa_moe_decode_step_roofline", "gqa_window_paged_decode_roofline",
       "window_flash_prefill_roofline", "kv_pages_held_over_one_table")


@pytest.fixture(scope="module")
def cell():
    return harness.Cell(ROOT, CELL)


def _reader(name):
    return harness.load_module(os.path.join(
        ROOT, "benchmark", "layer_metrics", name + ".py"))


def test_the_cell_is_the_one_the_issue_names(cell):
    tr = cell.traffic
    assert (tr["kind"], tr["clients"], tr["pool"], tr["poll_s"],
            tr["pairing_stride"]) == ("serve_closed_loop", 24, 32, 0.001, 7)
    assert tr["prompt_len"] == {"median": 6144, "sigma": 0.8, "min": 512,
                                "max": 16384}
    assert tr["output_len"] == {"median": 512, "sigma": 0.5, "min": 128,
                                "max": 1024}
    engine = cell.config["serving"]["engine"]
    assert (engine["max_batch"], engine["max_seq"]) == (32, 17408)
    assert engine["prefix_cache"] is False
    assert engine["weight_dtype"] == "bf16"
    # the full group holds about 320k tokens
    assert (engine["num_pages"] - 1) * engine["page_size"] == 320_000
    sizes = cell.kind.make_pool(tr)
    assert max(p + o for p, o in sizes) <= max(tr["reference_pads"])
    assert max(p + o for p, o in sizes) <= engine["max_seq"]
    assert max(o for _, o in sizes) <= tr["reference_rows"]
    window = cell.config["sliding_window"]
    beyond = [p for p, _ in sizes if p > window + engine["page_size"]]
    assert len(beyond) == 22                     # 69 % longer than the window
    ladder = engine["prefill_buckets"]
    assert len(ladder) <= 8
    assert max(p for p, _ in sizes) == ladder[-1] == 16384
    # every rung whole pages, whole flash blocks and whole expert chunks
    assert all(r % 512 == 0 and r % engine["page_size"] == 0
               for r in ladder)
    # no rung edge at the cycle's median prompt: the middle third of the
    # sizes lies inside one rung and its neighbour
    prompts = sorted(p for p, _ in sizes)
    rung = lambda n: min(r for r in ladder if r >= n)
    assert rung(prompts[15]) == rung(prompts[16]) == 7168
    assert all(abs(r - 6144) > 512 for r in ladder)
    reported = {m["name"] for g in ("end_to_end", "per_layer")
                for m in cell.metrics(g)}
    assert set(NEW) | {"moe_grouped_matmul_roofline",
                       "moe_expert_load_max_over_mean", "serve_tick_ms",
                       "device_idle.serve", "sched_occupancy", "gap_p95_ms",
                       "recompiles_in_window", "serve_tokens_per_s",
                       "ttft_p50_ms", "gap_p90_ms", "setup_s"} <= reported
    assert not {"decode_step_roofline", "moe_mla_decode_step_roofline",
                "mla_paged_decode_roofline"} & reported
    for m in cell.manifest["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL]
    # limits written with their readings
    assert "PLACEHOLDER" not in json.dumps(cell.spec)


@pytest.mark.parametrize("check", checks.MANIFEST_CHECKS,
                         ids=lambda c: c.__name__)
def test_manifest_checks_hold_on_the_tree(check):
    check(ROOT)


def test_program_span_readers_are_in_the_manifest_by_name():
    checks.program_span_readers_are_in_the_manifest_by_name(ROOT)


def _rehearse(control=False, trace=0, **over):
    if over:                    # a rehearsal of the cell with another control
        orig = harness.Cell.__init__

        def init(self, *a, **kw):
            orig(self, *a, **kw)
            for k, v in over.items():
                setattr(self, k, v)
        harness.Cell.__init__ = init
    try:
        return harness.run_cell(ROOT, CELL, 2 ** 31 + 5, 1.0, trace,
                                rehearsal=True, control=control,
                                out=io.StringIO())
    finally:
        if over:
            harness.Cell.__init__ = orig


def test_rehearsal_is_correct_and_both_controls_are_not():
    sound = _rehearse(trace=1)
    assert sound["correct"] and sound["failed"] == 0
    assert sound["attempted"] >= 4      # a slow host sends few in 1 s
    assert sound["device"]["platform"] == "cpu"
    assert sound["checks"]["served_logits_rel_rms"]["value"] < 1e-5
    # traced, with no device plane: the three device readers this cell
    # brought return None and the line leaves them out, as it does for a
    # parent that lacks the kernels; the counter reader reads the ticks
    got = sound["metrics"]
    assert not [name for name in got if name.endswith("_roofline")]
    assert 0.3 < got["kv_pages_held_over_one_table"]["value"] < 1.0
    assert {"serve_tick_ms", "serve_prefill_share",
            "moe_expert_load_max_over_mean"} <= set(got)
    json.dumps(sound)
    # the rehearsal's control: the reference with bfloat16 weights
    low = _rehearse(control=True)
    assert not low["correct"]
    assert low["checks"]["served_logits_rel_rms"]["value"] > 1e-3
    # the cell's own control at rehearsal size: int8 weights
    int8 = _rehearse(control=True, control_precision="int8w")
    assert not int8["correct"]
    assert (int8["checks"]["served_logits_rel_rms"]["value"]
            > low["checks"]["served_logits_rel_rms"]["value"])


def test_traced_rehearsal_reports_the_program_span_readers():
    got = checks.traced_rehearsal_reports_the_program_span_readers(ROOT,
                                                                   CELL)
    assert got["serve_tick_ms"]["value"] > 0


# ---------------------------------------------------------------------------
# the four readers on planted records
# ---------------------------------------------------------------------------

def _run(cell, ticks=(), prefills=(), kernels=(), modules=()):
    """A run whose ring holds ``ticks`` and ``prefills`` (attrs) and whose
    device plane holds ``kernels`` ((name, ns)) and programs ``modules``."""
    from benchmark import trace_reduce as T

    profile = types.SimpleNamespace(
        devices={"/device:TPU:0": [(name, i * 10 ** 8, d)
                                   for i, (name, d) in enumerate(kernels)]},
        modules={"/device:TPU:0": [(name, i * 10 ** 8, d)
                                   for i, (name, d) in enumerate(modules)]},
        spans=[])
    assert T                                  # the readers import it
    run = types.SimpleNamespace(
        cell=cell, profile=profile, trace_window=(0.0, 1e9),
        peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        counters={}, window=(0.0, 1e9))
    run._program_spans = ({
        "serve/decode_tick": [{"start_ns": 1, "dur_ns": 1, "attrs": a}
                              for a in ticks],
        "serve/prefill": [{"start_ns": 1, "dur_ns": 1, "attrs": a}
                          for a in prefills]}, None)
    return run


TICK = {"batch": 24, "experts_hit": 50, "rows_full": 200_000,
        "rows_window": 90_000, "held_over_one_table": 0.6,
        "expert_tokens": 190, "expert_load_max": 9}


def test_the_readers_on_planted_records(cell):
    from benchmark import program_spans

    real = program_spans._within
    program_spans._within = lambda records, a, b: records
    try:
        f, c = cell.family, cell.config
        run = _run(cell, ticks=[TICK, dict(TICK, held_over_one_table=0.7)],
                   prefills=[{"prompt_len": 6000, "pages_window": 65},
                             {"prompt_len": 900, "pages_window": 15},
                             {"prompt_len": 20000, "replayed": 3616,
                              "pages_window": 65}],
                   kernels=[("gqa_paged_decode.4", 2_000_000),
                            ("gqa_paged_decode.5", 2_000_000),
                            ("window_flash_fwd.1", 40_000_000),
                            ("fusion.1", 9_000_000)],
                   modules=[("jit__decode_fn_paged(1)", 16_000_000),
                            ("jit__decode_fn_paged(1)", 14_000_000)])
        assert _reader("kv_pages_held_over_one_table").read(run) == \
            pytest.approx(0.65)
        kv = 4096 * (200_000 + 3 * 90_000)
        assert _reader("gqa_window_paged_decode_roofline").read(run) == \
            pytest.approx(100 * 2 * kv / 819e9 / 0.004)
        least = f.bytes_per_swa_moe_decode_step(c, 50, 200_000, 90_000, 24)
        assert least > 50 * 3 * 4096 * 4096 * 2 + kv
        assert _reader("swa_moe_decode_step_roofline").read(run) == \
            pytest.approx(100 * least / 819e9 / 0.015)
        flops = f.band_attention_flops(c, 6000) + f.band_attention_flops(
            c, 900)                              # the replayed tail is none
        assert _reader("window_flash_prefill_roofline").read(run) == \
            pytest.approx(100 * flops / 197e12 / 0.04)
        # a parent's records carry none of the attributes: nothing
        old = {k: v for k, v in TICK.items() if k not in (
            "rows_full", "rows_window", "held_over_one_table")}
        parent = _run(cell, ticks=[old], prefills=[{"prompt_len": 6000}],
                      kernels=[("fusion.1", 9_000_000)],
                      modules=[("jit__decode_fn_paged(1)", 16_000_000)])
        for name in NEW:
            assert _reader(name).read(parent) is None, name
        # and no trace at all
        bare = types.SimpleNamespace(profile=None, peaks=None,
                                     trace_window=None, cell=cell)
        bare._program_spans = ({}, None)
        for name in NEW:
            assert _reader(name).read(bare) is None, name
    finally:
        program_spans._within = real
