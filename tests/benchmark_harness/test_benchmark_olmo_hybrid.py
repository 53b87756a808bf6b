"""The fourth family's cell, ``serve_olmo_hybrid_7b_l16_closed32``: the
source's sizes pinned here (the configuration file carries its own
``published`` record, which a slip could edit together with the value), the
cut and what it holds reckoned from them, the cell's rehearsal in process
with its controls, the manifest's checks on the tree, and the three readers
this cell brought, on planted traces."""
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
from benchmark import trace_reduce as T  # noqa: E402

CELL = "serve_olmo_hybrid_7b_l16_closed32"
PERIOD = ["linear_attention"] * 3 + ["full_attention"]
# huggingface.co/allenai/Olmo-Hybrid-7B/blob/main/config.json (the
# catalog's row), every number and switch that shapes the decoder
PINNED = {"vocab_size": 100352, "hidden_size": 3840,
          "intermediate_size": 11008, "num_hidden_layers": 32,
          "num_attention_heads": 30, "num_key_value_heads": 30,
          "max_position_embeddings": 65536, "rms_norm_eps": 1e-06,
          "linear_num_key_heads": 30, "linear_num_value_heads": 30,
          "linear_key_head_dim": 96, "linear_value_head_dim": 192,
          "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
          "attention_bias": False, "tie_word_embeddings": False,
          "hidden_act": "silu", "layer_types": PERIOD * 8,
          "rope_parameters": {"rope_theta": None}}
CUT = {"num_hidden_layers": 16, "layer_types": PERIOD * 4}


@pytest.fixture(scope="module")
def cell():
    return harness.Cell(ROOT, CELL)


def _reader(name):
    return harness.load_module(os.path.join(
        ROOT, "benchmark", "layer_metrics", name + ".py"))


def test_published_is_the_sources_own(cell):
    doc = cell.config
    assert doc["family"] == "olmo_hybrid"
    assert doc["model_type"] == "olmo_hybrid"
    for key, value in PINNED.items():
        assert doc["published"][key] == value, key
        assert doc[key] == CUT.get(key, value), key
    assert set(cell.family.WIDTH_KEYS) <= set(PINNED)
    assert not set(cell.family.WIDTH_KEYS) & set(CUT)
    assert sorted(doc["reduced"]) == sorted(CUT)
    assert doc["reduced_from"] == {k: PINNED[k] for k in CUT}
    entry = [c for c in cell.manifest["configs"]
             if c["name"] == "olmo-hybrid-7b-l16"][0]
    assert sorted(entry["reduced"]) == sorted(CUT)
    assert entry["source"] == doc["source"]
    # the file states the deployment, the cut and the readings it assumed
    assert "two" in doc["deployment"] and "pipeline" in doc["deployment"]
    assert {"block", "qk_norm", "rotary", "head_dim", "state_precision",
            "weights"} <= set(doc["assumed"])


def test_the_cell_is_the_one_the_issue_names(cell):
    tr = cell.traffic
    assert (tr["kind"], tr["clients"], tr["pool"], tr["poll_s"]) == (
        "serve_closed_loop", 32, 32, 0.001)
    assert tr["prompt_len"] == {"median": 1024, "sigma": 0.7, "min": 128,
                                "max": 4096}
    assert tr["output_len"] == {"median": 192, "sigma": 0.6, "min": 32,
                                "max": 512}
    engine = cell.config["serving"]["engine"]
    assert (engine["max_batch"], engine["max_seq"]) == (48, 4608)
    assert engine["prefix_cache"] is False
    assert engine["weight_dtype"] == "bf16"
    assert (engine["num_pages"] - 1) * engine["page_size"] >= 48 * 1024
    sizes = cell.kind.make_pool(tr)
    assert max(p + o for p, o in sizes) <= max(tr["reference_pads"])
    assert max(p + o for p, o in sizes) <= engine["max_seq"]
    assert max(o for _, o in sizes) <= tr["reference_rows"]
    ladder = engine["prefill_buckets"]
    assert max(p for p, _ in sizes) == ladder[-1] == 4096
    # every rung whole chunks of the delta rule, whole flash blocks and
    # whole pages; the cycle's median prompt well inside a rung
    assert all(r % 128 == 0 and r % engine["page_size"] == 0
               for r in ladder)
    prompts = sorted(p for p, _ in sizes)
    rung = lambda n: min(r for r in ladder if r >= n)
    assert len({rung(p) for p in prompts[12:21]}) == 1
    reported = {m["name"] for g in ("end_to_end", "per_layer")
                for m in cell.metrics(g)}
    assert {"gdn_decode_step_roofline", "gdn_state_update_roofline",
            "gdn_chunk_prefill_roofline", "serve_tick_ms",
            "serve_idle_unattributed", "device_idle.serve",
            "sched_occupancy", "recompiles_in_window"} <= reported
    assert not {"decode_step_roofline", "ssm_scan_roofline",
                "ssm_decode_step_roofline"} & reported
    # the three readers list this cell alone
    for m in cell.manifest["per_layer"]:
        if m["name"].startswith("gdn_"):
            assert m["workloads"] == [CELL] and m["unit"] == "%"


def test_sizes_reckoned_from_the_published_keys(cell):
    f, c = cell.family, cell.config
    kinds = f.layer_kinds(c)
    assert kinds == (["linear"] * 3 + ["full"]) * 4
    linear = sum(a * b for a, b in (
        s if len(s) == 2 else (s[0], 1)
        for s in f.leaf_shapes(c, "linear").values()))
    full = sum(a * b for a, b in (
        s if len(s) == 2 else (s[0], 1)
        for s in f.leaf_shapes(c, "full").values()))
    # ISSUE 35's count: 88.7M + 126.8M a linear layer, 185.8M a full one
    assert round(linear / 1e6, 1) == 215.6 and round(full / 1e6, 1) == 185.8
    assert f.param_count(c) == 4_100_788_944            # 8.20 GB bfloat16
    whole = dict(c, num_hidden_layers=32, layer_types=PERIOD * 8)
    assert round(f.param_count(whole) / 1e9, 2) == 7.43
    assert f.kv_bytes_per_token(c) == 4 * 2 * 30 * 128 * 2 == 61_440
    assert f.delta_state_bytes(c) == 12 * 30 * 96 * 192 * 4
    assert f.state_bytes_per_sequence(c) == 12 * (
        30 * 96 * 192 * 4 + 3 * 11520 * 2)              # 27.4 MB a slot
    assert round(f.state_bytes_per_sequence(c) / 1e6, 1) == 27.4
    # a tick of 32 riders at 1400 cached tokens each
    state = 32 * f.state_bytes_per_sequence(c)
    tick = f.bytes_per_gdn_decode_step(c, 32 * 1400, state)
    assert tick == (2 * f.matmul_param_count(c) + 2 * state
                    + 32 * 1400 * 61_440)
    # every parameter but the embedding (a lookup), the conv's taps, the
    # decay's constants and the gains is multiplied as a matrix
    small = (12 * (4 * 11520 + 2 * 30 + 192 + 2 * 3840)
             + 4 * (4 * 3840) + 3840)
    assert f.matmul_param_count(c) == (f.param_count(c) - 100352 * 3840
                                       - small)
    assert 0.13 < 2 * state / tick < 0.17               # a seventh of it
    assert f.state_update_bytes(c, 32) == 2 * 32 * f.delta_state_bytes(c)
    assert f.chunk_prefill_flops(c, 1000) == 12 * 30 * 1000 * (
        2 * 64 * (3 * 96 + 2 * 192) + 6 * 96 * 192)
    assert f.chunk_prefill_bytes(c, 1000, 2) == 12 * 30 * (
        1000 * ((2 * 96 + 2 * 192) * 2 + 8) + 2 * 96 * 192 * 4)


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
    # traced, with no device plane: the three readers this cell brought
    # return None and the line leaves them out, as it does for a parent
    # that lacks the kernels; the program's spans are read
    got = sound["metrics"]
    assert not [name for name in got if name.startswith("gdn_")]
    assert {"serve_tick_ms", "serve_prefill_share"} <= set(got)
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


def test_traced_rehearsal_passes_the_general_check():
    got = checks.traced_rehearsal_reports_the_program_span_readers(
        ROOT, CELL)
    assert "serve_tick_ms" in got


# ---------------------------------------------------------------------------
# the readers, on planted records
# ---------------------------------------------------------------------------

def _planted_run(cell, ring, profile, trace_window):
    run = types.SimpleNamespace(
        cell=cell, window=(0.0, 10.0), trace_window=trace_window,
        peaks={"hbm_bytes_per_s": 1e9, "bf16_flops_per_s": 1e12},
        profile=profile)
    run._program_spans = (ring, None)
    return run


def _record(name, start, **attrs):
    from paddle_tpu.observability import spans

    return {"name": name, "start_ns": spans.monotonic_to_ns(start),
            "dur_ns": 100, "attrs": attrs}


_BF16 = {"serving": {"engine": {"weight_dtype": "bf16"}}}
_KERNEL = ('%{}.{} = (f32[48,5760]{{1,0}}, f32[12,48,15,96,384]{{4,3,2,1,0}}) '
           'custom-call(s32[1] %layer, f32[12,48,15,96,384] %ssm), '
           'custom_call_target="tpu_custom_call"')
_OTHER = ('%flash_fwd.3 = bf16[8,128]{1,0} custom-call(bf16[8,128] %q), '
          'custom_call_target="tpu_custom_call"')


def test_gdn_decode_step_roofline_reads_state_bytes_of_the_ticks():
    family = types.SimpleNamespace(
        bytes_per_gdn_decode_step=lambda config, cached, state,
        weight_bytes: 1000 * weight_bytes + 2 * state + cached)
    cell = types.SimpleNamespace(family=family, config=_BF16)
    tick = lambda start, cached, **a: _record(
        "serve/decode_tick", start, cached_tokens=cached, **a)
    ring = {"serve/decode_tick": [tick(1.0, 500, state_bytes=250),
                                  tick(2.0, 1500, state_bytes=750),
                                  tick(9.0, 9999, state_bytes=9999)]}
    profile = T.Profile({}, {"/device:TPU:0": [
        ("jit__decode_fn_paged(1)", 0, 8000),
        ("jit__prefill_fn_paged(2)", 0, 99999)]}, [])
    run = _planted_run(cell, ring, profile, (0.5, 3.0))
    # ticks of 2000 + 500 + 500 and 2000 + 1500 + 1500 bytes: mean 4000
    # at 1 GB/s is 4 us; the program took 8 us
    reader = _reader("gdn_decode_step_roofline")
    assert reader.read(run) == pytest.approx(50.0)
    assert reader.META["share_of_peak"] is True
    # a program that writes no state_bytes, a family without the count (a
    # Mamba hybrid's, the parent's), a trace without the program: nothing
    old = {"serve/decode_tick": [tick(1.0, 500)]}
    assert reader.read(_planted_run(cell, old, profile, (0.5, 3.0))) is None
    bare = types.SimpleNamespace(family=types.SimpleNamespace(),
                                 config=_BF16)
    assert reader.read(_planted_run(bare, ring, profile, (0.5, 3.0))) is None
    assert reader.read(_planted_run(cell, ring, T.Profile({}, {}, []),
                                    (0.5, 3.0))) is None
    assert reader.read(_planted_run(cell, None, profile, (0.5, 3.0))) is None


def test_gdn_state_update_roofline_reads_the_riders_and_the_kernel_by_name():
    family = types.SimpleNamespace(
        state_update_bytes=lambda config, riders: 100 * riders)
    cell = types.SimpleNamespace(family=family, config={})
    tick = lambda start, **a: _record("serve/decode_tick", start, **a)
    ring = {"serve/decode_tick": [tick(1.0, state_slots=12),
                                  tick(2.0, state_slots=8),
                                  tick(2.5, state_slots=0),
                                  tick(9.0, state_slots=999)]}
    name = "gated_delta_update_rows"
    devices = {"/device:TPU:0": [
        (T.short_name(_KERNEL.format(name, 7)), 0, 2500),
        (T.short_name(_KERNEL.format(name, 9)), 3000, 1500),
        (T.short_name(_KERNEL.format("gated_delta_chunk_fwd", 2)), 5000,
         70000),
        (T.short_name(_OTHER), 80000, 7000)]}
    run = _planted_run(cell, ring, T.Profile(devices, {}, []), (0.5, 3.0))
    # 20 riders inside the traced window: 2000 bytes at 1 GB/s are 2 us;
    # the two update kernels took 4 us (the chunk kernel is not theirs)
    reader = _reader("gdn_state_update_roofline")
    assert reader.read(run) == pytest.approx(50.0)
    assert reader.META["share_of_peak"] is True
    # no kernel of that name (the parent, a Mamba hybrid), no state_slots
    rest = T.Profile({"/device:TPU:0": devices["/device:TPU:0"][2:]}, {}, [])
    assert reader.read(_planted_run(cell, ring, rest, (0.5, 3.0))) is None
    old = {"serve/decode_tick": [tick(1.0, batch=12)]}
    assert reader.read(_planted_run(cell, old, T.Profile(devices, {}, []),
                                    (0.5, 3.0))) is None
    bare = types.SimpleNamespace(family=types.SimpleNamespace(), config={})
    assert reader.read(_planted_run(bare, ring, T.Profile(devices, {}, []),
                                    (0.5, 3.0))) is None


def test_gdn_chunk_prefill_roofline_takes_the_larger_of_its_two_bounds():
    work = {"flops": 1e6, "bytes": 10}

    family = types.SimpleNamespace(
        chunk_prefill_flops=lambda config, tokens: work["flops"] * tokens,
        chunk_prefill_bytes=lambda config, tokens, sequences:
        work["bytes"] * tokens + 100 * sequences)
    cell = types.SimpleNamespace(family=family, config={})
    prefill = lambda start, **a: _record("serve/prefill", start, **a)
    ring = {"serve/prefill": [
        prefill(1.0, scan_tokens=30, delta_chunks=1),
        prefill(2.0, scan_tokens=50, delta_chunks=1),
        prefill(2.5, scan_tokens=70),            # a Mamba hybrid's: no chunks
        prefill(9.0, scan_tokens=999, delta_chunks=16)]}
    name = "gated_delta_chunk_fwd"
    devices = {"/device:TPU:0": [
        (T.short_name(_KERNEL.format(name, 7)), 0, 150000),
        (T.short_name(_KERNEL.format(name, 9)), 200000, 50000),
        (T.short_name(_KERNEL.format("gated_delta_update_rows", 2)), 300000,
         70000)]}
    run = _planted_run(cell, ring, T.Profile(devices, {}, []), (0.5, 3.0))
    # 80 tokens of two prompts: 8e7 operations at 1 TFLOP/s are 80 us,
    # 1000 bytes at 1 GB/s 1 us: the operations bound it; 200 us measured
    reader = _reader("gdn_chunk_prefill_roofline")
    assert reader.read(run) == pytest.approx(40.0)
    work.update(flops=1e3, bytes=1000)   # 0.08 us against 80.2 us of bytes
    assert reader.read(run) == pytest.approx(100 * 80.2 / 200)
    assert reader.META["share_of_peak"] is True
    # no kernel of that name, or prefills without delta_chunks: nothing
    rest = T.Profile({"/device:TPU:0": devices["/device:TPU:0"][2:]}, {}, [])
    assert reader.read(_planted_run(cell, ring, rest, (0.5, 3.0))) is None
    old = {"serve/prefill": [prefill(1.0, scan_tokens=30)]}
    assert reader.read(_planted_run(cell, old, T.Profile(devices, {}, []),
                                    (0.5, 3.0))) is None
