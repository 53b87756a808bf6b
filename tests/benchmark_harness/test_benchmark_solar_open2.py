"""The sixth family's cell, ``serve_solar_open2_ep8_closed64``: the source's
sizes pinned here (the configuration file carries its own ``published``
record, which a slip could edit together with the value), the cut and what
it holds reckoned from them, the cell as the issue names it with the cycle's
sizes counted by rung, the cell's rehearsal in process with its controls, the
manifest's checks on the tree, and the three readers this cell brought, on
planted traces."""
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

CELL = "serve_solar_open2_ep8_closed64"
NEW = ("kda_moe_decode_step_roofline", "kda_state_update_roofline",
       "kda_chunk_prefill_roofline")
# huggingface.co/upstage/Solar-Open2-250B/blob/main/config.json (the
# catalog's row), every number and switch that shapes the decoder
PINNED = {"model_type": "solar_open2", "partial_rotary_factor": 1,
          "linear_attn_config": {"short_conv_kernel_size": 4,
                                 "head_dim": 128, "num_heads": 64,
                                 "num_kv_heads": None},
          "hidden_size": 4096, "num_hidden_layers": 48,
          "num_attention_heads": 64, "head_dim": 128,
          "num_key_value_heads": 8, "vocab_size": 196608,
          "intermediate_size": 10240, "moe_intermediate_size": 1280,
          "rms_norm_eps": 1e-05, "rope_theta": 10000,
          "tie_word_embeddings": False, "max_position_embeddings": 1048576,
          "first_k_dense_replace": 0, "use_rope": False, "gqa_interval": 3,
          "gqa_layers": list(range(0, 48, 4)), "use_gqa_gate": True,
          "kda_use_full_proj": False, "kda_allow_neg_eigval": True,
          "n_routed_experts": 320, "n_shared_experts": 1,
          "norm_topk_prob": True, "routed_scaling_factor": 1,
          "num_experts_per_tok": 8}
CUT = {"num_hidden_layers": 4, "gqa_layers": [0], "n_routed_experts": 40,
       "vocab_size": 24576}
LADDER = [256, 640, 1408, 2560, 4096, 6144, 8192]


@pytest.fixture(scope="module")
def cell():
    return harness.Cell(ROOT, CELL)


def _reader(name):
    return harness.load_module(os.path.join(
        ROOT, "benchmark", "layer_metrics", name + ".py"))


def test_published_is_the_sources_own(cell):
    doc = cell.config
    assert doc["family"] == "solar_open2"
    assert set(doc["published"]) == set(PINNED)
    for key, value in PINNED.items():
        assert doc["published"][key] == value, key
        assert doc[key] == CUT.get(key, value), key
    widths = cell.family.WIDTH_KEYS
    assert set(widths) <= set(PINNED) and not set(widths) & set(CUT)
    # every width the issue names is held as published
    assert {"hidden_size", "num_attention_heads", "num_key_value_heads",
            "head_dim", "linear_attn_config", "moe_intermediate_size",
            "num_experts_per_tok", "n_shared_experts"} <= set(widths)
    assert doc["reduced"] == list(CUT)
    assert doc["reduced_from"] == {k: PINNED[k] for k in CUT}
    entry = [c for c in cell.manifest["configs"]
             if c["name"] == "solar-open2-ep8-l4"][0]
    assert entry["reduced"] == list(CUT)
    assert entry["source"] == doc["source"] == (
        "https://huggingface.co/upstage/Solar-Open2-250B/blob/main/"
        "config.json")
    assert doc["first_expert"] == 0
    # the file states the deployment, the cut and the readings it assumed
    for words in ("8 chips share each layer", "rank 0", "experts [0, 40)",
                  "rows [0, 24576)", "data-parallel",
                  "pipeline stages of 4 layers"):
        assert words in doc["deployment"], words
    assert {"kda_layer", "gqa_gate", "router", "block", "conv", "l2_norm",
            "output_norm", "state_precision", "weights", "positions"} <= set(
                doc["assumed"])
    assert set(doc["cut"]) >= set(CUT)


def test_the_cell_is_the_one_the_issue_names(cell):
    tr = cell.traffic
    assert (tr["kind"], tr["clients"], tr["pool"], tr["poll_s"],
            tr["pairing_stride"]) == ("serve_closed_loop", 64, 64, 0.001, 7)
    assert tr["prompt_len"] == {"median": 1024, "sigma": 1.0, "min": 128,
                                "max": 8192}
    assert tr["output_len"] == {"median": 1280, "sigma": 0.4, "min": 512,
                                "max": 3072}
    assert (tr["trace_after_s"], tr["trace_seconds"]) == (8.0, 4.0)
    assert cell.entry["chips"] == 1
    sv = cell.config["serving"]
    engine = sv["engine"]
    assert (engine["max_batch"], engine["max_seq"]) == (80, 11264)
    assert engine["prefix_cache"] is False
    assert engine["weight_dtype"] == "bf16"
    assert sv["compute_dtype"] == "bfloat16"
    assert sv["scheduler"]["max_new_tokens_cap"] == 3072
    # a page the grouped kernel takes (whole bfloat16 sublane tiles), and
    # the pool every slot's worst case at once
    assert engine["page_size"] == 64 and engine["page_size"] % 16 == 0
    assert engine["num_pages"] - 1 == 80 * (11264 // 64)
    sizes = cell.kind.make_pool(tr)
    assert len(sizes) == 64
    assert max(p + o for p, o in sizes) == 10620 <= max(tr["reference_pads"])
    assert max(tr["reference_pads"]) == engine["max_seq"]
    assert max(o for _, o in sizes) == 3072 == tr["reference_rows"]
    prompts = sorted(p for p, _ in sizes)
    outputs = [o for _, o in sizes]
    assert (min(prompts), max(prompts)) == (128, 8192)
    assert round(sum(prompts) / 64) == 1605          # the issue's mean
    assert round(sum(outputs) / 64) == 1380
    assert sum(p > 4096 for p in prompts) == 5
    # the ladder: whole pages, whole chunks of the delta rule, whole row
    # tiles of the experts' buffers; the cycle's sizes by rung
    ladder = engine["prefill_buckets"]
    assert ladder == LADDER and max(prompts) == ladder[-1]
    assert all(r % 128 == 0 and r % engine["page_size"] == 0
               for r in ladder)
    rung = lambda n: min(r for r in ladder if r >= n)
    by_rung = {r: sum(rung(p) == r for p in prompts) for r in ladder}
    assert by_rung == {256: 5, 640: 15, 1408: 20, 2560: 12, 4096: 7,
                       6144: 3, 8192: 2}
    # no rung edge near the cycle's median prompt (1004, 1044): the middle
    # third of the sizes lies inside one rung, its median 230 and 360 tokens
    # from the edges
    assert len({rung(p) for p in prompts[20:40]}) == 1
    assert rung(prompts[31]) == rung(prompts[32]) == 1408
    assert prompts[20] - 640 >= 2 and 1408 - prompts[39] >= 28
    reported = {m["name"] for g in ("end_to_end", "per_layer")
                for m in cell.metrics(g)}
    assert set(NEW) | {"moe_grouped_matmul_roofline",
                       "moe_expert_load_max_over_mean", "serve_tick_ms",
                       "serve_idle_unattributed", "device_idle.serve",
                       "sched_occupancy", "gap_p95_ms",
                       "recompiles_in_window", "serve_tokens_per_s",
                       "ttft_p50_ms", "gap_p90_ms", "setup_s"} <= reported
    assert not {"decode_step_roofline", "gdn_decode_step_roofline",
                "gdn_state_update_roofline", "gdn_chunk_prefill_roofline",
                "paged_decode_roofline", "gqa_window_paged_decode_roofline",
                "swa_moe_decode_step_roofline"} & reported
    for m in cell.manifest["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL] and m["unit"] == "%"
            assert m["layer"] == "kernels"
    # appended in this order (found by name: a later PR appends behind them)
    names = [m["name"] for m in cell.manifest["per_layer"]]
    assert sorted(NEW, key=names.index) == list(NEW)
    # limits written with their readings
    assert "PLACEHOLDER" not in json.dumps(cell.spec)
    assert cell.control_precision == "int8w"


def test_sizes_reckoned_from_the_published_keys(cell):
    f, c = cell.family, cell.config
    assert f.layer_kinds(c) == ["gqa", "kda", "kda", "kda"]
    count = lambda kind, skip=(): f._count(f.leaf_shapes(c, kind), skip)
    ffn = ("input_layernorm", "post_attention_layernorm", "gate",
           "e_score_correction_bias", "shared_gate_proj", "shared_up_proj",
           "shared_down_proj") + f.EXPERTS
    # the issue's counts: the mixers alone, a layer outside its routed
    # experts, a layer, the share
    assert count("kda", ffn) == 137_732_288             # 137.73M
    assert count("gqa", ffn) == 109_051_904             # 109.05M
    assert round(count("kda", f.EXPERTS) / 1e6, 2) == 154.78
    assert round(count("gqa", f.EXPERTS) / 1e6, 2) == 126.10
    assert f.expert_params(c) == 15_728_640
    assert round(count("kda") / 1e6, 1) == 783.9
    assert round(count("gqa") / 1e6, 1) == 755.2
    assert round(f.param_count(c) / 1e6, 1) == 3308.4   # 6.62 GB bfloat16
    # the whole model by the same equations: the name's 250B-A15B
    whole = dict(c, **{k: PINNED[k] for k in CUT})
    assert round(f.param_count(whole) / 1e9, 2) == 250.29
    active = (f.param_count(whole) - 48 * (320 - 8) * f.expert_params(c)
              - 196608 * 4096)          # the embedding is a lookup
    assert round(active / 1e9, 2) == 13.93
    assert round((active + 196608 * 4096) / 1e9, 2) == 14.74
    assert f.kv_bytes_per_token(c) == 1 * 2 * 8 * 128 * 2 == 4096
    assert f.kda_state_bytes(c) == 3 * 64 * 128 * 128 * 4   # 12.58 MB
    assert f.state_bytes_per_sequence(c) == 3 * (
        64 * 128 * 128 * 4 + 3 * 24576 * 2)
    assert round(f.state_bytes_per_sequence(c) / 1e6, 2) == 13.03
    # a tick of 64 riders at 2,300 cached tokens each, 32 of 40 experts a
    # layer with a token: the issue's 7.6 GB, a fifth of it matrix states
    state = 64 * f.state_bytes_per_sequence(c)
    tick = f.bytes_per_decode_step(c, 4 * 32, state, 64 * 2300, 64)
    held, small = f.dense_params_per_step(c)
    assert tick == (2 * held + 4 * small + 128 * 2 * f.expert_params(c)
                    + 64 * 4096 * 2 + 2 * state + 64 * 2300 * 4096)
    assert round(2 * held / 1e9, 2) == 1.37
    assert 7.5e9 < tick < 7.8e9
    assert 0.2 < 2 * state / tick < 0.23
    assert f.state_update_bytes(c, 64) == 2 * 64 * f.kda_state_bytes(c)
    assert f.chunk_prefill_flops(c, 1000) == 3 * 64 * 1000 * (
        2 * 64 * (3 * 128 + 2 * 128) + 6 * 128 * 128)
    assert f.chunk_prefill_bytes(c, 1000, 2) == 3 * 64 * (
        1000 * (4 * 128 * 2 + 4 * 128 + 4) + 2 * 128 * 128 * 4)
    nbytes, flops = f.grouped_matmul_work(c, 256, 128)
    assert nbytes == 128 * 2 * f.expert_params(c) + 256 * 2 * (
        2 * 4096 + 3 * 1280)
    assert flops == 2 * 256 * f.expert_params(c)
    s = f.dims(c)
    assert (s["G"], s["E"], s["L"], s["Ld"], s["k"]) == (40, 320, 4, 0, 8)


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
    # that lacks the kernels; the program's spans and counters are read
    got = sound["metrics"]
    assert not [name for name in got if name.endswith("_roofline")]
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
# the three readers, on planted records
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
_KERNEL = ('%{}.{} = (f32[80,64,128]{{2,1,0}}, f32[3,80,64,128,128]'
           '{{4,3,2,1,0}}) custom-call(s32[1] %layer, f32[3,80,64,128,128] '
           '%ssm), custom_call_target="tpu_custom_call"')


def test_kda_moe_decode_step_roofline_needs_state_and_experts_of_a_tick():
    family = types.SimpleNamespace(
        bytes_per_decode_step=lambda config, hit, state, cached, riders,
        weight_bytes: 1000 * weight_bytes + 100 * hit + 2 * state + cached
        + riders)
    cell = types.SimpleNamespace(family=family, config=_BF16)
    tick = lambda start, cached, **a: _record(
        "serve/decode_tick", start, cached_tokens=cached, **a)
    ring = {"serve/decode_tick": [
        tick(1.0, 480, state_bytes=250, experts_hit=5, batch=20),
        tick(2.0, 1470, state_bytes=750, experts_hit=5, batch=30),
        tick(9.0, 9999, state_bytes=9999, experts_hit=99, batch=64)]}
    profile = T.Profile({}, {"/device:TPU:0": [
        ("jit__decode_fn_paged(1)", 0, 8000),
        ("jit__prefill_fn_paged(2)", 0, 99999)]}, [])
    run = _planted_run(cell, ring, profile, (0.5, 3.0))
    # ticks of 2000 + 500 + 500 + 480 + 20 and 2000 + 500 + 1500 + 1470 +
    # 30 bytes: mean 4500 at 1 GB/s is 4.5 us; the program took 8 us
    reader = _reader("kda_moe_decode_step_roofline")
    assert reader.read(run) == pytest.approx(56.25)
    assert reader.META["share_of_peak"] is True
    # a delta-rule model without experts, an expert model without state, a
    # family without the count, a trace without the program: nothing
    for old in ({"serve/decode_tick": [tick(1.0, 500, state_bytes=250,
                                            batch=20)]},
                {"serve/decode_tick": [tick(1.0, 500, state_bytes=0,
                                            experts_hit=5, batch=20)]},
                None):
        assert reader.read(_planted_run(cell, old, profile,
                                        (0.5, 3.0))) is None
    bare = types.SimpleNamespace(family=types.SimpleNamespace(),
                                 config=_BF16)
    assert reader.read(_planted_run(bare, ring, profile, (0.5, 3.0))) is None
    assert reader.read(_planted_run(cell, ring, T.Profile({}, {}, []),
                                    (0.5, 3.0))) is None


def test_kda_state_update_roofline_reads_the_riders_and_the_kernel_by_name():
    family = types.SimpleNamespace(
        state_update_bytes=lambda config, riders: 100 * riders)
    cell = types.SimpleNamespace(family=family, config={})
    tick = lambda start, **a: _record("serve/decode_tick", start, **a)
    ring = {"serve/decode_tick": [tick(1.0, state_slots=12),
                                  tick(2.0, state_slots=8),
                                  tick(2.5, state_slots=0),
                                  tick(9.0, state_slots=999)]}
    devices = {"/device:TPU:0": [
        (T.short_name(_KERNEL.format("kda_update_rows", 7)), 0, 2500),
        (T.short_name(_KERNEL.format("kda_update_rows", 9)), 3000, 1500),
        (T.short_name(_KERNEL.format("kda_chunk_fwd", 2)), 5000, 70000),
        (T.short_name(_KERNEL.format("gated_delta_update_rows", 3)), 80000,
         7000)]}
    run = _planted_run(cell, ring, T.Profile(devices, {}, []), (0.5, 3.0))
    # 20 riders inside the traced window: 2000 bytes at 1 GB/s are 2 us;
    # the two kda_update kernels took 4 us (the chunk kernel and the
    # scalar-gate kernel are not theirs)
    reader = _reader("kda_state_update_roofline")
    assert reader.read(run) == pytest.approx(50.0)
    assert reader.META["share_of_peak"] is True
    # the scalar-gate reader sees its own kernel alone on the same plane
    assert _reader("gdn_state_update_roofline").read(run) == pytest.approx(
        100 * 2.0 / 7.0)
    # no kernel of that name (the parent, the delta-rule cell), no riders
    rest = T.Profile({"/device:TPU:0": devices["/device:TPU:0"][2:]}, {}, [])
    assert reader.read(_planted_run(cell, ring, rest, (0.5, 3.0))) is None
    old = {"serve/decode_tick": [tick(1.0, batch=12)]}
    assert reader.read(_planted_run(cell, old, T.Profile(devices, {}, []),
                                    (0.5, 3.0))) is None
    bare = types.SimpleNamespace(family=types.SimpleNamespace(), config={})
    assert reader.read(_planted_run(bare, ring, T.Profile(devices, {}, []),
                                    (0.5, 3.0))) is None


def test_kda_chunk_prefill_roofline_takes_the_larger_of_its_two_bounds():
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
    devices = {"/device:TPU:0": [
        (T.short_name(_KERNEL.format("kda_chunk_fwd", 7)), 0, 150000),
        (T.short_name(_KERNEL.format("kda_chunk_fwd", 9)), 200000, 50000),
        (T.short_name(_KERNEL.format("kda_update_rows", 2)), 300000,
         70000)]}
    run = _planted_run(cell, ring, T.Profile(devices, {}, []), (0.5, 3.0))
    # 80 tokens of two prompts: 8e7 operations at 1 TFLOP/s are 80 us,
    # 1000 bytes at 1 GB/s 1 us: the operations bound it; 200 us measured
    reader = _reader("kda_chunk_prefill_roofline")
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


def test_the_family_counts_at_the_cells_own_sizes_stay_under_a_peak(cell):
    """The real counts through the real readers: a tick and a prefill as
    the issue reckons them, at durations a chip could show, read between 0
    and 100 %."""
    f, c = cell.family, cell.config
    state = 64 * f.state_bytes_per_sequence(c)
    ring = {"serve/decode_tick": [_record(
        "serve/decode_tick", 1.0, cached_tokens=64 * 2300, batch=64,
        state_bytes=state, state_slots=64, experts_hit=128,
        expert_tokens=256, expert_load_max=6)],
        "serve/prefill": [_record(
            "serve/prefill", 2.0, scan_tokens=1024, delta_chunks=16,
            prompt_len=1024, expert_tokens=4096, experts_hit=160)]}
    kernel = lambda name, us: (T.short_name(_KERNEL.format(name, 1)), 0,
                               int(us * 1000))
    devices = {"/device:TPU:0": [kernel("kda_update_rows", 2500),
                                 kernel("kda_chunk_fwd", 40000)]}
    profile = T.Profile(devices, {"/device:TPU:0": [
        ("jit__decode_fn_paged(1)", 0, 14_000_000)]}, [])
    run = _planted_run(cell, ring, profile, (0.5, 3.0))
    run.peaks = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
    got = {name: _reader(name).read(run) for name in NEW}
    assert 60 < got["kda_moe_decode_step_roofline"] < 70      # 9.3 of 14 ms
    assert 75 < got["kda_state_update_roofline"] < 82         # 1.97 of 2.5
    assert 0.5 < got["kda_chunk_prefill_roofline"] < 2
