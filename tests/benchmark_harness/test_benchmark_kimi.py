"""The third family's cell, ``serve_kimi_k2p5_ep32_closed96``: the source's
sizes pinned here (the configuration file carries its own ``published``
record, which a slip could edit together with the value), what one chip of
the 32 holds reckoned from them, the cell's rehearsal in process with its
controls, the manifest's checks on the tree, and the four readers this cell
brought, on planted traces."""
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

CELL = "serve_kimi_k2p5_ep32_closed96"
# huggingface.co/moonshotai/Kimi-K2.5/blob/main/config.json (the catalog's
# row), every number and switch that shapes the text decoder
PINNED = {"first_k_dense_replace": 1, "hidden_size": 7168,
          "intermediate_size": 18432, "kv_lora_rank": 512,
          "max_position_embeddings": 262144, "moe_intermediate_size": 2048,
          "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 384,
          "n_shared_experts": 1, "num_attention_heads": 64,
          "num_experts_per_tok": 8, "num_hidden_layers": 61,
          "num_key_value_heads": 64, "num_nextn_predict_layers": 0,
          "q_lora_rank": 1536, "qk_nope_head_dim": 128,
          "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05,
          "rope_theta": 50000, "routed_scaling_factor": 2.827,
          "topk_group": 1, "v_head_dim": 128, "vocab_size": 163840,
          "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 64,
                           "mscale": 1, "mscale_all_dim": 1,
                           "original_max_position_embeddings": 4096,
                           "type": "yarn"}}
CUT = {"num_hidden_layers": 6, "n_routed_experts": 12, "vocab_size": 20480}


@pytest.fixture(scope="module")
def cell():
    return harness.Cell(ROOT, CELL)


def _reader(name):
    return harness.load_module(os.path.join(
        ROOT, "benchmark", "layer_metrics", name + ".py"))


def test_published_is_the_sources_own(cell):
    doc = cell.config
    assert doc["family"] == "kimi_k2" and doc["model_type"] == "kimi_k2"
    assert doc["scoring_func"] == "sigmoid"
    assert doc["topk_method"] == "noaux_tc" and doc["norm_topk_prob"]
    assert not doc["tie_word_embeddings"]
    for key, value in PINNED.items():
        assert doc["published"][key] == value, key
        assert doc[key] == CUT.get(key, value), key
    assert doc["reduced"] == list(CUT)
    assert doc["reduced_from"] == {k: PINNED[k] for k in CUT}
    assert set(cell.family.WIDTH_KEYS) <= set(PINNED)
    assert not set(cell.family.WIDTH_KEYS) & set(CUT)
    entry = [c for c in cell.manifest["configs"]
             if c["name"] == "kimi-k2.5-ep32-l6"][0]
    assert entry["reduced"] == list(CUT) and entry["source"] == doc["source"]
    eng = doc["serving"]["engine"]
    assert (eng["max_batch"], eng["max_seq"], eng["page_size"]) == (
        128, 3072, 16)
    assert eng["prefix_cache"] is False and eng["weight_dtype"] == "bf16"
    assert eng["prefill_buckets"][0] == 64
    assert eng["prefill_buckets"][-1] == 2048


def test_the_cell_is_the_one_the_issue_names(cell):
    tr = cell.traffic
    assert (tr["kind"], tr["clients"], tr["pool"], tr["pairing_stride"]) == (
        "serve_closed_loop", 96, 64, 7)
    assert tr["prompt_len"] == {"median": 512, "sigma": 0.8, "min": 64,
                                "max": 2048}
    assert tr["output_len"] == {"median": 384, "sigma": 0.6, "min": 64,
                                "max": 1024}
    assert cell.chips == 1
    sizes = cell.kind.make_pool(tr)
    assert max(p + o for p, o in sizes) <= max(tr["reference_pads"]) \
        <= cell.config["serving"]["engine"]["max_seq"]
    assert max(o for _, o in sizes) <= tr["reference_rows"]
    assert max(p for p, _ in sizes) <= 2048
    reported = {m["name"] for g in ("end_to_end", "per_layer")
                for m in cell.metrics(g)}
    assert {"serve_tokens_per_s", "ttft_p50_ms", "gap_p90_ms", "setup_s",
            "moe_mla_decode_step_roofline", "moe_grouped_matmul_roofline",
            "mla_paged_decode_roofline", "moe_expert_load_max_over_mean",
            "serve_tick_ms", "device_idle.serve"} <= reported
    assert "decode_step_roofline" not in reported
    assert "ssm_decode_step_roofline" not in reported


def test_one_chips_share_reckoned_from_the_published_keys(cell):
    f, c = cell.family, cell.config
    s = f.dims(c)
    assert (s["E"], s["G"], s["first"], s["k"]) == (384, 12, 0, 8)
    assert f.layer_kinds(c) == ["dense"] + ["moe"] * 5
    attn = sum(a * b for a, b in (
        (7168, 1536), (1536, 64 * 192), (7168, 576), (512, 64 * 256),
        (64 * 128, 7168)))
    assert round(attn / 1e6, 1) == 101.1
    assert f.expert_params(c) == 3 * 7168 * 2048        # 44.04 M, 88.1 MB
    norms = 2 * 7168 + 1536 + 512
    dense = attn + norms + 3 * 7168 * 18432
    moe = (attn + norms + 7168 * 384 + 384 + 3 * 7168 * 2048
           + 12 * f.expert_params(c))
    assert round(moe / 1e6, 1) == 676.4 and round(dense / 1e6, 1) == 497.5
    assert f.param_count(c) == dense + 5 * moe + 2 * 20480 * 7168 + 7168
    assert 8.3e9 < 2 * f.param_count(c) < 8.4e9         # bfloat16
    # what a token leaves in the cache: 576 values a layer, 1,152 bytes
    assert f.latent_bytes_per_token(c) == 6 * 1152
    full_heads = 6 * 64 * (192 + 128) * 2
    assert full_heads / f.latent_bytes_per_token(c) > 35
    # a tick of 96 riders at 1,000 cached tokens each that hit 55 experts
    latent = 96 * 1000 * f.latent_bytes_per_token(c)
    tick = f.bytes_per_moe_mla_decode_step(c, 55, latent, 96)
    held, router = f.dense_params_per_step(c)
    assert tick == (2 * held + 4 * router + 55 * 2 * f.expert_params(c)
                    + 96 * 7168 * 2 + latent + 96 * 6 * 1152)
    assert 0.55 < 55 * 2 * f.expert_params(c) / tick < 0.70
    assert 0.07 < latent / tick < 0.10
    nbytes, flops = f.grouped_matmul_work(c, 160, 55)
    assert nbytes == (55 * 2 * f.expert_params(c)
                      + 160 * (2 * 7168 + 3 * 2048) * 2)
    assert flops == 2 * 160 * f.expert_params(c)


@pytest.mark.parametrize("check", checks.MANIFEST_CHECKS,
                         ids=lambda c: c.__name__)
def test_manifest_checks_hold_on_the_tree(check):
    check(ROOT)


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
    from paddle_tpu.serving import metrics as smetrics

    sound = _rehearse(trace=1)
    assert sound["correct"] and sound["failed"] == 0
    assert sound["attempted"] > 20
    assert sound["device"]["platform"] == "cpu"
    assert sound["checks"]["served_logits_rel_rms"]["value"] < 1e-6
    # traced, with no device plane: the three readers of the device trace
    # return None and the line leaves them out, as it does for a parent
    # that lacks the spans' new attributes; the counter's reader reads
    got = sound["metrics"]
    for name in ("moe_mla_decode_step_roofline", "mla_paged_decode_roofline",
                 "moe_grouped_matmul_roofline"):
        assert name not in got
    assert got["moe_expert_load_max_over_mean"]["value"] >= 1.0
    assert {"serve_tick_ms", "serve_prefill_share"} <= set(got)
    json.dumps(sound)
    assert smetrics.m_moe_dropped.value == 0
    assert smetrics.m_moe_routed.labels("here").value > 0
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
    assert "moe_expert_load_max_over_mean" in got


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


def test_moe_mla_decode_step_roofline_reads_the_ticks_routing():
    family = types.SimpleNamespace(
        bytes_per_moe_mla_decode_step=lambda config, hit, latent, riders,
        weight_bytes: 1000 * weight_bytes + 100 * hit + latent + riders)
    cell = types.SimpleNamespace(family=family, config=_BF16)
    ring = {"serve/decode_tick": [
        _record("serve/decode_tick", 1.0, batch=10, cached_tokens=5,
                experts_hit=5, latent_bytes=490),
        _record("serve/decode_tick", 2.0, batch=20, cached_tokens=5,
                experts_hit=15, latent_bytes=1480)]}
    profile = T.Profile({}, {"/device:TPU:0": [
        ("jit__decode_fn_paged(1)", 0, 8000),
        ("jit__prefill_fn_paged(2)", 0, 99999)]}, [])
    run = _planted_run(cell, ring, profile, (0.5, 3.0))
    # ticks of 2000 + 500 + 490 + 10 and 2000 + 1500 + 1480 + 20 bytes:
    # mean 4000 at 1 GB/s is 4 us; the program took 8 us
    reader = _reader("moe_mla_decode_step_roofline")
    assert reader.read(run) == pytest.approx(50.0)
    assert reader.META["share_of_peak"] is True
    # a program from before the attributes (the parent), a family without
    # the count, a trace without the program, no ring: nothing, no error
    old = {"serve/decode_tick": [_record("serve/decode_tick", 1.0, batch=3,
                                         cached_tokens=500)]}
    assert reader.read(_planted_run(cell, old, profile, (0.5, 3.0))) is None
    bare = types.SimpleNamespace(family=types.SimpleNamespace(),
                                 config=_BF16)
    assert reader.read(_planted_run(bare, ring, profile, (0.5, 3.0))) is None
    assert reader.read(_planted_run(cell, ring, T.Profile({}, {}, []),
                                    (0.5, 3.0))) is None
    assert reader.read(_planted_run(cell, None, profile, (0.5, 3.0))) is None


def _kernel_events(head, other_ns=7000):
    kernel = ("%" + head + ".{} = bf16[128,4096]{{1,0}} custom-call("
              "s32[14] %a, bf16[128,7168] %x), "
              'custom_call_target="tpu_custom_call"')
    other = ('%flash_fwd.3 = bf16[8,128]{1,0} custom-call(bf16[8,128] %q), '
             'custom_call_target="tpu_custom_call"')
    return {"/device:TPU:0": [
        (T.short_name(kernel.format(7)), 0, 1500),
        (T.short_name(kernel.format(9)), 2000, 500),
        (T.short_name(other), 3000, other_ns)]}


def test_moe_grouped_matmul_roofline_reads_ticks_and_prefills():
    family = types.SimpleNamespace(
        grouped_matmul_work=lambda config, tokens, hit, weight_bytes:
        (100 * hit * weight_bytes + tokens, 2000 * tokens))
    cell = types.SimpleNamespace(family=family, config=_BF16)
    ring = {"serve/decode_tick": [
                _record("serve/decode_tick", 1.0, expert_tokens=100,
                        experts_hit=2, expert_load_max=60),
                _record("serve/decode_tick", 9.0, expert_tokens=999,
                        experts_hit=9, expert_load_max=1)],
            "serve/prefill": [
                _record("serve/prefill", 2.0, expert_tokens=1000,
                        experts_hit=1, prompt_len=4),
                _record("serve/prefill", 2.5, prompt_len=4)]}
    devices = _kernel_events("moe_grouped_matmul")
    run = _planted_run(cell, ring, T.Profile(devices, {}, []), (0.5, 3.0))
    # the tick: 500 bytes at 1 GB/s = 0.5 us against 200,000 operations at
    # 1 TFLOP/s = 0.2 us: bound by bytes; the prefill: 1200 bytes = 1.2 us
    # against 2 us of operations: bound by those; 2.5 us least, 2 us taken
    reader = _reader("moe_grouped_matmul_roofline")
    assert reader.read(run) == pytest.approx(125.0)
    assert reader.META["share_of_peak"] is True
    no_kernel = T.Profile({"/device:TPU:0": devices["/device:TPU:0"][2:]},
                          {}, [])
    assert reader.read(_planted_run(cell, ring, no_kernel,
                                    (0.5, 3.0))) is None
    old = {"serve/decode_tick": [_record("serve/decode_tick", 1.0,
                                         cached_tokens=3)]}
    assert reader.read(_planted_run(cell, old, T.Profile(devices, {}, []),
                                    (0.5, 3.0))) is None
    assert reader.read(_planted_run(cell, None, T.Profile(devices, {}, []),
                                    (0.5, 3.0))) is None


def test_mla_paged_decode_roofline_reads_latent_bytes_and_the_kernel():
    cell = types.SimpleNamespace(family=types.SimpleNamespace(), config={})
    ring = {"serve/decode_tick": [
        _record("serve/decode_tick", 1.0, latent_bytes=300),
        _record("serve/decode_tick", 2.0, latent_bytes=700),
        _record("serve/decode_tick", 2.5, cached_tokens=1),
        _record("serve/decode_tick", 9.0, latent_bytes=9999)]}
    devices = _kernel_events("mla_paged_decode")
    run = _planted_run(cell, ring, T.Profile(devices, {}, []), (0.5, 3.0))
    # 1000 bytes at 1 GB/s is 1 us; the kernel's two calls took 2 us
    reader = _reader("mla_paged_decode_roofline")
    assert reader.read(run) == pytest.approx(50.0)
    assert reader.META["share_of_peak"] is True
    gather = T.Profile({"/device:TPU:0": devices["/device:TPU:0"][2:]},
                       {}, [])                   # the tick gathers
    assert reader.read(_planted_run(cell, ring, gather, (0.5, 3.0))) is None
    old = {"serve/decode_tick": [_record("serve/decode_tick", 1.0,
                                         cached_tokens=3)]}
    assert reader.read(_planted_run(cell, old, T.Profile(devices, {}, []),
                                    (0.5, 3.0))) is None


def test_moe_expert_load_max_over_mean_reads_the_windows_ticks():
    family = types.SimpleNamespace(
        dims=lambda config: {"G": 4, "L": 3, "Ld": 1})
    cell = types.SimpleNamespace(family=family, config={})
    ring = {"serve/decode_tick": [
        _record("serve/decode_tick", 1.0, expert_tokens=16,
                expert_load_max=4),                      # 4 / (16 / 8) = 2
        _record("serve/decode_tick", 2.0, expert_tokens=8,
                expert_load_max=4),                      # 4 / (8 / 8) = 4
        _record("serve/decode_tick", 3.0, expert_tokens=0,
                expert_load_max=0),                      # routed nothing
        _record("serve/decode_tick", 4.0, cached_tokens=5)]}
    reader = _reader("moe_expert_load_max_over_mean")
    run = _planted_run(cell, ring, None, None)
    assert reader.read(run) == pytest.approx(3.0)
    assert "share_of_peak" not in reader.META
    old = {"serve/decode_tick": [_record("serve/decode_tick", 1.0,
                                         cached_tokens=3)]}
    assert reader.read(_planted_run(cell, old, None, None)) is None
    assert reader.read(_planted_run(cell, None, None, None)) is None
