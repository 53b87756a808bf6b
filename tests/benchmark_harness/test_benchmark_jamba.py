"""The second family's cell, ``serve_jamba2_3b_closed48``: the source's
sizes pinned here (the configuration file carries its own ``published``
record, which a slip could edit together with the value), the cell's
rehearsal in process with its controls, the family's counts, and the two
readers this cell brought, on planted traces."""
import io
import json
import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402
from benchmark import trace_reduce as T  # noqa: E402

CELL = "serve_jamba2_3b_closed48"
# huggingface.co/ai21labs/AI21-Jamba2-3B/blob/main/config.json, every number
PINNED = {"attn_layer_offset": 7, "attn_layer_period": 14,
          "expert_layer_offset": 1, "expert_layer_period": 2,
          "hidden_size": 2560, "intermediate_size": 8192,
          "mamba_d_conv": 4, "mamba_d_state": 16, "mamba_dt_rank": 160,
          "mamba_expand": 2, "max_position_embeddings": 262144,
          "num_attention_heads": 20, "num_experts": 1,
          "num_experts_per_tok": 1, "num_hidden_layers": 28,
          "num_key_value_heads": 1, "num_logits_to_keep": 1,
          "rms_norm_eps": 1e-06, "vocab_size": 65536}


@pytest.fixture(scope="module")
def cell():
    return harness.Cell(ROOT, CELL)


def _reader(name):
    return harness.load_module(os.path.join(
        ROOT, "benchmark", "layer_metrics", name + ".py"))


def test_published_is_the_sources_own(cell):
    doc = cell.config
    assert doc["family"] == "jamba" and doc["reduced"] == []
    assert doc["model_type"] == "jamba" and doc["tie_word_embeddings"]
    for key, value in PINNED.items():
        assert doc[key] == value == doc["published"][key], key
    assert set(cell.family.WIDTH_KEYS) <= set(PINNED)
    # the deployment's cut is a key of the serving group, not of the model
    assert doc["serving"]["engine"]["max_seq"] == 2048
    assert doc["serving"]["engine"]["prefix_cache"] is False
    entry = [c for c in cell.manifest["configs"]
             if c["name"] == "ai21-jamba2-3b"][0]
    assert entry["reduced"] == [] and entry["source"] == doc["source"]


def test_the_cell_is_the_one_the_issue_names(cell):
    tr = cell.traffic
    assert (tr["kind"], tr["clients"], tr["pool"]) == (
        "serve_closed_loop", 48, 32)
    assert tr["prompt_len"] == {"median": 160, "sigma": 0.8, "min": 32,
                                "max": 1024}
    assert tr["output_len"] == {"median": 160, "sigma": 0.6, "min": 32,
                                "max": 512}
    assert tr["check_samples"] >= 48
    sizes = cell.kind.make_pool(tr)
    assert max(p + o for p, o in sizes) <= max(tr["reference_pads"])
    assert max(o for _, o in sizes) <= tr["reference_rows"]
    ladder = cell.config["serving"]["engine"]["prefill_buckets"]
    assert max(p for p, _ in sizes) <= 1024 < ladder[-1] == 2048
    reported = {m["name"] for g in ("end_to_end", "per_layer")
                for m in cell.metrics(g)}
    assert {"ssm_decode_step_roofline", "ssm_scan_roofline",
            "serve_tick_ms", "serve_idle_unattributed"} <= reported
    assert "decode_step_roofline" not in reported


def test_sizes_reckoned_from_the_published_keys(cell):
    f, c = cell.family, cell.config
    assert f.layer_kinds(c).count("attention") == 2
    assert [i for i, k in enumerate(f.layer_kinds(c))
            if k == "attention"] == [7, 21]
    assert f.param_count(c) == 3_029_337_472          # 6.06 GB in bfloat16
    mamba = sum(a * b for a, b in (
        s if len(s) == 2 else (s[0], 1)
        for s in f.leaf_shapes(c, "mamba").values()))
    assert round(mamba / 1e6, 1) == 104.2
    assert f.kv_bytes_per_token(c) == 1024            # 1 KB a token
    assert f.state_bytes_per_sequence(c) == 26 * (5120 * 16 * 4
                                                  + 5120 * 3 * 2)
    # a tick of 48 riders at 200 cached tokens each
    state = 48 * f.state_bytes_per_sequence(c)
    tick = f.bytes_per_ssm_decode_step(c, 48 * 200, state)
    assert tick == (2 * f.matmul_param_count(c) + 2 * state
                    + 48 * 200 * 1024)
    assert 0.10 < 2 * state / tick < 0.14             # a tenth of the tick
    assert f.scan_bytes(c, 100, 2) == 26 * (
        100 * (4 * 5120 + 2 * 16) * 2 + 2 * 5120 * 16 * 4)


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
    assert sound["attempted"] > 20
    assert sound["device"]["platform"] == "cpu"
    checks = sound["checks"]
    assert checks["served_logits_rel_rms"]["value"] < 1e-6
    # traced, with no device plane: the two readers this cell brought
    # return None and the line leaves them out, as it does for a parent
    # that lacks the spans' new attributes; the program's spans are read
    got = sound["metrics"]
    assert "ssm_decode_step_roofline" not in got
    assert "ssm_scan_roofline" not in got
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


def _planted_run(cell, ring, profile, trace_window):
    run = types.SimpleNamespace(
        cell=cell, window=(0.0, 10.0), trace_window=trace_window,
        peaks={"hbm_bytes_per_s": 1e9}, profile=profile)
    run._program_spans = (ring, None)
    return run


def test_ssm_decode_step_roofline_reads_state_bytes_of_the_ticks():
    from paddle_tpu.observability import spans

    at = spans.monotonic_to_ns
    family = types.SimpleNamespace(
        bytes_per_ssm_decode_step=lambda config, cached, state,
        weight_bytes: 1000 * weight_bytes + 2 * state + cached)
    cell = types.SimpleNamespace(
        family=family,
        config={"serving": {"engine": {"weight_dtype": "bf16"}}})

    def tick(start, cached, **attrs):
        return {"name": "serve/decode_tick", "start_ns": at(start),
                "dur_ns": 100, "attrs": {"cached_tokens": cached, **attrs}}

    ring = {"serve/decode_tick": [tick(1.0, 500, state_bytes=250),
                                  tick(2.0, 1500, state_bytes=750)]}
    profile = T.Profile({}, {"/device:TPU:0": [
        ("jit__decode_fn_paged(1)", 0, 8000),
        ("jit__prefill_fn_paged(2)", 0, 99999)]}, [])
    run = _planted_run(cell, ring, profile, (0.5, 3.0))
    # ticks of 2000 + 500 + 500 and 2000 + 1500 + 1500 bytes: mean 4000
    # at 1 GB/s is 4 us; the program took 8 us
    reader = _reader("ssm_decode_step_roofline")
    assert reader.read(run) == pytest.approx(50.0)
    assert reader.META["share_of_peak"] is True
    # a program from before state_bytes (the parent), a family without the
    # count, a trace without the program: nothing, and no error
    old = {"serve/decode_tick": [tick(1.0, 500)]}
    assert reader.read(_planted_run(cell, old, profile, (0.5, 3.0))) is None
    bare = types.SimpleNamespace(family=types.SimpleNamespace(),
                                 config=cell.config)
    assert reader.read(_planted_run(bare, ring, profile, (0.5, 3.0))) is None
    assert reader.read(_planted_run(cell, ring, T.Profile({}, {}, []),
                                    (0.5, 3.0))) is None
    assert reader.read(_planted_run(cell, None, profile, (0.5, 3.0))) is None


def test_ssm_scan_roofline_reads_scan_tokens_and_the_kernels_by_name():
    from paddle_tpu.observability import spans

    at = spans.monotonic_to_ns
    family = types.SimpleNamespace(
        scan_bytes=lambda config, tokens, sequences:
        10 * tokens + 100 * sequences)
    cell = types.SimpleNamespace(family=family, config={})

    def prefill(start, **attrs):
        return {"name": "serve/prefill", "start_ns": at(start),
                "dur_ns": 100, "attrs": attrs}

    ring = {"serve/prefill": [prefill(1.0, scan_tokens=30),
                              prefill(2.0, scan_tokens=50),
                              prefill(2.5, scan_tokens=0),
                              prefill(9.0, scan_tokens=999)]}
    # on the v5e the Mosaic call and the in-place write of its state are
    # one fusion under the kernel's name (my chip run, PR 29)
    kernel = ("%selective_scan_fwd.{} = f32[26,64,16,5120]{{3,2,1,0}} "
              "fusion(f32[26,64,16,5120] %ssm, bf16[256,5120] %x), "
              "kind=kCustom, calls=%fused_computation.9")
    other = ('%flash_fwd.3 = bf16[8,128]{1,0} custom-call(bf16[8,128] %q), '
             'custom_call_target="tpu_custom_call"')
    devices = {"/device:TPU:0": [
        (T.short_name(kernel.format(7)), 0, 1500),
        (T.short_name(kernel.format(9)), 2000, 500),
        (T.short_name(other), 3000, 7000)]}
    run = _planted_run(cell, ring, T.Profile(devices, {}, []), (0.5, 3.0))
    # two prompts of 30 and 50 tokens inside the traced window: 1000 bytes
    # at 1 GB/s is 1 us; the two scan kernels took 2 us
    reader = _reader("ssm_scan_roofline")
    assert reader.read(run) == pytest.approx(50.0)
    assert reader.META["share_of_peak"] is True
    # no kernel of that name (the parent), no scan_tokens: nothing
    no_kernel = T.Profile({"/device:TPU:0": devices["/device:TPU:0"][2:]},
                          {}, [])
    assert reader.read(_planted_run(cell, ring, no_kernel,
                                    (0.5, 3.0))) is None
    old = {"serve/prefill": [prefill(1.0, prompt_len=30)]}
    assert reader.read(_planted_run(cell, old, T.Profile(devices, {}, []),
                                    (0.5, 3.0))) is None
