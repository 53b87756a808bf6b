"""``paged_decode_roofline`` (PR 42): the equal-heads paged decode kernel's
share of its keys' and values' HBM time, read in the GPT and the delta-rule
serving cell. The reader on planted records, as its sibling kernels'
readers have (tests/benchmark_harness/test_benchmark_cohere2_moe.py)."""
import json
import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402

NAME = "paged_decode_roofline"
CELLS = {"serve_cgpt1p3b_closed14": 24 * 2 * 16 * 128 * 2,
         "serve_olmo_hybrid_7b_l16_closed32": 4 * 2 * 30 * 128 * 2}


def _reader():
    return harness.load_module(os.path.join(
        ROOT, "benchmark", "layer_metrics", NAME + ".py"))


def _run(cell, ticks=(), kernels=()):
    """A run whose ring holds ``ticks`` (attrs of ``serve/decode_tick``)
    and whose device plane holds ``kernels`` ((name, ns)), one after
    another."""
    profile = types.SimpleNamespace(
        devices={"/device:TPU:0": [(name, i * 10 ** 8, d)
                                   for i, (name, d) in enumerate(kernels)]},
        modules={}, spans=[])
    run = types.SimpleNamespace(
        cell=cell, profile=profile, trace_window=(0.0, 1e9),
        peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        counters={}, window=(0.0, 1e9))
    run._program_spans = ({"serve/decode_tick": [
        {"start_ns": 1, "dur_ns": 1, "attrs": a} for a in ticks]}, None)
    return run


@pytest.fixture
def _every_record_in_the_window(monkeypatch):
    from benchmark import program_spans

    monkeypatch.setattr(program_spans, "_within",
                        lambda records, a, b: records)


def test_the_manifest_names_the_metric_and_its_two_cells():
    manifest = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    (entry,) = [m for m in manifest["per_layer"] if m["name"] == NAME]
    assert entry == {"name": NAME, "unit": "%", "better": "higher",
                     "source": "device_trace", "layer": "kernels",
                     "moves": "gap_p90_ms", "workloads": sorted(CELLS)}
    assert manifest["per_layer"][-1] is entry      # appended, nothing moved
    meta = _reader().META
    assert {k: meta[k] for k in ("name", "layer", "unit", "better", "source",
                                 "moves")} == {
        k: entry[k] for k in ("name", "layer", "unit", "better", "source",
                              "moves")}


@pytest.mark.parametrize("name", sorted(CELLS))
def test_a_cached_token_is_the_models_own_heads_in_every_attention_layer(
        name):
    """The GPT cell: 24 layers x 2 x 16 heads of 128 in bfloat16, 196,608
    B; the delta-rule cell: its 4 full layers x 2 x 30 heads of 128,
    61,440 B, none of a padded head row."""
    cell = harness.Cell(ROOT, name)
    assert _reader().kv_bytes_per_token(cell) == CELLS[name]


@pytest.mark.parametrize("name", sorted(CELLS))
def test_the_reader_on_planted_records(name, _every_record_in_the_window):
    cell = harness.Cell(ROOT, name)
    reader = _reader()
    ticks = [{"batch": 14, "cached_tokens": 14_000},
             {"batch": 13, "cached_tokens": 12_000}]
    kernels = [("paged_decode_attention.9", 3_000_000),
               ("paged_decode_attention.4", 2_000_000),
               ("gqa_paged_decode.4", 7_000_000),       # another kernel's
               ("mla_paged_decode.2", 7_000_000),
               ("fusion.136", 9_000_000)]
    least = 26_000 * CELLS[name] / 819e9
    got = reader.read(_run(cell, ticks, kernels))
    assert got == pytest.approx(100 * least / 0.005)
    # the bytes are the values': at the chip's full rate the share is 100,
    # and no count of padding can lift it past that
    flat_out = [("paged_decode_attention.9", int(least * 1e9) + 1)]
    assert 99.99 < reader.read(_run(cell, ticks, flat_out)) <= 100.0
    # where no such operation ran (a tick that gathers, a parent that
    # reads its pages through another kernel) the reader reads nothing
    assert reader.read(_run(cell, ticks, kernels[2:])) is None
    # nor without ticks in the window, nor with records that carry no
    # ``cached_tokens``, nor without a trace
    assert reader.read(_run(cell, [], kernels)) is None
    assert reader.read(_run(cell, [{"batch": 3}], kernels)) is None
    bare = types.SimpleNamespace(profile=None, peaks=None, trace_window=None,
                                 cell=cell)
    bare._program_spans = ({}, None)
    assert reader.read(bare) is None


def test_a_family_without_a_count_reads_nothing(_every_record_in_the_window):
    cell = types.SimpleNamespace(family=types.SimpleNamespace(), config={})
    run = _run(cell, [{"cached_tokens": 5}],
               [("paged_decode_attention.1", 1_000)])
    assert _reader().read(run) is None
