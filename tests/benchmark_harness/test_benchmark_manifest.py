"""BENCHMARK.json against its contract, and every name in it against the
file it stands for."""
import json
import os
import re
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_top_level_keys(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert manifest["command"] == ["python3", "benchmark/run.py"]
    assert manifest["paths"] == ["benchmark", "tests/benchmark_harness"]
    assert all(PATH.match(p) for p in manifest["paths"])
    assert isinstance(manifest["run_seconds"], int)
    assert 1 <= manifest["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024


def test_run_seconds_fits_a_full_check_of_24_cells(manifest):
    runs = 2 + 14 * 24
    total = runs * (manifest["run_seconds"] + 60) + 24 * 2 * 90 + 1200
    assert total <= 43200


def _one_line(text, limit=200):
    return 1 <= len(text) <= limit and "\n" not in text and "\t" not in text


def test_configs(manifest):
    names = [c["name"] for c in manifest["configs"]]
    assert len(set(names)) == len(names) and 1 <= len(names) <= 24
    files = [c["file"] for c in manifest["configs"]]
    assert len(set(files)) == len(files)
    used = {w["config"] for w in manifest["workloads"]}
    for c in manifest["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used
        assert _one_line(c["source"]) and _one_line(c["why"])
        assert c["file"].startswith("benchmark/configs/")
        assert len(c["reduced"]) <= 16
        doc = harness.load_json(os.path.join(ROOT, c["file"]))
        assert doc["name"] == c["name"] and doc["source"] == c["source"]
        assert doc["reduced"] == c["reduced"]
        for key in c["reduced"]:
            assert NAME.match(key) and key in doc
            # never a width
            assert not re.search(r"(_dim|_rank)$", key)
            assert key not in ("n_embd", "n_inner", "n_head")
        assert os.path.isfile(os.path.join(
            ROOT, "benchmark", "families", doc["family"] + ".py"))


def test_published_widths_are_kept(manifest):
    """Both configurations carry Cerebras-GPT-1.3B's published sizes; only
    the depth of the training one is cut."""
    published = {"n_embd": 2048, "n_head": 16, "n_inner": 8192,
                 "vocab_size": 50257, "n_positions": 2048, "n_layer": 24}
    for c in manifest["configs"]:
        doc = harness.load_json(os.path.join(ROOT, c["file"]))
        for key, value in published.items():
            if key in c["reduced"]:
                assert doc[key] != value
                assert doc["reduced_from"][key] == value
            else:
                assert doc[key] == value, (c["name"], key)
        # every cut says what it was cut from, the deployment's too
        assert set(doc.get("reduced_from", {})) == set(c["reduced"])


def test_workloads(manifest):
    cells = manifest["workloads"]
    names = [w["name"] for w in cells]
    assert len(set(names)) == len(names) and 1 <= len(names) <= 24
    pairs = [(w["config"], w["traffic"]) for w in cells]
    assert len(set(pairs)) == len(pairs)
    four = [w for w in cells if w["chips"] == 4]
    assert len(four) <= max(1, len(cells) // 4)
    for w in cells:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert all(NAME.match(w[k]) for k in ("name", "config", "traffic"))
        assert w["chips"] in (1, 4) and _one_line(w["why"])


@pytest.mark.parametrize("rehearsal", [False, True])
def test_every_cell_resolves_by_name(manifest, rehearsal):
    for w in manifest["workloads"]:
        cell = harness.Cell(ROOT, w["name"], rehearsal=rehearsal)
        assert cell.spec["name"] == w["name"]
        assert cell.spec["config"] == w["config"]
        assert cell.spec["traffic"] == w["traffic"]
        assert cell.spec["chips"] == w["chips"]
        assert hasattr(cell.kind, "run")
        for fn in ("build", "reference", "flops_per_token",
                   "bytes_per_decode_step"):
            assert hasattr(cell.family, fn)
        e2e = [m["name"] for m in cell.metrics("end_to_end")]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert cell.metrics("per_layer")


def test_metrics(manifest):
    e2e, layer = manifest["end_to_end"], manifest["per_layer"]
    assert 1 <= len(e2e) <= 16 and 1 <= len(layer) <= 128
    names = [m["name"] for m in e2e + layer]
    assert len(set(names)) == len(names)
    cells = {w["name"] for w in manifest["workloads"]}
    assert "setup_s" in [m["name"] for m in e2e]
    for m in e2e:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in layer:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}
        assert m["source"] in SOURCES and _one_line(m["layer"])
        assert m["moves"] in [e["name"] for e in e2e]
    for m in e2e + layer:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", [])) <= cells
    reporters = {e["name"]: set(e.get("workloads", cells)) for e in e2e}
    for m in layer:    # a layer metric's cells report what it moves
        assert set(m.get("workloads", cells)) <= reporters[m["moves"]]


def test_layer_metric_files_agree_with_the_manifest(manifest):
    for m in manifest["per_layer"]:
        reader = harness.load_module(os.path.join(
            ROOT, "benchmark", "layer_metrics", m["name"] + ".py"))
        for key in ("name", "layer", "unit", "better", "source", "moves"):
            assert reader.META[key] == m[key], (m["name"], key)
        assert callable(reader.read)
        # a share of a peak says so in its own file: the harness refuses a
        # reading over 100 % by that key, whatever the metric is called
        if "roofline" in m["name"] or "mfu" in m["name"]:
            assert reader.META["share_of_peak"] is True and m["unit"] == "%"


def test_a_share_of_a_peak_over_100_is_refused_whatever_its_name(
        monkeypatch):
    import types

    def reader(share, value):
        return types.SimpleNamespace(
            META={"name": "busy_part", "share_of_peak": share},
            read=lambda run: value)

    entry = {"name": "busy_part", "unit": "%"}
    run = types.SimpleNamespace(cell=types.SimpleNamespace(
        root=ROOT, metrics=lambda group: [entry]))
    monkeypatch.setattr(harness, "load_module", lambda path: reader(True,
                                                                    101.0))
    with pytest.raises(harness.Refused, match="of a peak"):
        harness.read_layer_metrics(run)
    monkeypatch.setattr(harness, "load_module", lambda path: reader(True,
                                                                    99.0))
    assert harness.read_layer_metrics(run)["busy_part"]["value"] == 99.0
    monkeypatch.setattr(harness, "load_module", lambda path: reader(False,
                                                                    101.0))
    assert harness.read_layer_metrics(run)["busy_part"]["value"] == 101.0


def test_files_under_paths_are_named_from_name_characters(manifest):
    for top in manifest["paths"]:
        for dirpath, dirnames, filenames in os.walk(os.path.join(ROOT, top)):
            dirnames[:] = [d for d in dirnames if d != "__pycache__"]
            for f in filenames:
                rel = os.path.relpath(os.path.join(dirpath, f), ROOT)
                assert PATH.match(rel), rel


def test_peaks_table_knows_the_v5e_and_refuses_the_unknown():
    v5e = harness.load_peaks("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9 and v5e["hbm_bytes"] == 16e9
    with pytest.raises(harness.Refused):
        harness.load_peaks("TPU v9 imaginary")
