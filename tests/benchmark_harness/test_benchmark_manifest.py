"""BENCHMARK.json against its contract, every name in it against the file
it stands for, and every configuration against its own record of what its
source publishes. The checks are functions of a root directory
(``benchmark/checks.py``): here they run on the repo, in
``test_benchmark_extend.py`` on a copy with a cell of another family."""
import json
import os
import shutil
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import checks, harness  # noqa: E402

# what the sources publish, written down here and not read from the files:
# the general check holds a file to its own ``published``, this one holds
# ``published`` to the source
PUBLISHED = {
    "cerebras-gpt-1.3b": {"n_embd": 2048, "n_head": 16, "n_inner": 8192,
                          "vocab_size": 50257, "n_positions": 2048,
                          "n_layer": 24},
    "cerebras-gpt-1.3b-l6": {"n_embd": 2048, "n_head": 16, "n_inner": 8192,
                             "vocab_size": 50257, "n_positions": 2048,
                             "n_layer": 24},
}


@pytest.mark.parametrize("check", checks.MANIFEST_CHECKS,
                         ids=lambda f: f.__name__)
def test_manifest(check):
    check(ROOT)


@pytest.mark.parametrize("name", sorted(PUBLISHED))
def test_published_is_the_sources_own(name):
    (entry,) = [c for c in checks.manifest(ROOT)["configs"]
                if c["name"] == name]
    doc = harness.load_json(os.path.join(ROOT, entry["file"]))
    assert doc["published"] == PUBLISHED[name]
    # only the depth of the training one is cut, and only deployment sizes
    # of the serving one
    assert set(entry["reduced"]) & set(PUBLISHED[name]) == (
        {"n_layer"} if name.endswith("-l6") else set())


def test_the_pinned_configurations_are_in_the_manifest():
    # a later configuration brings a pinned record of its own, in a test
    # file of its own: this one holds the two that are here to theirs
    assert set(PUBLISHED) <= {c["name"]
                              for c in checks.manifest(ROOT)["configs"]}


# ---------------------------------------------------------------------------
# the general check can fail
# ---------------------------------------------------------------------------

@pytest.fixture()
def copy(tmp_path):
    """The manifest and its configurations and families, to break."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for sub in ("configs", "families"):
        shutil.copytree(os.path.join(ROOT, "benchmark", sub),
                        tmp_path / "benchmark" / sub,
                        ignore=shutil.ignore_patterns("__pycache__"))
    return tmp_path


def _edit(root, config, change):
    path = root / "benchmark" / "configs" / (config + ".json")
    doc = json.loads(path.read_text())
    change(doc)
    path.write_text(json.dumps(doc))
    manifest = json.loads((root / "BENCHMARK.json").read_text())
    for c in manifest["configs"]:
        if c["name"] == config:
            c["reduced"] = doc["reduced"]
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))


def _cut_a_width(doc):
    doc["n_inner"] = 4096
    doc["reduced"].append("n_inner")
    doc["reduced_from"]["n_inner"] = 8192


def _cut_a_latent_rank(doc):
    doc["published"]["kv_lora_rank"] = 512
    doc["kv_lora_rank"] = 128
    doc["reduced"].append("kv_lora_rank")
    doc["reduced_from"]["kv_lora_rank"] = 512


def _cut_unlisted(doc):
    doc["vocab_size"] = 32768


def _cut_without_its_origin(doc):
    del doc["reduced_from"]["n_layer"]


def _cut_from_another_value(doc):
    doc["reduced_from"]["n_layer"] = 32


def _cut_that_cuts_nothing(doc):
    doc["n_layer"] = 24


def _origin_of_no_cut(doc):
    doc["reduced_from"]["n_positions"] = 4096


def _no_record(doc):
    del doc["published"]


def _record_without_a_width(doc):
    del doc["published"]["n_head"]


@pytest.mark.parametrize("change", [
    _cut_a_width, _cut_a_latent_rank, _cut_unlisted, _cut_without_its_origin,
    _cut_from_another_value, _cut_that_cuts_nothing, _origin_of_no_cut,
    _no_record, _record_without_a_width], ids=lambda f: f.__name__)
def test_a_configuration_that_leaves_its_record_is_refused(copy, change):
    checks.configurations_keep_what_their_source_publishes(str(copy))
    _edit(copy, "cerebras-gpt-1.3b-l6", change)
    with pytest.raises((AssertionError, KeyError)):
        checks.configurations_keep_what_their_source_publishes(str(copy))


def test_a_reader_whose_metric_went_is_refused(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmark", "layer_metrics"),
                    tmp_path / "benchmark" / "layer_metrics",
                    ignore=shutil.ignore_patterns("__pycache__"))
    checks.layer_metric_files_agree_with_the_manifest(str(tmp_path))
    manifest = json.loads((tmp_path / "BENCHMARK.json").read_text())
    gone = manifest["per_layer"].pop()
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(manifest))
    with pytest.raises(AssertionError):
        checks.layer_metric_files_agree_with_the_manifest(str(tmp_path))
    os.remove(tmp_path / "benchmark" / "layer_metrics" /
              (gone["name"] + ".py"))
    checks.layer_metric_files_agree_with_the_manifest(str(tmp_path))


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


def test_peaks_table_knows_the_v5e_and_refuses_the_unknown():
    v5e = harness.load_peaks("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9 and v5e["hbm_bytes"] == 16e9
    with pytest.raises(harness.Refused):
        harness.load_peaks("TPU v9 imaginary")
