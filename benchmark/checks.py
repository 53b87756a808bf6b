"""What has to hold of a benchmark as a whole, as functions of the directory
that holds it: ``BENCHMARK.json`` against its contract, every name in it
against the file it stands for, every configuration against its own record
of what its source publishes, and each cell's traced rehearsal against what
holds of any family.

The tests call each on the repo; ``test_benchmark_extend.py`` calls them on
a copy to which a cell of another family has been added as new files and
appended names, which is how a later PR finds out before the driver does.
A check fails with an ``AssertionError``.
"""
import io
import os
import re

from benchmark import harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WIDTH_ENDINGS = re.compile(r"(_dim|_rank)$")


def manifest(root):
    return harness.load_json(os.path.join(root, "BENCHMARK.json"))


def _config_doc(root, entry):
    return harness.load_json(os.path.join(root, entry["file"]))


def _family(root, doc):
    return harness.load_module(os.path.join(
        root, "benchmark", "families", doc["family"] + ".py"))


def _one_line(text, limit=200):
    return 1 <= len(text) <= limit and "\n" not in text and "\t" not in text


# ---------------------------------------------------------------------------
# the manifest
# ---------------------------------------------------------------------------

def top_level_keys(root):
    m = manifest(root)
    assert set(m) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert m["command"] == ["python3", "benchmark/run.py"]
    assert m["paths"] == ["benchmark", "tests/benchmark_harness"]
    assert all(PATH.match(p) for p in m["paths"])
    assert isinstance(m["run_seconds"], int)
    assert 1 <= m["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(root, "BENCHMARK.json")) < 64 * 1024


def run_seconds_fits_a_full_check_of_24_cells(root):
    runs = 2 + 14 * 24
    total = runs * (manifest(root)["run_seconds"] + 60) + 24 * 2 * 90 + 1200
    assert total <= 43200


def configs(root):
    m = manifest(root)
    names = [c["name"] for c in m["configs"]]
    assert len(set(names)) == len(names) and 1 <= len(names) <= 24
    files = [c["file"] for c in m["configs"]]
    assert len(set(files)) == len(files)
    used = {w["config"] for w in m["workloads"]}
    for c in m["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used
        assert _one_line(c["source"]) and _one_line(c["why"])
        assert c["file"].startswith("benchmark/configs/")
        assert len(c["reduced"]) <= 16
        doc = _config_doc(root, c)
        assert doc["name"] == c["name"] and doc["source"] == c["source"]
        assert doc["reduced"] == c["reduced"]
        assert all(NAME.match(key) and key in doc for key in c["reduced"])
        assert os.path.isfile(os.path.join(
            root, "benchmark", "families", doc["family"] + ".py"))


def configurations_keep_what_their_source_publishes(root):
    """Every configuration against its own record: ``published`` holds the
    source's value for every shape key the file carries, the family's
    ``WIDTH_KEYS`` among them. A key in ``reduced`` differs from what it
    was cut from, ``reduced_from`` says what that was (the published value,
    where the source publishes the key) and has no other key, and every
    other published key is as published. No cut is a width."""
    for c in manifest(root)["configs"]:
        doc = _config_doc(root, c)
        published, cut_from = doc["published"], doc.get("reduced_from", {})
        widths = _family(root, doc).WIDTH_KEYS
        assert published and set(widths) <= set(published), c["name"]
        assert set(cut_from) == set(c["reduced"]), c["name"]
        for key in c["reduced"]:
            assert key not in widths and not WIDTH_ENDINGS.search(key), \
                (c["name"], key)
            assert doc[key] != cut_from[key], (c["name"], key)
            if key in published:
                assert cut_from[key] == published[key], (c["name"], key)
        for key, value in published.items():
            if key not in c["reduced"]:
                assert doc[key] == value, (c["name"], key)


def workloads(root):
    cells = manifest(root)["workloads"]
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


def every_cell_resolves_by_name(root, rehearsal=False):
    for w in manifest(root)["workloads"]:
        cell = harness.Cell(root, w["name"], rehearsal=rehearsal)
        for key in ("name", "config", "traffic", "chips"):
            assert cell.spec[key] == w[key], (w["name"], key)
        assert callable(cell.kind.run)
        assert callable(cell.family.build)
        assert callable(cell.family.reference)
        e2e = [m["name"] for m in cell.metrics("end_to_end")]
        assert "setup_s" in e2e and len(e2e) >= 2, w["name"]
        assert cell.metrics("per_layer"), w["name"]


def every_cell_resolves_by_name_at_rehearsal_size(root):
    every_cell_resolves_by_name(root, rehearsal=True)


def metrics(root):
    m = manifest(root)
    e2e, layer = m["end_to_end"], m["per_layer"]
    assert 1 <= len(e2e) <= 16 and 1 <= len(layer) <= 128
    names = [x["name"] for x in e2e + layer]
    assert len(set(names)) == len(names)
    cells = {w["name"] for w in m["workloads"]}
    assert "setup_s" in [x["name"] for x in e2e]
    for x in e2e:
        assert set(x) - {"workloads"} == {"name", "unit", "better",
                                          "bound", "source"}
        assert x["source"] in ("host_clock", "device_trace")
        assert 0.01 <= x["bound"] <= 0.1
    for x in layer:
        assert set(x) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}
        assert x["source"] in SOURCES and _one_line(x["layer"])
        assert x["moves"] in [e["name"] for e in e2e]
    for x in e2e + layer:
        assert NAME.match(x["name"]) and UNIT.match(x["unit"])
        assert x["better"] in ("lower", "higher")
        listed = x.get("workloads", [])
        assert len(set(listed)) == len(listed) and set(listed) <= cells
    reporters = {e["name"]: set(e.get("workloads", cells)) for e in e2e}
    for x in layer:    # a layer metric's cells report what it moves
        assert set(x.get("workloads", cells)) <= reporters[x["moves"]], \
            x["name"]


def layer_metric_files_agree_with_the_manifest(root):
    per_layer = manifest(root)["per_layer"]
    for m in per_layer:
        reader = harness.load_module(os.path.join(
            root, "benchmark", "layer_metrics", m["name"] + ".py"))
        for key in ("name", "layer", "unit", "better", "source", "moves"):
            assert reader.META[key] == m[key], (m["name"], key)
        assert callable(reader.read)
        # a share of a peak says so in its own file: the harness refuses a
        # reading over 100 % by that key, whatever the metric is called
        if "roofline" in m["name"] or "mfu" in m["name"]:
            assert reader.META["share_of_peak"] is True and m["unit"] == "%"
    # and no reader is left behind by a metric that went
    here = {f[:-3] for f in os.listdir(os.path.join(
        root, "benchmark", "layer_metrics")) if f.endswith(".py")}
    assert here == {m["name"] for m in per_layer}


def files_under_paths_are_named_from_name_characters(root):
    for top in manifest(root)["paths"]:
        for dirpath, dirnames, filenames in os.walk(os.path.join(root, top)):
            dirnames[:] = [d for d in dirnames if d != "__pycache__"]
            for f in filenames:
                rel = os.path.relpath(os.path.join(dirpath, f), root)
                assert PATH.match(rel), rel


MANIFEST_CHECKS = (
    top_level_keys, run_seconds_fits_a_full_check_of_24_cells, configs,
    configurations_keep_what_their_source_publishes, workloads,
    every_cell_resolves_by_name,
    every_cell_resolves_by_name_at_rehearsal_size, metrics,
    layer_metric_files_agree_with_the_manifest,
    files_under_paths_are_named_from_name_characters)


# ---------------------------------------------------------------------------
# the readers of the program's own spans
# ---------------------------------------------------------------------------

# PR 26's eight, by name, with where each number comes from
PROGRAM_SPAN_READERS = {
    "serve_queue_wait_ms": "program_span",
    "serve_sched_self_ms": "program_span",
    "serve_tick_ms": "program_span",
    "serve_logits_fetch_ms": "program_span",
    "serve_prefill_share": "program_span",
    "serve_host_gap_ms": "device_trace",
    "serve_idle_unattributed": "device_trace",
    "train_dispatch_ms": "program_span"}


def traffic_kind(root, cell_entry):
    return harness.load_json(os.path.join(
        root, "benchmark", "traffic", cell_entry["traffic"] + ".json"))["kind"]


def program_span_readers_are_in_the_manifest_by_name(root):
    """Each of the eight is there, wherever in the list, with its reader
    and its source, and every cell it lists drives the path its name
    promises: the traffic kind's name starts as the metric's does
    (``serve_`` / ``train_``)."""
    m = manifest(root)
    cells = {w["name"]: w for w in m["workloads"]}
    by_name = {x["name"]: x for x in m["per_layer"]}
    for name, source in PROGRAM_SPAN_READERS.items():
        assert name in by_name, name
        entry = by_name[name]
        assert entry["source"] == source, name
        assert os.path.isfile(os.path.join(
            root, "benchmark", "layer_metrics", name + ".py")), name
        assert entry["workloads"], name
        path = name.split("_")[0] + "_"
        for cell in entry["workloads"]:
            assert traffic_kind(root, cells[cell]).startswith(path), \
                (name, cell)


def traced_rehearsal_reports_the_program_span_readers(root, cell,
                                                      seed=2 ** 31 + 11):
    """The cell's traced rehearsal: every ``program_span`` metric of the
    whole manifest that lists the cell is reported and positive (prefill's
    share may be 0), and what holds between them for any family does.
    Returns the reported metrics."""
    result = harness.run_cell(root, cell, seed, 1.0, 1, rehearsal=True,
                              out=io.StringIO())
    got = result["metrics"]
    want = [m["name"] for m in manifest(root)["per_layer"]
            if cell in m.get("workloads", []) and
            m["source"] == "program_span"]
    for name in want:
        assert name in got, (name, sorted(got))
        assert got[name]["value"] > 0 or name == "serve_prefill_share", \
            (name, got[name])
    # the CPU has no device plane: the readers of the trace report nothing
    assert "serve_host_gap_ms" not in got
    assert "serve_idle_unattributed" not in got

    def value(name):
        return got[name]["value"] if name in got else None

    if value("serve_prefill_share") is not None:
        assert 0 <= value("serve_prefill_share") < 100
    if None not in (value("serve_logits_fetch_ms"), value("serve_tick_ms")):
        assert value("serve_logits_fetch_ms") < value("serve_tick_ms")
    if None not in (value("train_dispatch_ms"), value("train_step_ms")):
        assert value("train_dispatch_ms") <= 1.5 * value("train_step_ms")
    return got
