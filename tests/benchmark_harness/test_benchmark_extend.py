"""A later PR adds files and entries and edits no file that is there. What
a ``model_config`` PR brings, here as a throw-away: a family of its own
(with its ``WIDTH_KEYS``), a configuration (with its ``published`` record
and a cut of depth), a traffic kind, a traffic mix, a cell and a per-layer
metric, as new files in a copy of the benchmark; and in the manifest new
entries, plus the cell's name appended to the ``workloads`` list of one
end-to-end metric and of one per-layer metric that are there. The
unchanged command runs the cell, every check of ``benchmark/checks.py``
passes on the copy, and no file that was there has changed."""
import hashlib
import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import checks, harness  # noqa: E402

FAMILY = '''
"""A throw-away family: ``depth`` layers of y = x @ w, trained by plain
SGD. Its program times its own dispatch with the program's tracer, as
``parallelize``'s step does."""
import jax
import jax.numpy as jnp

from paddle_tpu.observability import spans

WIDTH_KEYS = ("width",)


def _weights(config, seed):
    n, depth = config["width"], config["depth"]
    return jax.random.normal(jax.random.PRNGKey(seed % 1000),
                             (depth, n, n)) / n ** 0.5


def _loss(w, x):
    for layer in w:
        x = x @ layer
    return jnp.mean(x ** 2)


class Program:
    def __init__(self, config, seed):
        self.w = _weights(config, seed)
        self._step = jax.jit(lambda w, x: w - 0.01 * jax.grad(_loss)(w, x))

    def step(self, w, x):
        with spans.span("train/step"):
            return self._step(w, x)

    def free(self):
        self.w = None


def build(config, mode, devices, seed):
    return Program(config, seed)


def reference(config, mode, seed, precision="f32", **kw):
    w = _weights(config, seed)
    return w - 0.01 * jax.grad(_loss)(w, kw["x"])
'''

KIND = '''
"""A throw-away traffic kind: steps of the family's program for the
window, the first step compared with the family's reference."""
import time

import jax
import jax.numpy as jnp
import numpy as np


def run(run):
    cfg, tr = run.cell.config, run.cell.traffic
    program = run.cell.family.build(cfg, "train", run.devices, run.seed)
    x = jnp.asarray(np.random.default_rng(run.seed).normal(
        size=(tr["rows"], cfg["width"])), jnp.float32)
    w1 = program.step(program.w, x)
    t0 = run.open_window()
    w, steps = w1, 0
    run.start_trace()
    while time.monotonic() - t0 < run.seconds:
        with run.spans.span("toy_step"):
            w = program.step(w, x)
        steps += 1
    jax.block_until_ready(w)
    t1 = time.monotonic()
    run.stop_trace()
    run.window = (t0, t1)
    run.read_memory_peak()
    run.attempted, run.failed = steps, 0
    # its own end-to-end metric, and one that was there (a row is this
    # family's token)
    run.end_to_end["toy_rows_per_s"] = run.end_to_end[
        "train_tokens_per_s"] = steps * tr["rows"] / (t1 - t0)
    run.counters["toy_steps"] = steps
    ref = run.cell.family.reference(cfg, "train", run.seed, x=x)
    run.check("first_step_max_gap", float(jnp.abs(w1 - ref).max()),
              run.cell.limits["first_step_max_gap"])
'''

METRIC = '''
"""A throw-away per-layer metric: the steps the kind counted."""
META = {"name": "toy_steps", "layer": "toy layer", "unit": "count",
        "better": "higher", "source": "program_counter",
        "moves": "toy_rows_per_s"}


def read(run):
    return run.counters.get("toy_steps")
'''


def _digests(top):
    out = {}
    for dirpath, dirnames, filenames in os.walk(top):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        for f in filenames:
            path = os.path.join(dirpath, f)
            with open(path, "rb") as fh:
                out[os.path.relpath(path, top)] = hashlib.sha1(
                    fh.read()).hexdigest()
    return out


CELL, KIND_NAME = "toy_cell", "train_toy_steps"
# the lists that are there and take the new cell's name
APPENDED = {"end_to_end": "train_tokens_per_s", "per_layer":
            "train_dispatch_ms"}


@pytest.fixture(scope="module")
def extended(tmp_path_factory):
    """A copy of the benchmark with the throw-away cell added. Returns
    (root, the manifest before, the digests before)."""
    root = tmp_path_factory.mktemp("extended")
    shutil.copytree(os.path.join(ROOT, "benchmark"), root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(ROOT, "paddle_tpu"), root / "paddle_tpu")
    before = _digests(root / "benchmark")
    manifest = checks.manifest(ROOT)
    original = json.loads(json.dumps(manifest))

    bench = root / "benchmark"
    (bench / "configs" / "toy-net.json").write_text(json.dumps({
        "name": "toy-net", "family": "toy_family", "source": "none: a test",
        "width": 32, "depth": 2, "published": {"width": 32, "depth": 8},
        "reduced": ["depth"], "reduced_from": {"depth": 8}}))
    (bench / "families" / "toy_family.py").write_text(FAMILY)
    (bench / "traffic" / "toy_rows.json").write_text(json.dumps({
        "kind": KIND_NAME, "rows": 16}))
    (bench / "traffic_kinds" / (KIND_NAME + ".py")).write_text(KIND)
    (bench / "layer_metrics" / "toy_steps.py").write_text(METRIC)
    (bench / "workloads" / (CELL + ".json")).write_text(json.dumps({
        "name": CELL, "config": "toy-net", "traffic": "toy_rows",
        "chips": 1, "limits": {"first_step_max_gap": 1e-5},
        "rehearsal": {}}))
    manifest["configs"].append({
        "name": "toy-net", "source": "none: a test",
        "file": "benchmark/configs/toy-net.json", "reduced": ["depth"],
        "why": "a test"})
    manifest["workloads"].append({
        "name": CELL, "config": "toy-net", "traffic": "toy_rows",
        "chips": 1, "why": "a test"})
    manifest["end_to_end"].append({
        "name": "toy_rows_per_s", "unit": "rows/s", "better": "higher",
        "bound": 0.05, "source": "host_clock", "workloads": [CELL]})
    manifest["per_layer"].append({
        "name": "toy_steps", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "toy layer",
        "moves": "toy_rows_per_s", "workloads": [CELL]})
    for group, name in APPENDED.items():
        (entry,) = [m for m in manifest[group] if m["name"] == name]
        entry["workloads"].append(CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))
    return root, original, before


def _without_appended(manifest):
    """The manifest with the new cell's name taken out of every
    ``workloads`` list again."""
    out = json.loads(json.dumps(manifest))
    for group in ("end_to_end", "per_layer"):
        for m in out[group]:
            if CELL in m.get("workloads", []):
                m["workloads"].remove(CELL)
    return out


def test_the_unchanged_command_runs_the_new_cell(extended):
    root, _, _ = extended
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_ENABLE_COMPILATION_CACHE="false")
    lines = {}
    for trace in (0, 1):
        proc = subprocess.run(
            [sys.executable, str(root / "benchmark" / "rehearse.py"),
             "--workload", CELL, "--seed", "2147483700", "--seconds",
             "0.5", "--trace", str(trace)], env=env, cwd=root,
            capture_output=True, text=True, timeout=300)
        assert proc.returncode == 0, proc.stderr[-2000:]
        lines[trace] = json.loads(proc.stdout.strip().splitlines()[-1])
        # the numbers compared, beside their limits, end standard error
        assert "check first_step_max_gap" in \
            proc.stderr.strip().splitlines()[-1]
    assert lines[0]["correct"] is True and lines[0]["attempted"] > 0
    # its own end-to-end metric, and the one that was there
    assert set(lines[0]["metrics"]) == {"toy_rows_per_s", "setup_s",
                                        "train_tokens_per_s"}
    assert lines[0]["metrics"]["toy_rows_per_s"]["unit"] == "rows/s"
    assert lines[0]["metrics"]["train_tokens_per_s"]["unit"] == "tokens/s"
    # its own per-layer metric, and the one that was there, whose reader
    # finds the new family's ``train/step`` spans in the program's ring
    assert set(lines[1]["metrics"]) == {"toy_steps", "train_dispatch_ms"}
    assert lines[1]["metrics"]["toy_steps"]["value"] == \
        lines[1]["attempted"]
    assert lines[1]["metrics"]["train_dispatch_ms"]["value"] > 0


@pytest.mark.parametrize("check", checks.MANIFEST_CHECKS + (
    checks.program_span_readers_are_in_the_manifest_by_name,),
    ids=lambda f: f.__name__)
def test_every_check_passes_on_the_extended_copy(extended, check):
    check(str(extended[0]))


def test_the_new_cells_traced_rehearsal_passes_the_general_check(extended):
    got = checks.traced_rehearsal_reports_the_program_span_readers(
        str(extended[0]), CELL)
    assert got["train_dispatch_ms"]["value"] > 0


def test_nothing_that_was_there_was_touched(extended):
    root, original, before = extended
    after = _digests(root / "benchmark")
    assert {k: after[k] for k in before} == before
    assert len(after) == len(before) + 6
    manifest = checks.manifest(str(root))
    # entries are appended, and so are names inside the lists that are
    # there: with the new cell's name taken out again, what was there is
    # what it was
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        assert _without_appended(manifest)[group][:len(original[group])] \
            == original[group]
    for group, name in APPENDED.items():
        (entry,) = [m for m in manifest[group] if m["name"] == name]
        (was,) = [m for m in original[group] if m["name"] == name]
        assert entry["workloads"] == was["workloads"] + [CELL]
    # and the old cells still resolve beside the new one, reporting what
    # they did
    for w in original["workloads"]:
        cell = harness.Cell(str(root), w["name"])
        was = harness.Cell(ROOT, w["name"])
        for group in ("end_to_end", "per_layer"):
            assert [m["name"] for m in cell.metrics(group)] == \
                [m["name"] for m in was.metrics(group)]
    new = harness.Cell(str(root), CELL)
    assert [m["name"] for m in new.metrics("per_layer")] == [
        "train_dispatch_ms", "toy_steps"]
