"""A later PR adds files and entries and edits no file that is there: a
throw-away cell with a configuration, a model family, a traffic mix, a
traffic kind and a per-layer metric of its own arrives as new files in a
copy of the benchmark, and the unchanged command runs it."""
import hashlib
import json
import os
import shutil
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

FAMILY = '''
"""A throw-away family: y = x @ w, trained by plain SGD."""
import jax
import jax.numpy as jnp


def flops_per_token(config, seq_len):
    return 6 * config["width"] ** 2


def bytes_per_decode_step(config, live_lengths, **kw):
    return 2 * config["width"] ** 2


class Program:
    def __init__(self, config, seed):
        n = config["width"]
        self.w = jax.random.normal(jax.random.PRNGKey(seed % 1000), (n, n))
        self.step = jax.jit(lambda w, x: w - 0.01 * jax.grad(
            lambda w: jnp.mean((x @ w) ** 2))(w))

    def free(self):
        self.w = None


def build(config, mode, devices, seed):
    return Program(config, seed)


def reference(config, mode, seed, precision="f32", **kw):
    n = config["width"]
    w = jax.random.normal(jax.random.PRNGKey(seed % 1000), (n, n))
    x = kw["x"]
    g = 2 * x.T @ (x @ w) / (x.shape[0] * n)
    return w - 0.01 * g
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
    while time.monotonic() - t0 < run.seconds:
        with run.spans.span("toy_step"):
            w = program.step(w, x)
        steps += 1
    jax.block_until_ready(w)
    t1 = time.monotonic()
    run.window = (t0, t1)
    run.read_memory_peak()
    run.attempted, run.failed = steps, 0
    run.end_to_end["toy_rows_per_s"] = steps * tr["rows"] / (t1 - t0)
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


def test_new_cell_family_kind_and_metric_arrive_as_new_files(tmp_path):
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(ROOT, "paddle_tpu"), tmp_path / "paddle_tpu")
    before = _digests(tmp_path / "benchmark")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    original = json.loads(json.dumps(manifest))

    bench = tmp_path / "benchmark"
    (bench / "configs" / "toy-net.json").write_text(json.dumps({
        "name": "toy-net", "family": "toy_family", "source": "none: a test",
        "width": 32, "reduced": []}))
    (bench / "families" / "toy_family.py").write_text(FAMILY)
    (bench / "traffic" / "toy_rows.json").write_text(json.dumps({
        "kind": "toy_kind", "rows": 16}))
    (bench / "traffic_kinds" / "toy_kind.py").write_text(KIND)
    (bench / "layer_metrics" / "toy_steps.py").write_text(METRIC)
    (bench / "workloads" / "toy_cell.json").write_text(json.dumps({
        "name": "toy_cell", "config": "toy-net", "traffic": "toy_rows",
        "chips": 1, "limits": {"first_step_max_gap": 1e-5},
        "rehearsal": {}}))
    manifest["configs"].append({
        "name": "toy-net", "source": "none: a test",
        "file": "benchmark/configs/toy-net.json", "reduced": [],
        "why": "a test"})
    manifest["workloads"].append({
        "name": "toy_cell", "config": "toy-net", "traffic": "toy_rows",
        "chips": 1, "why": "a test"})
    manifest["end_to_end"].append({
        "name": "toy_rows_per_s", "unit": "rows/s", "better": "higher",
        "bound": 0.05, "source": "host_clock", "workloads": ["toy_cell"]})
    manifest["per_layer"].append({
        "name": "toy_steps", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "toy layer",
        "moves": "toy_rows_per_s", "workloads": ["toy_cell"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(manifest))

    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_ENABLE_COMPILATION_CACHE="false")
    lines = {}
    for trace in (0, 1):
        proc = subprocess.run(
            [sys.executable, str(bench / "rehearse.py"), "--workload",
             "toy_cell", "--seed", "2147483700", "--seconds", "0.5",
             "--trace", str(trace)], env=env, cwd=tmp_path,
            capture_output=True, text=True, timeout=300)
        assert proc.returncode == 0, proc.stderr[-2000:]
        lines[trace] = json.loads(proc.stdout.strip().splitlines()[-1])
    assert lines[0]["correct"] is True and lines[0]["attempted"] > 0
    assert set(lines[0]["metrics"]) == {"toy_rows_per_s", "setup_s"}
    assert lines[0]["metrics"]["toy_rows_per_s"]["unit"] == "rows/s"
    assert set(lines[1]["metrics"]) == {"toy_steps"}
    assert lines[1]["metrics"]["toy_steps"]["value"] == \
        lines[1]["attempted"]

    # nothing that was there was touched, in the copy or in the manifest
    after = _digests(tmp_path / "benchmark")
    assert {k: after[k] for k in before} == before
    assert len(after) == len(before) + 6
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        assert manifest[group][:len(original[group])] == original[group]
    # and the old cells still resolve beside the new one
    sys.path.insert(0, ROOT)
    from benchmark import harness

    for w in original["workloads"]:
        cell = harness.Cell(str(tmp_path), w["name"])
        assert "toy_steps" not in [m["name"]
                                   for m in cell.metrics("per_layer")]
