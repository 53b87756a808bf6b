"""The command end to end: the rehearsal entry at tiny sizes on the CPU for
both cells, traced and not; the measuring entry's refusals."""
import io
import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)
CELLS = [w["name"] for w in MANIFEST["workloads"]]
ENV = dict(os.environ, JAX_PLATFORMS="cpu",
           JAX_ENABLE_COMPILATION_CACHE="false")


def _last_line(text):
    return json.loads([x for x in text.splitlines() if x.strip()][-1])


def _check_line(result, cell, trace):
    assert set(result) - {"breakdown"} == set(harness.RESULT_KEYS)
    assert result["correct"] is True
    assert result["attempted"] > 0 and result["failed"] == 0
    assert list(result)[-1] == "checks"
    assert result["device"]["platform"] == "cpu"       # stamped, no record
    assert set(result["device"]) >= {"platform", "kind", "count",
                                     "memory_peak_bytes"}
    group = "per_layer" if trace else "end_to_end"
    declared = {m["name"]: m["unit"] for m in MANIFEST[group]
                if cell in m.get("workloads", [cell])}
    assert result["metrics"], "no metric reported"
    for name, m in result["metrics"].items():
        assert set(m) == {"value", "unit"} and m["unit"] == declared[name]
        assert isinstance(m["value"], float)
    if not trace:
        assert set(result["metrics"]) == set(declared)
        assert all(m["value"] > 0 for m in result["metrics"].values())
    else:
        # the CPU has no device plane: device metrics are left out, never
        # written from a host number
        assert not [n for n in result["metrics"]
                    if "idle" in n or "roofline" in n or "mfu" in n]


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_in_process(cell, trace):
    out = io.StringIO()
    result = harness.run_cell(ROOT, cell, 2 ** 31 + 7, 1.0, trace,
                              rehearsal=True, out=out)
    assert _last_line(out.getvalue()) == result
    _check_line(result, cell, trace)
    # every number compared is printed beside its limit, and is in the
    # result line under the key that comes last there
    checks = [x for x in out.getvalue().splitlines() if "check " in x]
    assert checks and all("(limit" in x for x in checks)
    assert list(result)[-1] == "checks"
    assert len(result["checks"]) == len(checks)
    assert all(set(c) == {"value", "limit"} and c["value"] <= c["limit"]
               for c in result["checks"].values())
    # the trace directory is this process's own (another worker may be
    # tracing the same cell in the same checkout), and it is gone
    assert not os.path.exists(os.path.join(
        ROOT, ".bench_trace", f"{cell}.{os.getpid()}"))


def test_rehearsal_command_last_line_has_exactly_the_contracts_keys():
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "rehearse.py"),
         "--workload", CELLS[0], "--seed", "3000000001", "--seconds", "1",
         "--trace", "0"], env=ENV, capture_output=True, text=True,
        timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    result = _last_line(proc.stdout)
    assert set(result) == set(harness.RESULT_KEYS)
    _check_line(result, CELLS[0], 0)


@pytest.mark.parametrize("cell", CELLS)
def test_measuring_command_refuses_a_backend_that_is_no_tpu(cell):
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", cell, "--seed", "1", "--seconds", "1", "--trace",
         "0"], env=ENV, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 2
    assert "not a TPU" in proc.stderr
    assert "{" not in proc.stdout            # no result line


def test_a_cell_that_wants_more_chips_than_there_are_is_refused():
    import jax

    with pytest.raises(harness.Refused, match="chips"):
        harness.pick_devices(len(jax.devices()) + 1, rehearsal=True)
    with pytest.raises(harness.Refused, match="not a TPU"):
        harness.pick_devices(1, rehearsal=False)
    assert len(harness.pick_devices(4, rehearsal=True)) == 4


def test_refuses_a_directory_that_holds_only_the_benchmark(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for top in MANIFEST["paths"]:
        shutil.copytree(os.path.join(ROOT, top), tmp_path / top,
                        ignore=shutil.ignore_patterns("__pycache__"))
    proc = subprocess.run(
        [sys.executable, str(tmp_path / "benchmark" / "run.py"),
         "--workload", CELLS[0], "--seed", "1", "--seconds", "1",
         "--trace", "0"], env=ENV, cwd=tmp_path, capture_output=True,
        text=True, timeout=300)
    assert proc.returncode != 0
    assert "{" not in proc.stdout


def test_unknown_workload_is_refused_without_a_result(capsys):
    assert harness.main(["--workload", "no_such_cell", "--seed", "1",
                         "--seconds", "1", "--trace", "0"],
                        rehearsal=True) == 2
    assert "{" not in capsys.readouterr().out
