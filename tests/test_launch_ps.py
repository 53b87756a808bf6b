"""distributed.launch_ps: the PS-cluster launcher spawns real pserver +
trainer processes of a fleet script over the PADDLE_* env contract
(reference python/paddle/distributed/launch_ps.py)."""
import os
import sys

from paddle_tpu.distributed import cloud_utils, fs_wrapper, launch_ps


def test_parse_args_reference_cli_shape():
    a = launch_ps.parse_args(["--worker_num", "3", "--server_num", "1",
                              "train.py", "--epochs", "2"])
    assert a.worker_num == 3 and a.server_num == 1
    assert a.training_script == "train.py"
    assert a.training_script_args == ["--epochs", "2"]


def test_launch_ps_end_to_end(tmp_path):
    script = os.path.join(os.path.dirname(__file__),
                          "ps_launch_script.py")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    servers, trainers = launch_ps.start_procs(
        worker_num=2, server_num=1, training_script=script,
        log_dir=str(tmp_path), env=env)
    rc = launch_ps.wait_procs(servers, trainers, timeout=240)
    assert rc == 0, [open(os.path.join(str(tmp_path), f)).read()[-800:]
                     for f in os.listdir(str(tmp_path))]
    logs = "".join(open(os.path.join(str(tmp_path), f)).read()
                   for f in os.listdir(str(tmp_path)))
    assert logs.count("TRAINER_DONE") == 2, logs[-1000:]


def test_cloud_utils_and_fs_wrapper(monkeypatch):
    monkeypatch.setenv("PADDLE_TRAINERS_NUM", "4")
    assert cloud_utils.get_trainers_num() == 4
    monkeypatch.setenv("PADDLE_TRAINERS", "10.0.0.1,10.0.0.2")
    monkeypatch.setenv("POD_IP", "10.0.0.2")
    monkeypatch.setenv("PADDLE_PORT", "6170")
    c = cloud_utils.get_cloud_cluster()
    assert c["nranks"] == 2 and c["rank"] == 1
    assert c["current_endpoint"] == "10.0.0.2:6170"
    fs = fs_wrapper.LocalFS()
    assert hasattr(fs, "ls") and hasattr(fs, "mkdirs")


def test_cloud_cluster_rejects_unknown_pod_ip(monkeypatch):
    import pytest
    monkeypatch.setenv("PADDLE_TRAINERS", "10.0.0.1, 10.0.0.2")
    monkeypatch.setenv("POD_IP", "10.9.9.9")
    monkeypatch.setenv("PADDLE_PORT", "6170")
    with pytest.raises(ValueError, match="not in the cluster"):
        cloud_utils.get_cloud_cluster()
    # comma+space list parses without empty segments
    monkeypatch.setenv("POD_IP", "10.0.0.2")
    c = cloud_utils.get_cloud_cluster()
    assert c["nranks"] == 2 and c["rank"] == 1
