"""Elastic training (ISSUE 7, docs/elastic.md): crash-safe checkpoint
store (commit markers, integrity manifest, retention), dp=8 -> dp=4
reshard-on-restore bit-parity, preemption-tolerant train loops, and the
supervised launcher (graceful shutdown, exit-code propagation, restarts
with backoff)."""
import json
import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

import jax

from paddle_tpu.models import gpt as G
from paddle_tpu.parallel import parallelize as PZ
from paddle_tpu.parallel.checkpoint import (
    CheckpointCorruptError, CheckpointError, ElasticCheckpointer,
    ShardedCheckpointer, build_restore_broadcast_program, reshard_flat,
    restore_train_state,
)
import importlib

# the package re-exports the launch() FUNCTION under the module's name, so
# plain attribute import would shadow the module
launch_mod = importlib.import_module("paddle_tpu.parallel.launch")

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
needs_8dev = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs 8 (virtual) devices")


def _small_state(seed=0):
    rng = np.random.default_rng(seed)
    return {"params": {"w": rng.standard_normal((8, 4)).astype(np.float32),
                       "b": rng.standard_normal((4,)).astype(np.float32)},
            "opt": {"step": np.int32(3)}}


def _tree_equal(a, b):
    for x, y in zip(jax.tree_util.tree_leaves(a),
                    jax.tree_util.tree_leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ---------------------------------------------------------------------------
# Store semantics: commit markers, corruption, retention
# ---------------------------------------------------------------------------

def test_midsave_kill_never_selected(tmp_path):
    """A step directory without its COMMIT marker (killed mid-save) is
    invisible to step selection and swept by GC."""
    ck = ElasticCheckpointer(tmp_path / "ckpt", use_async=False)
    ck.save(1, _small_state())
    # simulate a mid-save kill at a later step: leaves on disk, no COMMIT
    partial = tmp_path / "ckpt" / "step_00000005" / "leaves"
    partial.mkdir(parents=True)
    (partial / "leaf_0.bin").write_bytes(b"\x00" * 64)
    assert ck.all_steps() == [1]
    assert ck.latest_step() == 1
    assert ck.latest_valid_step() == 1
    state, man = ck.restore()
    assert man["step"] == 1
    # restore reconstructs the saved nested-dict structure
    _tree_equal(state, _small_state())
    removed = ck.gc()
    assert any("step_00000005" in p for p in removed)
    assert not (tmp_path / "ckpt" / "step_00000005").exists()


def test_corrupt_shard_detected_with_clear_message(tmp_path):
    ck = ElasticCheckpointer(tmp_path / "ckpt", use_async=False)
    ck.save(1, _small_state(0))
    ck.save(2, _small_state(1))
    # truncate one shard of the newest step
    shard = tmp_path / "ckpt" / "step_00000002" / "leaves" / "leaf_0.bin"
    shard.write_bytes(shard.read_bytes()[:2])
    problems = ck.verify(2)
    assert problems and "truncated" in problems[0]
    with pytest.raises(CheckpointCorruptError) as ei:
        ck.restore(2)
    assert "leaf_0.bin" in str(ei.value) and "step 2" in str(ei.value)
    # bit-flip (same size) is caught by the crc
    shard2 = tmp_path / "ckpt" / "step_00000001" / "leaves" / "leaf_1.bin"
    data = bytearray(shard2.read_bytes())
    data[0] ^= 0xFF
    shard2.write_bytes(bytes(data))
    assert any("checksum mismatch" in p for p in ck.verify(1))
    # selection falls back to the newest step that verifies clean
    ck.save(3, _small_state(2))
    assert ck.latest_valid_step() == 3


def test_keep_last_retention_and_async_snapshot(tmp_path):
    ck = ElasticCheckpointer(tmp_path / "ckpt", use_async=True, keep_last=2)
    state = _small_state()
    for step in range(1, 5):
        ck.save(step, state)
        # async-safety: mutating the caller's buffer after save() must not
        # corrupt the in-flight write (the snapshot happened in save)
        state["params"]["w"] += 1.0
    ck.wait()
    assert ck.all_steps() == [3, 4]
    raw, _ = ck.restore(4)
    # step 4 snapshot was taken when w had been incremented 3 times
    expect = _small_state()["params"]["w"]
    for _ in range(3):
        expect += 1.0    # same f32 rounding sequence as the loop
    np.testing.assert_array_equal(raw["params"]["w"], expect)
    ck.close()


def test_sharded_checkpointer_skips_uncommitted(tmp_path):
    ck = ShardedCheckpointer(tmp_path / "ckpt", use_async=False)
    ck.save(1, {"a": np.arange(4, dtype=np.float32)})
    # uncommitted debris: a step dir without orbax's _CHECKPOINT_METADATA
    (tmp_path / "ckpt" / "step_00000002" / "d").mkdir(parents=True)
    # and an orbax tmp dir
    (tmp_path / "ckpt" / "step_00000003.orbax-checkpoint-tmp-9").mkdir()
    assert ck.all_steps() == [1]
    assert ck.latest_step() == 1
    with pytest.raises(CheckpointError):
        ck.restore(2, None)
    removed = ck.gc()
    assert len(removed) == 2
    # keep_last retention through save()
    for step in (4, 5, 6):
        ck.save(step, {"a": np.arange(4, dtype=np.float32)}, force=True,
                keep_last=2)
    assert ck.all_steps() == [5, 6]
    ck.close()


# ---------------------------------------------------------------------------
# Reshard-on-restore
# ---------------------------------------------------------------------------

def test_reshard_flat_pure():
    from paddle_tpu.parallel.comm_opt import build_bucket_layout

    shapes = [((24,), np.float32), ((8,), np.float32), ((40,), np.float32)]
    lay8 = build_bucket_layout(shapes, ranks=8, cap_bytes=1 << 7)
    lay4 = build_bucket_layout(shapes, ranks=4, cap_bytes=1 << 20)
    rng = np.random.default_rng(0)
    leaves = [rng.standard_normal(s[0]).astype(np.float32) for s in shapes]

    def pack(lay, repl):
        parts = []
        for b in lay.buckets:
            for idx, _sh, n in b.entries:
                parts.append(leaves[idx])
            parts.append(np.zeros((b.pad,), np.float32))
        flat = np.concatenate(parts)
        sl = lay.shard_len
        return np.concatenate([np.tile(flat[d * sl:(d + 1) * sl], repl)
                               for d in range(lay.ranks)])

    v8 = pack(lay8, 2)   # dp=8, pp*tp=2
    v4 = pack(lay4, 1)
    got = reshard_flat(v8, lay8, lay4, src_repl=2, dst_repl=1)
    np.testing.assert_array_equal(got, v4)
    # and back
    np.testing.assert_array_equal(
        reshard_flat(v4, lay4, lay8, src_repl=1, dst_repl=2), v8)
    # mismatched leaf sets raise
    lay_other = build_bucket_layout(shapes[:2], ranks=4, cap_bytes=1 << 20)
    with pytest.raises(CheckpointError):
        reshard_flat(v8, lay8, lay_other, src_repl=2)


@needs_8dev
def test_dp8_save_dp4_restore_bit_parity(tmp_path):
    """The acceptance bar: a save at dp=8 restores at dp=4 with every
    param leaf AND the dp-sharded flat moments bit-exact."""
    cfg = G.GPT_TINY.scaled(num_layers=2)
    p8 = PZ.ParallelConfig(dp=8, pp=1, tp=1, microbatches=1)
    mesh8 = PZ.build_mesh(p8)
    params, opt = PZ.init_sharded(jax.random.PRNGKey(0), cfg, p8, mesh8,
                                  grad_reduce="reduce_scatter")
    step8 = PZ.make_train_step(cfg, p8, mesh8, lr=1e-2,
                               grad_reduce="reduce_scatter")
    rng = np.random.default_rng(0)
    toks = rng.integers(0, cfg.vocab_size, (1, 8, 16), dtype=np.int32)
    labs = rng.integers(0, cfg.vocab_size, (1, 8, 16), dtype=np.int32)
    params, opt, loss8, _ = step8(params, opt, toks, labs)
    lay8, repl8 = PZ.rs_param_layout(cfg, p8)

    ck = ElasticCheckpointer(tmp_path / "ckpt", use_async=True)
    ck.save(1, {"params": params, "opt": opt},
            mesh={"dp": 8, "pp": 1, "tp": 1},
            layout=lay8, layout_repl=repl8)
    ck.wait()
    man = ck.manifest(1)
    assert man["layout"]["ranks"] == 8 and man["mesh"]["dp"] == 8

    p4 = PZ.ParallelConfig(dp=4, pp=1, tp=1, microbatches=1)
    mesh4 = PZ.build_mesh(p4)
    params4, opt4 = PZ.init_sharded(jax.random.PRNGKey(7), cfg, p4, mesh4,
                                    grad_reduce="reduce_scatter")
    lay4, repl4 = PZ.rs_param_layout(cfg, p4)
    rp, ro, _man = restore_train_state(ck, params4, opt4,
                                       layout=lay4, layout_repl=repl4)
    # params: bit-exact, placed under the dp=4 mesh
    _tree_equal(params, rp)
    assert dict(jax.tree_util.tree_leaves(rp)[0].sharding.mesh.shape) == \
        dict(mesh4.shape)
    # moments: reshard the restored dp=4 buffer BACK to the dp=8 layout and
    # compare bitwise against the original
    for key in ("m", "v"):
        back = reshard_flat(np.asarray(ro[key]), lay4, lay8,
                            src_repl=repl4, dst_repl=repl8)
        np.testing.assert_array_equal(back, np.asarray(opt[key]))
    assert int(ro["step"]) == int(opt["step"])
    # the restored state trains at dp=4
    step4 = PZ.make_train_step(cfg, p4, mesh4, lr=1e-2,
                               grad_reduce="reduce_scatter")
    _, _, loss4, _ = step4(rp, ro, toks, labs)
    assert np.isfinite(float(loss4))
    ck.close()


# ---------------------------------------------------------------------------
# Preemption-tolerant executor train loop (fluid path)
# ---------------------------------------------------------------------------

def _mlp_program(fluid):
    prog, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(prog, startup):
        x = fluid.layers.data("x", [6], dtype="float32")
        y = fluid.layers.data("y", [1], dtype="int64")
        h = fluid.layers.fc(x, 16, act="relu")
        logits = fluid.layers.fc(h, 3)
        loss = fluid.layers.reduce_mean(
            fluid.layers.softmax_with_cross_entropy(logits, y))
        fluid.optimizer.SGD(0.1).minimize(loss)
    return prog, startup, loss


def _mlp_dataset(fluid, tmpdir, rows=48, batch=8):
    from paddle_tpu.dataset import DatasetFactory

    rng = np.random.RandomState(0)
    path = os.path.join(str(tmpdir), "part-0")
    os.makedirs(str(tmpdir), exist_ok=True)
    with open(path, "w") as f:
        for _ in range(rows):
            xs = " ".join(f"{v:.6f}" for v in rng.randn(6))
            f.write(f"6 {xs} 1 {int(rng.randint(0, 3))}\n")
    ds = DatasetFactory().create_dataset("InMemoryDataset")
    ds.set_batch_size(batch)
    ds.set_filelist([path])
    return ds


def _train_mlp(fluid, tmpdir, ckpt_dir=None):
    """One full train_from_dataset pass; returns the final fc weights.
    Var names and initial weights are forced deterministic so repeated
    builds (baseline / resumed run) are comparable by name."""
    import jax.numpy as jnp

    from paddle_tpu.framework import unique_name

    unique_name.switch()    # fc_0/fc_1 names on every build
    prog, startup, loss = _mlp_program(fluid)
    ds = _mlp_dataset(fluid, tmpdir)
    ds.set_use_var([prog.global_block().var("x"),
                    prog.global_block().var("y")])
    ds.load_into_memory()
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.XLAPlace(0))
        exe.run(startup)
        for i, p in enumerate(prog.global_block().all_parameters()):
            shape = np.asarray(scope.find_var(p.name)).shape
            rng = np.random.RandomState(100 + i)
            scope.set_var(p.name, jnp.asarray(
                rng.uniform(-0.1, 0.1, shape).astype(np.float32)))
        exe.train_from_dataset(prog, ds, fetch_list=[loss],
                               checkpoint_dir=ckpt_dir,
                               checkpoint_interval=2)
        weights = {name: np.asarray(scope.find_var(name))
                   for name in (p.name for p in
                                prog.global_block().all_parameters())}
    return weights


def test_executor_checkpoint_resume_matches_uninterrupted(tmp_path):
    """train_from_dataset(checkpoint_dir=...) resumes deterministically:
    restore the persistables, skip the consumed batches, and land on the
    same final weights as an uninterrupted run."""
    import paddle_tpu as fluid

    base = _train_mlp(fluid, tmp_path / "d0")
    ckpt_dir = str(tmp_path / "ckpt")
    full = _train_mlp(fluid, tmp_path / "d1", ckpt_dir=ckpt_dir)
    for k in base:
        np.testing.assert_array_equal(base[k], full[k])
    # simulate a preemption that lost everything after step 4: drop the
    # newer checkpoints, then "restart the job" — it must restore step 4,
    # skip 4 batches, train the remaining 2, and match the baseline
    ck = ElasticCheckpointer(ckpt_dir)
    steps = ck.all_steps()
    assert steps, "periodic checkpointing produced no committed steps"
    for s in steps:
        if s > 4:
            import shutil

            shutil.rmtree(os.path.join(ckpt_dir, f"step_{s:08d}"))
    assert ck.latest_valid_step() == 4
    resumed = _train_mlp(fluid, tmp_path / "d2", ckpt_dir=ckpt_dir)
    for k in base:
        np.testing.assert_array_equal(base[k], resumed[k])


def test_executor_sigterm_checkpoints_and_resumes(tmp_path):
    """A preemption signal mid-train checkpoints synchronously and returns
    cleanly; the rerun resumes to the exact uninterrupted trajectory."""
    import paddle_tpu as fluid

    sig = launch_mod.install_preemption_handler()
    ckpt_dir = str(tmp_path / "ckpt")
    try:
        os.kill(os.getpid(), signal.SIGTERM)   # "preempted" before step 1
        assert sig.triggered
        _train_mlp(fluid, tmp_path / "d1", ckpt_dir=ckpt_dir)
        ck = ElasticCheckpointer(ckpt_dir)
        assert ck.latest_valid_step() == 1     # one step ran, then exit
    finally:
        sig.reset()
    resumed = _train_mlp(fluid, tmp_path / "d2", ckpt_dir=ckpt_dir)
    base = _train_mlp(fluid, tmp_path / "d0")
    for k in base:
        np.testing.assert_array_equal(base[k], resumed[k])


# ---------------------------------------------------------------------------
# Supervised launcher
# ---------------------------------------------------------------------------

def _script(tmp_path, body):
    path = tmp_path / "worker.py"
    path.write_text(body)
    return str(path)


def test_launch_propagates_first_failing_exit_code(tmp_path):
    rc = launch_mod.launch(
        _script(tmp_path, "import sys; sys.exit(7)\n"), [])
    assert rc == 7


def test_launch_maps_signal_death_to_128_plus_n(tmp_path):
    rc = launch_mod.launch(
        _script(tmp_path,
                "import os, signal; os.kill(os.getpid(), signal.SIGKILL)\n"),
        [])
    assert rc == 128 + signal.SIGKILL


def test_launch_supervised_restart_with_backoff(tmp_path):
    """First incarnation crashes; the supervisor restarts the gang and the
    second incarnation succeeds — rc 0 and the restart counter ticks."""
    from paddle_tpu.observability import default_registry

    marker = tmp_path / "ran_once"
    script = _script(tmp_path, f"""
import os, sys
m = {str(marker)!r}
if not os.path.exists(m):
    open(m, "w").write("x")
    sys.exit(3)
sys.exit(0)
""")

    def counts():
        snap = default_registry().snapshot()
        series = snap.get("paddle_restarts_total", {}).get("series", [])
        return {s["labels"][0]: s["value"] for s in series}

    before = counts()
    t0 = time.time()
    rc = launch_mod.launch(script, [], max_restarts=2,
                           restart_backoff_s=0.2, grace_period_s=2.0)
    assert rc == 0
    assert time.time() - t0 >= 0.2    # the backoff actually slept
    after = counts()
    # a plain nonzero exit restarts with cause=crash (ISSUE 8 taxonomy:
    # hang | crash | preempt — see tests/test_health.py for the full set)
    assert after.get("crash", 0) == before.get("crash", 0) + 1


def test_launch_restarts_exhausted_propagates(tmp_path):
    script = _script(tmp_path, "import sys; sys.exit(5)\n")
    rc = launch_mod.launch(script, [], max_restarts=1,
                           restart_backoff_s=0.1, grace_period_s=1.0)
    assert rc == 5


def test_launcher_sigterm_forwards_and_exits_clean(tmp_path):
    """SIGTERM on the launcher forwards to the children, which checkpoint
    (here: write a marker) and exit 0 inside the grace period — the
    launcher then exits 0 (clean preemption)."""
    marker = tmp_path / "worker_got_term"
    ready = tmp_path / "worker_ready"
    worker = _script(tmp_path, f"""
import signal, sys, time
def h(sig, frame):
    open({str(marker)!r}, "w").write("ok")
    sys.exit(0)
signal.signal(signal.SIGTERM, h)
open({str(ready)!r}, "w").write("up")
time.sleep(60)
""")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.Popen(
        [sys.executable, "-m", "paddle_tpu.parallel.launch",
         "--grace_period", "15", worker],
        cwd=REPO, env=env,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
    deadline = time.time() + 60
    while not ready.exists():
        assert proc.poll() is None, proc.communicate()[0]
        assert time.time() < deadline, "worker never came up"
        time.sleep(0.1)
    proc.send_signal(signal.SIGTERM)
    out, _ = proc.communicate(timeout=60)
    assert proc.returncode == 0, out
    assert marker.exists(), out


def test_init_collective_with_retry():
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise ConnectionRefusedError("peer not up yet")

    launch_mod.init_collective_with_retry(flaky, retries=5, backoff_s=0.01)
    assert calls["n"] == 3
    with pytest.raises(ConnectionRefusedError):
        launch_mod.init_collective_with_retry(
            lambda: (_ for _ in ()).throw(ConnectionRefusedError()),
            retries=2, backoff_s=0.01)


# ---------------------------------------------------------------------------
# Lint acceptance of restore-time resharding collectives
# ---------------------------------------------------------------------------

def test_restore_broadcast_program_lints_clean():
    from paddle_tpu import analysis

    prog = build_restore_broadcast_program(
        [("w", (4, 4), "float32"), ("m_flat", (64,), "bfloat16")])
    res = analysis.analyze_program(prog, feed_names=["found_checkpoint"],
                                   fetch_names=[])
    assert res.ok, "\n".join(f.format() for f in res.errors)
    codes = [f.code for f in res.findings]
    # accepted as INFO, not the conditional_collective deadlock ERROR,
    # and no sub-f32 precision warning on the bf16 moment broadcast
    assert "restore_conditional_collective" in codes
    assert "conditional_collective" not in codes
    assert "subf32_collective" not in codes


@pytest.mark.slow
def test_fault_bench_smoke(tmp_path):
    """The fault-injection lane end-to-end (SIGKILL mid-step + corrupt
    shard recovery on a dp=2 mesh). ~1 min; the full matrix is
    `python tools/fault_bench.py`."""
    out = str(tmp_path / "FAULT_BENCH.json")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "fault_bench.py"),
         "--smoke", "--out", out],
        capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    data = json.load(open(out))
    assert data["pass"] is True
    assert data["scenarios"]["sigkill_midstep"]["match_baseline"] == \
        "bit_exact"
    assert data["scenarios"]["corrupt_shard"]["no_partial_selected"]
