#!/usr/bin/env python
"""End-to-end telemetry gate: a 5-step CPU MLP train with monitoring on.

Runs ``Executor.train_from_dataset`` with a ``TrainMonitor`` attached,
then asserts:
  * the per-step JSONL contains every required key
    ({step, step_time_ms, host_dispatch_ms, device_wait_ms, examples_per_s,
      mfu, loss, nan_inf}) with finite values, plus the live-HBM
    accounting field (live_buffer_bytes);
  * the metrics registry caught the dispatch/compile counters;
  * the program-report JSONL (FLAGS_program_report_dir) holds >= 1 record
    per compiled executable with finite flops / bytes-accessed /
    compile wall-ms;
  * the Prometheus textfile parses line-by-line against the exposition
    grammar (the same regex validator tests/test_observability.py uses)
    and carries the paddle_program_* / live-HBM gauges;
  * the goodput ledger (ISSUE 10) attributes >= 99% of the monitored
    run's wall-clock (``other`` < 1%), sums to wall-clock, exports every
    category of ``paddle_goodput_seconds_total``, and every monitor row
    carries the per-step ``goodput_ms`` breakdown;
  * the serving smoke leaves complete request traces (root span +
    queue-wait/prefill/decode-tick/evict children, no orphans, no
    cross-request leakage) and the queue-wait histogram;
  * the roofline attribution (ISSUE 14) of a profiled tiny-GPT step
    passes its schema gate: version stamp, finite values, fractions in
    [0,1], non-empty residue naming the layernorm/add/optimizer tail;
  * the fleet tracing + live SLO layer (ISSUE 18): a stub disagg gang
    leaves ONE stitched trace per request across router/prefill/decode
    processes with zero orphan spans, ``GET /fleet`` serves per-role
    rollups plus a valid replica-labeled merged exposition, and a
    seeded SLO breach fires exactly one burn-rate alert with exactly
    one forensic dump (latched until recovery);
  * the Pallas megakernel paths (docs/kernels.md): a fused-opt smoke
    train moves ``paddle_megakernel_launches_total{kernel="opt_sgd"}``
    by exactly one (trace-time, one launch per param group per
    compile), and a warmed fused-decode engine serves with zero
    steady-state recompiles and zero post-warmup launch-counter motion;
  * the measurement-driven autotuner (ISSUE 20, docs/autotune.md): a
    3-candidate micro train tune executes EXACTLY 2 measured probes
    (``paddle_autotune_probes_total``), statically prunes a seeded
    over-HBM candidate without running it
    (``paddle_autotune_pruned_total{reason="over_hbm"}``), leaves one
    ``autotune/probe`` span per execution, and a cached resume over the
    same probe log moves NO counter (probe count conserved).

Wired into tier-1 as tests/test_metrics_check.py (``-m 'not slow'``), so
the telemetry path is exercised end-to-end on every run. Standalone:

  JAX_PLATFORMS=cpu python tools/metrics_check.py [--out DIR]
"""
import json
import math
import os
import re
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

REQUIRED_KEYS = ("step", "step_time_ms", "host_dispatch_ms",
                 "device_wait_ms", "examples_per_s", "mfu", "loss",
                 "nan_inf", "overlap_fraction", "input_wait_ms",
                 "quarantined_records")

# Prometheus text exposition grammar, line by line (comment | sample).
PROM_LINE_RX = re.compile(
    r"^(?:"
    r"# (?:HELP|TYPE) [a-zA-Z_:][a-zA-Z0-9_:]* .*"
    r"|[a-zA-Z_:][a-zA-Z0-9_:]*"
    r"(?:\{[a-zA-Z_][a-zA-Z0-9_]*=\"[^\"\n]*\""
    r"(?:,[a-zA-Z_][a-zA-Z0-9_]*=\"[^\"\n]*\")*\})?"
    r" (?:[+-]?(?:[0-9]*\.?[0-9]+(?:[eE][+-]?[0-9]+)?|Inf|NaN))"
    r"(?: [0-9]+)?"
    r")$")


def validate_prom_text(text: str) -> int:
    """Raise on the first malformed line; returns the sample count."""
    samples = 0
    for i, line in enumerate(text.splitlines(), 1):
        if not line:
            continue
        if not PROM_LINE_RX.match(line):
            raise AssertionError(f"prom line {i} malformed: {line!r}")
        if not line.startswith("#"):
            samples += 1
    if samples == 0:
        raise AssertionError("prom exposition contains no samples")
    return samples


def _write_mlp_files(tmpdir, rows=96, din=8, classes=4, name="part-0",
                     poison_rows=()):
    import numpy as np

    rng = np.random.RandomState(0)
    path = os.path.join(tmpdir, name)
    with open(path, "w") as f:
        for i in range(rows):
            x = rng.randn(din).astype(np.float32)
            y = int(rng.randint(0, classes))
            if i in poison_rows:
                x = np.full(din, np.nan, np.float32)
            xs = " ".join(f"{v:.6f}" for v in x)
            f.write(f"{din} {xs} 1 {y}\n")
    return [path]


def run_check(out_dir: str) -> dict:
    import numpy as np  # noqa: F401

    import paddle_tpu as fluid
    from paddle_tpu.dataset import DatasetFactory
    from paddle_tpu.framework.core import get_flag, set_flags
    from paddle_tpu.observability import (TrainMonitor, default_registry, hw,
                                          prom)

    prev_report_dir = get_flag("FLAGS_program_report_dir")
    set_flags({"FLAGS_program_report_dir": out_dir})
    try:
        return _run_check_inner(out_dir)
    finally:
        set_flags({"FLAGS_program_report_dir": prev_report_dir})


def _run_check_inner(out_dir: str) -> dict:
    import glob

    import numpy as np  # noqa: F401

    import paddle_tpu as fluid
    from paddle_tpu.dataset import DatasetFactory
    from paddle_tpu.observability import (TrainMonitor, default_registry, hw,
                                          prom)

    def _counter_sum(name):
        snap_h = default_registry().snapshot()
        return sum(s["value"]
                   for s in snap_h.get(name, {}).get("series", []))

    # delta-based: an in-process caller (tests/test_observability.py) may
    # follow watchdog tests that legitimately ticked the hang counter —
    # the gate is that THIS clean run never moves it (a fresh standalone
    # process asserts absolute zero by the same check)
    hangs_before = _counter_sum("paddle_hangs_total")

    din, classes, batch = 8, 4, 16
    prog, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(prog, startup):
        x = fluid.layers.data("x", [din], dtype="float32")
        y = fluid.layers.data("y", [1], dtype="int64")
        h = fluid.layers.fc(x, 32, act="relu")
        logits = fluid.layers.fc(h, classes)
        loss = fluid.layers.reduce_mean(
            fluid.layers.softmax_with_cross_entropy(logits, y))
        fluid.optimizer.SGD(0.1).minimize(loss)

    dataset = DatasetFactory().create_dataset("InMemoryDataset")
    dataset.set_use_var([x, y])
    dataset.set_batch_size(batch)
    dataset.set_filelist(_write_mlp_files(out_dir))
    dataset.load_into_memory()

    jsonl_path = os.path.join(out_dir, "train_monitor.jsonl")
    mon = TrainMonitor(
        path=jsonl_path, examples_per_step=batch,
        flops_per_step=hw.program_train_flops(prog, batch=batch),
        peak_flops=hw.peak_bf16_flops())
    exe = fluid.Executor(fluid.XLAPlace(0))
    exe.run(startup)
    ckpt_dir = os.path.join(out_dir, "ckpt")
    exe.train_from_dataset(prog, dataset, fetch_list=[loss], monitor=mon,
                           checkpoint_dir=ckpt_dir, checkpoint_interval=2)
    mon.close()

    # --- goodput ledger (docs/observability.md, ISSUE 10) ---------------
    # the run window the train loop just closed must attribute >= 99% of
    # its wall-clock (unaccounted `other` < 1%), the category taxonomy
    # must be fully present with finite values, and the ledger must sum
    # to the wall-clock it claims (exclusive accounting is exact)
    from paddle_tpu.observability import goodput

    gp_window = goodput.ledger().last_window
    assert gp_window is not None, "train loop closed no goodput window"
    gp_cats = gp_window["categories"]
    assert set(gp_cats) == set(goodput.CATEGORIES), gp_cats
    for c, v in gp_cats.items():
        assert isinstance(v, (int, float)) and math.isfinite(v) \
            and v >= 0, f"goodput {c}={v!r}"
    assert abs(sum(gp_cats.values()) - gp_window["wall_s"]) \
        <= max(0.01 * gp_window["wall_s"], 2e-3), gp_window
    # 1%-relative with a small absolute floor, same discipline as the sum
    # check above: on a sub-second smoke window 1% is a few ms, below the
    # scheduler-noise floor of an in-process caller sharing the host with
    # the rest of the suite
    gp_unacc_s = gp_window["unaccounted_fraction"] * gp_window["wall_s"]
    assert gp_unacc_s <= max(0.01 * gp_window["wall_s"], 1e-2), \
        f"goodput ledger left {gp_window['unaccounted_fraction']:.2%} " \
        f"({gp_unacc_s * 1e3:.1f} ms) of wall-clock unaccounted " \
        f"(gate < max(1%, 10ms)): {gp_window}"
    assert gp_window["categories"]["productive_step"] > 0, gp_window
    assert gp_window["categories"]["compile"] >= 0, gp_window
    assert gp_window["categories"]["checkpoint_save"] > 0, gp_window
    snap_gp = default_registry().snapshot()
    gp_series = {s["labels"][0]: s["value"] for s in
                 snap_gp["paddle_goodput_seconds_total"]["series"]}
    for c in goodput.CATEGORIES:
        assert c in gp_series and math.isfinite(gp_series[c]), \
            f"goodput category {c!r} missing from the counter family"
    assert snap_gp["paddle_goodput_wall_seconds_total"]["series"][0][
        "value"] > 0

    # --- JSONL: >= 5 steps, required keys, finite values ---------------
    records = [json.loads(ln) for ln in open(jsonl_path)]
    assert len(records) >= 5, f"expected >=5 monitored steps, got " \
                              f"{len(records)}"
    for rec in records:
        for key in REQUIRED_KEYS:
            assert key in rec, f"record missing {key!r}: {rec}"
            v = rec[key]
            if isinstance(v, bool):
                continue
            assert isinstance(v, (int, float)) and math.isfinite(v), \
                f"{key}={v!r} not finite in {rec}"
        assert rec["nan_inf"] is False, f"NaN/Inf flagged: {rec}"
        assert rec["step_time_ms"] >= rec["host_dispatch_ms"] >= 0, rec
        assert rec["mfu"] >= 0, rec
        # live-HBM accounting rides on every monitored row
        assert "live_buffer_bytes" in rec, f"no live_buffer_bytes: {rec}"
        assert isinstance(rec["live_buffer_bytes"], int) \
            and rec["live_buffer_bytes"] > 0, rec
        # per-row goodput breakdown (ISSUE 10 satellite): ms per ledger
        # category since the previous row
        assert isinstance(rec.get("goodput_ms"), dict), rec
        for c, v in rec["goodput_ms"].items():
            assert isinstance(v, (int, float)) and math.isfinite(v) \
                and v >= 0, f"goodput_ms[{c}]={v!r} in {rec}"
        assert "productive_step" in rec["goodput_ms"], rec

    # --- registry: the executor self-reported --------------------------
    snap = default_registry().snapshot()
    dispatched = sum(s["value"] for s in
                     snap["paddle_executor_dispatch_total"]["series"])
    assert dispatched >= len(records), snap.keys()
    assert snap["paddle_executor_compile_total"]["series"][0]["value"] >= 1
    assert "paddle_train_steps_total" in snap
    assert "paddle_prefetch_queue_depth" in snap

    # --- program reports: one JSONL record per compiled executable -----
    report_files = glob.glob(
        os.path.join(out_dir, "program_reports.*.jsonl"))
    assert report_files, f"no program-report JSONL under {out_dir}"
    reports = [json.loads(ln) for p in report_files for ln in open(p)]
    assert len(reports) >= 1, "program-report JSONL is empty"
    for rep in reports:
        for key in ("flops", "bytes_accessed", "compile_ms"):
            v = rep.get(key)
            assert isinstance(v, (int, float)) and math.isfinite(v) \
                and v >= 0, f"report {key}={v!r} not finite: {rep}"
        assert rep.get("program"), rep
        assert "memory" in rep, rep

    # --- elastic checkpoint metrics (docs/elastic.md) -------------------
    # the train loop above checkpointed every 2 steps through the elastic
    # store: the save-time histogram and committed-bytes counter must have
    # fired with finite values, and the store must hold >= 1 committed step
    from paddle_tpu.parallel.checkpoint import ElasticCheckpointer

    snap = default_registry().snapshot()
    save_ms = snap["paddle_checkpoint_save_ms"]["series"][0]
    assert save_ms["count"] >= 1 and math.isfinite(save_ms["sum"]) \
        and save_ms["sum"] >= 0, f"paddle_checkpoint_save_ms: {save_ms}"
    ckpt_bytes = snap["paddle_checkpoint_bytes_total"]["series"][0]["value"]
    assert math.isfinite(ckpt_bytes) and ckpt_bytes > 0, \
        f"paddle_checkpoint_bytes_total={ckpt_bytes}"
    _ck = ElasticCheckpointer(ckpt_dir)
    committed = _ck.all_steps()
    assert committed, f"no committed checkpoint under {ckpt_dir}"
    assert not _ck.verify(committed[-1]), "latest checkpoint fails verify"
    # the restart counter family registers with the launcher (supervised
    # restarts increment it); its exposition presence is gated below

    # --- collective wire-byte accounting (docs/comm_opt.md) ------------
    # with >=2 devices (the tier-1 conftest forces 8 virtual), trace one
    # shard_map psum through comm_opt and check the counter counts the
    # ring-model bytes; on a 1-device host, presence of the registered
    # counter in the exposition is the gate
    import jax

    from paddle_tpu.parallel import comm_opt
    if jax.device_count() >= 2:
        import jax.numpy as jnp
        from jax.sharding import Mesh, PartitionSpec as P

        from paddle_tpu.parallel.parallelize import shard_map_compat

        n_dev = jax.device_count()
        mesh = Mesh(np.array(jax.devices()).reshape(n_dev), ("dp",))
        before = {tuple(s["labels"]): s["value"] for s in
                  default_registry().snapshot()
                  ["paddle_collective_bytes_total"].get("series", [])} \
            if "paddle_collective_bytes_total" in \
            default_registry().snapshot() else {}

        def f(x):
            comm_opt.record_collective("psum", x.dtype, x.size * 4, n_dev)
            return jax.lax.psum(x, "dp")

        xs = np.ones((n_dev * 8,), np.float32)
        jax.jit(shard_map_compat(f, mesh, in_specs=P("dp"),
                                 out_specs=P("dp")))(xs)
        after = {tuple(s["labels"]): s["value"] for s in
                 default_registry().snapshot()
                 ["paddle_collective_bytes_total"]["series"]}
        delta = sum(after.values()) - sum(before.values())
        # ring all-reduce of the per-rank [8] f32 shard: 2*(N-1)/N * bytes
        local_bytes = (xs.size // n_dev) * 4
        expect = 2 * (n_dev - 1) * local_bytes // n_dev
        assert delta == expect, \
            f"collective byte counter: got {delta}, want {expect}"

    # --- in-run health metrics (docs/health.md) -------------------------
    # a hang counter that ticked during this clean run would mean the
    # watchdog misfired (delta vs the top-of-run snapshot)
    assert _counter_sum("paddle_hangs_total") == hangs_before, \
        "paddle_hangs_total moved during a clean run"

    # guardrail skip counter, EXACT: a second guarded train over a dataset
    # with exactly one seeded NaN batch must skip exactly one step and
    # finish with finite weights
    from paddle_tpu.parallel.health import GuardrailConfig

    skips_before = _counter_sum("paddle_guardrail_skipped_steps_total")
    g_prog, g_startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(g_prog, g_startup):
        gx = fluid.layers.data("gx", [din], dtype="float32")
        gy = fluid.layers.data("gy", [1], dtype="int64")
        gh = fluid.layers.fc(gx, 16, act="relu")
        g_loss = fluid.layers.reduce_mean(
            fluid.layers.softmax_with_cross_entropy(
                fluid.layers.fc(gh, classes), gy))
        fluid.optimizer.SGD(0.1).minimize(g_loss)
    g_ds = DatasetFactory().create_dataset("InMemoryDataset")
    g_ds.set_use_var([gx, gy])
    g_ds.set_batch_size(batch)
    # rows 32..47 = batch index 2 — one poisoned batch out of six
    g_ds.set_filelist(_write_mlp_files(
        out_dir, name="part-guard", poison_rows=range(32, 48)))
    g_ds.load_into_memory()
    g_scope = fluid.Scope()
    with fluid.scope_guard(g_scope):
        g_exe = fluid.Executor(fluid.XLAPlace(0))
        g_exe.run(g_startup)
        g_final = g_exe.train_from_dataset(
            g_prog, g_ds, fetch_list=[g_loss],
            guardrails=GuardrailConfig())
        import numpy as _np

        for p in g_prog.global_block().all_parameters():
            w = _np.asarray(g_scope.find_var(p.name))
            assert _np.isfinite(w).all(), \
                f"guarded train left non-finite weights in {p.name}"
    assert g_final is not None and math.isfinite(float(g_final[0].ravel()[0]))
    skips_delta = _counter_sum("paddle_guardrail_skipped_steps_total") \
        - skips_before
    assert skips_delta == 1, \
        f"guardrail skip counter moved by {skips_delta}, expected exactly " \
        "1 for the single seeded NaN batch"

    # --- streaming input families (docs/data.md, ISSUE 11) --------------
    # a seeded faulty stream: shard-0's first open fails once (the retry
    # must absorb it), shard-1 carries one undecodable record (quarantine
    # sidecar + counter), shard-2 decodes slowly (the consumer wait must
    # land in the goodput ledger's input_stall and the per-shard progress
    # gauge must expose the resume offsets)
    import time as _time

    from paddle_tpu.dataset import streaming as STR
    from paddle_tpu.observability import goodput as goodput_mod

    sdir = os.path.join(out_dir, "stream_shards")
    os.makedirs(sdir, exist_ok=True)
    stream_paths = []
    for si in range(3):
        p = os.path.join(sdir, f"shard-{si}")
        with open(p, "w") as f:
            for j in range(8):
                f.write(f"{si} {j}\n")
            if si == 1:
                f.write("CORRUPT not-an-int\n")
        stream_paths.append(p)

    def _sdecode(raw):
        a, b = raw.split()
        if int(a) == 2:
            _time.sleep(0.02)   # the seeded slow shard
        return (int(a), int(b))

    _opens = {"fails": 0}

    def _sopen(path):
        if path.endswith("shard-0") and _opens["fails"] < 1:
            _opens["fails"] += 1
            raise OSError("injected transient open fault")
        return open(path, "rb")

    qpath = os.path.join(out_dir, "quarantine.jsonl")
    retries_before = _counter_sum("paddle_input_retries_total")
    quarantined_before = _counter_sum(
        "paddle_input_records_quarantined_total")
    stall_before = goodput_mod.ledger().category_seconds("input_stall")
    st = STR.ShardedStream(
        stream_paths, _sdecode,
        STR.StreamConfig(batch_size=4, num_workers=2, skip_budget=2,
                         quarantine_path=qpath,
                         retry=STR.RetryPolicy(max_attempts=3,
                                               base_delay_s=0.01,
                                               max_delay_s=0.02)),
        open_fn=_sopen, name="metrics_check")
    stream_recs = [r for b in st.batches() for r in b]
    assert stream_recs == [(si, j) for si in range(3) for j in range(8)], \
        f"stream yielded wrong records: {stream_recs}"
    retries_delta = _counter_sum("paddle_input_retries_total") \
        - retries_before
    assert retries_delta >= 1, \
        f"paddle_input_retries_total moved by {retries_delta} under a " \
        "seeded transient open fault (expected >= 1)"
    quarantined_delta = _counter_sum(
        "paddle_input_records_quarantined_total") - quarantined_before
    assert quarantined_delta == 1, \
        f"quarantine counter moved by {quarantined_delta} for exactly 1 " \
        "seeded corrupt record"
    q_entries = [json.loads(ln) for ln in open(qpath)]
    assert len(q_entries) == 1 and q_entries[0]["shard"] == "shard-1", \
        q_entries
    input_stall_delta = goodput_mod.ledger().category_seconds(
        "input_stall") - stall_before
    assert input_stall_delta > 0, \
        "goodput input_stall did not move under the seeded slow shard"
    snap = default_registry().snapshot()
    progress = {s["labels"][0]: s["value"] for s in
                snap["paddle_input_shard_progress"]["series"]}
    assert progress.get("shard-0") == 8 and progress.get("shard-2") == 8, \
        progress
    assert progress.get("shard-1") == 9, \
        f"shard-1 offset must include the quarantined record: {progress}"

    # --- static-analysis lint counter (docs/static_analysis.md) --------
    # lint the same MLP program the train loop just ran: the program must
    # be error-clean, and every finding must land in
    # paddle_lint_findings_total{severity} so lint noise rides the same
    # observability pipeline as the runtime telemetry
    from paddle_tpu import analysis

    def _lint_counts():
        snap2 = default_registry().snapshot()
        series = snap2.get("paddle_lint_findings_total", {}) \
            .get("series", [])
        return {s["labels"][0]: s["value"] for s in series}

    lint_before = _lint_counts()
    lint_res = analysis.analyze_program(prog, feed_names=["x", "y"],
                                        fetch_names=[loss.name])
    assert lint_res.ok, "trained MLP program has lint errors:\n" + \
        "\n".join(f.format() for f in lint_res.errors)
    lint_after = _lint_counts()
    lint_delta = (sum(lint_after.values()) - sum(lint_before.values()))
    assert lint_delta == len(lint_res.findings), \
        f"paddle_lint_findings_total counted {lint_delta}, " \
        f"expected {len(lint_res.findings)}"
    assert lint_after.get("error", 0) == lint_before.get("error", 0), \
        "error-severity lint findings appeared on the clean MLP program"

    # --- sharding propagation counter (docs/sharding.md, ISSUE 12) ------
    # annotate the SAME trained MLP program batch-sharded over dp and
    # propagate: the loss reduction over the sharded batch dim is one
    # implied psum edge, which must land in
    # paddle_resharding_bytes_total{edge} (edge names the op/var), and
    # the propagation must be conflict-free
    from paddle_tpu import sharding as _sharding

    def _reshard_series():
        snap3 = default_registry().snapshot()
        series = snap3.get("paddle_resharding_bytes_total", {}) \
            .get("series", [])
        return {s["labels"][0]: s["value"] for s in series}

    reshard_before = _reshard_series()
    shard_prog = prog.clone()
    _sharding.annotate_program(
        shard_prog, {"x": ("dp", None), "y": ("dp", None)},
        mesh_axes=[("dp", 8)], data_axis="dp")
    shard_res = _sharding.propagate_program(shard_prog)
    assert shard_res.complete, \
        "sharding propagation conflicts on the annotated MLP:\n" + \
        "\n".join(c.format() for c in shard_res.conflicts)
    assert shard_res.reshards, \
        "annotated MLP propagation recorded no reshard edge (the " \
        "sharded-batch loss reduction must imply one psum)"
    reshard_after = _reshard_series()
    reshard_delta = (sum(reshard_after.values())
                     - sum(reshard_before.values()))
    assert reshard_delta == shard_res.total_reshard_bytes > 0, \
        f"paddle_resharding_bytes_total moved {reshard_delta}, " \
        f"expected {shard_res.total_reshard_bytes}"
    assert any("reduce_mean" in e for e in reshard_after), \
        f"reshard edge labels {sorted(reshard_after)} do not name the " \
        "reduce_mean psum edge"

    # --- serving gate (docs/serving.md): warmed 20-request smoke serve --
    # the whole point of the AOT-bucketed engine is that a WARMED server
    # never compiles again: the recompile-explainer counter must not move
    # across the load, every request must come back 200, and the
    # paddle_serve_* families must carry finite samples
    import urllib.request

    import jax.random as jrandom

    from paddle_tpu import serving as pserving
    from paddle_tpu.models import gpt as gpt_model

    def _recompile_total():
        return _counter_sum("paddle_recompiles_total")

    def _kv_transfer_state():
        snap_kv = default_registry().snapshot()
        return {
            "bytes": {tuple(s["labels"]): s["value"] for s in
                      snap_kv.get("paddle_kv_transfer_bytes_total", {})
                      .get("series", [])},
            "count": sum(s["count"] for s in
                         snap_kv.get("paddle_kv_transfer_ms", {})
                         .get("series", [])),
        }

    kv_before = _kv_transfer_state()

    scfg = gpt_model.GPT_TINY.scaled(num_layers=2, max_seq_len=64)
    sparams = gpt_model.init_params(jrandom.PRNGKey(7), scfg)
    sengine = pserving.DecodeEngine(
        sparams, scfg, pserving.EngineConfig(
            max_batch=4, max_seq=32, prefill_buckets=(8, 16),
            page_size=8))
    sengine.warmup()
    ssched = pserving.Scheduler(sengine)
    sfront = pserving.FrontDoor(scheduler=ssched, max_queue=32).start()
    recompiles_before = _recompile_total()
    try:
        srng = np.random.RandomState(3)
        for i in range(20):
            plen = int(srng.randint(2, 15))
            prompt = srng.randint(0, scfg.vocab_size, size=plen).tolist()
            req = urllib.request.Request(
                f"http://127.0.0.1:{sfront.port}/generate",
                data=json.dumps({"prompt": prompt,
                                 "max_new_tokens": 4}).encode(),
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=30) as r:
                body = json.loads(r.read().decode())
                assert r.status == 200, f"serve request {i}: {r.status}"
            assert len(body["tokens"]) == 4, body
            assert math.isfinite(body["ttft_ms"]), body
    finally:
        sfront.stop()
    serve_recompiles = _recompile_total() - recompiles_before
    assert serve_recompiles == 0, \
        f"warmed smoke serve recompiled {serve_recompiles} time(s) — " \
        "the zero-recompile steady-state contract is broken"
    assert sengine.steady_state_recompiles == 0
    snap = default_registry().snapshot()
    serve_200 = {tuple(s["labels"]): s["value"] for s in
                 snap["paddle_serve_requests_total"]["series"]}
    assert serve_200.get(("200",), 0) >= 20, serve_200
    # ttft/tpot are split by {phase, role} since the disagg work — a
    # colocated serve lands everything on one labeled child, but sum
    # across children so the assertion survives mixed-role runs
    ttft_series = snap["paddle_serve_ttft_ms"]["series"]
    assert sum(s["count"] for s in ttft_series) >= 20, ttft_series
    assert all(math.isfinite(s["sum"]) and s["sum"] >= 0
               for s in ttft_series), ttft_series
    tpot_series = snap["paddle_serve_tpot_ms"]["series"]
    assert sum(s["count"] for s in tpot_series) >= 20, tpot_series
    assert all(math.isfinite(s["sum"]) for s in tpot_series), tpot_series
    assert math.isfinite(
        snap["paddle_serve_tokens_per_s"]["series"][0]["value"])
    assert snap["paddle_serve_tokens_total"]["series"][0]["value"] >= 80

    # request spans (ISSUE 10): every request's life is a trace — root
    # serve/request span + queue-wait/prefill/decode-tick children with
    # no orphans and no cross-request leakage
    from paddle_tpu.observability import spans as ospans

    ring = ospans.default_tracer().spans()
    roots = [s for s in ring if s["name"] == "serve/request"
             and not s["attrs"].get("open")]
    assert len(roots) >= 20, f"only {len(roots)} serve/request spans"
    by_trace = {}
    for s in ring:
        by_trace.setdefault(s["trace"], []).append(s)
    for root in roots[-20:]:
        fam = by_trace[root["trace"]]
        names = {s["name"] for s in fam}
        assert {"serve/queue_wait", "serve/prefill",
                "serve/evict"} <= names, names
        # one serve/decode_tick record a tick, on the loop's trace: the
        # request's first_step..last_step is the range it rode
        rode = [t for t in ospans.default_tracer().attr_range(
            "serve/decode_tick", "step", root["attrs"]["first_step"],
            root["attrs"]["last_step"])
            if root["attrs"]["request_id"] in t["attrs"]["riders"]]
        assert len(rode) == root["attrs"]["tokens"] - 1, (root, rode)
        for s in fam:
            if s["name"] == "serve/request":
                continue
            # children parent to THIS request's root — nothing leaks in
            # from another request, nothing is orphaned
            assert s["parent"] in {root["span"], *(
                x["span"] for x in fam)}, s
    rollup = ospans.default_tracer().summary()
    assert rollup["serve/request"]["count"] >= 20, rollup
    assert rollup["serve/prefill"]["p99_ms"] >= 0
    queue_wait = snap["paddle_serve_queue_wait_ms"]["series"][0]
    assert queue_wait["count"] >= 20 and math.isfinite(
        queue_wait["sum"]), queue_wait

    # --- paged serving gate (ISSUE 13, docs/serving.md): the prefix
    # cache must make a REPEATED system prompt prefill exactly once —
    # the second request's prefill covers only its suffix tokens — and
    # the page-pool gauges must carry live values
    def _gauge_value(name):
        s = default_registry().snapshot().get(name, {}).get("series", [])
        return s[0]["value"] if s else None

    def _prefill_tok_total():
        return _counter_sum("paddle_serve_prefill_tokens_total")

    pengine = pserving.DecodeEngine(
        sparams, scfg, pserving.EngineConfig(
            max_batch=4, max_seq=32, prefill_buckets=(8, 16),
            page_size=8))
    pengine.warmup()
    psched = pserving.Scheduler(pengine)
    recompiles_before = _recompile_total()
    system_prompt = [7] * 10 + [3, 5]          # 12 tokens -> 1 full page
    tok_before = _prefill_tok_total()
    r1 = psched.submit(system_prompt, max_new_tokens=3)
    while psched.pending():
        psched.step()
    d1 = _prefill_tok_total() - tok_before
    r2 = psched.submit(system_prompt, max_new_tokens=3)
    while psched.pending():
        psched.step()
    d2 = _prefill_tok_total() - tok_before - d1
    assert r1.state == "done" and r2.state == "done", (r1.state, r2.state)
    assert r1.tokens == r2.tokens, "prefix-cached decode diverged"
    assert d1 == 12, f"first prefill covered {d1} tokens, expected 12"
    assert d2 == 4, \
        f"repeated system prompt re-prefilled {d2} tokens (expected " \
        "only the 4-token suffix — the shared prefix must prefill ONCE)"
    pc = {s["labels"][0]: s["value"] for s in
          default_registry().snapshot()
          ["paddle_serve_prefix_cache_total"]["series"]}
    assert pc.get("hit", 0) >= 1 and pc.get("miss", 0) >= 1, pc
    occ = _gauge_value("paddle_serve_page_pool_occupancy")
    frag = _gauge_value("paddle_serve_page_pool_fragmentation")
    assert occ is not None and 0.0 <= occ <= 1.0, occ
    assert frag is not None and 0.0 <= frag <= 1.0, frag
    assert _recompile_total() - recompiles_before == 0, \
        "paged smoke serve recompiled — zero-recompile contract broken"
    assert pengine.steady_state_recompiles == 0

    # --- serving resilience gate (ISSUE 15, docs/serving.md
    # "Resilience"): the persistent prefix store must round-trip —
    # publish on engine C, restore on engine D, and the repeated system
    # prompt prefills ONLY its suffix on the restarted engine — with
    # EXACT save/restore counter deltas; and the deadline-aware shed
    # path must emit its counter + Retry-After from the measured drain
    # rate
    def _prefix_store_ops():
        s = default_registry().snapshot().get(
            "paddle_serve_prefix_store_total", {}).get("series", [])
        return {tuple(x["labels"])[0]: x["value"] for x in s}

    store_dir = os.path.join(out_dir, "prefix_store")
    ps_before = _prefix_store_ops()
    cstore = pserving.PrefixStore(store_dir)
    cengine = pserving.DecodeEngine(
        sparams, scfg, pserving.EngineConfig(
            max_batch=4, max_seq=32, prefill_buckets=(8, 16),
            page_size=8))
    assert cengine.attach_prefix_store(cstore) == 0
    cengine.warmup()
    csched = pserving.Scheduler(cengine)
    tok_before = _prefill_tok_total()
    cr1 = csched.submit(system_prompt, max_new_tokens=3)
    while csched.pending():
        csched.step()
    cstore.wait()
    ps_mid = _prefix_store_ops()
    assert ps_mid.get("save", 0) - ps_before.get("save", 0) == 1, \
        (ps_before, ps_mid)
    # "restart": fresh engine + fresh store handle over the same dir
    dstore = pserving.PrefixStore(store_dir)
    dengine = pserving.DecodeEngine(
        sparams, scfg, pserving.EngineConfig(
            max_batch=4, max_seq=32, prefill_buckets=(8, 16),
            page_size=8))
    restored = dengine.attach_prefix_store(dstore)
    assert restored == 1, restored
    ps_after = _prefix_store_ops()
    assert ps_after.get("restore", 0) - ps_mid.get("restore", 0) == 1
    assert ps_after.get("restore_skipped", 0) == \
        ps_before.get("restore_skipped", 0)
    dengine.warmup()
    dsched = pserving.Scheduler(dengine)
    tok_before = _prefill_tok_total()
    cr2 = dsched.submit(system_prompt, max_new_tokens=3)
    while dsched.pending():
        dsched.step()
    warm_delta = _prefill_tok_total() - tok_before
    assert warm_delta == 4, \
        f"restarted engine prefilled {warm_delta} tokens for the " \
        "repeated system prompt (expected only the 4-token suffix — " \
        "the prefix store must survive the restart)"
    assert cr1.tokens == cr2.tokens, "warm-restarted decode diverged"

    # deadline-aware shedding: seeded drain rate + a queued backlog ->
    # shed_decision rejects with reason=deadline and a Retry-After
    # computed from that rate (exact counter delta)
    def _shed_by_reason():
        s = default_registry().snapshot().get(
            "paddle_serve_shed_total", {}).get("series", [])
        return {tuple(x["labels"])[0]: x["value"] for x in s}

    shsched = pserving.Scheduler(sengine, pserving.SchedulerConfig(
        max_queue=8))
    import time as _time2

    _now = _time2.monotonic()
    with shsched._rate_lock:
        shsched._done_times.extend(
            [_now - 8, _now - 6, _now - 4, _now - 2])   # ~0.5 req/s
    for _ in range(4):
        shsched.submit([1, 2, 3])
    shed_before = _shed_by_reason()
    verdict = pserving.shed_decision(shsched, timeout_s=1.0)
    assert verdict is not None and verdict[0] == "deadline", verdict
    assert verdict[1] >= 1
    shed_after = _shed_by_reason()
    assert shed_after.get("deadline", 0) - \
        shed_before.get("deadline", 0) == 1, (shed_before, shed_after)
    assert pserving.shed_decision(shsched, timeout_s=120.0) is None
    shsched.abort_all("metrics_check cleanup")

    # --- spec-decode gate: the acceptance histogram must meter windows
    # (draft == target -> every proposal accepted)
    starget = pserving.DecodeEngine(
        sparams, scfg, pserving.EngineConfig(
            max_batch=2, max_seq=32, prefill_buckets=(8,),
            page_size=8, verify_window=3))
    sdraft = pserving.DecodeEngine(
        sparams, scfg, pserving.EngineConfig(
            max_batch=2, max_seq=32, prefill_buckets=(8,),
            page_size=8))
    sspec = pserving.SpecDecodeEngine(starget, sdraft)
    sspec.warmup()
    recompiles_before = _recompile_total()
    slot, _lg, tok = sspec.start_sequence_sampled(
        [2, 4, 6], pserving.GREEDY)
    emitted = [tok]
    for _ in range(3):
        out = sspec.generate_step({slot: emitted[-1]},
                                  {slot: pserving.GREEDY})
        emitted.extend(out[slot])
    sspec.free_sequence(slot)
    assert _recompile_total() - recompiles_before == 0, \
        "spec-decode steady state recompiled"
    spec_hist = default_registry().snapshot()[
        "paddle_serve_spec_accepted_tokens"]["series"][0]
    assert spec_hist["count"] >= 3 and math.isfinite(spec_hist["sum"])
    assert sspec.stats.acceptance_rate == 1.0, \
        f"self-draft acceptance {sspec.stats.acceptance_rate} != 1.0"

    # --- megakernel launch gate (docs/kernels.md) -----------------------
    # paddle_megakernel_launches_total{kernel} ticks at TRACE time — one
    # tick per launch site per compile, never per step. Two exact checks:
    # (1) a fused-opt smoke train (flat sweep + Pallas megakernel forced
    # on) compiles its program ONCE and the MLP's four f32 params share a
    # single (dtype, hparam-sig) group, so kernel="opt_sgd" must move by
    # EXACTLY 1 — and steps 2..3 hit the dispatch cache and must not
    # move it again; (2) a warmed fused-decode engine serves with zero
    # steady-state recompiles AND zero post-warmup launch-counter motion
    # (a retrace of the decode program would tick it).
    from paddle_tpu.framework.core import get_flag as _get_flag2
    from paddle_tpu.framework.core import set_flags as _set_flags2

    def _mk_counts():
        s = default_registry().snapshot().get(
            "paddle_megakernel_launches_total", {}).get("series", [])
        return {tuple(x["labels"])[0]: x["value"] for x in s}

    mk_section_before = _mk_counts()
    prev_pallas = _get_flag2("FLAGS_fuse_optimizer_pallas")
    _set_flags2({"FLAGS_fuse_optimizer_pallas": True})
    try:
        f_prog, f_startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(f_prog, f_startup):
            fx = fluid.layers.data("fx", [din], dtype="float32")
            fy = fluid.layers.data("fy", [1], dtype="int64")
            fh = fluid.layers.fc(fx, 16, act="relu")
            f_loss = fluid.layers.reduce_mean(
                fluid.layers.softmax_with_cross_entropy(
                    fluid.layers.fc(fh, classes), fy))
            fluid.optimizer.SGD(0.1, fuse=True).minimize(f_loss)
        f_scope = fluid.Scope()
        f_rng = np.random.RandomState(11)
        with fluid.scope_guard(f_scope):
            f_exe = fluid.Executor(fluid.XLAPlace(0))
            f_exe.run(f_startup)
            # snapshot AFTER program build: append_op shape inference runs
            # the op lowering under eval_shape once, which also traces the
            # launch site — the exactness gate covers the compile proper
            mk_before = _mk_counts()
            f_feed = {"fx": f_rng.randn(batch, din).astype(np.float32),
                      "fy": f_rng.randint(0, classes,
                                          (batch, 1)).astype(np.int64)}
            f_exe.run(f_prog, feed=f_feed, fetch_list=[f_loss])
            mk_compiled = _mk_counts()
            for _ in range(2):
                f_exe.run(f_prog, feed=f_feed, fetch_list=[f_loss])
    finally:
        _set_flags2({"FLAGS_fuse_optimizer_pallas": prev_pallas})
    mk_train = _mk_counts()
    opt_sgd_delta = mk_train.get("opt_sgd", 0) - mk_before.get("opt_sgd", 0)
    assert opt_sgd_delta == 1, \
        f"opt_sgd megakernel launches moved by {opt_sgd_delta}, expected " \
        "exactly 1 (one launch per (dtype, hparam-sig) group per compile)"
    assert mk_train.get("opt_sgd", 0) == mk_compiled.get("opt_sgd", 0), \
        "cached fused-opt steps re-traced the optimizer megakernel"

    fengine = pserving.DecodeEngine(
        sparams, scfg, pserving.EngineConfig(
            max_batch=4, max_seq=32, prefill_buckets=(8, 16),
            page_size=8, fused_decode=True))
    fengine.warmup()
    mk_warm = _mk_counts()
    assert mk_warm.get("decode_paged", 0) \
        > mk_train.get("decode_paged", 0), \
        "fused-decode warmup traced no decode_paged megakernel launch"
    assert mk_warm.get("decode_logits_head", 0) \
        > mk_train.get("decode_logits_head", 0), \
        "fused-decode warmup traced no decode_logits_head launch"
    recompiles_before = _recompile_total()
    fslot, flogits = fengine.start_sequence([3, 5, 7])
    ftok = int(np.argmax(flogits))
    for _ in range(6):
        fout = fengine.decode_step({fslot: ftok})
        ftok = int(np.argmax(fout[fslot]))
    fengine.free_sequence(fslot)
    fused_decode_recompiles = _recompile_total() - recompiles_before
    assert fused_decode_recompiles == 0, \
        f"warmed fused-decode engine recompiled {fused_decode_recompiles}" \
        " time(s) — the zero-recompile steady-state contract is broken"
    assert fengine.steady_state_recompiles == 0
    mk_after = _mk_counts()
    assert mk_after == mk_warm, \
        f"steady-state fused decode re-traced megakernels: " \
        f"{mk_warm} -> {mk_after}"

    # --- roofline attribution gate (ISSUE 14, docs/observability.md) ----
    # profile a decode tick of the ALREADY-WARMED GPT serving engine
    # (zero extra compiles — the train-step attribution twin, with its
    # layernorm-grad/add/optimizer residue assertions, runs its own
    # compiles in tests/test_attribution.py) and gate the
    # ATTRIBUTION.json schema: version stamp, finite values, roofline
    # fractions in [0,1], and a NON-EMPTY residue list
    from paddle_tpu.observability import attribution as ATT
    from paddle_tpu.observability import program_report as prep_mod

    aslot, alogits = sengine.start_sequence([3, 5, 7])
    atok = int(np.argmax(alogits))
    atrace = os.path.join(out_dir, "attr_trace")
    import time as _t

    t0 = _t.perf_counter()
    with jax.profiler.trace(atrace):
        for _ in range(4):
            aout = sengine.decode_step({aslot: atok})
            atok = int(np.argmax(aout[aslot]))
    awall_ms = (_t.perf_counter() - t0) * 1e3 / 4
    sengine.free_sequence(aslot)
    try:
        ahlo = sengine._exec["decode"].as_text()
    except Exception:
        ahlo = None
    arep = next((r for r in reversed(prep_mod.recent_reports())
                 if r.get("program") == "serve/decode"), {})
    attr_doc = ATT.build_from_trace(
        atrace, steps=4, wall_ms_per_step=awall_ms,
        hlo_texts=[ahlo] if ahlo else [], mode="decode",
        spec="metrics_check_gpt_decode_smoke",
        step_flops=arep.get("flops"),
        step_bytes=arep.get("bytes_accessed"),
        programs=[arep] if arep else None,
        config={"mode": "decode", "weight_dtype": "f32"},
        generated_by="tools/metrics_check.py")
    # the schema gate proper: raises naming the offending field
    ATT.validate(attr_doc, require_residue=True)
    attr_labels = {g["label"] for g in attr_doc["residue"]["groups"]}
    assert attr_labels & {"layernorm", "elementwise", "data_movement",
                          "matmul"}, \
        f"GPT decode-smoke residue ranking carries no recognizable " \
        f"small-op labels: {sorted(attr_labels)}"
    assert attr_doc["degraded"] is (jax.devices()[0].platform != "tpu")
    apath = os.path.join(out_dir, "ATTRIBUTION.json")
    ATT.write(attr_doc, apath)

    # --- disagg KV-transfer gate (ISSUE 17, docs/serving.md
    # "Disaggregation"): the transfer counters must move ONLY on disagg
    # runs. Everything above was plain colocated serving — HTTP smoke,
    # prefix-cache smoke, warm restart, spec decode, fused decode
    # — so the counters must be EXACTLY where they started; then one
    # in-process export/adopt exchange must move them by the exact
    # stats-reported byte totals, under the chunk-residency budget
    from paddle_tpu.serving import kv_transfer as kvt_mod

    kv_flat = _kv_transfer_state()
    assert kv_flat == kv_before, \
        f"KV transfer counters moved on a colocated-only run: " \
        f"{kv_before} -> {kv_flat} (they must move only on disagg)"
    xprompt = [2, 4, 6, 8, 10, 12, 14, 16]
    xslot, xlogits = pengine.start_sequence(xprompt)
    xtok = int(np.argmax(xlogits))
    handoff = pserving.export_slot(pengine, xslot, tokens=xprompt)
    yslot = pserving.adopt_into_engine(dengine, handoff)
    # bit-identical greedy continuation across the handoff
    xout = pengine.decode_step({xslot: xtok})
    yout = dengine.decode_step({yslot: xtok})
    assert int(np.argmax(xout[xslot])) == int(np.argmax(yout[yslot])), \
        "greedy token diverged across the KV handoff"
    pengine.free_sequence(xslot)
    dengine.free_sequence(yslot)
    exp_stats = kvt_mod.last_stats("export")
    adp_stats = kvt_mod.last_stats("adopt")
    assert exp_stats is not None and adp_stats is not None
    assert adp_stats.peak_bytes <= adp_stats.budget_bytes, \
        f"adopt peak residency {adp_stats.peak_bytes} exceeded the " \
        f"chunk budget {adp_stats.budget_bytes}"
    kv_moved = _kv_transfer_state()
    assert kv_moved["bytes"].get(("out",), 0) - \
        kv_before["bytes"].get(("out",), 0) == exp_stats.total_bytes, \
        (kv_before, kv_moved, exp_stats.total_bytes)
    assert kv_moved["bytes"].get(("in",), 0) - \
        kv_before["bytes"].get(("in",), 0) == adp_stats.total_bytes, \
        (kv_before, kv_moved, adp_stats.total_bytes)
    assert kv_moved["count"] - kv_before["count"] == 2, \
        (kv_before["count"], kv_moved["count"])

    # --- fleet tracing + live SLO gate (ISSUE 18, docs/observability.md
    # "Fleet & SLO"): a stub disagg gang must leave ONE trace per request
    # spanning router + prefill + decode processes with zero orphan spans
    # across the stitched per-process files; GET /fleet must serve live
    # per-role rollups and a VALID merged exposition that keeps the
    # replica label; a seeded SLO breach must fire EXACTLY one burn-rate
    # alert and write EXACTLY one forensic dump, and recovery must re-arm
    # the latch without a second dump
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import trace_assemble as TA

    from paddle_tpu.observability import slo as slo_mod
    from paddle_tpu.serving.gang import (GangConfig, GangFrontDoor,
                                         ReplicaGang)

    gang_dir = os.path.join(out_dir, "stub_gang")
    tgang = ReplicaGang({"stub": {}}, gang_dir,
                        GangConfig(n_replicas=2,
                                   roles=("prefill", "decode"),
                                   fleet_poll_interval_s=0.2)).start()
    tfront = GangFrontDoor(tgang).start()
    try:
        trace_ids = []
        for i in range(3):
            treq = urllib.request.Request(
                f"http://127.0.0.1:{tfront.port}/generate",
                data=json.dumps({"prompt": [1, 2, 3 + i],
                                 "max_new_tokens": 4,
                                 "request_id": f"mc-trace-{i}"}).encode(),
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(treq, timeout=15) as r:
                tpay = json.loads(r.read().decode())
            assert tpay.get("disagg") is True, tpay
            assert tpay.get("trace_id"), tpay
            trace_ids.append(int(tpay["trace_id"]))
        ta_report = TA.assemble_dir(tgang.trace_dir)
        assert ta_report["n_orphans"] == 0, ta_report["orphans"]
        assert ta_report["n_duplicates"] == 0, ta_report["duplicates"]
        ta_by_hex = {t["trace"]: t for t in ta_report["traces"]}
        for tid in trace_ids:
            t = ta_by_hex.get(f"{tid:x}")
            assert t is not None, (tid, sorted(ta_by_hex))
            # one shared trace id across the supervisor's and BOTH phase
            # replicas' span files — the request is one end-to-end trace
            assert {"gang", "prefill", "decode"} <= set(t["roles"]), t
            assert len(t["files"]) >= 3, t
        import time as _t3

        _t3.sleep(0.5)               # let the poller tick at least once
        with urllib.request.urlopen(
                f"http://127.0.0.1:{tfront.port}/fleet", timeout=10) as r:
            fleet_doc = json.loads(r.read().decode())
        assert fleet_doc["n_alive"] == 2, fleet_doc
        assert {"prefill", "decode"} <= set(fleet_doc["roles"]), fleet_doc
        assert "objectives" in fleet_doc.get("slo", {}), fleet_doc
        with urllib.request.urlopen(
                f"http://127.0.0.1:{tfront.port}/fleet/metrics",
                timeout=10) as r:
            fleet_expo = r.read().decode()
        validate_prom_text(fleet_expo)
        assert 'replica="0"' in fleet_expo and 'replica="1"' in fleet_expo
        assert 'role="prefill"' in fleet_expo and \
            'role="decode"' in fleet_expo, "role label lost in merge"
        gang_slo = slo_mod.slo_status()      # the gang installed itself
        assert "objectives" in gang_slo and "ok" in gang_slo, gang_slo
    finally:
        tfront.stop()
        tgang.stop()

    slo_fdir = os.path.join(out_dir, "slo_forensics")
    sforensics = slo_mod.ForensicDir(slo_fdir, keep=8)
    seng = slo_mod.SLOEngine(forensics=sforensics, min_events=8)
    t_base = 1000.0
    for i in range(20):
        seng.note_request(ttft_ms=10 * seng.objectives[0].target,
                          tpot_ms=1.0, code=200, trace_id=1234,
                          request_id=f"breach-{i}", t=t_base + i)
    slo_st1 = seng.evaluate(now=t_base + 20)
    slo_st2 = seng.evaluate(now=t_base + 21)
    assert slo_st1["objectives"]["ttft_p99"]["alert_fired"] is True, \
        slo_st1["objectives"]["ttft_p99"]
    assert slo_st1["alerts_total"].get("ttft_p99") == 1, slo_st1
    assert slo_st2["alerts_total"].get("ttft_p99") == 1, \
        "alert latch re-fired on the second evaluation of one breach"
    assert not slo_st1["ok"] and "ttft_p99" in slo_st1["alerting"]
    slo_dumps = sforensics.files()
    assert len(slo_dumps) == 1, \
        f"seeded breach wrote {len(slo_dumps)} forensic dumps, expected 1"
    dump_doc = json.load(open(os.path.join(slo_fdir, slo_dumps[0])))
    assert dump_doc["kind"] == "slo_breach" and \
        dump_doc["objective"] == "ttft_p99", dump_doc
    assert dump_doc["worst_request"]["trace_id"] == 1234, dump_doc
    for i in range(40):                      # recovery re-arms the latch
        seng.note_request(ttft_ms=1.0, tpot_ms=1.0, code=200,
                          t=t_base + 700 + i)
    slo_st3 = seng.evaluate(now=t_base + 740)
    assert slo_st3["ok"] and not slo_st3["alerting"], slo_st3
    assert len(sforensics.files()) == 1, "recovery wrote a second dump"

    # --- autotuner gate (ISSUE 20, docs/autotune.md) --------------------
    # exact-count discipline on the measurement-driven tuner: a
    # 3-candidate micro train tune (incumbent + one measured challenger +
    # one seeded over-HBM candidate) must execute EXACTLY 2 probes
    # (paddle_autotune_probes_total{phase}), prune the seeded candidate
    # statically WITHOUT a probe (paddle_autotune_pruned_total
    # {reason="over_hbm"}, real roofline path against a forced 1-byte
    # budget), leave one autotune/probe span per execution, and a
    # SECOND tune over the same probe log must replay from cache with
    # ZERO counter motion (the resume-conservation contract)
    from paddle_tpu.tuning import driver as at_driver
    from paddle_tpu.tuning import probe as at_probe
    from paddle_tpu.tuning import space as at_space
    from paddle_tpu.tuning import static_cost as at_static

    def _at_counts(name):
        s = default_registry().snapshot().get(name, {}).get("series", [])
        return {tuple(x["labels"])[0]: x["value"] for x in s}

    at_di = at_probe.device_info()
    at_ctx = at_space.SpaceContext(
        dp=1, n_devices=at_di.n_devices, platform=at_di.platform,
        vocab_size=32, max_seq=16, max_batch=2, page_size=8,
        on_acc=at_di.on_acc)
    at_inc = at_space.train_incumbent(at_ctx)
    at_measured = at_inc.replace(remat="full")
    at_seeded = at_inc.replace(remat="dots")     # statically killed below
    at_geom = at_probe.TrainProbeGeometry(
        d_model=16, num_layers=1, num_heads=2, d_ff=32, T=8,
        vocab_size=32, batch=2)
    at_hw_tiny = at_static.HwModel(peak_flops=1e12, peak_hbm_bps=50e9,
                                   hbm_capacity_bytes=1.0, on_acc=False)

    def at_probe_fn(cand, steps, rung):
        return at_probe.run_train_probe(cand, at_geom, steps, seed=0)

    def at_static_fn(cand, inc_result):
        if cand.key != at_seeded.key:
            return None            # the challenger goes to the measured
        rep = (inc_result or {}).get("report") or {}    # phase unpruned
        base = at_static.BaseStats(
            flops=float(rep.get("flops") or 1e6),
            bytes_accessed=float(rep.get("bytes_accessed") or 1e6),
            peak_hbm_bytes=float(rep.get("peak_hbm_bytes") or 1e5),
            param_bytes=float((inc_result or {}).get("params") or 1e3)
            * 4.0,
            tokens_per_step=at_geom.batch * at_geom.T,
            vocab_size=at_geom.vocab_size, incumbent=at_inc)
        est = at_static.predict_train(cand, base, at_hw_tiny, dp=1)
        assert est.over_hbm, \
            f"seeded 1-byte HBM budget did not trip over_hbm: {est}"
        return est

    at_spans_before = sum(
        1 for s in ospans.default_tracer().spans()
        if s["name"] == "autotune/probe")
    at_probes_before = _at_counts("paddle_autotune_probes_total")
    at_pruned_before = _at_counts("paddle_autotune_pruned_total")
    at_log_path = os.path.join(out_dir, "autotune_probes.jsonl")
    at_log = at_driver.ProbeLog(at_log_path)
    at_tr = at_driver.tune(
        space="train", candidates=[at_inc, at_measured, at_seeded],
        incumbent=at_inc, probe_fn=at_probe_fn, static_fn=at_static_fn,
        rungs=((1, 1.0),), log=at_log, phase="metrics_check")
    at_log.close()
    assert at_tr.probes_executed == 2, \
        f"3-candidate smoke tune executed {at_tr.probes_executed} " \
        "probes, expected exactly 2 (incumbent + measured challenger)"
    assert at_tr.pruned == {"over_hbm": 1}, \
        f"seeded over-HBM candidate pruned as {at_tr.pruned}, " \
        "expected exactly {'over_hbm': 1}"
    at_probes_delta = _at_counts("paddle_autotune_probes_total").get(
        "metrics_check", 0) - at_probes_before.get("metrics_check", 0)
    assert at_probes_delta == 2, \
        f"paddle_autotune_probes_total moved by {at_probes_delta}, " \
        "expected exactly 2"
    at_pruned_delta = _at_counts("paddle_autotune_pruned_total").get(
        "over_hbm", 0) - at_pruned_before.get("over_hbm", 0)
    assert at_pruned_delta == 1, \
        f"paddle_autotune_pruned_total{{over_hbm}} moved by " \
        f"{at_pruned_delta}, expected exactly 1"
    at_spans_delta = sum(
        1 for s in ospans.default_tracer().spans()
        if s["name"] == "autotune/probe") - at_spans_before
    assert at_spans_delta == 2, \
        f"{at_spans_delta} autotune/probe spans for 2 executed probes"
    # resume conservation: same log, same candidates — everything cached
    at_log2 = at_driver.ProbeLog(at_log_path)
    at_tr2 = at_driver.tune(
        space="train", candidates=[at_inc, at_measured, at_seeded],
        incumbent=at_inc, probe_fn=at_probe_fn, static_fn=at_static_fn,
        rungs=((1, 1.0),), log=at_log2, phase="metrics_check")
    at_log2.close()
    assert at_tr2.probes_executed == 0 and at_tr2.pruned == {}, \
        (at_tr2.probes_executed, at_tr2.pruned)
    assert at_tr2.winner.key == at_tr.winner.key, \
        "resumed tune picked a different winner from cached probes"
    at_resume_delta = _at_counts("paddle_autotune_probes_total").get(
        "metrics_check", 0) - at_probes_before.get("metrics_check", 0)
    assert at_resume_delta == 2, \
        "cached resume moved paddle_autotune_probes_total — the probe " \
        "count must be conserved across a resume"

    # --- Prometheus exposition (incl. the new compile/memory gauges) ---
    prom_path = os.path.join(out_dir, "metrics.prom")
    prom.write_textfile(prom_path)
    prom_text = open(prom_path).read()
    samples = validate_prom_text(prom_text)
    for gauge in ("paddle_program_flops", "paddle_program_peak_hbm_bytes",
                  "paddle_live_buffer_bytes"):
        assert f"\n{gauge}" in prom_text or \
            prom_text.startswith(gauge), f"{gauge} missing from exposition"
    assert "paddle_collective_bytes_total" in prom_text, \
        "collective wire-byte counter missing from exposition"
    assert 'paddle_lint_findings_total{severity=' in prom_text, \
        "lint findings counter missing from exposition"
    # elastic checkpoint/restart metrics (docs/elastic.md): the save
    # histogram + bytes counter carry samples; the supervised-restart
    # counter family is registered (HELP/TYPE rendered) even when this
    # in-process run never restarted a gang
    for name in ("paddle_checkpoint_save_ms", "paddle_checkpoint_bytes_total",
                 "paddle_restarts_total"):
        assert name in prom_text, f"{name} missing from exposition"
    # in-run health families (docs/health.md): the hang/straggler counters
    # are registered (HELP/TYPE rendered) even when this clean in-process
    # run never hung or straggled; the guardrail skip counter carries the
    # exact single-NaN-batch sample from the guarded train above
    for name in ("paddle_hangs_total", "paddle_straggler_detected_total",
                 "paddle_rank_step_time_ewma_ms",
                 "paddle_guardrail_rollbacks_total"):
        assert name in prom_text, f"{name} missing from exposition"
    assert 'paddle_guardrail_skipped_steps_total{reason="nonfinite"} 1' \
        in prom_text or skips_before > 0, \
        "guardrail skip sample missing from exposition"
    # serving families (docs/serving.md): the smoke serve above must have
    # left well-formed samples in the exposition
    for name in ("paddle_serve_requests_total", "paddle_serve_queue_depth",
                 "paddle_serve_batch_occupancy", "paddle_serve_ttft_ms",
                 "paddle_serve_tpot_ms", "paddle_serve_tokens_per_s",
                 "paddle_serve_prefill_ms", "paddle_serve_decode_step_ms",
                 "paddle_serve_queue_wait_ms",
                 # ISSUE 13 families: prefix cache, page pool,
                 # spec-decode acceptance
                 "paddle_serve_prefix_cache_total",
                 "paddle_serve_prefill_tokens_total",
                 "paddle_serve_page_pool_occupancy",
                 "paddle_serve_page_pool_fragmentation",
                 "paddle_serve_spec_accepted_tokens",
                 "paddle_serve_spec_windows_total",
                 "paddle_serve_preemptions_total",
                 "paddle_serve_hol_bypass_admits_total",
                 # ISSUE 15 resilience families: overload shedding,
                 # gang replica recycles, failover re-dispatch, prefix
                 # store save/restore (docs/serving.md "Resilience")
                 "paddle_serve_shed_total",
                 "paddle_serve_replica_restarts_total",
                 "paddle_serve_failover_requests_total",
                 "paddle_serve_prefix_store_total",
                 # ISSUE 17 disagg families: KV handoff wire bytes +
                 # latency, pool-level prefix cache, phase fallback
                 # (docs/serving.md "Disaggregation")
                 "paddle_kv_transfer_bytes_total",
                 "paddle_kv_transfer_ms",
                 "paddle_serve_pool_prefix_cache_total",
                 "paddle_serve_disagg_fallback_total",
                 # ISSUE 18 fleet + SLO families: live fleet poller,
                 # burn-rate alerts, error budget, forensic dumps
                 # (docs/observability.md "Fleet & SLO")
                 "paddle_fleet_alive_replicas",
                 "paddle_fleet_polls_total",
                 "paddle_fleet_scrape_errors_total",
                 "paddle_slo_ok",
                 "paddle_slo_burn_rate",
                 "paddle_slo_budget_remaining",
                 "paddle_slo_alerts_total",
                 "paddle_slo_forensic_dumps_total"):
        assert name in prom_text, f"{name} missing from exposition"
    # the seeded breach above left exactly one labeled alert sample
    assert 'paddle_slo_alerts_total{objective="ttft_p99"' in prom_text, \
        "seeded SLO breach alert sample missing from exposition"
    assert 'paddle_serve_requests_total{code="200"}' in prom_text
    assert 'paddle_serve_prefix_cache_total{event="hit"}' in prom_text
    assert 'paddle_serve_prefix_cache_total{event="miss"}' in prom_text
    # the resilience smoke above left exact samples for shed + store
    assert 'paddle_serve_shed_total{reason="deadline"}' in prom_text
    assert 'paddle_serve_prefix_store_total{op="save"}' in prom_text
    assert 'paddle_serve_prefix_store_total{op="restore"}' in prom_text
    # the disagg exchange above left exact per-direction wire samples
    assert 'paddle_kv_transfer_bytes_total{direction="out"}' in prom_text
    assert 'paddle_kv_transfer_bytes_total{direction="in"}' in prom_text
    # streaming input families (docs/data.md): the seeded faulty stream
    # above must have left retry/quarantine/progress samples
    for name in ("paddle_input_retries_total",
                 "paddle_input_records_quarantined_total",
                 "paddle_input_shard_progress",
                 "paddle_input_worker_recycles_total",
                 "paddle_input_stall_seconds_total"):
        assert name in prom_text, f"{name} missing from exposition"
    assert 'paddle_input_retries_total{stage="open"}' in prom_text, \
        "open-stage retry sample missing from exposition"
    assert 'paddle_input_shard_progress{shard=' in prom_text, \
        "per-shard progress gauge missing from exposition"
    # sharding family (docs/sharding.md): the propagation above must have
    # exposed its implied-reshard accounting
    assert "paddle_resharding_bytes_total" in prom_text, \
        "paddle_resharding_bytes_total missing from exposition"
    assert 'paddle_resharding_bytes_total{edge=' in prom_text, \
        "reshard edge sample missing from exposition"
    # megakernel launch counter (docs/kernels.md): the fused-opt train and
    # fused-decode serve above left per-kernel trace-time samples
    assert 'paddle_megakernel_launches_total{kernel="opt_sgd"}' \
        in prom_text, "opt_sgd megakernel sample missing from exposition"
    assert 'paddle_megakernel_launches_total{kernel="decode_paged"}' \
        in prom_text, "decode_paged megakernel sample missing"
    # autotune families (docs/autotune.md): the smoke tune above left
    # exactly-counted probe/prune samples
    for name in ("paddle_autotune_probes_total",
                 "paddle_autotune_pruned_total"):
        assert name in prom_text, f"{name} missing from exposition"
    assert 'paddle_autotune_probes_total{phase="metrics_check"}' \
        in prom_text, "autotune probe sample missing from exposition"
    assert 'paddle_autotune_pruned_total{reason="over_hbm"}' \
        in prom_text, "over_hbm prune sample missing from exposition"
    # goodput families (docs/observability.md): every category present
    for c in goodput.CATEGORIES:
        assert f'paddle_goodput_seconds_total{{category="{c}"}}' \
            in prom_text, f"goodput category {c} missing from exposition"
    assert "paddle_goodput_wall_seconds_total" in prom_text

    return {"steps": len(records), "prom_samples": samples,
            "input_retries": retries_delta,
            "input_quarantined": quarantined_delta,
            "input_stall_s": round(input_stall_delta, 4),
            "serve_requests": int(serve_200.get(("200",), 0)),
            "serve_steady_state_recompiles": int(serve_recompiles),
            "prefix_cache": {"hit": int(pc.get("hit", 0)),
                             "miss": int(pc.get("miss", 0)),
                             "first_prefill_tokens": int(d1),
                             "repeat_prefill_tokens": int(d2)},
            "prefix_store": {"saved": int(cstore.saved),
                             "restored": int(restored),
                             "warm_restart_prefill_tokens":
                                 int(warm_delta)},
            "spec_acceptance_rate": round(sspec.stats.acceptance_rate, 4),
            "kv_transfer": {
                "export_bytes": int(exp_stats.total_bytes),
                "adopt_bytes": int(adp_stats.total_bytes),
                "adopt_peak_bytes": int(adp_stats.peak_bytes),
                "adopt_budget_bytes": int(adp_stats.budget_bytes)},
            "megakernel_launches": {
                k: int(v - mk_section_before.get(k, 0))
                for k, v in mk_after.items()},
            "fused_decode_steady_state_recompiles":
                int(fused_decode_recompiles),
            "autotune": {
                "probes_executed": int(at_tr.probes_executed),
                "pruned": dict(at_tr.pruned),
                "winner": at_tr.winner.key,
                "resume_probes_executed": int(at_tr2.probes_executed),
                "probe_log": at_log_path},
            "program_reports": len(reports),
            "attribution": {
                "path": apath,
                "fusions": int(attr_doc["fusion_count"]),
                "residue_count": int(attr_doc["residue"]["count"]),
                "residue_share": attr_doc["residue"]["share_of_busy"],
                "residue_groups": [g["label"] for g in
                                   attr_doc["residue"]["groups"][:6]],
            },
            "checkpoint_steps": committed,
            "checkpoint_bytes": ckpt_bytes,
            "lint_findings": lint_after,
            "resharding_bytes": reshard_delta,
            "guardrail_skips": skips_delta,
            "goodput_window": gp_window,
            "fleet_trace": {
                "traces": int(ta_report["n_traces"]),
                "spans": int(ta_report["n_spans"]),
                "orphans": int(ta_report["n_orphans"]),
                "span_files": len(ta_report["files"])},
            "slo": {"alerts": dict(slo_st1["alerts_total"]),
                    "forensic_dumps": len(slo_dumps)},
            "serve_span_rollups": {k: v for k, v in rollup.items()
                                   if k.startswith("serve/")},
            "jsonl": jsonl_path, "prom": prom_path,
            "last_record": records[-1]}


def main():
    out_dir = None
    if "--out" in sys.argv:
        out_dir = sys.argv[sys.argv.index("--out") + 1]
        os.makedirs(out_dir, exist_ok=True)
    else:
        out_dir = tempfile.mkdtemp(prefix="metrics_check_")
    result = run_check(out_dir)
    print(json.dumps(result, indent=1))
    print("[metrics_check] OK")
    return result


if __name__ == "__main__":
    main()
