#!/usr/bin/env python
"""Communication-lever A/B bench: the reduce-scatter gradient path,
quantized collectives, and the double-buffered pipeline tick, end to end
on one mesh (docs/comm_opt.md).

Per config it measures:
  * per-step per-rank wire bytes, split into gradient-reduction bytes and
    total collective bytes — read off the ``paddle_collective_bytes_total``
    {op,dtype} counter delta across the step trace (static ring-model
    accounting recorded at lowering time, see comm_opt.record_collective);
  * median step wall time over the measured steps;
  * comm/compute overlap fraction from a profiler capture of one step
    (comm_opt.measure_overlap_fraction; ~0 on CPU, where the runtime
    serializes — the honest off-TPU answer);
  * the 5-step loss trajectory, and for the f32 reduce-scatter config a
    bit-parity check against the psum baseline.

``--smoke`` runs the 8-virtual-device CPU mesh (dp=8) end to end: a
correctness lane for bytes and bit parity, labelled ``degraded``. Without
``--smoke`` the bench measures a real mesh and refuses a backend that is
not a TPU. Emits one JSON row per config on stdout and writes
COMM_BENCH.json.

  python tools/comm_bench.py --smoke --out COMM_BENCH.json
  python tools/comm_bench.py --dp 4 --steps 8 --profile-overlap   # 4 chips
"""
import argparse
import json
import os
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)



def _prepare_backend(smoke: bool) -> None:
    """Before the first jax import: the smoke lane's CPU mesh needs 8
    virtual devices; the measuring lane needs the overlap preset in
    LIBTPU_INIT_ARGS before libtpu loads."""
    if smoke:
        os.environ["JAX_PLATFORMS"] = "cpu"
        if "host_platform_device_count" not in os.environ.get(
                "XLA_FLAGS", ""):
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + " --xla_force_host_platform_device_count=8")
    else:
        from paddle_tpu.sysconfig import tpu_perf_flags

        tpu_perf_flags()


CONFIGS = (
    # (name, make_train_step kwargs)
    ("psum_f32", {}),
    ("reduce_scatter_f32", {"grad_reduce": "reduce_scatter"}),
    ("reduce_scatter_bf16", {"grad_reduce": "reduce_scatter",
                             "grad_allreduce_dtype": "bf16"}),
    ("reduce_scatter_int8_ef", {"grad_reduce": "reduce_scatter",
                                "grad_allreduce_dtype": "int8",
                                "error_feedback": True}),
    ("psum_bf16", {"grad_allreduce_dtype": "bf16"}),
)

GRAD_REDUCE_OPS = ("psum", "psum_scatter", "all_to_all")


def _wire_snapshot():
    from paddle_tpu.observability import metrics as M

    snap = M.default_registry().snapshot()
    series = snap.get("paddle_collective_bytes_total", {}).get("series", [])
    return {tuple(s["labels"]): s["value"] for s in series}


def _wire_delta(before, after):
    out = {}
    for k, v in after.items():
        d = v - before.get(k, 0)
        if d:
            out[k] = d
    return out


def run_config(name, kw, cfg, pcfg, mesh, tokens, labels, steps,
               profile_overlap, lr=1e-2, grad_clip=None, monitor=None):
    import numpy as np
    import jax

    from paddle_tpu.parallel import comm_opt, parallelize as PZ

    init_kw = {k: v for k, v in kw.items()
               if k in ("grad_reduce", "bucket_mb", "error_feedback",
                        "grad_allreduce_dtype", "sharding")}
    params, opt = PZ.init_sharded(jax.random.PRNGKey(0), cfg, pcfg, mesh,
                                  **init_kw)
    step = PZ.make_train_step(cfg, pcfg, mesh, lr=lr, grad_clip=grad_clip,
                              **kw)

    # one shared warmup/compile/timing loop (paddle_tpu.tuning.probe,
    # ISSUE 20); per-step-synced — wall time IS step time here. The
    # after_compile hook snapshots the wire counters across exactly the
    # first call: it traces exactly once (AOT lower+compile keeps the
    # executable), so the delta IS the per-step bytes.
    from paddle_tpu.tuning import probe as tuning_probe

    state = {"params": params, "opt": opt}

    def _step(i):
        state["params"], state["opt"], loss, gnorm = step(
            state["params"], state["opt"], tokens, labels)
        return loss, gnorm

    wire = {}
    before = _wire_snapshot()
    timing = tuning_probe.timed_loop(
        _step, steps - 1, sync=lambda v: float(v[0]),
        after_compile=lambda: wire.update(
            _wire_delta(before, _wire_snapshot())))
    params, opt = state["params"], state["opt"]
    compile_s = timing.compile_s
    losses = [float(v[0]) for v in timing.values]
    gnorm = timing.values[-1][1]
    times = timing.step_times_s

    overlap = None
    if profile_overlap:
        import jax.profiler

        tdir = tempfile.mkdtemp(prefix=f"comm_bench_{name}_")
        with jax.profiler.trace(tdir):
            params, opt, loss, _ = step(params, opt, tokens, labels)
            float(loss)
        overlap = comm_opt.measure_overlap_fraction(tdir)

    grad_bytes = sum(v for (op, dt), v in wire.items()
                     if op in GRAD_REDUCE_OPS)
    total_bytes = sum(wire.values())
    row = {
        "config": name,
        "step_kwargs": {k: str(v) for k, v in kw.items()},
        "steps": steps,
        "ms_per_step": round(float(np.median(times)) * 1e3, 3)
        if times else None,
        "compile_s": round(compile_s, 2),
        "grad_reduce_bytes_per_step": int(grad_bytes),
        "total_collective_bytes_per_step": int(total_bytes),
        "wire_bytes_by_op_dtype": {f"{op}/{dt}": int(v)
                                   for (op, dt), v in sorted(wire.items())},
        "losses": [round(l, 6) for l in losses],
        "gnorm_last": round(float(gnorm), 6),
        "overlap_fraction": (round(overlap["overlap_fraction"], 4)
                             if overlap else 0.0),
        "overlap_source": (overlap["source"] if overlap
                           else "no_collective_events_in_trace"
                           if profile_overlap else "not_profiled"),
    }
    if overlap:
        row["collective_ms"] = round(overlap["collective_ms"], 3)
        row["exposed_collective_ms"] = round(overlap["exposed_ms"], 3)
    if monitor:
        # one TrainMonitor JSONL row per measured step, with the measured
        # overlap fraction stamped into the schema's overlap_fraction field
        from paddle_tpu.observability import TrainMonitor

        mon = TrainMonitor(path=monitor,
                           examples_per_step=tokens.shape[1],
                           extra_static={"config": name},
                           sample_hbm=False)
        for t, loss_v in zip(times, losses[1:]):
            mon.record_step(t * 1e3, loss=loss_v,
                            overlap_fraction=row["overlap_fraction"])
        mon.close()
    return row, params


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=os.path.join(REPO, "COMM_BENCH.json"))
    ap.add_argument("--smoke", action="store_true",
                    help="CPU correctness lane on 8 virtual devices (the "
                         "only lane that runs without a TPU)")
    ap.add_argument("--dp", type=int, default=8)
    ap.add_argument("--pp", type=int, default=1)
    ap.add_argument("--tp", type=int, default=1)
    ap.add_argument("--steps", type=int, default=5)
    ap.add_argument("--batch", type=int, default=16, help="global batch")
    ap.add_argument("--d", type=int, default=128)
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--T", type=int, default=64)
    ap.add_argument("--vocab", type=int, default=512)
    ap.add_argument("--bucket-mb", type=float, default=None,
                    help="override CommConfig.bucket_mb for the rs configs")
    ap.add_argument("--sharding", default=None,
                    help="comma list of GSPMD sharding-plan presets "
                         "(dp,fsdp) to bench as extra gspmd_* configs "
                         "through the propagated-NamedSharding lowering "
                         "(docs/sharding.md)")
    ap.add_argument("--profile-overlap", action="store_true", default=None)
    ap.add_argument("--monitor", default=None,
                    help="also write TrainMonitor JSONL rows per config")
    args = ap.parse_args()
    _prepare_backend(args.smoke)

    import numpy as np
    import jax
    import jax.numpy as jnp

    from paddle_tpu.framework.core import ensure_compile_cache
    from paddle_tpu.models import gpt as G
    from paddle_tpu.parallel import parallelize as PZ
    from paddle_tpu.tuning.probe import require_tpu

    dev = require_tpu("tools/comm_bench.py", args.smoke).device
    ensure_compile_cache()
    on_acc = dev.platform != "cpu"
    if args.profile_overlap is None:
        args.profile_overlap = True  # cheap at this scale; honest 0 on CPU

    cfg = G.GPT_TINY.scaled(
        d_model=args.d, num_layers=args.layers, num_heads=4,
        d_ff=4 * args.d, max_seq_len=args.T, vocab_size=args.vocab,
        dtype=jnp.bfloat16 if on_acc else jnp.float32)
    pcfg = PZ.ParallelConfig(dp=args.dp, pp=args.pp, tp=args.tp,
                             microbatches=max(1, args.pp))
    mesh = PZ.build_mesh(pcfg)
    rng = np.random.default_rng(0)
    m = pcfg.microbatches
    tokens = rng.integers(0, cfg.vocab_size, (m, args.batch, args.T),
                          dtype=np.int32)
    labels = rng.integers(0, cfg.vocab_size, (m, args.batch, args.T),
                          dtype=np.int32)

    configs = list(CONFIGS)
    if args.sharding:
        # sharding-layer lanes (ISSUE 12): same model/mesh, lowered via
        # the propagated-NamedSharding GSPMD step; wire bytes come from
        # the plan's static comm_opt estimate (GSPMD's own collectives
        # aren't individually instrumented)
        for mode in args.sharding.split(","):
            mode = mode.strip()
            if mode and mode != "none":
                configs.append((f"gspmd_{mode}", {"sharding": mode}))

    rows, final_params = [], {}
    for name, kw in configs:
        if args.bucket_mb is not None and kw.get("grad_reduce") == \
                "reduce_scatter":
            kw = dict(kw, bucket_mb=args.bucket_mb)
        print(f"[comm_bench] {name} ...", file=sys.stderr, flush=True)
        row, params = run_config(name, kw, cfg, pcfg, mesh, tokens, labels,
                                 args.steps, args.profile_overlap,
                                 monitor=args.monitor)
        rows.append(row)
        final_params[name] = params
        print(json.dumps(row), flush=True)

    by_name = {r["config"]: r for r in rows}
    base = by_name["psum_f32"]

    # bit-parity: f32 reduce-scatter vs the psum baseline (same grad_clip
    # disabled on every config so the clip-scale reduction order — the one
    # float-association difference between the paths — is out of the game)
    p0 = jax.tree_util.tree_leaves(final_params["psum_f32"])
    p1 = jax.tree_util.tree_leaves(final_params["reduce_scatter_f32"])
    bit_identical = all(bool((np.asarray(a) == np.asarray(b)).all())
                        for a, b in zip(p0, p1)) and \
        base["losses"] == by_name["reduce_scatter_f32"]["losses"]
    by_name["reduce_scatter_f32"]["bit_identical_to_psum"] = bool(
        bit_identical)

    if "gspmd_dp" in by_name:
        # the sharding-layer dp plan must reproduce the psum baseline's
        # weight trajectory bit-for-bit (same grad_clip=None discipline
        # as the rs parity pair)
        pg = jax.tree_util.tree_leaves(final_params["gspmd_dp"])
        by_name["gspmd_dp"]["params_bit_identical_to_psum"] = bool(all(
            bool((np.asarray(a) == np.asarray(b)).all())
            for a, b in zip(p0, pg)))

    def ratio(a, b):
        return round(a / b, 3) if b else None

    summary = {
        "grad_reduce_bytes_baseline": base["grad_reduce_bytes_per_step"],
        "rs_f32_grad_bytes_reduction_x": ratio(
            base["grad_reduce_bytes_per_step"],
            by_name["reduce_scatter_f32"]["grad_reduce_bytes_per_step"]),
        "rs_bf16_vs_rs_f32_grad_bytes_reduction_x": ratio(
            by_name["reduce_scatter_f32"]["grad_reduce_bytes_per_step"],
            by_name["reduce_scatter_bf16"]["grad_reduce_bytes_per_step"]),
        "rs_bf16_vs_baseline_grad_bytes_reduction_x": ratio(
            base["grad_reduce_bytes_per_step"],
            by_name["reduce_scatter_bf16"]["grad_reduce_bytes_per_step"]),
        "bit_identical_rs_f32": bool(bit_identical),
    }

    out = {
        "bench": "comm_bench",
        "backend": dev.platform,
        "device_kind": str(getattr(dev, "device_kind", dev.platform)),
        "degraded": not on_acc,   # CPU mesh measures bytes + parity, not
                                  # real ICI time/overlap
        "mesh": {"dp": args.dp, "pp": args.pp, "tp": args.tp},
        "model": {"d_model": args.d, "layers": args.layers, "T": args.T,
                  "vocab": args.vocab, "batch": args.batch},
        "steps": args.steps,
        "summary": summary,
        "configs": rows,
    }
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)
    print(f"[comm_bench] wrote {args.out}", file=sys.stderr)
    print(json.dumps({"summary": summary}))
    return out


if __name__ == "__main__":
    main()
