#!/usr/bin/env python
"""Measurement-driven autotuner CLI (ISSUE 20; core logic in
paddle_tpu/tuning/, schema + runbook in docs/autotune.md).

Enumerates the train/serve knob spaces, prunes with the static roofline
model anchored on the incumbent's AOT program report, probes survivors
successive-halving style, and writes TUNED.json — the reproducible
artifact ``bench.py --tuned=``, ``tools/serve_bench.py --tuned=`` and
``make_train_step(tuned=)`` accept (hw-fingerprint gated).

  python tools/autotune.py --smoke              # CPU-lane end-to-end
  python tools/autotune.py --space train --out TUNED.json
  python tools/autotune.py --smoke --log probes.jsonl   # resumable:
      # a killed tune re-run with the same --log continues — completed
      # probes come back from the JSONL without re-running (probe count
      # conserved), only the remainder executes

Arbitration: after the tune, the winner runs one monitored confirm
probe and tools/perf_diff.py diffs it against PERF_BASELINE.json; the
verdict is stamped into TUNED.json ``arbitration`` and the process
exits non-zero if the tuned config regresses the committed baseline.
"""
import argparse
import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _parse_rungs(spec: str):
    rungs = []
    for part in spec.split(","):
        steps, keep = part.split(":")
        rungs.append((int(steps), float(keep)))
    return tuple(rungs)


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="measurement-driven autotuner (docs/autotune.md)")
    ap.add_argument("--space", default="all",
                    choices=("train", "serve", "all"))
    ap.add_argument("--smoke", action="store_true",
                    help="tiny geometry + trimmed serve axes: the CPU "
                         "correctness lane, the only one that runs "
                         "without a TPU")
    ap.add_argument("--out", default=os.path.join(REPO, "TUNED.json"))
    ap.add_argument("--log", default=None,
                    help="probe-log JSONL (default <out>.probes.jsonl); "
                         "re-running with the same log resumes")
    ap.add_argument("--train-rungs", default="2:0.5,4:1.0",
                    help="steps:keep_frac[,steps:keep_frac...]")
    ap.add_argument("--serve-rungs", default="4:0.5,8:1.0",
                    help="requests:keep_frac[,...]")
    ap.add_argument("--static-margin", type=float, default=0.20)
    ap.add_argument("--improve-margin", type=float, default=0.03)
    ap.add_argument("--hbm-budget", type=float, default=None,
                    help="override the hw.py HBM capacity budget in "
                         "bytes (tests seed an over-HBM candidate here)")
    ap.add_argument("--no-arbitrate", action="store_true")
    ap.add_argument("--baseline",
                    default=os.path.join(REPO, "PERF_BASELINE.json"))
    ap.add_argument("--seed", type=int, default=0)
    # geometry (defaults are the bench.py gpt_tiny_cpu smoke shape)
    ap.add_argument("--d", type=int, default=64)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--nh", type=int, default=4)
    ap.add_argument("--ff", type=int, default=128)
    ap.add_argument("--T", type=int, default=32)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--vocab", type=int, default=256)
    ap.add_argument("--max-seq", type=int, default=64)
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--page-size", type=int, default=8)
    ap.add_argument("--requests", type=int, default=None,
                    help="override terminal-rung request count (serve)")
    args = ap.parse_args(argv)

    from paddle_tpu.tuning import driver, probe, space, static_cost
    from paddle_tpu.tuning import tuned as tuned_mod

    di = probe.require_tpu("tools/autotune.py", args.smoke)
    fp = probe.hw_fingerprint(di)
    print(f"[autotune] device: {di.platform}/{di.device_kind} "
          f"x{di.n_devices} degraded={di.degraded} "
          f"fingerprint={fp['fingerprint']}", flush=True)
    ctx = space.SpaceContext(
        dp=1, n_devices=di.n_devices, platform=di.platform,
        vocab_size=args.vocab, max_seq=args.max_seq,
        max_batch=args.max_batch, page_size=args.page_size,
        on_acc=di.on_acc)

    log_path = args.log or args.out + ".probes.jsonl"
    plog = driver.ProbeLog(log_path)
    hwm = static_cost.HwModel.for_device(
        di.device, hbm_capacity_bytes=(
            args.hbm_budget if args.hbm_budget is not None else ...))
    say = lambda m: print(f"[autotune] {m}", flush=True)  # noqa: E731
    results = {}

    if args.space in ("train", "all"):
        results["train"] = _tune_train(args, ctx, di, hwm, plog, say)
    if args.space in ("serve", "all"):
        results["serve"] = _tune_serve(args, ctx, di, hwm, plog, say)
    plog.close()

    doc = tuned_mod.build_doc(
        results, fp, args=" ".join(argv if argv is not None
                                   else sys.argv[1:]))
    tuned_mod.save(args.out, doc)
    say(f"wrote {args.out}")

    rc = 0
    if not args.no_arbitrate and "train" in results:
        rc = _arbitrate(args, results["train"], doc, say)
        tuned_mod.save(args.out, doc)    # with the arbitration stamp
    for s, tr in results.items():
        say(f"{s}: winner={tr.winner.key} improved={tr.improved} "
            f"probes_executed={tr.probes_executed} "
            f"pruned={json.dumps(tr.pruned)}")
    return rc


def _tune_train(args, ctx, di, hwm, plog, say):
    from paddle_tpu.tuning import driver, probe, space, static_cost

    axes = space.train_axes(ctx)
    valid, refused = space.enumerate_space("train", axes, ctx)
    say(f"train: {len(valid) + len(refused)} enumerated, "
        f"{len(refused)} refused by validity predicates")
    incumbent = space.train_incumbent(ctx)
    geom = probe.TrainProbeGeometry(
        d_model=args.d, num_layers=args.layers, num_heads=args.nh,
        d_ff=args.ff, T=args.T, vocab_size=args.vocab, batch=args.batch,
        dp=ctx.dp)

    def probe_fn(cand, steps, rung):
        return probe.run_train_probe(cand, geom, steps, warmup=1,
                                     seed=args.seed)

    def static_fn(cand, inc_result):
        rep = (inc_result or {}).get("report") or {}
        if not rep.get("flops") or not rep.get("bytes_accessed"):
            return None               # no AOT report: measure instead
        base = static_cost.BaseStats(
            flops=float(rep["flops"]),
            bytes_accessed=float(rep["bytes_accessed"]),
            peak_hbm_bytes=float(rep.get("peak_hbm_bytes") or 0.0),
            param_bytes=float(inc_result.get("params") or 0) * 4.0,
            tokens_per_step=geom.batch * geom.T,
            vocab_size=args.vocab, incumbent=incumbent)
        return static_cost.predict_train(cand, base, hwm, dp=ctx.dp)

    return driver.tune(
        space="train", candidates=valid, refusals=refused,
        incumbent=incumbent, probe_fn=probe_fn, static_fn=static_fn,
        rungs=_parse_rungs(args.train_rungs),
        improve_margin=args.improve_margin,
        static_margin=args.static_margin, log=plog, phase="train",
        progress=say)


def _tune_serve(args, ctx, di, hwm, plog, say):
    from paddle_tpu.tuning import driver, probe, space, static_cost

    if args.smoke:
        axes = space.serve_axes(
            ctx, max_batches=(args.max_batch,),
            bucket_ladders=((max(args.page_size, args.max_seq // 4),
                             args.max_seq // 2),
                            (args.max_seq // 2,)),
            specs=(0, 2), disagg_ratios=("off", "1:1"),
            disagg_decode_batches=(1,))
    else:
        axes = space.serve_axes(ctx)
    valid, refused = space.enumerate_space("serve", axes, ctx)
    say(f"serve: {len(valid) + len(refused)} enumerated, "
        f"{len(refused)} refused by validity predicates")
    incumbent = space.serve_incumbent(ctx)
    geom = probe.ServeProbeGeometry(
        d_model=args.d, num_layers=args.layers, num_heads=args.nh,
        d_ff=args.ff, vocab_size=args.vocab, max_seq=args.max_seq,
        page_size=args.page_size)

    # analytic decode-tick base: one token re-reads the weights once
    # (flops 2N, bytes ~param_bytes) — enough for RELATIVE pruning
    from paddle_tpu.models import gpt as G
    import jax

    cfg = G.GPT_TINY.scaled(d_model=args.d, num_layers=args.layers,
                            num_heads=args.nh, d_ff=args.ff,
                            vocab_size=args.vocab,
                            max_seq_len=args.max_seq)
    n_params = G.num_params(G.init_params(jax.random.PRNGKey(0), cfg))
    param_bytes = n_params * 4.0
    kv_page_bytes = 2.0 * args.layers * args.d * args.page_size * 4.0

    def probe_fn(cand, steps, rung):
        return probe.run_serve_probe(cand, geom, n_requests=steps,
                                     seed=args.seed)

    def static_fn(cand, inc_result):
        base = static_cost.BaseStats(
            flops=2.0 * n_params, bytes_accessed=param_bytes,
            peak_hbm_bytes=3.0 * param_bytes,
            param_bytes=param_bytes, incumbent=space.serve_incumbent(ctx))
        return static_cost.predict_serve(cand, base, hwm,
                                         kv_page_bytes=kv_page_bytes)

    rungs = _parse_rungs(args.serve_rungs)
    if args.requests:
        rungs = rungs[:-1] + ((args.requests, rungs[-1][1]),)
    return driver.tune(
        space="serve", candidates=valid, refusals=refused,
        incumbent=incumbent, probe_fn=probe_fn, static_fn=static_fn,
        rungs=rungs, improve_margin=args.improve_margin,
        static_margin=args.static_margin, log=plog, phase="serve",
        progress=say)


def _arbitrate(args, train_result, doc, say):
    """Confirm the train winner with a monitored probe, then let
    perf_diff.py arbitrate tuned-vs-PERF_BASELINE. Only the monitor
    artifact is supplied — absent artifacts are skipped (listed, not
    failed), and on the degraded CPU baseline timing bands demote to
    structural checks, so the gate is 'no structural regression', not
    a wall-clock race against a different machine."""
    from paddle_tpu.tuning import probe

    geom = probe.TrainProbeGeometry(
        d_model=args.d, num_layers=args.layers, num_heads=args.nh,
        d_ff=args.ff, T=args.T, vocab_size=args.vocab, batch=args.batch)
    mon_path = args.out + ".confirm.jsonl"
    if os.path.exists(mon_path):
        os.unlink(mon_path)
    winner = train_result.winner
    say(f"arbitration: confirm probe of {winner.key}")
    confirm = probe.run_train_probe(winner, geom, steps=4, warmup=1,
                                    monitor=mon_path, seed=args.seed)
    out = args.out + ".regression.json"
    cmd = [sys.executable, os.path.join(REPO, "tools", "perf_diff.py"),
           "--baseline", args.baseline, "--monitor", mon_path,
           "--attribution", "", "--goodput", "", "--dispatch", "",
           "--comm", "", "--serve", "", "--out", out,
           "--lane", "autotune",
           "--notes", f"tuned winner {winner.key}"]
    rc = subprocess.call(cmd)
    say(f"arbitration: perf_diff rc={rc} "
        f"(confirm {confirm.get('ms_per_step')} ms/step)")
    doc["arbitration"] = {
        "ran": True, "ok": rc == 0, "exit_code": rc,
        "baseline": args.baseline, "monitor": mon_path,
        "regression": out,
        "confirm_ms_per_step": confirm.get("ms_per_step"),
        "at": round(time.time(), 1),
    }
    return rc


if __name__ == "__main__":
    sys.exit(main())
