#!/usr/bin/env python
"""Measured device-time breakdown + roofline attribution of a step.

Train mode captures a jax.profiler xplane trace of N steps of the
flagship GPT train step at a sweep-spec config (tools/mfu_sweep.py spec
grammar), aggregates per-HLO-op measured device nanoseconds (the legacy
PROFILE_STEP.json view), and — new in ISSUE 14 — joins the measured
per-fusion time with the static HLO flops/bytes and the hw.py peak
tables into a schema-versioned ATTRIBUTION.json: every fusion placed on
the roofline, inter-op gap share, and the ranked small-op residue list
(ROADMAP item 3's megakernel target list).

Serve mode (``--serve``) profiles a warmed DecodeEngine decode tick
through the same attribution path, emitting the decode residue ranking
ROADMAP item 3(b) needs.

Usage:
  python tools/profile_step.py [spec] [--steps 6] [--dir /tmp/gpt-trace]
      [--attr-out ATTRIBUTION.json]
  python tools/profile_step.py --smoke          # tiny CPU-sized lane
  python tools/profile_step.py --smoke --tuned=TUNED.json
      # profile the autotuner winner; attribution config carries the
      # full tuned knob vector + tuned_from path/hash
  python tools/profile_step.py --serve [--ticks 16] [--attr-out PATH]
      [--fused-decode]                          # one-launch decode step
      [--disagg] [--role prefill|decode]  # stamp disagg=1 + role into
      # the attribution config so phase-split captures diff cleanly
      # against colocated ones (docs/serving.md "Disaggregation")
  python tools/profile_step.py --compare A.json B.json
      # residue-diff two attribution captures (per-group ms/step and
      # event-count deltas) — the before/after gate for each megakernel

Spec keys fln=1 / fopt=1 turn on the fused layernorm block kernel and
the Pallas optimizer megakernel (docs/kernels.md).

``--tuned=TUNED.json`` profiles the autotuner's winner (ISSUE 20): the
document is hw-fingerprint gated (mismatch warns + falls back), tuned
knobs apply only where the spec/flags left the default, and the
attribution ``config`` stamp carries the FULL tuned knob vector per
space (incl. disagg ratio, spec window, page pool) plus a ``tuned_from``
path+hash pointer — perf_diff cause-attributes a regression to the
exact tune, not "config lever unknown".

Reference analogue: platform/device_tracer.cc (CUPTI per-kernel times);
here the XLA device plane carries the measured per-fusion times and the
optimized HLO text carries the static costs.
"""
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

SMOKE_SPEC = "d=32,L=2,nh=2,ff=64,b=2,T=16,vocab=512,steps=3"
DEFAULT_SPEC = "d=2048,L=6,nh=16,ff=8192,b=16,remat=dots,celim=1073741824"


def _flag(name, default=None, cast=str):
    if name in sys.argv:
        return cast(sys.argv[sys.argv.index(name) + 1])
    return default


def _load_tuned(tuned_path, mode):
    """Fingerprint-gated TUNED.json load (None when absent/REFUSED)."""
    if not tuned_path:
        return None
    from paddle_tpu.tuning import probe as tuning_probe
    from paddle_tpu.tuning import tuned as tuned_mod

    doc = tuned_mod.load_for_device(tuned_path, tuning_probe.device_info())
    print(f"[profile{' --serve' if mode == 'serve' else ''}] tuned config "
          f"{'applied' if doc else 'REFUSED'} from {tuned_path}",
          file=sys.stderr, flush=True)
    return doc


def train_profile(spec_str: str, trace_dir: str, steps: int = 6,
                  attr_out: str = None, profile_out: str = None,
                  runs: int = 1, tuned: str = None):
    """Profile the GPT train step at ``spec_str``; returns (profile doc,
    attribution doc) and writes PROFILE_STEP.json + ATTRIBUTION.json.

    ``runs > 1`` traces the SAME warmed step that many times (one
    compile) and returns a list of (profile, attribution) pairs — the
    A/A-stability gate in tests/test_attribution.py diffs two
    back-to-back runs without paying a second compile; the JSON sinks
    record the last run."""
    import numpy as np
    import jax

    from paddle_tpu.models import gpt as G
    from paddle_tpu.observability import attribution as ATT
    from paddle_tpu.observability import goodput as GP
    from paddle_tpu.observability import program_report as PREP
    from paddle_tpu.parallel import parallelize as PZ
    from paddle_tpu.utils import device_trace as DT

    spec = dict(kv.split("=") for kv in spec_str.split(","))
    batch = int(spec.get("b", 16))
    T = int(spec.get("T", 1024))
    steps = int(spec.get("steps", steps))
    bq, bk = int(spec.get("bq", 512)), int(spec.get("bk", 512))
    if bq != 512 or bk != 512:
        # route the spec's flash tile sizes through the default entry
        # point, exactly like tools/mfu_sweep.py — a copied sweep row
        # must profile the configuration it measured
        from paddle_tpu.ops import pallas_kernels as PK

        orig = PK.flash_attention

        def patched(q, k, v, causal=True, sm_scale=None, block_q=512,
                    block_k=512, bias=None):
            return orig(q, k, v, causal=causal, sm_scale=sm_scale,
                        block_q=bq, block_k=bk, bias=bias)

        PK.flash_attention = patched
    unknown = set(spec) - {"b", "T", "steps", "bq", "bk", "d", "L", "ff",
                           "nh", "remat", "celim", "flash", "scan", "mom",
                           "chunk", "vocab", "fln", "fopt"}
    if unknown:
        raise SystemExit(f"profile_step: unknown spec keys {sorted(unknown)}")
    # fln=1 routes block layernorms through the fused Pallas block kernel
    # (ops/pallas_kernels.fused_ln); fopt=1 turns on the flat-buffer fused
    # optimizer sweep AND forces the Pallas optimizer megakernel so the
    # before/after residue capture reflects the fused lowering even on the
    # CPU (interpret) lane. See docs/kernels.md.
    fused_ln = spec.get("fln", "0") == "1"
    fused_opt = spec.get("fopt", "0") == "1"
    kw = dict(
        fused_ln=fused_ln,
        max_seq_len=T,
        use_flash=spec.get("flash", "1") == "1",
        d_model=int(spec.get("d", 768)),
        num_layers=int(spec.get("L", 12)),
        d_ff=int(spec.get("ff", 4 * int(spec.get("d", 768)))),
        remat=spec.get("remat", "full") != "none",
        remat_policy=("dots" if spec.get("remat") == "dots" else "full"),
        scan_layers=spec.get("scan", "1") == "1",
    )
    if "nh" in spec:
        kw["num_heads"] = int(spec["nh"])
    if "vocab" in spec:
        kw["vocab_size"] = int(spec["vocab"])
    if "celim" in spec:
        kw["ce_direct_bytes_limit"] = int(spec["celim"])
    if "chunk" in spec:
        kw["ce_chunk"] = int(spec["chunk"])
    tuned_doc = _load_tuned(tuned, "train")
    if tuned_doc is not None:
        # tuned knobs only where the spec left the default — a spec key
        # always beats the tuner (same discipline as bench.py --tuned)
        from paddle_tpu.tuning import tuned as tuned_mod

        ck = tuned_mod.train_cfg_kwargs(tuned_doc)
        if "remat" not in spec and "remat" in ck:
            kw["remat"] = ck["remat"]
            kw["remat_policy"] = ck["remat_policy"]
        if "fln" not in spec and ck.get("fused_ln"):
            fused_ln = True
            kw["fused_ln"] = True
        if "fopt" not in spec:
            tcfg = (tuned_doc.get("spaces") or {}).get("train", {}).get(
                "config") or {}
            fused_opt = fused_opt or bool(tcfg.get("fused_opt"))
        if "chunk" not in spec and "celim" not in spec and \
                ck.get("ce_vocab_chunk"):
            kw["ce_vocab_chunk"] = ck["ce_vocab_chunk"]
            kw["ce_direct_bytes_limit"] = ck["ce_direct_bytes_limit"]
    cfg = G.GPT_SMALL.scaled(**kw)

    dev = jax.devices()[0]
    pcfg = PZ.ParallelConfig(dp=1, pp=1, tp=1, microbatches=1)
    mesh = PZ.build_mesh(pcfg, devices=[dev])
    import jax.numpy as jnp
    params, opt = PZ.init_sharded(
        jax.random.PRNGKey(0), cfg, pcfg, mesh, fused_opt=fused_opt,
        moment_dtype=jnp.bfloat16 if spec.get("mom") == "bf16" else None)
    step = PZ.make_train_step(cfg, pcfg, mesh, lr=1e-4,
                              fused_opt=fused_opt,
                              fused_opt_pallas=True if fused_opt else None)
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, cfg.vocab_size, (1, batch, T), dtype=np.int32)
    labels = rng.integers(0, cfg.vocab_size, (1, batch, T), dtype=np.int32)

    print(f"[profile] compiling {spec_str}", file=sys.stderr, flush=True)
    params, opt, loss, _ = step(params, opt, tokens, labels)
    float(loss)

    hlo = step.hlo_text() if hasattr(step, "hlo_text") else None
    report = next((r for r in reversed(PREP.recent_reports())
                   if r.get("program") == getattr(step, "report_name",
                                                  None)), {})
    config = {
        "mode": "train", "spec": spec_str,
        "remat": cfg.remat_policy if cfg.remat else "none",
        "flash": spec.get("flash", "1") == "1",
        "scan": spec.get("scan", "1") == "1",
        "moment_dtype": spec.get("mom", "f32"),
        "ce_chunk": int(spec.get("chunk", 0)),
        "batch": batch, "seq": T,
        "d_model": cfg.d_model, "layers": cfg.num_layers,
        "fused_opt": fused_opt,
        "fused_ln": fused_ln,
    }
    if tuned_doc is not None:
        from paddle_tpu.tuning import tuned as tuned_mod

        # full tuned-knob vector + tuned_from provenance (ISSUE 20)
        config.update(tuned_mod.config_stamp(tuned_doc, tuned))

    results = []
    for run_i in range(max(1, runs)):
        tdir = trace_dir if runs <= 1 else f"{trace_dir}_r{run_i}"
        print(f"[profile] tracing {steps} steps"
              + (f" (run {run_i + 1}/{runs})" if runs > 1 else ""),
              file=sys.stderr, flush=True)
        t0 = time.perf_counter()
        with GP.ledger().run_window(export=False):
            with jax.profiler.trace(tdir):
                for _ in range(steps):
                    params, opt, loss, _ = step(params, opt, tokens,
                                                labels)
                float(loss)
        wall_s = time.perf_counter() - t0

        # legacy per-HLO-family view (PROFILE_STEP.json)
        agg = {}
        total_ns = 0.0
        for _module, hlo_op, dur in DT.device_events(tdir,
                                                     exclusive=True):
            fam = hlo_op.split(".")[0]
            a = agg.setdefault(fam, [0.0, 0])
            a[0] += dur
            a[1] += 1
            total_ns += dur
        rows = sorted(
            ({"op": k, "ms_per_step": v[0] / 1e6 / steps, "events": v[1]}
             for k, v in agg.items()),
            key=lambda r: -r["ms_per_step"])

        wall_ms = wall_s * 1e3 / steps
        busy_ms = total_ns / 1e6 / steps
        print(f"\n=== {spec_str} on "
              f"{getattr(dev, 'device_kind', dev.platform)}")
        print(f"wall {wall_ms:.1f} ms/step | device busy {busy_ms:.1f} "
              f"ms/step | gap {wall_ms - busy_ms:.1f} ms/step")
        for r in rows[:25]:
            print(f"{r['ms_per_step']:9.2f} ms  x{r['events']:<5d} "
                  f"{r['op']}")
        profile = {"spec": spec_str, "wall_ms_per_step": round(wall_ms, 2),
                   "device_busy_ms_per_step": round(busy_ms, 2),
                   "rows": [{**r, "ms_per_step": round(r["ms_per_step"],
                                                       3)}
                            for r in rows[:40]]}
        path = profile_out or os.path.join(REPO, "PROFILE_STEP.json")
        with open(path, "w") as f:
            json.dump(profile, f, indent=1)
        print(f"[profile] wrote {path}", file=sys.stderr)

        # roofline attribution (ISSUE 14): measured x static HLO costs
        attribution = ATT.build_from_trace(
            tdir, steps=steps, wall_ms_per_step=wall_ms,
            hlo_texts=[hlo] if hlo else [], device=dev, mode="train",
            spec=spec_str, step_flops=report.get("flops"),
            step_bytes=report.get("bytes_accessed"),
            programs=[report] if report else None, config=config,
            generated_by="tools/profile_step.py")
        apath = attr_out or os.path.join(REPO, "ATTRIBUTION.json")
        ATT.write(attribution, apath)
        res = attribution["residue"]
        print(f"[profile] attribution: {attribution['fusion_count']} "
              f"fusions, residue {res['count']} ops "
              f"({res['share_of_busy']:.1%} of busy; top groups "
              f"{[g['label'] for g in res['groups'][:4]]}) -> {apath}",
              file=sys.stderr)
        results.append((profile, attribution))
    return results if runs > 1 else results[0]


def serve_profile(trace_dir: str, ticks: int = 16, attr_out: str = None,
                  d: int = 64, layers: int = 4, nh: int = 4, ff: int = 128,
                  vocab: int = 256, max_batch: int = 4, max_seq: int = 64,
                  weight_dtype: str = "f32",
                  fused_decode: bool = False, role: str = "colocated",
                  tuned: str = None):
    """Profile a warmed DecodeEngine decode tick: fill every slot, trace
    ``ticks`` full-batch decode steps, attribute through the same
    roofline path — the decode residue ranking is ROADMAP item 3(b)'s
    megakernel target list."""
    import numpy as np
    import jax

    from paddle_tpu import serving
    from paddle_tpu.models import gpt
    from paddle_tpu.observability import attribution as ATT
    from paddle_tpu.observability import program_report as PREP

    dev = jax.devices()[0]
    tuned_doc = _load_tuned(tuned, "serve")
    if tuned_doc is not None:
        # dtype/fused-decode only where the flags stayed default
        from paddle_tpu.tuning import tuned as tuned_mod

        scfg = (tuned_doc.get("spaces") or {}).get("serve", {}).get(
            "config") or {}
        if weight_dtype == "f32" and scfg.get("weight_dtype"):
            weight_dtype = scfg["weight_dtype"]
        if not fused_decode and scfg.get("fused_decode"):
            fused_decode = True
    cfg = gpt.GPTConfig(vocab_size=vocab, max_seq_len=max(max_seq, 64),
                        num_layers=layers, num_heads=nh, d_model=d,
                        d_ff=ff, remat=False)
    params = gpt.init_params(jax.random.PRNGKey(0), cfg)
    ekw = dict(max_batch=max_batch, max_seq=max_seq,
               prefill_buckets=(8, 16), weight_dtype=weight_dtype,
               fused_decode=fused_decode, role=role, page_size=8)
    if tuned_doc is not None and scfg.get("num_pages"):
        ekw["num_pages"] = int(scfg["num_pages"])
    engine = serving.DecodeEngine(params, cfg,
                                  serving.EngineConfig(**ekw))
    print("[profile --serve] warmup (AOT prefill ladder + decode)",
          file=sys.stderr, flush=True)
    engine.warmup()

    rng = np.random.RandomState(0)
    slots, last = [], {}
    for _ in range(max_batch):
        prompt = rng.randint(0, vocab, size=6).tolist()
        slot, logits = engine.start_sequence(prompt)
        slots.append(slot)
        last[slot] = int(np.argmax(logits))
    # warm the full-batch decode signature before tracing
    out = engine.decode_step({s: last[s] for s in slots})
    last = {s: int(np.argmax(v)) for s, v in out.items()}

    print(f"[profile --serve] tracing {ticks} decode ticks "
          f"(batch {max_batch})", file=sys.stderr, flush=True)
    t0 = time.perf_counter()
    with jax.profiler.trace(trace_dir):
        for _ in range(ticks):
            out = engine.decode_step({s: last[s] for s in slots})
            last = {s: int(np.argmax(v)) for s, v in out.items()}
    wall_ms = (time.perf_counter() - t0) * 1e3 / ticks
    for s in slots:
        engine.free_sequence(s)

    hlo_texts = []
    try:
        hlo_texts.append(engine._exec["decode"].as_text())
    except Exception:
        pass
    reports = [r for r in PREP.recent_reports()
               if str(r.get("program", "")).startswith("serve/")]
    decode_rep = next((r for r in reversed(reports)
                       if r.get("program") == "serve/decode"), {})
    config = {
        "mode": "decode", "weight_dtype": weight_dtype,
        "max_batch": max_batch,
        "max_seq": max_seq, "d_model": d, "layers": layers,
        "fused_decode": fused_decode,
        # disagg stamp (ISSUE 17): phase-split captures must be
        # distinguishable from colocated ones when residue-diffed —
        # a prefill-only replica's roofline is not a decode replica's
        "disagg": 1 if role in ("prefill", "decode") else 0,
        "role": role,
    }
    if tuned_doc is not None:
        # full tuned-knob vector + tuned_from provenance (ISSUE 20)
        config.update(tuned_mod.config_stamp(tuned_doc, tuned))
    attribution = ATT.build_from_trace(
        trace_dir, steps=ticks, wall_ms_per_step=wall_ms,
        hlo_texts=hlo_texts, device=dev, mode="decode",
        spec=f"serve:d={d},L={layers},b={max_batch},"
             f"{weight_dtype}"
             + (",fused" if fused_decode else "")
             + (f",{role}" if role != "colocated" else ""),
        step_flops=decode_rep.get("flops"),
        step_bytes=decode_rep.get("bytes_accessed"),
        programs=reports[-8:] or None, config=config,
        generated_by="tools/profile_step.py --serve")
    apath = attr_out or os.path.join(REPO, "ATTRIBUTION_DECODE.json")
    ATT.write(attribution, apath)
    res = attribution["residue"]
    print(f"[profile --serve] decode tick {wall_ms:.2f} ms | busy "
          f"{attribution['device_busy_ms_per_step']:.2f} ms | "
          f"{attribution['fusion_count']} fusions | residue "
          f"{res['count']} ops ({res['share_of_busy']:.1%}) "
          f"groups {[g['label'] for g in res['groups'][:4]]} -> {apath}",
          file=sys.stderr)
    return attribution


def compare_attributions(path_a: str, path_b: str, out=sys.stdout):
    """Residue-diff two attribution docs (the before/after gate for each
    megakernel): per-residue-group ms/step and event-count deltas, plus
    the config levers that changed between the two captures. Returns the
    joined per-group rows so tests can assert on the deltas."""
    with open(path_a) as f:
        a = json.load(f)
    with open(path_b) as f:
        b = json.load(f)

    def _groups(doc):
        return {g["label"]: g for g in
                doc.get("residue", {}).get("groups", [])}

    ga, gb = _groups(a), _groups(b)
    ca, cb = a.get("config") or {}, b.get("config") or {}
    print(f"=== residue diff: A={path_a}  B={path_b}", file=out)
    levers = sorted(k for k in set(ca) | set(cb)
                    if ca.get(k) != cb.get(k))
    for k in levers:
        print(f"CONFIG {k}: {ca.get(k)!r} -> {cb.get(k)!r}", file=out)
    ra, rb = a.get("residue", {}), b.get("residue", {})
    print(f"residue total: {ra.get('ms_per_step', 0):.4f} -> "
          f"{rb.get('ms_per_step', 0):.4f} ms/step | "
          f"{ra.get('count', 0)} -> {rb.get('count', 0)} ops | "
          f"fusions {a.get('fusion_count', 0)} -> "
          f"{b.get('fusion_count', 0)}", file=out)
    print(f"{'group':<16}{'ms/step A':>11}{'ms/step B':>11}"
          f"{'d(ms)':>9}{'ev A':>8}{'ev B':>8}{'d(ev)':>8}", file=out)
    rows = []
    for label in sorted(set(ga) | set(gb),
                        key=lambda l: -(ga.get(l, {})
                                        .get("ms_per_step", 0.0))):
        xa, xb = ga.get(label, {}), gb.get(label, {})
        ms_a = xa.get("ms_per_step", 0.0)
        ms_b = xb.get("ms_per_step", 0.0)
        ev_a = xa.get("events_per_step", 0.0)
        ev_b = xb.get("events_per_step", 0.0)
        rows.append({"label": label, "ms_a": ms_a, "ms_b": ms_b,
                     "ev_a": ev_a, "ev_b": ev_b})
        print(f"{label:<16}{ms_a:>11.4f}{ms_b:>11.4f}"
              f"{ms_b - ms_a:>+9.4f}{ev_a:>8.1f}{ev_b:>8.1f}"
              f"{ev_b - ev_a:>+8.1f}", file=out)
    return rows


def main():
    if "--compare" not in sys.argv:
        from paddle_tpu.framework.core import ensure_compile_cache
        from paddle_tpu.tuning.probe import require_tpu

        require_tpu("tools/profile_step.py",
                                 "--smoke" in sys.argv)
        ensure_compile_cache()
    trace_dir = _flag("--dir", "/tmp/gpt-trace")
    attr_out = _flag("--attr-out")
    tuned = _flag("--tuned") or next(
        (a.split("=", 1)[1] for a in sys.argv
         if a.startswith("--tuned=")), None)
    if "--compare" in sys.argv:
        i = sys.argv.index("--compare")
        compare_attributions(sys.argv[i + 1], sys.argv[i + 2])
        return
    if "--serve" in sys.argv:
        role = _flag("--role", "colocated")
        if "--disagg" in sys.argv and role == "colocated":
            role = "decode"      # decode replicas are the tick being traced
        serve_profile(trace_dir, ticks=int(_flag("--ticks", 16, int)),
                      attr_out=attr_out,
                      weight_dtype=_flag("--weight-dtype", "f32"),
                      max_batch=int(_flag("--max-batch", 4, int)),
                      fused_decode="--fused-decode" in sys.argv,
                      role=role, tuned=tuned)
        return
    if "--smoke" in sys.argv:
        spec_str = SMOKE_SPEC
    else:
        spec_str = sys.argv[1] if len(sys.argv) > 1 and "=" in sys.argv[1] \
            else DEFAULT_SPEC
    steps = int(_flag("--steps", 6, int))
    train_profile(spec_str, trace_dir, steps=steps, attr_out=attr_out,
                  profile_out=_flag("--profile-out"), tuned=tuned)


if __name__ == "__main__":
    main()
