#!/usr/bin/env python
"""MFU sweep harness for the flagship GPT bench (tools/, not part of bench.py).

Runs one training-throughput measurement per config in an isolated subprocess
(OOM/compile failures can't poison the next config) and prints a ranked table.
Used to pick the bench.py defaults; keep bench.py's MFU formula as the single
source of truth (this file reuses it by construction: 6N + attention term over
peak bf16 FLOP/s).

Usage:
  python tools/mfu_sweep.py                 # run the standard sweep
  python tools/mfu_sweep.py --one b=32,remat=dots,bq=512,bk=512
  # HBM-lever axes: cross the base config with CE vocab-chunk sizes and
  # the fused flat-buffer optimizer (docs/memory_levers.md)
  python tools/mfu_sweep.py --ce-chunk 0,1024 --fused-opt 0,1
  python tools/mfu_sweep.py --base d=64,L=2,nh=4,ff=128,T=32,b=4,steps=2,flash=0 \
      --ce-chunk 0,64 --fused-opt 0,1      # CPU-sized end-to-end run
  # communication-lever axes (docs/comm_opt.md): cross the base config with
  # the gradient-reduction strategy, the collective wire dtype, and the
  # reduce-scatter bucket cap (dp>1 specs need that many devices)
  python tools/mfu_sweep.py --base d=64,L=2,nh=4,ff=128,T=32,b=8,steps=2,flash=0,dp=8 \
      --grad-reduce psum,reduce_scatter --comm-dtype f32,bf16 --bucket-mb 32

  # sharding-layer axis (docs/sharding.md): run the same base config via
  # the propagated-NamedSharding GSPMD step instead of the shard_map path
  python tools/mfu_sweep.py --base d=64,L=2,nh=4,ff=128,T=32,b=8,steps=2,flash=0,dp=8 \
      --sharding none,dp,fsdp

Spec keys: b, steps, remat (none|full|dots|save_only_flash), bq, bk, nh, d,
L, ff, T, flash, mom (f32|bf16), scan, celim, chunk (CE row chunk),
vchunk (CE vocab chunk, 0 = off), fused (1 = flat-buffer fused optimizer),
dp (data-parallel ranks; b is the GLOBAL batch), gr (psum|reduce_scatter),
cdt (f32|bf16|int8 collective wire dtype), bmb (bucket cap MiB),
ef (1 = error-feedback residual for quantized comm),
shard (none|dp|fsdp|tp — lower through the GSPMD sharding plan; ISSUE 12).
Every config's result is emitted as one machine-readable JSON row on stdout
(the ranked human table follows after).
"""
import itertools
import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _ensure_devices(specs):
    """dp>1 specs need that many devices; on the host platform that means
    forcing virtual devices BEFORE jax imports (no-op for real TPUs — the
    flag only affects the host backend)."""
    need = 1
    for s in specs:
        try:
            need = max(need, int(dict(kv.split("=") for kv in
                                      s.split(",")).get("dp", 1)))
        except Exception:
            pass
    flags = os.environ.get("XLA_FLAGS", "")
    if need > 1 and "host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = \
            f"{flags} --xla_force_host_platform_device_count={need}"


def worker():
    sys.path.insert(0, REPO)
    _ensure_devices([sys.argv[2]])
    import numpy as np
    import jax

    _measure_spec(sys.argv[2], np, jax)


def multi_worker(specs):
    """All configs inside ONE process: a chip belongs to one process at a
    time and every new process pays its own start-up and cold compiles."""
    sys.path.insert(0, REPO)
    _ensure_devices(specs)
    import numpy as np
    import jax

    for spec in specs:
        print(f"[multi] {spec}", file=sys.stderr, flush=True)
        try:
            _measure_spec(spec, np, jax)
        except Exception as e:  # OOM etc: report and continue
            # surface the OOM/limit lines buried in long compiler errors
            # (str, not repr: repr escapes newlines into one giant line)
            keyw = [ln.strip()[:200] for ln in str(e).splitlines()
                    if any(k in ln.lower() for k in
                           ("exhausted", "memory", "hbm", "exceeds", "oom"))]
            print(json.dumps({"spec": spec, "error": repr(e)[:400],
                              "error_keylines": keyw[:4]}), flush=True)


def _measure_spec(spec_str, np, jax):
    spec = dict(kv.split("=") for kv in spec_str.split(","))
    batch = int(spec.get("b", 16))
    steps = int(spec.get("steps", 10))
    remat = spec.get("remat", "full")          # full | dots | none
    bq = int(spec.get("bq", 512))
    bk = int(spec.get("bk", 512))
    heads = int(spec.get("nh", 0))             # 0 = config default
    d_model = int(spec.get("d", 768))
    layers = int(spec.get("L", 12))
    d_ff = int(spec.get("ff", 4 * d_model))
    T = int(spec.get("T", 1024))
    flash = spec.get("flash", "1") == "1"
    mom = spec.get("mom", "f32")               # f32 | bf16 Adam moments
    scan = spec.get("scan", "1") == "1"        # 0 = unroll the layer loop
    fused = spec.get("fused", "0") == "1"      # flat-buffer fused optimizer
    dp = int(spec.get("dp", 1))                # data-parallel ranks
    grad_reduce = spec.get("gr", "psum")       # psum | reduce_scatter
    comm_dtype = spec.get("cdt", "f32")        # f32 | bf16 | int8 wire dtype
    bucket_mb = float(spec.get("bmb", 32))     # reduce-scatter bucket cap
    error_fb = spec.get("ef", "0") == "1"      # quantized-comm residual
    shard = spec.get("shard", "none")          # GSPMD sharding plan preset

    from paddle_tpu.models import gpt as G
    from paddle_tpu.parallel import parallelize as PZ
    from paddle_tpu.ops import pallas_kernels as PK

    # route the sweep's block sizes through the default entry point; ALWAYS
    # reset first — in a --multi process a previous spec's patch would
    # otherwise leak into every later default-block spec
    orig = getattr(PK, "_sweep_orig_flash", None)
    if orig is None:
        orig = PK._sweep_orig_flash = PK.flash_attention
    PK.flash_attention = orig
    if bq != 512 or bk != 512:
        def patched(q, k, v, causal=True, sm_scale=None, block_q=512,
                    block_k=512, bias=None):
            return orig(q, k, v, causal=causal, sm_scale=sm_scale,
                        block_q=bq, block_k=bk, bias=bias)
        PK.flash_attention = patched

    # remat by NAME through the first-class policy API (old spellings are
    # aliases — "none"/"full"/"dots"/"save_only_flash" all valid here)
    from paddle_tpu.parallel import remat as remat_mod

    rpolicy = remat_mod.resolve(remat)
    kw = dict(max_seq_len=T, use_flash=flash, d_model=d_model,
              num_layers=layers, d_ff=d_ff,
              remat=not rpolicy.is_none, scan_layers=scan,
              remat_policy=rpolicy.name)
    if "celim" in spec:
        kw["ce_direct_bytes_limit"] = int(spec["celim"])
    if "chunk" in spec:
        kw["ce_chunk"] = int(spec["chunk"])
    if "vchunk" in spec:
        kw["ce_vocab_chunk"] = int(spec["vchunk"])
    if heads:
        kw["num_heads"] = heads
    cfg = G.GPT_SMALL.scaled(**kw)

    dev = jax.devices()[0]
    if batch % dp:
        raise ValueError(f"global batch {batch} not divisible by dp={dp}")
    pcfg = PZ.ParallelConfig(dp=dp, pp=1, tp=1, microbatches=1)
    mesh = PZ.build_mesh(pcfg, devices=jax.devices()[:dp])
    import jax.numpy as jnp
    comm_kw = dict(grad_reduce=grad_reduce, grad_allreduce_dtype=comm_dtype,
                   bucket_mb=bucket_mb, error_feedback=error_fb)
    if shard != "none":
        comm_kw["sharding"] = shard   # GSPMD plan lowering (ISSUE 12)
    params, opt = PZ.init_sharded(
        jax.random.PRNGKey(0), cfg, pcfg, mesh,
        moment_dtype=jnp.bfloat16 if mom == "bf16" else None,
        fused_opt=fused, **comm_kw)
    step = PZ.make_train_step(cfg, pcfg, mesh, lr=1e-4, fused_opt=fused,
                              **comm_kw)
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, cfg.vocab_size, (1, batch, T), dtype=np.int32)
    labels = rng.integers(0, cfg.vocab_size, (1, batch, T), dtype=np.int32)

    # one shared warmup/compile/timing loop (paddle_tpu.tuning.probe,
    # ISSUE 20); block-timed with a single trailing sync — the
    # throughput discipline, donated params serialize steps on-device
    from paddle_tpu.tuning import probe as tuning_probe

    state = {"params": params, "opt": opt}

    def _step(i):
        state["params"], state["opt"], loss, _ = step(
            state["params"], state["opt"], tokens, labels)
        return loss

    timing = tuning_probe.timed_loop(_step, steps, sync=float,
                                     per_step_sync=False)
    params = state["params"]
    compile_s = timing.compile_s
    tokens_per_s = steps * batch * T / timing.block_s

    n_params = G.num_params(params)
    attn = 12 * cfg.num_layers * cfg.d_model * T
    # single source of truth for the bf16-peak table (bench._peak_flops:
    # v5e = 197e12 — 394 is its int8 rate; PEAK_PROBE.json holds the
    # measured 171.3 TFLOP/s matmul ceiling backing it)
    from bench import _peak_flops
    # dp ranks: tokens/s is global, so the denominator is dp x one chip
    mfu = tokens_per_s * (6 * n_params + attn) / (_peak_flops(dev) * dp)
    print(json.dumps({"spec": spec_str, "tokens_per_s": round(tokens_per_s, 1),
                      "mfu": round(mfu, 4),
                      "ms_per_step": round(timing.ms_per_step, 1),
                      "compile_s": round(compile_s, 1),
                      "params": int(n_params)}), flush=True)


def run_one(spec, timeout=420):
    """SIGINT-first teardown: give the child a grace window to unwind the
    PJRT client and release the chip before it is killed."""
    import signal

    cmd = [sys.executable, os.path.abspath(__file__), "--worker", spec]
    proc = subprocess.Popen(cmd, cwd=REPO, stdout=subprocess.PIPE,
                            stderr=subprocess.PIPE, text=True)
    try:
        out, err = proc.communicate(timeout=timeout)
    except subprocess.TimeoutExpired:
        proc.send_signal(signal.SIGINT)
        try:
            out, err = proc.communicate(timeout=30)
        except subprocess.TimeoutExpired:
            proc.kill()
            out, err = proc.communicate()
        return {"spec": spec, "error": "timeout"}
    if proc.returncode != 0:
        return {"spec": spec, "error": f"rc={proc.returncode}",
                "tail": (err or "").strip().splitlines()[-6:]}
    for line in reversed(out.strip().splitlines()):
        if line.startswith("{"):
            return json.loads(line)
    return {"spec": spec, "error": "no json"}


_WINNER_BASE = "d=2048,L=6,nh=16,ff=8192,b=16,remat=dots,mom=bf16," \
               "celim=1073741824,steps=8"


def _flag_values(flag, default):
    """``--flag a,b`` -> [a, b]; bare ``--flag`` -> default; absent -> None."""
    if flag not in sys.argv:
        return None
    i = sys.argv.index(flag)
    if i + 1 < len(sys.argv) and not sys.argv[i + 1].startswith("--"):
        return sys.argv[i + 1].split(",")
    return default


def build_specs():
    """The spec list for this invocation. --ce-chunk / --fused-opt /
    --grad-reduce / --comm-dtype / --bucket-mb cross the base config
    (--base SPEC, default: the measured winner) with CE vocab-chunk sizes,
    the fused flat-buffer optimizer, and the communication levers."""
    if "--one" in sys.argv:
        return [sys.argv[sys.argv.index("--one") + 1]]
    ce_axis = _flag_values("--ce-chunk", ["0", "1024"])
    fused_axis = _flag_values("--fused-opt", ["0", "1"])
    gr_axis = _flag_values("--grad-reduce", ["psum", "reduce_scatter"])
    cdt_axis = _flag_values("--comm-dtype", ["f32", "bf16"])
    bmb_axis = _flag_values("--bucket-mb", ["32"])
    shard_axis = _flag_values("--sharding", ["none", "dp", "fsdp"])
    if gr_axis or cdt_axis or bmb_axis or shard_axis:
        base = (sys.argv[sys.argv.index("--base") + 1]
                if "--base" in sys.argv else _WINNER_BASE)
        specs = []
        for sh in (shard_axis or [None]):
            for gr in (gr_axis or [None]):
                for cdt in (cdt_axis or [None]):
                    for bmb in (bmb_axis or [None]):
                        s = base
                        if sh is not None and sh != "none":
                            s += f",shard={sh}"
                        if gr is not None:
                            s += f",gr={gr}"
                        if cdt is not None and cdt != "f32":
                            s += f",cdt={cdt}"
                        if bmb is not None and gr == "reduce_scatter":
                            s += f",bmb={bmb}"
                        specs.append(s)
        return specs
    if ce_axis is None and fused_axis is None:
        # default sweep = the measured-winner neighborhood (KERNEL_NOTES
        # session-4 table: 0.7168 at b=16 dots + bf16 moments) + its two
        # controlled A/Bs (flash off, f32 moments)
        return [
            _WINNER_BASE,
            "d=2048,L=6,nh=16,ff=8192,b=16,remat=dots,mom=bf16,celim=1073741824,flash=0,steps=8",
            "d=2048,L=6,nh=16,ff=8192,b=16,remat=dots,celim=1073741824,steps=8",
            "d=2048,L=6,nh=16,ff=8192,b=32,remat=full,mom=bf16,celim=1073741824,steps=8",
        ]
    base = (sys.argv[sys.argv.index("--base") + 1]
            if "--base" in sys.argv else _WINNER_BASE)
    specs = []
    for vc in (ce_axis or [None]):
        for fo in (fused_axis or [None]):
            s = base
            if vc is not None and int(vc):
                s += f",vchunk={vc}"
            if fo is not None:
                s += f",fused={fo}"
            specs.append(s)
    return specs


def main():
    if "--multi" in sys.argv:
        i = sys.argv.index("--multi")
        multi_worker(sys.argv[i + 1:])
        return
    if "--worker" in sys.argv:
        worker()
        return
    specs = build_specs()
    results = []
    for s in specs:
        print(f"[sweep] {s} ...", file=sys.stderr, flush=True)
        r = run_one(s)
        print(f"[sweep]   -> {r}", file=sys.stderr, flush=True)
        results.append(r)
        # one machine-readable row per config, as it lands (errors included
        # — a crashed config must not vanish from the record)
        print(json.dumps(r), flush=True)
    ok = [r for r in results if "mfu" in r]
    ok.sort(key=lambda r: -r["mfu"])
    for r in ok:
        print(f"{r['mfu']:.4f}  {r['tokens_per_s']:>10.0f} tok/s  "
              f"{r['ms_per_step']:>6.1f} ms  {r['spec']}")
    for r in results:
        if "mfu" not in r:
            print(f"FAILED  {r}")


if __name__ == "__main__":
    main()
