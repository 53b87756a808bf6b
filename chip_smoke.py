#!/usr/bin/env python
"""The quickest proof that the system still starts on the chip.

    chiprun -- python chip_smoke.py                       # one chip, phases 1-3
    chiprun --chips 4 -- python chip_smoke.py --four-chips  # phase 4 only

Drives the main paths once, through the entry points a user would call, at
the full gpt_wide width (d_model 2048, 16 heads of 128, d_ff 8192, 6 layers,
T=1024, ~0.4B parameters; random weights from ``--seed``):

  1. train — build_mesh / init_sharded / make_train_step on the one-chip
     mesh, Mosaic flash attention, remat=dots, b=16: five steps on one batch;
  2. serve — the same widths through DecodeEngine (paged KV, bf16 weights) +
     Scheduler + FrontDoor on a localhost port: warmup, six HTTP requests;
  3. fluid — Program -> Executor(XLAPlace(0)) at ResNet-50, b=128, bf16 AMP;
  4. (--four-chips, alone) the dp=2 x tp=2 train step against the same step
     on a one-device mesh, same seed and batch, same process.

Every phase checks what came out (finite, the expected shape, agreeing with
a reference) and raises otherwise; nothing is caught. Times and rates
printed here are smoke output stamped with the device kind, not a record.

Exits non-zero, before any phase and with no result line, unless
``jax.devices()[0].platform == "tpu"``. One process uses the chip. The last
line of stdout is ``{"ok": true, "device": {...}}``.
"""
import argparse
import gc
import json
import os
import sys
import threading
import time
import urllib.request

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)

BF16_LOSS_RTOL = 1e-2        # bf16 has 8 mantissa bits: eps = 7.8e-3


def say(msg):
    print(f"[chip_smoke] {msg}", flush=True)


def tpu_devices():
    """First act on jax: the devices, or a non-zero exit without a TPU. The comm/compute-overlap preset has to be in
    LIBTPU_INIT_ARGS before libtpu loads; libtpu refuses an unknown flag at
    load, so reaching the platform check also shows it took all of them."""
    from paddle_tpu.sysconfig import tpu_perf_flags
    from paddle_tpu.tuning.probe import require_tpu

    tpu_perf_flags()
    require_tpu("chip_smoke.py")
    import jax

    return jax.devices()


def free_device_memory():
    import jax

    gc.collect()
    jax.clear_caches()


# ---------------------------------------------------------------------------
# phase 1: train
# ---------------------------------------------------------------------------

def make_batch(cfg, batch, T, seed):
    import numpy as np

    rng = np.random.default_rng(seed)
    tokens = rng.integers(0, cfg.vocab_size, (1, batch, T), dtype=np.int32)
    labels = rng.integers(0, cfg.vocab_size, (1, batch, T), dtype=np.int32)
    return tokens, labels


def run_steps(cfg, pcfg, devices, tokens, labels, steps, seed):
    """init_sharded + make_train_step on ``devices``; returns the step, the
    per-step losses, seconds for (compile + step 1) and for the rest, and
    the final (params, opt_state)."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.parallel import parallelize as PZ

    mesh = PZ.build_mesh(pcfg, devices=devices)
    params, opt = PZ.init_sharded(jax.random.PRNGKey(seed), cfg, pcfg, mesh,
                                  moment_dtype=jnp.bfloat16)
    step = PZ.make_train_step(cfg, pcfg, mesh)
    t0 = time.perf_counter()
    params, opt, loss, _ = step(params, opt, tokens, labels)
    losses = [float(loss)]
    t1 = time.perf_counter()
    rest = []
    for _ in range(steps - 1):
        params, opt, loss, _ = step(params, opt, tokens, labels)
        rest.append(loss)
    losses += [float(x) for x in rest]       # forces the chain
    t2 = time.perf_counter()
    return step, losses, t1 - t0, t2 - t1, (params, opt)


def phase_train(cfg, batch, T, steps, seed, dev):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.models import gpt as G
    from paddle_tpu.observability import program_report as PREP
    from paddle_tpu.parallel import parallelize as PZ

    pcfg = PZ.ParallelConfig(dp=1, pp=1, tp=1)
    tokens, labels = make_batch(cfg, batch, T, seed)

    # the reference for Mosaic flash: the plain XLA attention, same params
    # (same seed), forward only
    mesh = PZ.build_mesh(pcfg, devices=[dev])
    params, _ = PZ.init_sharded(jax.random.PRNGKey(seed), cfg, pcfg, mesh,
                                moment_dtype=jnp.bfloat16)
    xla_cfg = cfg.scaled(use_flash=False)
    loss_xla = float(jax.jit(
        lambda p, t, l: G.loss_fn(p, t, l, xla_cfg))(
            params, tokens[0], labels[0]))
    del params
    free_device_memory()

    step, losses, first_s, rest_s, _state = run_steps(
        cfg, pcfg, [dev], tokens, labels, steps, seed)
    hlo = step.hlo_text()
    assert hlo is not None, "the AOT executable was not kept"
    if dev.platform == "tpu" and cfg.use_flash:
        assert "tpu_custom_call" in hlo, "no Mosaic kernel in the train step"
    assert all(np.isfinite(losses)), losses
    assert losses[-1] < losses[0], f"loss did not fall: {losses}"
    rel = abs(losses[0] - loss_xla) / abs(loss_xla)
    assert rel < BF16_LOSS_RTOL, (
        f"step-1 loss with flash {losses[0]} vs XLA attention {loss_xla}: "
        f"rel {rel:.2e}")
    ms = rest_s / (steps - 1) * 1e3
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    # the attached compile's own memory analysis, next to what the
    # allocator saw (they disagreed 2x with the described compile, PERF.md)
    mem = next(r for r in reversed(PREP.recent_reports())
               if r.get("program") == step.report_name).get("memory")
    say(f"train: program report memory {mem}")
    say(f"train: losses {[round(x, 4) for x in losses]}; flash vs XLA "
        f"attention step-1 loss rel diff {rel:.2e}; compile+step1 "
        f"{first_s:.1f}s; {ms:.1f} ms/step, {batch * T / ms * 1e3:.0f} "
        f"tokens/s, peak_bytes_in_use {peak} on {dev.device_kind} "
        "(smoke output, not a record)")


# ---------------------------------------------------------------------------
# phase 2: serve
# ---------------------------------------------------------------------------

def make_prompts(cfg, seed, lengths, shared_prefix):
    """One prompt per length; the first two share their first
    ``shared_prefix`` tokens."""
    import numpy as np

    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, cfg.vocab_size, n).tolist() for n in lengths]
    prompts[1][:shared_prefix] = prompts[0][:shared_prefix]
    return prompts


def post_generate(port, prompt, max_new_tokens):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/generate",
        data=json.dumps({"prompt": prompt, "max_new_tokens": max_new_tokens,
                         "timeout_s": 120}).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=150) as resp:
        return resp.status, json.loads(resp.read())


def phase_serve(cfg, ecfg_kw, prompt_lens, shared_prefix, new_tokens,
                eval_len, seed, dev):
    import jax
    import numpy as np

    from paddle_tpu import serving
    from paddle_tpu.models import gpt as G
    from paddle_tpu.serving import quant as squant
    from tools.serve_bench import _recompile_total, decode_logits_stream

    params = G.init_params(jax.random.PRNGKey(seed), cfg)
    eng = serving.DecodeEngine(params, cfg, serving.EngineConfig(**ecfg_kw))
    t0 = time.perf_counter()
    eng.warmup()
    warm_s = time.perf_counter() - t0

    # teacher-forced logits through the serving path vs the model's own
    # full forward, same weights
    seq = np.random.default_rng(seed + 1).integers(
        0, cfg.vocab_size, eval_len)
    stream = decode_logits_stream(eng, seq)
    ref = np.asarray(jax.jit(lambda p, t: G.forward(p, t, cfg))(
        params, seq[None].astype(np.int32))[0], np.float32)
    assert stream.shape == ref.shape == (eval_len, cfg.vocab_size)
    stats = squant.logit_error_stats(ref, stream)
    assert np.isfinite(stream).all()
    assert stats["max_rel_err"] < squant.INT8_LOGIT_TOL, stats
    eng.drop_reference_params()
    del params, ref

    prompts = make_prompts(cfg, seed + 2, prompt_lens, shared_prefix)
    front = serving.FrontDoor(scheduler=serving.Scheduler(eng)).start()
    try:
        recompiles0, hits0 = _recompile_total(), eng.prefix.hits
        t0 = time.perf_counter()
        # the first prompt publishes the shared prefix's pages; the rest
        # arrive together and batch
        results = [post_generate(front.port, prompts[0], new_tokens)]
        rest = [None] * (len(prompts) - 1)

        def client(i):
            rest[i] = post_generate(front.port, prompts[i + 1], new_tokens)

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(len(rest))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=200)
        serve_s = time.perf_counter() - t0
        results += rest
    finally:
        front.stop()
    assert all(r is not None and r[0] == 200 for r in results), results
    for _, body in results:
        assert len(body["tokens"]) == new_tokens, body
        assert all(0 <= t < cfg.vocab_size for t in body["tokens"])
    recompiles = _recompile_total() - recompiles0
    assert recompiles == 0 and eng.steady_state_recompiles == 0, \
        f"{recompiles} recompiles while serving"
    hits = eng.prefix.hits - hits0
    assert hits >= 1, "the shared prefix registered no prefix-cache hit"
    say(f"serve: warmup {warm_s:.1f}s ({eng.compiles} executables); "
        f"teacher-forced logits vs full forward max_rel_err "
        f"{stats['max_rel_err']:.4f} (bar {squant.INT8_LOGIT_TOL}), top1 "
        f"agreement {stats.get('top1_agreement')}; {len(results)} requests "
        f"x {new_tokens} tokens in {serve_s:.2f}s, ttft_ms "
        f"{[r[1]['ttft_ms'] for r in results]}, 0 recompiles, {hits} "
        f"prefix-cache hit(s) on {dev.device_kind} (smoke output)")


# ---------------------------------------------------------------------------
# phase 3: fluid
# ---------------------------------------------------------------------------

def phase_fluid(batch, hw, steps, dev):
    import numpy as np

    import bench
    import paddle_tpu as fluid

    main, startup, loss = bench.build_resnet50_program(batch, hw)
    exe = fluid.Executor(fluid.XLAPlace(0))
    scope = fluid.Scope()
    exe.run(startup, scope=scope)
    t0 = time.perf_counter()
    losses = []
    for _ in range(steps):
        (v,) = exe.run(main, feed={}, fetch_list=[loss], scope=scope)
        losses.append(float(np.asarray(v)))
        if len(losses) == 1:
            first_s = time.perf_counter() - t0
    total_s = time.perf_counter() - t0
    assert all(np.isfinite(losses)), losses
    blocks = [rec.exe for rec in exe._dispatch_records.values()]
    assert blocks and all(b._executable is not None for b in blocks), \
        "the executor's AOT executable was not kept"
    say(f"fluid: resnet50 b={batch} {hw}px bf16 losses "
        f"{[round(x, 4) for x in losses]}; compile+step1 {first_s:.1f}s, "
        f"{(total_s - first_s) / max(steps - 1, 1) * 1e3:.1f} ms/step on "
        f"{dev.device_kind} (smoke output)")


# ---------------------------------------------------------------------------
# phase 4: four chips (dp=2 x tp=2) against one
# ---------------------------------------------------------------------------

def phase_four_chips(cfg, batch, T, steps, seed, devs):
    import jax
    import numpy as np

    from paddle_tpu.models import gpt as G
    from paddle_tpu.parallel import parallelize as PZ

    tokens, labels = make_batch(cfg, batch, T, seed)
    one = PZ.ParallelConfig(dp=1, pp=1, tp=1)
    _, ref_losses, _, _, state = run_steps(
        cfg, one, devs[:1], tokens, labels, steps, seed)
    del state
    free_device_memory()

    pcfg = PZ.ParallelConfig(dp=2, pp=1, tp=2)
    step, losses, first_s, rest_s, (params, opt) = run_steps(
        cfg, pcfg, devs[:4], tokens, labels, steps, seed)
    assert all(np.isfinite(losses)), losses
    for a, b in zip(losses, ref_losses):
        assert abs(a - b) / abs(b) < BF16_LOSS_RTOL, (losses, ref_losses)

    # the tp-sharded leaves live on all four devices, 1/tp of each on every
    # device — nothing parked on device 0
    tp_ax = pcfg.axis_names[2]
    spec_of = dict(jax.tree_util.tree_leaves_with_path(
        G.param_specs(cfg, pp=pcfg.axis_names[1], tp=tp_ax),
        is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec)))
    checked = 0
    for tree in (params, opt["m"], opt["v"]):
        for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
            if tp_ax not in jax.tree_util.tree_leaves(tuple(spec_of[path])):
                continue
            assert len(leaf.sharding.device_set) == 4, (path, leaf.sharding)
            shard_bytes = {s.data.nbytes for s in leaf.addressable_shards}
            assert shard_bytes == {leaf.nbytes // pcfg.tp}, (
                path, shard_bytes, leaf.nbytes)
            checked += 1
    assert checked > 0
    per_dev = {}
    for leaf in jax.tree_util.tree_leaves((params, opt)):
        for s in leaf.addressable_shards:
            per_dev[s.device.id] = per_dev.get(s.device.id, 0) + s.data.nbytes
    whole = sum(x.nbytes for x in jax.tree_util.tree_leaves((params, opt)))

    hlo = step.hlo_text()
    found = {op: hlo.count(f" {op}(") + hlo.count(f" {op}-start(")
             for op in ("all-reduce", "reduce-scatter", "all-gather",
                        "collective-permute")}
    assert found["all-reduce"] > 0, found            # dp grads, tp sums
    assert found["reduce-scatter"] + found["all-gather"] > 0, found  # tp seq
    say(f"four chips dp=2 tp=2: losses {[round(x, 4) for x in losses]} vs "
        f"one device {[round(x, 4) for x in ref_losses]} (rtol "
        f"{BF16_LOSS_RTOL}); {checked} tp-sharded leaves on 4 devices at "
        f"1/{pcfg.tp} each; state bytes per device {per_dev} of {whole} "
        f"unsharded; collectives in the compiled HLO {found}; "
        f"compile+step1 {first_s:.1f}s, {rest_s / (steps - 1) * 1e3:.1f} "
        f"ms/step on {devs[0].device_kind} x4 (smoke output)")


# ---------------------------------------------------------------------------

def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the dp=2 x tp=2 phase (needs 4 chips)")
    args = ap.parse_args()

    devs = tpu_devices()
    import bench
    from paddle_tpu.framework.core import (compile_cache_counters,
                                           ensure_compile_cache)

    cache_dir = ensure_compile_cache()
    say(f"{devs[0].device_kind} x{len(devs)}; compile cache at {cache_dir}")
    cfg = bench.gpt_wide_config(use_flash=True)
    t_start = time.perf_counter()

    def timed(name, fn, *a):
        h0, m0 = compile_cache_counters()
        t0 = time.perf_counter()
        fn(*a)
        h1, m1 = compile_cache_counters()
        say(f"phase {name}: {time.perf_counter() - t0:.1f}s; compile cache "
            f"{h1 - h0} hits, {m1 - m0} misses")
        free_device_memory()

    if args.four_chips:
        if len(devs) < 4:
            sys.exit(f"chip_smoke: --four-chips needs 4 chips, found "
                     f"{len(devs)}")
        timed("four_chips", phase_four_chips, cfg, 16, 1024, 3, args.seed,
              devs)
    else:
        timed("train", phase_train, cfg, 16, 1024, 5, args.seed, devs[0])
        timed("serve", phase_serve, cfg,
              dict(max_seq=1024, max_batch=8, weight_dtype="bf16"),
              (300, 700, 64, 128, 411, 650), 256, 32, 32, args.seed,
              devs[0])
        timed("fluid", phase_fluid, 128, 224, 3, devs[0])
    say(f"all phases passed in {time.perf_counter() - t_start:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)


if __name__ == "__main__":
    main()
