"""Shared measurement harness for every probe loop in the repo
(ISSUE 20, docs/autotune.md).

One warmup/compile/timing implementation, factored out of the three
ad-hoc copies (``bench.py --worker``, ``tools/mfu_sweep.py``,
``tools/comm_bench.py``) plus the autotuner's own short probes:

* :func:`device_info` / :func:`hw_fingerprint` — the single derivation
  of ``platform / device_kind / degraded`` every lane used to re-derive
  per worker, and the fingerprint TUNED.json is validated against;
* :func:`timed_loop` — first call timed as the compile, then ``steps``
  timed calls, per-step-synced (monitored lanes, comm_bench) or
  block-timed with one trailing sync (throughput lanes, mfu_sweep);
* :func:`run_train_probe` — build + measure one train-space candidate
  (N warmup + M timed steps, optional TrainMonitor rollup + goodput
  shares, AOT program report captured for the static model);
* :func:`run_serve_probe` — short closed-loop serving drive of one
  serve-space candidate (scheduler + engine loop, disagg-router lane for
  ratio candidates), scored by the PR 18 SLO engine's verdict.

jax imports stay inside the functions: launcher processes import this
module before deciding whether a backend should initialize at all.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import time
from typing import Any, Callable, Dict, List, Optional

from .space import Candidate, parse_disagg_ratio

__all__ = ["DeviceInfo", "device_info", "require_tpu",
           "hw_fingerprint", "ProbeTiming",
           "timed_loop", "TrainProbeGeometry", "run_train_probe",
           "ServeProbeGeometry", "run_serve_probe"]


# ---------------------------------------------------------------------------
# device identity (the bench.py per-lane re-derivation, hoisted)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class DeviceInfo:
    platform: str
    device_kind: str
    n_devices: int
    on_acc: bool                 # any accelerator backend
    degraded: bool               # not a real TPU — timing numbers are
                                 # mechanism checks, not hardware facts
    device: Any = None           # the jax device object


def device_info() -> DeviceInfo:
    import jax

    d = jax.devices()[0]
    on_acc = d.platform != "cpu"
    return DeviceInfo(
        platform=d.platform,
        device_kind=str(getattr(d, "device_kind", d.platform)),
        n_devices=jax.device_count(),
        on_acc=on_acc,
        degraded=d.platform != "tpu",
        device=d)


def require_tpu(tool: str, smoke: bool = False) -> DeviceInfo:
    """The measuring entry points' first act on jax (chip_smoke.py,
    bench.py, tools/serve_bench.py, profile_step.py, autotune.py,
    comm_bench.py): a backend that is not a TPU is refused — a timing from
    the CPU is not a measurement. The tools' ``--smoke`` lanes (``smoke=
    True``) are CPU correctness runs and say so in what they write
    (``degraded``/``cpu_smoke``)."""
    di = device_info()
    if not smoke and di.platform != "tpu":
        raise SystemExit(
            f"{tool}: backend is {di.platform!r}, not a TPU — nothing was "
            "measured (run it through the chip tool"
            + ("; --smoke runs the CPU correctness lane)"
               if tool.startswith("tools/") else ")"))
    return di


def hw_fingerprint(di: Optional[DeviceInfo] = None) -> Dict[str, Any]:
    """Stable identity of the hardware a tune ran on. TUNED.json carries
    this; appliers refuse (warn + fall back to defaults) on mismatch so a
    CPU-tuned config never silently lands on a TPU."""
    di = di or device_info()
    doc = {"platform": di.platform, "device_kind": di.device_kind,
           "n_devices": di.n_devices, "degraded": di.degraded}
    doc["fingerprint"] = hashlib.sha256(
        json.dumps(doc, sort_keys=True).encode()).hexdigest()[:12]
    return doc


# ---------------------------------------------------------------------------
# the one warmup/compile/timing loop
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class ProbeTiming:
    compile_s: float             # first (tracing+compile) call, synced
    step_times_s: List[float]    # per-step wall, per_step_sync mode only
    block_s: float               # the whole timed region
    steps: int
    values: List[Any]            # step_fn returns, compile call included

    @property
    def ms_per_step(self) -> float:
        import numpy as np

        if self.step_times_s:
            return float(np.median(self.step_times_s)) * 1e3
        return self.block_s / max(self.steps, 1) * 1e3


def timed_loop(step_fn: Callable[[int], Any], steps: int, *,
               sync: Callable[[Any], Any] = lambda v: v,
               per_step_sync: bool = True,
               warmup: int = 0,
               after_compile: Optional[Callable[[], Any]] = None
               ) -> ProbeTiming:
    """Run ``step_fn(i)`` once for compile (timed, synced), ``warmup``
    extra untimed calls, then ``steps`` timed calls.

    ``per_step_sync=True`` syncs and times every step (the monitored /
    comm_bench discipline — wall time IS step time); ``False`` dispatches
    the whole block and syncs once at the end (the throughput discipline
    — donated params serialize steps on-device, per-step syncs would
    bill a host round-trip each). ``after_compile`` runs between the
    compile call and the timed region (metric snapshots that must span
    exactly the compile, e.g. comm_bench's wire-byte delta)."""
    t0 = time.perf_counter()
    v = step_fn(0)
    sync(v)
    compile_s = time.perf_counter() - t0
    values = [v]
    if after_compile is not None:
        after_compile()
    for w in range(warmup):
        v = step_fn(w + 1)
        sync(v)
        values.append(v)
    times: List[float] = []
    t_block = time.perf_counter()
    for i in range(steps):
        t1 = time.perf_counter()
        v = step_fn(warmup + 1 + i)
        if per_step_sync:
            sync(v)
            times.append(time.perf_counter() - t1)
        values.append(v)
    if not per_step_sync and values:
        sync(values[-1])
    block_s = time.perf_counter() - t_block
    return ProbeTiming(compile_s=compile_s, step_times_s=times,
                       block_s=block_s, steps=steps, values=values)


# ---------------------------------------------------------------------------
# train-space probe
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class TrainProbeGeometry:
    d_model: int = 64
    num_layers: int = 2
    num_heads: int = 4
    d_ff: int = 128
    T: int = 32
    vocab_size: int = 256
    batch: int = 4               # GLOBAL batch
    dp: int = 1
    use_flash: bool = False
    lr: float = 1e-4


def _probe_report(step):
    from ..observability import program_report as prep

    name = getattr(step, "report_name", None)
    return next((r for r in reversed(prep.recent_reports())
                 if r.get("program") == name), {})


def run_train_probe(cand: Candidate, geom: TrainProbeGeometry, steps: int,
                    *, warmup: int = 0, monitor: Optional[str] = None,
                    seed: int = 0) -> Dict[str, Any]:
    """Measure one train-space candidate; returns a result dict whose
    ``score`` (ms/step, lower better) drives the search."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    from ..models import gpt as G
    from ..observability import goodput as gp
    from ..parallel import parallelize as PZ
    from ..parallel import remat as remat_mod

    di = device_info()
    rpolicy = remat_mod.resolve(cand.get("remat", "none"))
    vchunk = int(cand.get("ce_vocab_chunk", 0))
    cfg = G.GPT_TINY.scaled(
        d_model=geom.d_model, num_layers=geom.num_layers,
        num_heads=geom.num_heads, d_ff=geom.d_ff, max_seq_len=geom.T,
        vocab_size=geom.vocab_size,
        dtype=jnp.bfloat16 if di.on_acc else jnp.float32,
        use_flash=geom.use_flash and di.on_acc,
        remat=not rpolicy.is_none, remat_policy=rpolicy.name,
        fused_ln=bool(cand.get("fused_ln", False)),
        ce_vocab_chunk=vchunk,
        ce_direct_bytes_limit=0 if vchunk else G.GPT_TINY.ce_direct_bytes_limit)

    dp = geom.dp
    pcfg = PZ.ParallelConfig(dp=dp, pp=1, tp=1, microbatches=1)
    mesh = PZ.build_mesh(pcfg, devices=jax.devices()[:dp])
    comm_dtype = cand.get("comm_dtype", "f32")
    kw = dict(grad_reduce=cand.get("grad_reduce", "psum"),
              grad_allreduce_dtype=None if comm_dtype == "f32"
              else comm_dtype,
              bucket_mb=float(cand.get("bucket_mb", 32.0)),
              error_feedback=bool(cand.get("error_feedback", False)))
    fused = bool(cand.get("fused_opt", False))
    params, opt = PZ.init_sharded(jax.random.PRNGKey(seed), cfg, pcfg,
                                  mesh, fused_opt=fused, **kw)
    step = PZ.make_train_step(cfg, pcfg, mesh, lr=geom.lr,
                              fused_opt=fused, **kw)
    rng = np.random.default_rng(seed)
    tokens = rng.integers(0, cfg.vocab_size, (1, geom.batch, geom.T),
                          dtype=np.int32)
    labels = rng.integers(0, cfg.vocab_size, (1, geom.batch, geom.T),
                          dtype=np.int32)

    state = [params, opt]
    mon = None
    if monitor:
        from ..observability import TrainMonitor

        n_params = None   # filled after the compile call

    def step_fn(i):
        p, o, loss, gnorm = step(state[0], state[1], tokens, labels)
        state[0], state[1] = p, o
        return loss, gnorm

    compute0 = gp.ledger().category_seconds("compute")
    if monitor:
        # monitored discipline: per-step sync, one JSONL record per step
        from ..observability import TrainMonitor

        timing = timed_loop(step_fn, 0, sync=lambda v: float(v[0]))
        n_params = G.num_params(state[0])
        flops_tok = G.train_flops_per_token(cfg, n_params, geom.T)
        from ..observability import hw as hw_mod

        mon = TrainMonitor(
            path=monitor, examples_per_step=geom.batch,
            tokens_per_step=geom.batch * geom.T,
            flops_per_step=flops_tok * geom.batch * geom.T,
            peak_flops=hw_mod.peak_bf16_flops(di.device),
            extra_static={"config": cand.key})
        for w in range(warmup):
            float(step_fn(w + 1)[0])
        times = []
        for i in range(steps):
            with mon.step() as s:
                t1 = time.perf_counter()
                loss, gnorm = step_fn(warmup + 1 + i)
                s.dispatched()
                s.observe(loss=loss, grad_norm=gnorm)
                times.append(time.perf_counter() - t1)
        loss_last = mon.last_record.get("loss")
        mon.close()
        timing = ProbeTiming(compile_s=timing.compile_s,
                             step_times_s=times,
                             block_s=sum(times), steps=steps,
                             values=[])
    else:
        timing = timed_loop(step_fn, steps, warmup=warmup,
                            sync=lambda v: float(v[0]),
                            per_step_sync=False)
        loss_last = float(timing.values[-1][0])
        n_params = G.num_params(state[0])
    report = _probe_report(step)
    compute_s = gp.ledger().category_seconds("compute") - compute0
    tokens_per_s = steps * geom.batch * geom.T / max(timing.block_s, 1e-9)
    return {
        "score": timing.ms_per_step,
        "ms_per_step": round(timing.ms_per_step, 3),
        "tokens_per_s": round(tokens_per_s, 2),
        "compile_s": round(timing.compile_s, 3),
        "loss": round(float(loss_last), 6) if loss_last is not None
        else None,
        "steps": steps,
        "params": int(n_params) if n_params else None,
        "goodput_compute_s": round(compute_s, 4),
        "report": {k: report.get(k) for k in ("flops", "bytes_accessed",
                                              "compile_ms")} | {
            "peak_hbm_bytes": (report.get("memory") or {}).get(
                "peak_hbm_bytes")},
    }


# ---------------------------------------------------------------------------
# serve-space probe
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ServeProbeGeometry:
    d_model: int = 64
    num_layers: int = 2
    num_heads: int = 4
    d_ff: int = 128
    vocab_size: int = 256
    max_seq: int = 64
    page_size: int = 8
    max_new_tokens: int = 8
    prompt_len_max: int = 12


def _build_probe_engine(params, cfg, cand: Candidate,
                        geom: ServeProbeGeometry, *, role="colocated",
                        max_batch=None):
    import jax

    from .. import serving
    from ..models import gpt as G

    kw = dict(
        max_batch=int(max_batch or cand.get("max_batch", 8)),
        max_seq=geom.max_seq,
        prefill_buckets=tuple(cand.get("buckets", (geom.max_seq // 2,))),
        weight_dtype=cand.get("weight_dtype", "f32"),
        fused_decode=bool(cand.get("fused_decode", False)),
        page_size=geom.page_size,
        num_pages=int(cand.get("num_pages", 0)),
        role=role)
    if cand.get("sharding", "none") == "tp":
        kw.update(sharding="tp", tp=int(cand.get("tp", 2)))
    k = int(cand.get("spec", 0))
    if k > 0:
        target = serving.DecodeEngine(params, cfg, serving.EngineConfig(
            verify_window=k + 1, **kw))
        dcfg = cfg.scaled(num_layers=max(1, cfg.num_layers // 2))
        dparams = G.init_params(jax.random.PRNGKey(99), dcfg)
        draft = serving.DecodeEngine(dparams, dcfg,
                                     serving.EngineConfig(**kw))
        return serving.SpecDecodeEngine(target, draft)
    return serving.DecodeEngine(params, cfg, serving.EngineConfig(**kw))


def _slo_verdict(ttfts_ms, tpots_ms, failed: int):
    from ..observability import slo as slo_mod

    eng = slo_mod.SLOEngine(min_events=1)
    t = 1000.0
    for i, ttft in enumerate(ttfts_ms):
        tpot = tpots_ms[i] if i < len(tpots_ms) else None
        eng.note_request(ttft_ms=ttft, tpot_ms=tpot, code=200, t=t)
        t += 0.001
    for _ in range(failed):
        eng.note_request(code=500, t=t)
        t += 0.001
    st = eng.evaluate(t)
    return {"ok": bool(st["ok"]),
            "alerting": list(st.get("alerting", []))}


def run_serve_probe(cand: Candidate, geom: ServeProbeGeometry,
                    n_requests: int, *, seed: int = 0) -> Dict[str, Any]:
    """Short CLOSED-LOOP drive of one serve-space candidate; ``score``
    is ms per generated token (lower better), gated by the live SLO
    engine's verdict (a failing lane scores inf — the measured phase's
    rejection)."""
    import numpy as np

    import jax

    from .. import serving
    from ..models import gpt as G
    from ..observability import program_report as prep

    def recompiles():
        from ..observability import metrics as om

        snap = om.default_registry().snapshot()
        return sum(s["value"] for s in
                   snap.get("paddle_recompiles_total", {}).get("series",
                                                               []))

    di = device_info()
    import jax.numpy as jnp

    cfg = G.GPTConfig(
        vocab_size=geom.vocab_size, max_seq_len=max(geom.max_seq, 64),
        num_layers=geom.num_layers, num_heads=geom.num_heads,
        d_model=geom.d_model, d_ff=geom.d_ff,
        dtype=jnp.float32 if not di.on_acc else jnp.bfloat16,
        remat=False)
    params = G.init_params(jax.random.PRNGKey(seed), cfg)

    rng = np.random.RandomState(seed)
    prompts = [rng.randint(0, cfg.vocab_size, size=int(
        rng.randint(2, geom.prompt_len_max + 1))).tolist()
        for _ in range(n_requests)]

    ratio = parse_disagg_ratio(cand.get("disagg", "off"))
    t_build = time.perf_counter()
    if ratio:
        from ..serving.disagg import DisaggRouter, LocalReplica

        n_p, n_d = ratio
        mult = int(cand.get("disagg_decode_batch", 1))
        base_mb = int(cand.get("max_batch", 8))
        reps = [LocalReplica(
            _build_probe_engine(params, cfg, cand, geom, role="prefill",
                                max_batch=base_mb), name=f"p{i}")
            for i in range(n_p)]
        reps += [LocalReplica(
            _build_probe_engine(params, cfg, cand, geom, role="decode",
                                max_batch=base_mb * mult), name=f"d{i}")
            for i in range(n_d)]
        for r in reps:
            r.engine.warmup()
        router = DisaggRouter(reps)
        warm_s = time.perf_counter() - t_build
        rc0 = recompiles()
        ttfts, tpots, failed, total_tokens = [], [], 0, 0
        t0 = time.perf_counter()
        for p in prompts:
            req = router.generate(p, max_new_tokens=geom.max_new_tokens,
                                  timeout_s=60.0)
            if req is None or req.state != "done":
                failed += 1
                continue
            if req.ttft_ms is not None:
                ttfts.append(req.ttft_ms)
            if len(req.token_times) > 1:
                tpots.append(float(np.median(
                    np.diff(req.token_times)) * 1e3))
            total_tokens += len(req.tokens)
        span = time.perf_counter() - t0
        rc = recompiles() - rc0
        for r in reps:
            r.stop()
    else:
        engine = _build_probe_engine(params, cfg, cand, geom)
        engine.warmup()
        warm_s = time.perf_counter() - t_build
        sched = serving.Scheduler(engine, serving.SchedulerConfig(
            max_queue=max(16, n_requests), default_timeout_s=60.0))
        loop = serving.EngineLoop(sched).start()
        rc0 = recompiles()
        ttfts, tpots, failed, total_tokens = [], [], 0, 0
        t0 = time.perf_counter()
        try:
            for p in prompts:
                req = sched.submit(p,
                                   max_new_tokens=geom.max_new_tokens)
                loop.wake()
                req.wait(timeout=60.0)
                if req.state != "done":
                    failed += 1
                    continue
                if req.ttft_ms is not None:
                    ttfts.append(req.ttft_ms)
                if len(req.token_times) > 1:
                    tpots.append(float(np.median(
                        np.diff(req.token_times)) * 1e3))
                total_tokens += len(req.tokens)
        finally:
            loop.stop()
        span = time.perf_counter() - t0
        rc = recompiles() - rc0

    slo = _slo_verdict(ttfts, tpots, failed)
    tok_s = total_tokens / max(span, 1e-9)
    ms_per_tok = span * 1e3 / max(total_tokens, 1)
    score = float("inf") if (failed or not slo["ok"] or rc) \
        else ms_per_tok
    return {
        "score": score,
        "ms_per_token": round(ms_per_tok, 3),
        "tokens_per_s": round(tok_s, 2),
        "ttft_p50_ms": round(float(np.median(ttfts)), 3) if ttfts
        else None,
        "requests": n_requests,
        "failed": failed,
        "steady_state_recompiles": int(rc),
        "warmup_s": round(warm_s, 3),
        "slo": slo,
    }
