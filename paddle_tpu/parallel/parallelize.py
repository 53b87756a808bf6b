"""4D-parallel training engine: dp x pp x tp (+sequence parallel) on one mesh.

The reference's parallelism is NCCL data-parallel (ParallelExecutor SSA graph,
framework/parallel_executor.cc) plus a threaded pipeline trainer
(framework/pipeline_trainer.cc + section_worker.cc: stages pass Scopes through
blocking queues) — there is no tensor or sequence parallelism (SURVEY.md §2.3).
This module is the TPU-native superset, one compiled XLA program instead of
thread queues:

- **dp**: batch sharded over the ``dp`` mesh axis; gradient all-reduce is a
  single psum (replaces AllReduceOpHandle / FusedAllReduceOpHandle —
  framework/details/all_reduce_op_handle.cc).
- **pp**: GPipe. Block params are stacked [num_layers, ...] and sharded over
  ``pp`` on the layer axis; the microbatch schedule is a ``lax.scan`` over
  M + S - 1 ticks with a ``ppermute`` shifting activations stage->stage+1
  over ICI each tick (replaces SectionWorker scope queues).
- **tp + sp**: Megatron tensor parallel over ``tp`` (QKV/fc column-split,
  proj/out row-split) with *sequence parallelism*: activations between blocks
  stay sharded on the sequence dim over ``tp``, so the row-parallel psum
  becomes a reduce_scatter and layernorms/dropout run on 1/tp of the tokens.

Gradient correctness uses one uniform rule: inside shard_map each rank
differentiates the *global* (fully psum-ed) loss w.r.t. its local param
shards, then each leaf's grad is psum-ed over every mesh axis **not**
appearing in that leaf's PartitionSpec. This is valid because every
replicated-leaf use happens on sequence-sharded activations (partial sums
over tp), tick-masked stages contribute exact zeros (over pp), and the loss
is batch-partial over dp.
"""
from __future__ import annotations

import dataclasses
import itertools
from functools import partial
from typing import Any, Dict, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from . import comm_opt
from . import health as _health
from . import mesh as mesh_mod
from ..models import gpt as gpt_mod
from ..models.gpt import GPTConfig
from ..observability import metrics as _obs_metrics
from .comm_opt import CommConfig

# Collective self-reporting. Collectives execute inside ONE fused XLA
# program, so their wall time is only observable on the device timeline:
# every collective here is wrapped in a jax.named_scope whose name lands in
# each HLO instruction's metadata, and the profiler's merged trace
# (observability/trace_merge.py) then shows `collective/...` spans on the
# device track. The counter below registers at TRACE time (once per
# compile), giving an always-live count of collectives lowered per step.
_m_collectives = _obs_metrics.default_registry().counter(
    "paddle_collective_lowered_total",
    "Collective ops lowered into compiled train steps", ("kind",))


def _named_collective(kind: str):
    """named_scope + lowering counter for one collective call site."""
    _m_collectives.labels(kind).inc()
    return jax.named_scope(f"collective/{kind}")


def shard_map_compat(f, mesh, in_specs, out_specs):
    """``jax.shard_map`` without the varying-manual-axes check: the
    per-rank bodies here psum by hand and return replicated values the
    checker cannot prove."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                         check_vma=False)


@dataclasses.dataclass(frozen=True)
class ParallelConfig:
    dp: int = 1
    pp: int = 1
    tp: int = 1
    microbatches: int = 1          # GPipe microbatches (>= pp for low bubble)
    axis_names: Tuple[str, str, str] = ("dp", "pp", "tp")

    @property
    def n_devices(self) -> int:
        return self.dp * self.pp * self.tp


def build_mesh(pcfg: ParallelConfig, devices=None) -> Mesh:
    devices = list(devices if devices is not None else jax.devices())
    n = pcfg.n_devices
    if len(devices) < n:
        raise ValueError(f"need {n} devices, have {len(devices)}")
    return mesh_mod.build_mesh(
        list(zip(pcfg.axis_names, (pcfg.dp, pcfg.pp, pcfg.tp))), devices[:n])


def _axes_not_in_spec(spec: P, axis_names) -> Tuple[str, ...]:
    used = set()
    for entry in spec:
        if entry is None:
            continue
        if isinstance(entry, (tuple, list)):
            used.update(entry)
        else:
            used.add(entry)
    return tuple(a for a in axis_names if a not in used)


def psum_grads_by_spec(grads, specs, axis_names, skip_axes=(),
                       comm_dtype=None, quant_chunk=256):
    """psum each grad leaf over the mesh axes its param is replicated on.

    ``skip_axes`` leaves named axes un-reduced (the reduce-scatter path
    handles dp itself, bucketed). ``comm_dtype`` routes the reduction
    through :func:`comm_opt.quantized_allreduce` (chunk-scaled wire payload,
    f32 accumulation) — applied per axis, a hierarchical all-reduce.
    """
    def one(g, s):
        axes = tuple(a for a in _axes_not_in_spec(s, axis_names)
                     if a not in skip_axes)
        if not axes:
            return g
        with _named_collective("psum_grad"):
            if comm_dtype is not None:
                for a in axes:
                    g = comm_opt.quantized_allreduce(
                        g, a, comm_dtype, quant_chunk=quant_chunk)
                return g
            comm_opt.record_collective(
                "psum", g.dtype, g.size * g.dtype.itemsize,
                comm_opt._axes_size(axes), site="psum_grads_by_spec")
            return jax.lax.psum(g, axes)

    return jax.tree_util.tree_map(one, grads, specs,
                                  is_leaf=lambda x: isinstance(x, P))


def shard_params(params, specs, mesh):
    """Place a param pytree on the mesh per its specs."""
    return jax.tree_util.tree_map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
        params, specs)


# ---------------------------------------------------------------------------
# The per-rank loss: full GPipe/TP/SP forward + CE, returns the GLOBAL loss.
# ---------------------------------------------------------------------------

def _pipeline_loss(params, tokens, labels, cfg: GPTConfig,
                   pcfg: ParallelConfig, double_buffer: bool = False):
    """Runs inside shard_map. Local shapes:
    tokens/labels [M, mb_local, T]; params['blocks'] leaves [L/pp, ...] with
    tp-local head/ffn dims; replicated leaves full-size.
    Returns the global mean token loss (replicated scalar).

    ``double_buffer=True`` moves the stage-boundary ppermute from the tail
    of each tick to the head of the NEXT tick (the carry holds the
    un-permuted activation): microbatch t's activation is in flight while
    tick t+1 computes its embedding, so XLA's async collective-permute +
    latency-hiding scheduler (sysconfig.tpu_perf_flags) can overlap the
    send with compute. Tick values are identical to the serial schedule
    (the permute commutes with the carry), so the loss trajectory matches
    bit-for-bit — tested in tests/test_comm_opt.py.
    """
    dp_ax, pp_ax, tp_ax = pcfg.axis_names
    S, M = pcfg.pp, pcfg.microbatches
    tp = pcfg.tp
    stage = jax.lax.axis_index(pp_ax)
    tp_idx = jax.lax.axis_index(tp_ax)

    M_, mb, T = tokens.shape
    Ts = T // tp
    blocks = params["blocks"]

    def seq_chunk(x2d):  # [mb, T] -> tp-local [mb, Ts]
        return jax.lax.dynamic_slice_in_dim(x2d, tp_idx * Ts, Ts, axis=1)

    def stage_fn(x):
        return gpt_mod.run_blocks(blocks, x, cfg,
                                  tp_axis=tp_ax if tp > 1 else None)

    def mb_loss(x, lbl):  # x [mb, Ts, D] seq-sharded; lbl [mb, T]
        # chunked CE: full [mb*Ts, V] logits never materialize (see
        # gpt.ce_from_hidden) — the classic big-vocab OOM at wide batch
        return gpt_mod.ce_from_hidden(params, x, seq_chunk(lbl), cfg)

    perm = [(i, (i + 1) % S) for i in range(S)]
    total_tokens = M * mb * T  # per-dp-rank token count (dp summed via psum)

    def _permute_act(x):
        with _named_collective("ppermute_activation"):
            comm_opt.record_collective(
                "ppermute", x.dtype, x.size * x.dtype.itemsize, S,
                site="ppermute_activation")
            return jax.lax.ppermute(x, pp_ax, perm)

    def tick(carry, t):
        state, loss_acc = carry
        if double_buffer and S > 1:
            # the carry holds LAST tick's un-permuted output: start its
            # ppermute now so the send is in flight while this tick embeds
            state = _permute_act(state)
        mb_in = jnp.clip(t, 0, M - 1)
        tok = jax.lax.dynamic_index_in_dim(tokens, mb_in, axis=0,
                                           keepdims=False)
        # stage 0 consumes the embedded microbatch; others consume the
        # ppermuted activation from the previous stage
        x_emb = gpt_mod.embed(params, seq_chunk(tok), cfg,
                              pos_offset=tp_idx * Ts)
        x_in = jnp.where(stage == 0, x_emb, state)
        out = stage_fn(x_in)
        # last stage emits a finished microbatch at ticks S-1 .. S-1+M-1
        out_idx = t - (S - 1)
        valid = (stage == S - 1) & (out_idx >= 0) & (out_idx < M)
        lbl = jax.lax.dynamic_index_in_dim(
            labels, jnp.clip(out_idx, 0, M - 1), axis=0, keepdims=False)
        # lax.cond: the vocab projection + CE only runs on the last stage's
        # M valid ticks instead of every tick on every rank (it costs more
        # than a stage's transformer blocks at GPT_SMALL scale)
        l = jax.lax.cond(valid, lambda: mb_loss(out, lbl),
                         lambda: jnp.float32(0.0))
        loss_acc = loss_acc + l
        if double_buffer or S == 1:
            state = out
        else:
            state = _permute_act(out)
        return (state, loss_acc), None

    D = cfg.d_model
    state0 = jnp.zeros((mb, Ts, D), cfg.dtype)
    n_ticks = M + S - 1
    (state, loss_sum), _ = jax.lax.scan(
        tick, (state0, jnp.float32(0.0)), jnp.arange(n_ticks))

    # Return the rank-LOCAL partial loss normalized by the GLOBAL token count.
    # Deliberately no psum here: this function is differentiated per-rank
    # under shard_map, and with replication checking off a psum would
    # transpose to another psum, scaling every grad by the rank count.
    # Summing the per-rank scalars happens (a) implicitly for grads — SPMD AD
    # seeds cotangent 1 on every rank, so collective transposes yield
    # d(sum_r local_r)/d(local shard) — and (b) explicitly for the reported
    # loss value, via the psum in grad_fn OUTSIDE value_and_grad.
    denom = total_tokens * pcfg.dp
    return loss_sum / denom


# ---------------------------------------------------------------------------
# Public API
# ---------------------------------------------------------------------------

def init_adamw_state(params, moment_dtype=None, fused=False):
    """moment_dtype=jnp.bfloat16 halves the 2x-params-f32 of Adam state —
    at GPT-wide scale that is ~4 GB of a 16 GB HBM, the difference between
    remat and no-remat fitting (update math still runs in f32; bf16's 8-bit
    mantissa on m/v costs <0.1% step-loss drift, checked in
    tests/test_gpt_parallel.py::test_bf16_moments_track_f32).

    ``fused=True`` stores m/v as ONE flat [total_numel] megabuffer each
    (the _adamw_update_fused layout): two donated buffers for the whole
    optimizer state instead of two per leaf."""
    if fused:
        total = sum(int(x.size) for x in jax.tree_util.tree_leaves(params))
        dt = moment_dtype or jnp.float32
        return {"m": jnp.zeros((total,), dt), "v": jnp.zeros((total,), dt),
                "step": jnp.zeros((), jnp.int32)}

    def zeros(p):
        return jax.tree_util.tree_map(
            lambda x: jnp.zeros_like(x, dtype=moment_dtype or x.dtype), p)
    return {"m": zeros(params), "v": zeros(params),
            "step": jnp.zeros((), jnp.int32)}


def _clip_scale(gnorm, grad_clip):
    """grad_clip=None disables clipping with a bit-exact scale of 1.0 (the
    reduce-scatter parity tests rely on x*1.0 == x)."""
    if grad_clip is None:
        return jnp.float32(1.0)
    return jnp.minimum(1.0, grad_clip / (gnorm + 1e-6))


def _adamw_update(params, grads, opt, lr, b1=0.9, b2=0.95, eps=1e-8,
                  weight_decay=0.1, grad_clip=1.0):
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                         for g in jax.tree_util.tree_leaves(grads)))
    scale = _clip_scale(gnorm, grad_clip)
    step = opt["step"] + 1
    c1 = 1 - b1 ** step.astype(jnp.float32)
    c2 = 1 - b2 ** step.astype(jnp.float32)

    def upd(p, g, m, v):
        g = g.astype(jnp.float32) * scale
        mf = b1 * m.astype(jnp.float32) + (1 - b1) * g
        vf = b2 * v.astype(jnp.float32) + (1 - b2) * g * g
        u = (mf / c1) / (jnp.sqrt(vf / c2) + eps)
        # standard GPT/Megatron recipe: no decay on 1-D params (biases,
        # layernorm scales) — only matmul/embedding matrices
        wd = weight_decay if p.ndim >= 2 else 0.0
        # moments round-trip through their storage dtype (possibly bf16 —
        # init_adamw_state moment_dtype); math stays f32
        return p - lr * (u + wd * p), mf.astype(m.dtype), vf.astype(v.dtype)

    flat_p, treedef = jax.tree_util.tree_flatten(params)
    flat_g = treedef.flatten_up_to(grads)
    flat_m = treedef.flatten_up_to(opt["m"])
    flat_v = treedef.flatten_up_to(opt["v"])
    out = [upd(p, g, m, v) for p, g, m, v in zip(flat_p, flat_g, flat_m, flat_v)]
    new_p = treedef.unflatten([o[0] for o in out])
    new_m = treedef.unflatten([o[1] for o in out])
    new_v = treedef.unflatten([o[2] for o in out])
    return new_p, {"m": new_m, "v": new_v, "step": step}, gnorm


def _adamw_update_fused(params, grads, opt, lr, b1=0.9, b2=0.95, eps=1e-8,
                        weight_decay=0.1, grad_clip=1.0, use_pallas=False):
    """Flat-buffer AdamW sweep: every leaf's grad/param is concatenated into
    one f32 megabuffer, the moments live flat (init_adamw_state fused=True),
    and the whole update is ONE vectorized expression — the per-param
    optimizer stream (hundreds of tiny fusions + donations at GPT depth)
    collapses to a handful of full-bandwidth passes over contiguous HBM.
    Same math as _adamw_update leaf-by-leaf; parity tested in
    tests/test_memory_levers.py. Single-device / replicated-param layouts
    only (make_train_step guards).

    ``use_pallas`` routes the elementwise sweep through ONE Pallas
    megakernel launch (ops/pallas_kernels.megakernel_adamw_flat) instead
    of XLA's residual elementwise-fusion stream — the grad-norm reduction
    and clip scale stay outside and ride in as scalars, so the in-kernel
    expression order matches this function bit-for-bit at f32 moments."""
    flat_p, treedef = jax.tree_util.tree_flatten(params)
    flat_g = treedef.flatten_up_to(grads)
    sizes = [int(p.size) for p in flat_p]
    gf = jnp.concatenate([g.astype(jnp.float32).reshape(-1) for g in flat_g])
    pf = jnp.concatenate([p.astype(jnp.float32).reshape(-1) for p in flat_p])
    # no decay on 1-D leaves (biases, layernorm scales) — same rule as the
    # per-leaf path, precomputed as a flat constant mask
    wd_mask = jnp.concatenate(
        [jnp.full((n,), 1.0 if p.ndim >= 2 else 0.0, jnp.float32)
         for p, n in zip(flat_p, sizes)])

    gnorm = jnp.sqrt(jnp.sum(jnp.square(gf)))
    scale = _clip_scale(gnorm, grad_clip)
    step = opt["step"] + 1
    c1 = 1 - b1 ** step.astype(jnp.float32)
    c2 = 1 - b2 ** step.astype(jnp.float32)
    if use_pallas:
        from ..ops.pallas_kernels import megakernel_adamw_flat

        new_flat, m_out, v_out = megakernel_adamw_flat(
            pf, gf, opt["m"], opt["v"], wd_mask, lr, scale, c1, c2,
            b1=b1, b2=b2, eps=eps, weight_decay=weight_decay)
    else:
        gf = gf * scale
        mf = b1 * opt["m"].astype(jnp.float32) + (1 - b1) * gf
        vf = b2 * opt["v"].astype(jnp.float32) + (1 - b2) * gf * gf
        u = (mf / c1) / (jnp.sqrt(vf / c2) + eps)
        new_flat = pf - lr * (u + weight_decay * wd_mask * pf)
        m_out = mf.astype(opt["m"].dtype)
        v_out = vf.astype(opt["v"].dtype)

    new_leaves, off = [], 0
    for p, n in zip(flat_p, sizes):
        new_leaves.append(new_flat[off:off + n].reshape(p.shape)
                          .astype(p.dtype))
        off += n
    new_p = treedef.unflatten(new_leaves)
    return new_p, {"m": m_out, "v": v_out, "step": step}, gnorm


def _rs_param_layout(cfg: GPTConfig, pcfg: ParallelConfig,
                     ccfg: CommConfig):
    """Bucket layout over the rank-LOCAL param shard shapes (tree-flatten
    order) for the reduce-scatter path. Deterministic in (cfg, pcfg, ccfg)
    so ``init_sharded`` and ``make_train_step`` agree."""
    dp_ax, pp_ax, tp_ax = pcfg.axis_names
    specs = gpt_mod.param_specs(cfg, pp=pp_ax, tp=tp_ax)
    sizes = dict(zip(pcfg.axis_names, (pcfg.dp, pcfg.pp, pcfg.tp)))
    avals = jax.eval_shape(partial(gpt_mod.init_params, cfg=cfg),
                           jax.ShapeDtypeStruct((2,), jnp.uint32))
    flat_avals, treedef = jax.tree_util.tree_flatten(avals)
    flat_specs = treedef.flatten_up_to(specs)

    def local_shape(shape, spec):
        out = list(shape)
        for d, entry in enumerate(spec):
            if entry is None:
                continue
            axes = entry if isinstance(entry, (tuple, list)) else (entry,)
            div = int(np.prod([sizes[a] for a in axes]))
            if out[d] % div:
                raise ValueError(
                    f"param dim {shape}[{d}] not divisible by mesh {axes}")
            out[d] //= div
        return tuple(out)

    for s in flat_specs:
        if dp_ax in _spec_axes(s):
            raise NotImplementedError(
                "reduce_scatter grad path expects dp-replicated params")
    shapes_dtypes = [(local_shape(a.shape, s), a.dtype)
                     for a, s in zip(flat_avals, flat_specs)]
    pad_multiple = ccfg.quant_chunk if ccfg.comm_dtype == "int8" else 1
    layout = comm_opt.build_bucket_layout(
        shapes_dtypes, ranks=pcfg.dp,
        cap_bytes=int(ccfg.bucket_mb * (1 << 20)),
        pad_multiple=pad_multiple)
    return layout, specs, treedef


def rs_param_layout(cfg: GPTConfig, pcfg: ParallelConfig,
                    comm: Optional[CommConfig] = None,
                    **comm_kw) -> Tuple[Any, int]:
    """Public accessor for the reduce-scatter bucket layout: returns
    ``(BucketLayout, repl)`` where ``repl`` (= pp*tp) is how many times each
    dp shard repeats in the addressable flat moment buffer
    (``init_sharded`` shards it over EVERY mesh axis).  Checkpoint
    manifests record exactly this pair so a restore onto a different dp
    can reshard the moments bit-exactly
    (parallel/checkpoint.py:reshard_flat, docs/elastic.md)."""
    ccfg = comm if comm is not None else CommConfig(
        grad_reduce="reduce_scatter", **comm_kw)
    layout, _, _ = _rs_param_layout(cfg, pcfg, ccfg)
    return layout, pcfg.pp * pcfg.tp


def _spec_axes(spec: P):
    out = set()
    for entry in spec:
        if entry is None:
            continue
        if isinstance(entry, (tuple, list)):
            out.update(entry)
        else:
            out.add(entry)
    return out


def _make_rs_step(cfg: GPTConfig, pcfg: ParallelConfig, mesh: Mesh,
                  ccfg: CommConfig, lr, weight_decay, grad_clip,
                  specs, param_sh, data_spec, data_sh, double_buffer,
                  skip_nonfinite: bool = False):
    """The reduce-scatter train step: ONE shard_map holding grad, bucketed
    psum_scatter, the sharded flat AdamW sweep, and the param all_gather.

    Per dp rank: grads are flat-concatenated into the bucket layout
    (comm_opt.build_bucket_layout over the rank-local leaf shards), each
    bucket is reduced with ``lax.psum_scatter`` (or the quantized
    all_to_all exchange) so the rank owns 1/dp of it, AdamW runs on the
    shard against dp-sharded flat moments, and the updated param shards
    are ``all_gather``-ed back into replicated leaves. Every bucket's
    collectives sit in ``collective/rs_bucket<i>`` / ``collective/
    ag_bucket<i>`` named scopes so the merged trace measures overlap.
    """
    dp_ax = pcfg.axis_names[0]
    dp = pcfg.dp
    layout, _, treedef = _rs_param_layout(cfg, pcfg, ccfg)
    buckets = layout.buckets
    # static per-bucket flat constants: weight-decay mask (no decay on
    # 1-D leaves) and the grad-norm replication weight (a leaf replicated
    # over pp/tp appears on every such rank; weight 1/replication so the
    # all-axes psum counts each unique element once)
    sizes = dict(zip(pcfg.axis_names, (pcfg.dp, pcfg.pp, pcfg.tp)))
    flat_specs = treedef.flatten_up_to(specs)
    wd_masks, repl_w = [], []
    for b in buckets:
        parts = []
        for idx, shape, numel in b.entries:
            repl = int(np.prod([sizes[a] for a in pcfg.axis_names[1:]
                                if a not in _spec_axes(flat_specs[idx])]))
            parts.append(np.full((numel,), 1.0 / repl, np.float32))
        parts.append(np.zeros((b.pad,), np.float32))
        repl_w.append(np.concatenate(parts))
        wd_masks.append(comm_opt.bucket_wd_mask(b))
    b1, b2, eps = 0.9, 0.95, 1e-8

    def per_rank(params, opt, tokens, labels):
        local_loss, grads = jax.value_and_grad(_pipeline_loss)(
            params, tokens, labels, cfg, pcfg, double_buffer)
        with _named_collective("psum_loss"):
            comm_opt.record_collective("psum", jnp.float32, 4,
                                       pcfg.n_devices, site="psum_loss")
            loss = jax.lax.psum(local_loss, pcfg.axis_names)
        # pp/tp replication is still a per-leaf psum; the dp reduction is
        # the bucketed scatter below
        grads = psum_grads_by_spec(
            grads, specs, pcfg.axis_names, skip_axes=(dp_ax,),
            comm_dtype=ccfg.comm_dtype, quant_chunk=ccfg.quant_chunk)
        flat_g = jax.tree_util.tree_leaves(grads)
        flat_p = jax.tree_util.tree_leaves(params)
        dp_idx = jax.lax.axis_index(dp_ax)

        g_shards, p_shards, wd_shards, w_shards, ef_out = [], [], [], [], []
        ef_off = 0
        for i, b in enumerate(buckets):
            blen = b.size // dp
            with jax.named_scope(f"collective/rs_bucket{i}"):
                _m_collectives.labels("psum_scatter_grad").inc()
                vec = comm_opt.flatten_bucket(flat_g, b, jnp.float32)
                if ccfg.error_feedback:
                    vec = vec + jax.lax.dynamic_slice(
                        opt["ef"], (ef_off,), (b.size,))
                shard, resid = comm_opt.reduce_scatter_flat(vec, dp_ax, ccfg)
                g_shards.append(shard)
                if ccfg.error_feedback:
                    ef_out.append(resid)
            pvec = comm_opt.flatten_bucket(flat_p, b, jnp.float32)
            start = dp_idx * blen
            p_shards.append(jax.lax.dynamic_slice(pvec, (start,), (blen,)))
            wd_shards.append(jax.lax.dynamic_slice(
                jnp.asarray(wd_masks[i]), (start,), (blen,)))
            w_shards.append(jax.lax.dynamic_slice(
                jnp.asarray(repl_w[i]), (start,), (blen,)))
            ef_off += b.size

        gf = jnp.concatenate(g_shards) if len(g_shards) > 1 else g_shards[0]
        pf = jnp.concatenate(p_shards) if len(p_shards) > 1 else p_shards[0]
        wd_mask = jnp.concatenate(wd_shards) if len(wd_shards) > 1 \
            else wd_shards[0]
        w = jnp.concatenate(w_shards) if len(w_shards) > 1 else w_shards[0]

        with jax.named_scope("train/opt_update"):
            gnorm = jnp.sqrt(jax.lax.psum(
                jnp.sum(jnp.square(gf) * w), pcfg.axis_names))
            gf = gf * _clip_scale(gnorm, grad_clip)
            step_no = opt["step"] + 1
            c1 = 1 - b1 ** step_no.astype(jnp.float32)
            c2 = 1 - b2 ** step_no.astype(jnp.float32)
            mf = b1 * opt["m"].astype(jnp.float32) + (1 - b1) * gf
            vf = b2 * opt["v"].astype(jnp.float32) + (1 - b2) * gf * gf
            u = (mf / c1) / (jnp.sqrt(vf / c2) + eps)
            new_flat = pf - lr * (u + weight_decay * wd_mask * pf)

        # gather updated shards back into replicated leaves, per bucket
        new_by_idx = {}
        off = 0
        for i, b in enumerate(buckets):
            blen = b.size // dp
            with jax.named_scope(f"collective/ag_bucket{i}"):
                _m_collectives.labels("all_gather_params").inc()
                full = comm_opt.all_gather_flat(new_flat[off:off + blen],
                                                dp_ax)
            new_by_idx.update(comm_opt.unflatten_bucket(full, b))
            off += blen
        new_leaves = [new_by_idx[i].astype(p.dtype)
                      for i, p in enumerate(flat_p)]
        new_params = jax.tree_util.tree_unflatten(treedef, new_leaves)
        new_opt = {"m": mf.astype(opt["m"].dtype),
                   "v": vf.astype(opt["v"].dtype), "step": step_no}
        if ccfg.error_feedback:
            new_opt["ef"] = (jnp.concatenate(ef_out)
                             if len(ef_out) > 1 else ef_out[0])
        return loss, new_params, new_opt, gnorm

    flat_spec = P(tuple(pcfg.axis_names))
    opt_specs = {"m": flat_spec, "v": flat_spec, "step": P()}
    if ccfg.error_feedback:
        opt_specs["ef"] = flat_spec
    sharded = shard_map_compat(
        per_rank, mesh,
        in_specs=(specs, opt_specs, data_spec, data_spec),
        out_specs=(P(), specs, opt_specs, P()),
    )
    opt_sh = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), opt_specs,
        is_leaf=lambda x: isinstance(x, P))

    @partial(jax.jit,
             in_shardings=(param_sh, opt_sh, data_sh, data_sh),
             out_shardings=(param_sh, opt_sh, None, None),
             donate_argnums=(0, 1))
    def step(params, opt_state, tokens, labels):
        with jax.named_scope("train/grad"):
            loss, new_params, new_opt, gnorm = sharded(
                params, opt_state, tokens, labels)
        if skip_nonfinite:
            # divergence guardrail (docs/health.md): loss and gnorm are
            # psum'd over the whole mesh, so every rank selects the same
            # branch and the next step's collectives stay matched
            with jax.named_scope("train/guardrail"):
                (new_params, new_opt), _bad = _health.nonfinite_guard(
                    (params, opt_state), (new_params, new_opt), loss, gnorm)
        return new_params, new_opt, loss, gnorm

    return step


def _make_gspmd_step(cfg: GPTConfig, pcfg: ParallelConfig, mesh: Mesh,
                     plan, lr, weight_decay, grad_clip,
                     skip_nonfinite: bool = False):
    """The sharding-layer train step (ISSUE 12, docs/sharding.md): pure
    ``jax.jit`` + ``NamedSharding`` from a propagated
    :class:`~paddle_tpu.sharding.ShardingPlan` — no shard_map, no
    hand-written collectives; GSPMD inserts whatever the specs imply
    (grad all-reduce for dp, all-gather/reduce-scatter for fsdp, the
    Megatron pattern for tp).

    The loss reduction is grouped by dp rank (reshape [B] ->
    [dp, B/dp], per-group CE, sum of per-group loss/denom) so the f32
    arithmetic ORDER matches the hand-written psum baseline exactly —
    that is what makes the dp parity test bit-identical, not just close.
    """
    from ..sharding.spec import spec_axes as _spec_axes_of

    dp_ax = pcfg.axis_names[0]
    dp = pcfg.dp
    param_specs = plan.param_specs
    param_sh = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), param_specs,
        is_leaf=lambda x: isinstance(x, P))
    opt_specs = {"m": param_specs, "v": param_specs, "step": P()}
    opt_sh = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), opt_specs,
        is_leaf=lambda x: isinstance(x, P))
    data_sh = NamedSharding(mesh, plan.data_spec)

    # static wire-byte accounting (comm_opt ring model, recorded once at
    # trace time like the explicit collectives): a dp-replicated leaf's
    # grad implies one psum over dp; a dp-sharded (fsdp) leaf implies
    # grad reduce-scatter + param all-gather. GSPMD inserts the real
    # collectives itself, so this is the plan-level estimate feeding the
    # same paddle_collective_bytes_total family comm_bench reads.
    _comm_recorded = {"done": False}

    def _record_static_comm():
        if _comm_recorded["done"] or dp <= 1:
            return
        _comm_recorded["done"] = True
        avals = jax.eval_shape(partial(gpt_mod.init_params, cfg=cfg),
                               jax.ShapeDtypeStruct((2,), jnp.uint32))
        flat_avals, treedef = jax.tree_util.tree_flatten(avals)
        flat_specs = treedef.flatten_up_to(param_specs)
        for a, s in zip(flat_avals, flat_specs):
            nbytes = int(np.prod(a.shape)) * 4  # f32 grads
            if dp_ax in _spec_axes_of(tuple(s)):
                comm_opt.record_collective("psum_scatter", jnp.float32,
                                           nbytes, dp,
                                           site="static_estimate")
                comm_opt.record_collective("all_gather", jnp.float32,
                                           nbytes, dp,
                                           site="static_estimate")
            else:
                comm_opt.record_collective("psum", jnp.float32, nbytes, dp,
                                           site="static_estimate")

    def loss_fn(params, tokens, labels):
        M, B, T = tokens.shape
        denom = jnp.float32(M * B * T)
        total = jnp.float32(0.0)
        for i in range(M):
            x = gpt_mod.embed(params, tokens[i], cfg)
            x = gpt_mod.run_blocks(params["blocks"], x, cfg)
            if dp > 1 and B % dp == 0:
                D = x.shape[-1]
                xg = jax.lax.with_sharding_constraint(
                    x.reshape(dp, B // dp, T, D),
                    NamedSharding(mesh, P(dp_ax)))
                lg = labels[i].reshape(dp, B // dp, T)
                ce = jax.vmap(
                    lambda a, b: gpt_mod.ce_from_hidden(params, a, b, cfg)
                )(xg, lg)
                total = total + jnp.sum(ce / denom)
            else:
                total = total + gpt_mod.ce_from_hidden(
                    params, x, labels[i], cfg) / denom
        return total

    @partial(jax.jit,
             in_shardings=(param_sh, opt_sh, data_sh, data_sh),
             out_shardings=(param_sh, opt_sh, None, None),
             donate_argnums=(0, 1))
    def step(params, opt_state, tokens, labels):
        _record_static_comm()  # host-side, runs once at trace time
        with jax.named_scope("train/grad"):
            loss, grads = jax.value_and_grad(loss_fn)(
                params, tokens, labels)
            # pin grads to the plan layouts: fsdp grads stay sharded (no
            # full-size grad materialization), dp grads replicate
            grads = jax.tree_util.tree_map(
                lambda g, s: jax.lax.with_sharding_constraint(
                    g, NamedSharding(mesh, s)),
                grads, param_specs,
                is_leaf=lambda x: isinstance(x, P))
        with jax.named_scope("train/opt_update"):
            new_params, new_opt, gnorm = _adamw_update(
                params, grads, opt_state, lr,
                weight_decay=weight_decay, grad_clip=grad_clip)
        if skip_nonfinite:
            # loss/gnorm are global (GSPMD reduces them), so the skip
            # decision is identical on every device (docs/health.md)
            with jax.named_scope("train/guardrail"):
                (new_params, new_opt), _bad = _health.nonfinite_guard(
                    (params, opt_state), (new_params, new_opt),
                    loss, gnorm)
        return new_params, new_opt, loss, gnorm

    return step


def make_train_step(cfg: GPTConfig, pcfg: ParallelConfig, mesh: Mesh,
                    lr: float = 3e-4, weight_decay: float = 0.1,
                    fused_opt: bool = False, fused_opt_pallas=None,
                    grad_reduce: str = "psum",
                    grad_allreduce_dtype=None, bucket_mb: float = 32.0,
                    error_feedback: bool = False, grad_clip=1.0,
                    comm: Optional[CommConfig] = None,
                    skip_nonfinite: bool = False,
                    sharding=None, tuned=None):
    """Build the jitted 4D-parallel training step.

    Returns ``step(params, opt_state, tokens, labels) ->
    (params, opt_state, loss, gnorm)``. tokens/labels are
    [microbatches, global_batch, T] int32.

    ``fused_opt=True`` runs the optimizer as a flat-buffer sweep
    (_adamw_update_fused; opt state from ``init_sharded(fused_opt=True)``).
    Single-device meshes only — concatenating differently-sharded leaves
    would force an all-gather per step. ``fused_opt_pallas`` additionally
    lowers that sweep through ONE Pallas megakernel launch
    (ops/pallas_kernels.megakernel_adamw_flat) — None = auto (TPU only),
    True/False forces; ignored without ``fused_opt``.

    Communication levers (docs/comm_opt.md; or pass a ready
    :class:`CommConfig` as ``comm``):

    - ``grad_reduce="reduce_scatter"``: per-leaf dp psum is replaced by
      size-capped flat gradient buckets reduced with ``lax.psum_scatter``;
      each dp rank applies AdamW to its shard (moments + the flat master
      sweep live dp-sharded — opt state from
      ``init_sharded(grad_reduce="reduce_scatter")``) and the updated
      params return via ``all_gather``. Gradient-reduction wire bytes
      halve; optimizer-state HBM drops by dp x. f32 comm is bit-identical
      to the psum baseline (tests/test_comm_opt.py).
    - ``grad_allreduce_dtype="bf16"|"int8"``: chunk-scaled quantized wire
      payload with f32 accumulation (comm_opt.py); ``error_feedback=True``
      (reduce_scatter mode) carries the per-rank quantization residual in
      the train state.
    - ``grad_clip=None`` disables gradient clipping exactly (scale 1.0).

    ``skip_nonfinite=True`` arms the in-jit divergence guardrail
    (``health.nonfinite_guard``, docs/health.md): a step whose psum'd loss
    or grad norm is NaN/Inf keeps the old ``(params, opt_state)`` wholesale
    (step counter included) — the batch is skipped identically on every dp
    rank, the full-precision generalization of AMP's overflow skip.

    ``sharding=`` routes through the GSPMD sharding layer (ISSUE 12,
    docs/sharding.md): a preset name (``"dp"`` | ``"fsdp"`` | ``"tp"``),
    an annotation dict on the weight leaves, or a ready
    :class:`paddle_tpu.sharding.ShardingPlan`. The plan's propagated
    specs drive a pure ``jax.jit`` + ``NamedSharding`` step (no
    shard_map) — dp is bit-identical to the hand-written psum baseline
    (f32 comm, tests/test_sharding.py), fsdp shards params AND optimizer
    moments dp-ways, tp derives the Megatron split from six annotations.
    Combining ``sharding=`` with the comm levers keeps comm_opt as the
    lowering underneath: a dp-replicated plan + ``grad_reduce=
    "reduce_scatter"``/quantized wire dtypes runs the existing bucketed
    shard_map path (the plan only supplies the layout contract); plans
    that shard params over dp cannot take that path and raise.

    ``tuned=`` accepts a TUNED.json path (or loaded doc) from
    tools/autotune.py. Application is fingerprint-gated (a config tuned
    on different hardware warns and falls back to the kwargs as given)
    and only overrides knobs left at their documented defaults — an
    explicit caller choice, or a ready ``comm=`` CommConfig, always
    wins over the tuner.
    """
    if tuned is not None and comm is None:
        kw = _resolve_tuned(tuned, pcfg, dict(
            grad_reduce=grad_reduce,
            grad_allreduce_dtype=grad_allreduce_dtype,
            bucket_mb=bucket_mb, error_feedback=error_feedback,
            fused_opt=fused_opt))
        grad_reduce = kw["grad_reduce"]
        grad_allreduce_dtype = kw["grad_allreduce_dtype"]
        bucket_mb = kw["bucket_mb"]
        error_feedback = kw["error_feedback"]
        fused_opt = kw["fused_opt"]
    ccfg = comm if comm is not None else CommConfig(
        grad_reduce=grad_reduce, comm_dtype=grad_allreduce_dtype,
        bucket_mb=bucket_mb, error_feedback=error_feedback)
    plan = None
    if sharding is not None:
        from ..sharding import resolve_plan

        plan = resolve_plan(sharding, cfg, pcfg)
        if pcfg.pp > 1:
            raise NotImplementedError(
                "sharding= plans do not cover GPipe pipeline stages; use "
                "the hand-written pp path (pp=1 required)")
        wants_comm_opt = (ccfg.grad_reduce == "reduce_scatter"
                          or ccfg.comm_dtype is not None)
        if not wants_comm_opt:
            step = _make_gspmd_step(cfg, pcfg, mesh, plan, lr,
                                    weight_decay, grad_clip,
                                    skip_nonfinite=skip_nonfinite)
            return _wrap_step_with_report(
                step, pcfg, report_name=(
                    f"parallel_train_step/dp{pcfg.dp}pp{pcfg.pp}"
                    f"tp{pcfg.tp}mb{pcfg.microbatches}"
                    f"_gspmd-{plan.mode}"),
                extra_mode=f"gspmd+named_sharding:{plan.mode}")
        if not plan.params_replicated_over(pcfg.axis_names[0]):
            raise NotImplementedError(
                "comm_opt grad reduction (reduce_scatter / quantized "
                "wire dtypes) needs dp-replicated params; plan "
                f"{plan.mode!r} shards params over "
                f"{pcfg.axis_names[0]!r} — drop the comm levers or use "
                "sharding='dp'")
        # dp-replicated plan + comm levers: fall through to the
        # hand-written comm_opt lowerings below — the plan's layout
        # contract matches them by construction
    if fused_opt and pcfg.n_devices > 1 and ccfg.grad_reduce != "reduce_scatter":
        raise NotImplementedError(
            "fused_opt on a multi-device mesh requires "
            "grad_reduce='reduce_scatter' (the bucketed flat sweep) "
            f"(got dp={pcfg.dp} pp={pcfg.pp} tp={pcfg.tp})")
    if ccfg.error_feedback and ccfg.grad_reduce != "reduce_scatter":
        raise NotImplementedError(
            "error_feedback requires grad_reduce='reduce_scatter' "
            "(the residual rides the sharded train state)")
    dp_ax, pp_ax, tp_ax = pcfg.axis_names
    specs = gpt_mod.param_specs(cfg, pp=pp_ax, tp=tp_ax)
    data_spec = P(None, dp_ax, None)
    db = ccfg.pipeline_double_buffer

    def grad_fn(params, tokens, labels):
        local_loss, grads = jax.value_and_grad(_pipeline_loss)(
            params, tokens, labels, cfg, pcfg, db)
        with _named_collective("psum_loss"):
            comm_opt.record_collective("psum", jnp.float32, 4,
                                       pcfg.n_devices, site="psum_loss")
            loss = jax.lax.psum(local_loss, pcfg.axis_names)
        grads = psum_grads_by_spec(
            grads, specs, pcfg.axis_names,
            comm_dtype=ccfg.comm_dtype, quant_chunk=ccfg.quant_chunk)
        return loss, grads

    param_sh = jax.tree_util.tree_map(lambda s: NamedSharding(mesh, s), specs,
                                      is_leaf=lambda x: isinstance(x, P))
    data_sh = NamedSharding(mesh, data_spec)

    if ccfg.grad_reduce == "reduce_scatter":
        step = _make_rs_step(cfg, pcfg, mesh, ccfg, lr, weight_decay,
                             grad_clip, specs, param_sh, data_spec, data_sh,
                             db, skip_nonfinite=skip_nonfinite)
    else:
        sharded_grad = shard_map_compat(
            grad_fn, mesh,
            in_specs=(specs, data_spec, data_spec),
            out_specs=(P(), specs),
        )

        if fused_opt:
            opt_specs = {"m": P(), "v": P(), "step": P()}
        else:
            opt_specs = {"m": specs, "v": specs, "step": P()}
        opt_sh = jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s), opt_specs,
            is_leaf=lambda x: isinstance(x, P))
        if fused_opt:
            from ..ops.pallas_kernels import use_opt_megakernel

            update = partial(
                _adamw_update_fused,
                use_pallas=use_opt_megakernel(fused_opt_pallas))
        else:
            update = _adamw_update

        @partial(jax.jit,
                 in_shardings=(param_sh, opt_sh, data_sh, data_sh),
                 out_shardings=(param_sh, opt_sh, None, None),
                 donate_argnums=(0, 1))
        def step(params, opt_state, tokens, labels):
            # named scopes stamp the phase into HLO metadata: the merged
            # host+device trace shows train/grad vs train/opt_update spans
            with jax.named_scope("train/grad"):
                loss, grads = sharded_grad(params, tokens, labels)
            # optimizer update is elementwise: GSPMD partitions it with zero
            # communication (replaces the reference's fuse_optimizer_ops pass)
            with jax.named_scope("train/opt_update"):
                new_params, new_opt, gnorm = update(
                    params, grads, opt_state, lr,
                    weight_decay=weight_decay, grad_clip=grad_clip)
            if skip_nonfinite:
                # loss/gnorm are already all-reduced: every rank takes the
                # same skip branch (docs/health.md)
                with jax.named_scope("train/guardrail"):
                    (new_params, new_opt), _bad = _health.nonfinite_guard(
                        (params, opt_state), (new_params, new_opt),
                        loss, gnorm)
            return new_params, new_opt, loss, gnorm

    report_name = (f"parallel_train_step/dp{pcfg.dp}pp{pcfg.pp}tp{pcfg.tp}"
                   f"mb{pcfg.microbatches}"
                   + ("_fused" if fused_opt else "")
                   + ("_rs" if ccfg.grad_reduce == "reduce_scatter" else "")
                   + (f"_{ccfg.comm_dtype}" if ccfg.comm_dtype else "")
                   + (f"_plan-{plan.mode}" if plan is not None else ""))
    return _wrap_step_with_report(step, pcfg, report_name=report_name,
                                  extra_mode="gspmd+shard_map")


def _wrap_step_with_report(step, pcfg: ParallelConfig, report_name: str,
                           extra_mode: str):
    # Program-report capture (observability/program_report.py): the first
    # invocation with each (tokens, labels) signature lowers + compiles
    # explicitly, keeps the executable as the dispatch target, and records
    # cost/memory analysis, compile wall-ms and the donation map — the same
    # introspection surface Executor.run's compiled blocks get. A compile
    # the backend refuses raises: there is no second try under implicit jit.
    from ..framework.core import ensure_compile_cache
    from ..observability import goodput as _goodput
    from ..observability import program_report as _prep
    from ..observability import spans as _spans

    aot = {}            # (tokens, labels) aval signature -> executable
    calls = itertools.count()

    def step_with_report(params, opt_state, tokens, labels):
        # hang-watchdog progress stamp (docs/health.md): one tuple store
        _health.progress("train_step")
        # the host's part of a step: the dispatch of the compiled step
        # (the loss fetch is the caller's), numbered by call
        with _spans.span("train/step", attrs={"seq": next(calls)}):
            sig = (tokens.shape, str(tokens.dtype), labels.shape,
                   str(labels.dtype))
            compiled = aot.get(sig)
            if compiled is None:
                compiled = aot[sig] = _compile(params, opt_state, tokens,
                                               labels)
            with _goodput.timer("productive_step"):
                return compiled(params, opt_state, tokens, labels)

    def _compile(params, opt_state, tokens, labels):
        import time as _time

        ensure_compile_cache()
        t0 = _time.perf_counter_ns()
        # first-call XLA compile can run for minutes: pause the
        # hang-watchdog deadline clock for its duration
        with _spans.span("train/compile"), _health.suspend():
            compiled = step.lower(params, opt_state, tokens,
                                  labels).compile()
        _prep.capture(
            report_name, compiled=compiled,
            compile_ms=(_time.perf_counter_ns() - t0) / 1e6,
            donated=["params", "opt_state"],
            inputs=(params, opt_state, tokens, labels),
            extra={"mode": extra_mode,
                   "mesh": {a: int(s) for a, s in
                            zip(pcfg.axis_names,
                                (pcfg.dp, pcfg.pp, pcfg.tp))}})
        return compiled

    def _hlo_text():
        # optimized HLO of the newest kept executable (None before the
        # first call) — the roofline attribution
        # (observability/attribution.py) joins its per-instruction static
        # costs with the measured device trace
        if not aot:
            return None
        return list(aot.values())[-1].as_text()

    step_with_report.report_name = report_name
    step_with_report.hlo_text = _hlo_text
    # the jitted step's own lower(): a compile for a described, unattached
    # chip passes shapes, which the dispatch wrapper above cannot run
    step_with_report.lower = step.lower
    return step_with_report


def make_forward(cfg: GPTConfig, pcfg: ParallelConfig, mesh: Mesh):
    """Jitted inference forward under dp+tp (GSPMD; pipeline folds into one
    stage pass per rank is only needed for training throughput)."""
    specs = gpt_mod.param_specs(cfg, pp=pcfg.axis_names[1],
                                tp=pcfg.axis_names[2])
    param_sh = jax.tree_util.tree_map(lambda s: NamedSharding(mesh, s), specs,
                                      is_leaf=lambda x: isinstance(x, P))

    @partial(jax.jit, in_shardings=(param_sh, NamedSharding(mesh, P(pcfg.axis_names[0], None))))
    def fwd(params, tokens):
        return gpt_mod.forward(params, tokens, cfg)

    return fwd


def _resolve_tuned(tuned, pcfg, current):
    """Fingerprint-gate + apply a TUNED.json onto the caller's step
    kwargs (paddle_tpu/tuning/tuned.py owns the semantics)."""
    from ..tuning import tuned as tuned_mod

    doc = tuned_mod.load_for_device(tuned)
    if doc is None:
        return current
    return tuned_mod.resolve_train_step_kwargs(doc, pcfg, current)


def init_sharded(key, cfg: GPTConfig, pcfg: ParallelConfig, mesh: Mesh,
                 moment_dtype=None, fused_opt: bool = False,
                 grad_reduce: str = "psum", bucket_mb: float = 32.0,
                 error_feedback: bool = False, grad_allreduce_dtype=None,
                 comm: Optional[CommConfig] = None, sharding=None,
                 tuned=None):
    """Initialize params + AdamW state directly with mesh shardings (large
    models never materialize unsharded).

    ``grad_reduce="reduce_scatter"`` (pass the same comm kwargs as
    ``make_train_step``) lays the AdamW moments out as dp-sharded flat
    megabuffers matching the comm_opt bucket layout — optimizer-state HBM
    per device drops by dp x vs the replicated per-leaf layout.

    ``sharding=`` (a preset / annotation dict / ShardingPlan, same as
    ``make_train_step``) lays params AND per-leaf AdamW moments out per
    the plan's propagated specs — under ``"fsdp"`` both drop by dp x
    without the flat-buffer layout (comm levers then use the rs path
    above instead).

    ``tuned=`` mirrors ``make_train_step(tuned=)`` — pass the SAME
    TUNED.json to both so the optimizer-state layout matches the step
    the tuner picked."""
    if tuned is not None and comm is None:
        kw = _resolve_tuned(tuned, pcfg, dict(
            grad_reduce=grad_reduce,
            grad_allreduce_dtype=grad_allreduce_dtype,
            bucket_mb=bucket_mb, error_feedback=error_feedback,
            fused_opt=fused_opt))
        grad_reduce = kw["grad_reduce"]
        grad_allreduce_dtype = kw["grad_allreduce_dtype"]
        bucket_mb = kw["bucket_mb"]
        error_feedback = kw["error_feedback"]
        fused_opt = kw["fused_opt"]
    if sharding is not None:
        from ..sharding import resolve_plan

        plan = resolve_plan(sharding, cfg, pcfg)
        wants_comm_opt = (grad_reduce == "reduce_scatter"
                          or (comm is not None
                              and (comm.grad_reduce == "reduce_scatter"
                                   or comm.comm_dtype is not None))
                          or comm_opt.normalize_comm_dtype(
                              grad_allreduce_dtype) is not None)
        if not wants_comm_opt:
            param_sh = jax.tree_util.tree_map(
                lambda s: NamedSharding(mesh, s), plan.param_specs,
                is_leaf=lambda x: isinstance(x, P))
            init_jit = jax.jit(lambda k: gpt_mod.init_params(k, cfg),
                               out_shardings=param_sh)
            params = init_jit(key)
            opt_sh = {"m": param_sh, "v": param_sh, "step": None}
            opt_jit = jax.jit(
                partial(init_adamw_state, moment_dtype=moment_dtype),
                out_shardings=opt_sh)
            return params, opt_jit(params)
        # comm levers: the plan must be dp-replicated and the flat rs
        # layout below is the (sharded-state) source of truth
        if not plan.params_replicated_over(pcfg.axis_names[0]):
            raise NotImplementedError(
                "comm_opt grad reduction needs dp-replicated params; "
                f"plan {plan.mode!r} shards them")
    specs = gpt_mod.param_specs(cfg, pp=pcfg.axis_names[1], tp=pcfg.axis_names[2])
    param_sh = jax.tree_util.tree_map(lambda s: NamedSharding(mesh, s), specs,
                                      is_leaf=lambda x: isinstance(x, P))
    ccfg = comm if comm is not None else CommConfig(
        grad_reduce=grad_reduce, comm_dtype=grad_allreduce_dtype,
        bucket_mb=bucket_mb, error_feedback=error_feedback)

    init_jit = jax.jit(lambda k: gpt_mod.init_params(k, cfg),
                       out_shardings=param_sh)
    params = init_jit(key)

    if ccfg.grad_reduce == "reduce_scatter":
        layout, _, _ = _rs_param_layout(cfg, pcfg, ccfg)
        n_dev = pcfg.n_devices
        flat_sh = NamedSharding(mesh, P(tuple(pcfg.axis_names)))
        mdt = moment_dtype or jnp.float32
        shapes = {"m": ((n_dev * layout.shard_len,), mdt),
                  "v": ((n_dev * layout.shard_len,), mdt),
                  "step": ((), jnp.int32)}
        opt_sh = {"m": flat_sh, "v": flat_sh,
                  "step": NamedSharding(mesh, P())}
        if ccfg.error_feedback:
            shapes["ef"] = ((n_dev * layout.total_len,), jnp.float32)
            opt_sh["ef"] = flat_sh
        opt_jit = jax.jit(
            lambda: {k: jnp.zeros(sh, dt) for k, (sh, dt) in shapes.items()},
            out_shardings=opt_sh)
        return params, opt_jit()

    if fused_opt:
        flat_sh = NamedSharding(mesh, P())
        opt_sh = {"m": flat_sh, "v": flat_sh, "step": None}
    else:
        opt_sh = {"m": param_sh, "v": param_sh, "step": None}
    opt_jit = jax.jit(partial(init_adamw_state, moment_dtype=moment_dtype,
                              fused=fused_opt),
                      out_shardings=opt_sh)
    return params, opt_jit(params)
