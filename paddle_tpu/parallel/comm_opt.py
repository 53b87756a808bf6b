"""Communication-optimization layer for the parallel engine.

The baseline multi-chip gradient path (parallelize.py) reduces every
gradient leaf with one full-precision replicated ``psum`` and keeps a full
copy of the optimizer state on every dp rank — the unfused, unsharded,
unoverlapped baseline GSPMD (arXiv:2105.04663) and EQuARX
(arXiv:2506.17615) show leaves 1.2-2x on the table at dp>=4. This module
holds the three levers (see docs/comm_opt.md):

1. **Bucketed reduce-scatter** (:class:`BucketLayout`,
   :func:`reduce_scatter_flat`): gradients are flat-concatenated by dtype
   into size-capped buckets (default ~32 MiB), reduced with
   ``lax.psum_scatter`` so each dp rank owns 1/dp of every bucket, the
   optimizer runs on the shard (moments live sharded — optimizer-state HBM
   drops by dp x), and updated params return via ``all_gather``. Gradient
   reduction bytes on the wire halve vs all-reduce.
2. **Quantized collectives** (:func:`reduce_scatter_flat` /
   :func:`quantized_allreduce` with ``comm_dtype="bf16"|"int8"``):
   EQuARX-style chunk-scaled quantize -> exchange -> dequantize. The
   exchange is an ``all_to_all`` of the quantized payload so accumulation
   happens locally in f32 (scales stay f32); an optional error-feedback
   residual carries the per-rank quantization error into the next step.
3. **Wire-byte accounting** (:func:`record_collective`): every collective
   lowered through this module (and parallelize.py / ops/collective.py)
   increments ``paddle_collective_bytes_total{op,dtype}`` with ring-model
   per-rank bytes at TRACE time, so per-step bytes read straight off the
   metrics registry (tools/comm_bench.py -> COMM_BENCH.json).

Comm/compute overlap itself is scheduling: ``sysconfig.tpu_perf_flags()``
sets the XLA async-collective / latency-hiding-scheduler flags, the
pipeline tick is double-buffered (parallelize.py / pipeline_program.py),
and :func:`measure_overlap_fraction` reads the achieved overlap off a
profiler capture.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from ..observability import flight as _flight
from ..observability import metrics as _obs_metrics

__all__ = [
    "CommConfig", "BucketLayout", "Bucket", "build_bucket_layout",
    "axis_size", "record_collective", "wire_bytes", "quantize_chunked",
    "dequantize_chunked", "reduce_scatter_flat", "quantized_allreduce",
    "quantized_reduce_scatter_op", "measure_overlap_fraction",
]

# Per-rank bytes-on-wire, ring model, recorded at trace time (collectives
# run inside one fused XLA program; static shapes make the byte count a
# compile-time constant). tools/comm_bench.py reads the per-step delta.
_m_wire_bytes = _obs_metrics.default_registry().counter(
    "paddle_collective_bytes_total",
    "Per-rank wire bytes of collectives lowered into compiled programs "
    "(ring model, counted once per trace)", ("op", "dtype"))


def axis_size(name) -> int:
    """Static size of a named mesh axis inside ``shard_map``."""
    return int(lax.axis_size(name))


def _axes_size(axes) -> int:
    if isinstance(axes, (tuple, list)):
        n = 1
        for a in axes:
            n *= axis_size(a)
        return n
    return axis_size(axes)


def wire_bytes(op: str, payload_bytes: int, ranks: int) -> int:
    """Ring-model per-rank bytes for one collective of ``payload_bytes``
    global payload over ``ranks`` participants."""
    if ranks <= 1:
        return 0
    if op == "psum":                      # ring all-reduce: RS + AG legs
        return 2 * (ranks - 1) * payload_bytes // ranks
    if op in ("psum_scatter", "all_gather", "all_to_all"):
        return (ranks - 1) * payload_bytes // ranks
    if op == "ppermute":
        return payload_bytes
    raise ValueError(f"unknown collective op {op!r}")


def record_collective(op: str, dtype, payload_bytes: int, ranks: int,
                      site: Optional[str] = None) -> int:
    """Count one lowered collective into the wire-bytes counter; returns
    the per-rank ring bytes recorded.

    This is THE chokepoint every collective call site flows through
    (ops/collective.py lowerings, parallelize.py psum/ppermute sites,
    and this module's own bucketed/quantized wrappers), so it also
    stamps the flight recorder's lowered-collective sequence stream
    (ISSUE 19): one monotone (lseq, op, dtype, bytes, ranks, site)
    event per collective baked into a traced program.  Ranks trace
    identical programs in identical order, so the stream is the
    cross-rank fingerprint tools/flight_assemble.py checks for
    divergence.  ``site`` labels the calling wrapper (defaults to
    ``op``); tools/paddle_lint.py statically verifies every wrapper
    reaches this stamp."""
    b = wire_bytes(op, int(payload_bytes), int(ranks))
    if b:
        _m_wire_bytes.labels(op, str(jnp.dtype(dtype).name)).inc(b)
        _flight.stamp_collective(op, jnp.dtype(dtype).name,
                                 payload_bytes, ranks, site=site)
    return b


# ---------------------------------------------------------------------------
# Config
# ---------------------------------------------------------------------------

_COMM_DTYPES = {
    None: None, "": None, "f32": None, "fp32": None, "float32": None,
    "bf16": "bf16", "bfloat16": "bf16",
    "int8": "int8",
}


def normalize_comm_dtype(name) -> Optional[str]:
    if name not in _COMM_DTYPES:
        raise ValueError(
            f"comm dtype {name!r}: expected one of f32/bf16/int8")
    return _COMM_DTYPES[name]


@dataclasses.dataclass(frozen=True)
class CommConfig:
    """The communication levers of one train step (docs/comm_opt.md)."""
    grad_reduce: str = "psum"            # "psum" | "reduce_scatter"
    comm_dtype: Optional[str] = None     # None(f32) | "bf16" | "int8"
    bucket_mb: float = 32.0              # per-bucket cap, MiB of grad bytes
    error_feedback: bool = False         # carry quantization residual
    quant_chunk: int = 256               # elements per int8 scale chunk
    pipeline_double_buffer: bool = True  # overlap ppermute with next tick

    def __post_init__(self):
        if self.grad_reduce not in ("psum", "reduce_scatter"):
            raise ValueError(
                f"grad_reduce {self.grad_reduce!r}: "
                "expected 'psum' or 'reduce_scatter'")
        object.__setattr__(
            self, "comm_dtype", normalize_comm_dtype(self.comm_dtype))
        if self.error_feedback and self.comm_dtype is None:
            raise ValueError("error_feedback requires a quantized comm_dtype")


# ---------------------------------------------------------------------------
# Bucket layout: flat concat by dtype, size-capped, padded for the mesh
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Bucket:
    """One flat comm bucket: contiguous concat of whole leaves (by
    tree-flatten order), zero-padded to ``size`` (a multiple of the
    reduce group size, and of the quant chunk when quantizing)."""
    dtype: str                       # numpy dtype name of the leaves
    entries: Tuple[Tuple[int, Tuple[int, ...], int], ...]  # (leaf_idx, shape, numel)
    size: int                        # padded flat length
    pad: int

    @property
    def nbytes(self) -> int:
        return self.size * np.dtype(self.dtype).itemsize


@dataclasses.dataclass(frozen=True)
class BucketLayout:
    buckets: Tuple[Bucket, ...]
    ranks: int                       # reduce-scatter group size
    total_len: int                   # sum of bucket sizes (padded)

    @property
    def shard_len(self) -> int:
        return self.total_len // self.ranks


def build_bucket_layout(shapes_dtypes: Sequence[Tuple[Tuple[int, ...], Any]],
                        ranks: int, cap_bytes: int,
                        pad_multiple: int = 1) -> BucketLayout:
    """Greedy size-capped bucketing of leaves (local shard shapes), grouped
    by dtype. A leaf larger than the cap gets its own bucket — leaves are
    never split, so flatten/unflatten stay cheap reshapes."""
    ranks = max(1, int(ranks))
    align = ranks * max(1, int(pad_multiple))
    by_dtype: Dict[str, List[Tuple[int, Tuple[int, ...], int]]] = {}
    for idx, (shape, dt) in enumerate(shapes_dtypes):
        name = np.dtype(dt).name
        numel = int(np.prod(shape)) if shape else 1
        by_dtype.setdefault(name, []).append((idx, tuple(shape), numel))

    buckets: List[Bucket] = []
    for dt_name in sorted(by_dtype):
        cur: List[Tuple[int, Tuple[int, ...], int]] = []
        cur_bytes = 0
        itemsize = np.dtype(dt_name).itemsize

        def flush():
            nonlocal cur, cur_bytes
            if not cur:
                return
            n = sum(e[2] for e in cur)
            size = -(-n // align) * align
            buckets.append(Bucket(dtype=dt_name, entries=tuple(cur),
                                  size=size, pad=size - n))
            cur, cur_bytes = [], 0

        for entry in by_dtype[dt_name]:
            if cur and cur_bytes + entry[2] * itemsize > cap_bytes:
                flush()
            cur.append(entry)
            cur_bytes += entry[2] * itemsize
        flush()
    total = sum(b.size for b in buckets)
    return BucketLayout(buckets=tuple(buckets), ranks=ranks, total_len=total)


def flatten_bucket(leaves: Sequence[Any], bucket: Bucket,
                   dtype=jnp.float32):
    """Concat the bucket's leaves (flattened, cast to ``dtype``) + pad."""
    parts = [jnp.asarray(leaves[i]).astype(dtype).reshape(-1)
             for i, _, _ in bucket.entries]
    vec = jnp.concatenate(parts) if len(parts) > 1 else parts[0]
    if bucket.pad:
        vec = jnp.concatenate([vec, jnp.zeros((bucket.pad,), dtype)])
    return vec


def unflatten_bucket(vec, bucket: Bucket) -> Dict[int, Any]:
    """Inverse of :func:`flatten_bucket`: {leaf_idx: array of leaf shape}
    (still in ``vec``'s dtype — caller casts)."""
    out: Dict[int, Any] = {}
    off = 0
    for idx, shape, numel in bucket.entries:
        out[idx] = vec[off:off + numel].reshape(shape)
        off += numel
    return out


def bucket_wd_mask(bucket: Bucket) -> np.ndarray:
    """Flat weight-decay mask for one bucket (1.0 on >=2-D leaves, the
    standard no-decay-on-bias/layernorm rule — parallelize._adamw_update)."""
    parts = [np.full((numel,), 1.0 if len(shape) >= 2 else 0.0, np.float32)
             for _, shape, numel in bucket.entries]
    parts.append(np.zeros((bucket.pad,), np.float32))
    return np.concatenate(parts)


# ---------------------------------------------------------------------------
# Chunk-scaled quantization (EQuARX-style)
# ---------------------------------------------------------------------------

def quantize_chunked(x, comm_dtype: Optional[str], chunk: int):
    """f32 [n] -> (payload, scales|None). bf16 is a plain cast (no scales);
    int8 is chunk-scaled symmetric: per ``chunk`` elements one f32 scale =
    absmax/127. ``n`` must be a chunk multiple for int8."""
    if comm_dtype is None:
        return x, None
    if comm_dtype == "bf16":
        return x.astype(jnp.bfloat16), None
    if comm_dtype != "int8":
        raise ValueError(f"bad comm dtype {comm_dtype!r}")
    xr = x.reshape(-1, chunk)
    scale = jnp.max(jnp.abs(xr), axis=1, keepdims=True) / 127.0
    scale = jnp.where(scale > 0, scale, 1.0)
    q = jnp.clip(jnp.round(xr / scale), -127, 127).astype(jnp.int8)
    return q.reshape(-1), scale[:, 0]


def dequantize_chunked(payload, scales, comm_dtype: Optional[str],
                       chunk: int):
    """Inverse of :func:`quantize_chunked`, always f32 out."""
    if comm_dtype is None:
        return payload.astype(jnp.float32)
    if comm_dtype == "bf16":
        return payload.astype(jnp.float32)
    q = payload.reshape(-1, chunk).astype(jnp.float32)
    return (q * scales[:, None]).reshape(-1)


# ---------------------------------------------------------------------------
# The collectives
# ---------------------------------------------------------------------------

def reduce_scatter_flat(vec, axis, ccfg: CommConfig, residual=None,
                        record: bool = True):
    """Reduce ``vec`` (f32, length divisible by the axis size — and by
    size*quant_chunk for int8) over mesh ``axis``; each rank keeps its
    1/ranks shard, reduced in f32.

    f32 comm lowers to a native ``lax.psum_scatter`` (bit-identical to
    ``psum`` + slice — tested). Quantized comm quantizes the local vector
    chunk-scaled, exchanges shards via ``all_to_all`` (wire payload in
    comm_dtype, the reduce-scatter-optimal (ranks-1)/ranks bytes), and
    accumulates the dequantized shards locally in f32.

    Returns ``(shard, new_residual)`` — ``new_residual`` is the local
    quantization error when ``ccfg.error_feedback`` (caller adds the
    incoming ``residual`` to ``vec`` BEFORE calling; it is accepted here so
    the two stay paired in the train step), else None.
    """
    ranks = axis_size(axis)
    n = vec.shape[0]
    if ccfg.comm_dtype is None:
        if record:
            record_collective("psum_scatter", jnp.float32, n * 4, ranks,
                              site="reduce_scatter_flat")
        if ranks == 1:
            return vec, None
        return lax.psum_scatter(vec, axis, scatter_dimension=0,
                                tiled=True), None

    payload, scales = quantize_chunked(vec, ccfg.comm_dtype, ccfg.quant_chunk)
    new_residual = None
    if ccfg.error_feedback:
        new_residual = vec - dequantize_chunked(
            payload, scales, ccfg.comm_dtype, ccfg.quant_chunk)
    if ranks == 1:
        shard = dequantize_chunked(payload, scales, ccfg.comm_dtype,
                                   ccfg.quant_chunk)
        return shard, new_residual

    if record:
        record_collective(
            "all_to_all", payload.dtype, n * payload.dtype.itemsize, ranks,
            site="reduce_scatter_flat")
    rows = lax.all_to_all(payload.reshape(ranks, n // ranks), axis,
                          split_axis=0, concat_axis=0)
    if scales is not None:
        if record:
            record_collective("all_to_all", jnp.float32,
                              scales.size * 4, ranks,
                              site="reduce_scatter_flat")
        srows = lax.all_to_all(scales.reshape(ranks, -1), axis,
                               split_axis=0, concat_axis=0)
        deq = jax.vmap(lambda p, s: dequantize_chunked(
            p, s, ccfg.comm_dtype, ccfg.quant_chunk))(rows, srows)
    else:
        deq = rows.astype(jnp.float32)
    return jnp.sum(deq, axis=0), new_residual


def all_gather_flat(shard, axis, record: bool = True):
    """Gather per-rank shards back into the full flat vector."""
    ranks = axis_size(axis)
    if ranks == 1:
        return shard
    if record:
        record_collective("all_gather", shard.dtype,
                          shard.size * shard.dtype.itemsize * ranks, ranks,
                          site="all_gather_flat")
    return lax.all_gather(shard, axis, tiled=True)


def _pad_to(vec, multiple: int):
    pad = (-vec.shape[0]) % multiple
    if pad:
        vec = jnp.concatenate([vec, jnp.zeros((pad,), vec.dtype)])
    return vec, pad


def quantized_allreduce(x, axis, comm_dtype, quant_chunk: int = 256,
                        mean: bool = False, record: bool = True):
    """All-reduce with wire payload in ``comm_dtype`` and f32 accumulation:
    quantized reduce-scatter leg, requantize the reduced shard, quantized
    all-gather leg (the EQuARX RS+AG structure). Arbitrary shapes; returns
    ``x``'s dtype. Used by the fluid ``c_allreduce_*`` lowerings and the
    GradientMergeOptimizer tail (FLAGS_collective_comm_dtype)."""
    cd = normalize_comm_dtype(comm_dtype)
    ranks = axis_size(axis)
    if cd is None or ranks == 1:
        if record:
            record_collective("psum", x.dtype, x.size * x.dtype.itemsize,
                              ranks, site="quantized_allreduce")
        out = lax.psum(x, axis)
        return out / ranks if mean else out
    ccfg = CommConfig(comm_dtype=cd, quant_chunk=quant_chunk)
    orig_dtype, orig_shape, n = x.dtype, x.shape, x.size
    flat = x.astype(jnp.float32).reshape(-1)
    flat, _ = _pad_to(flat, ranks * quant_chunk)
    shard, _ = reduce_scatter_flat(flat, axis, ccfg, record=record)
    if mean:
        shard = shard / ranks
    # requantize the reduced shard for the gather leg (fresh scales: the
    # sum's range grew by up to ranks x)
    shard, _ = _pad_to(shard, quant_chunk)
    payload, scales = quantize_chunked(shard, cd, quant_chunk)
    full_q = all_gather_flat(payload, axis, record=record)
    if scales is not None:
        full_s = all_gather_flat(scales, axis, record=record)
    else:
        full_s = None
    full = dequantize_chunked(full_q, full_s, cd, quant_chunk)
    return full[:n].reshape(orig_shape).astype(orig_dtype)


def quantized_reduce_scatter_op(x, axis, comm_dtype, quant_chunk: int = 256,
                                record: bool = True):
    """c_reducescatter semantics ([ranks*k, ...] -> [k, ...] reduced shard)
    with a quantized wire payload and f32 accumulation."""
    cd = normalize_comm_dtype(comm_dtype)
    ranks = axis_size(axis)
    if cd is None or ranks == 1:
        if record:
            record_collective("psum_scatter", x.dtype,
                              x.size * x.dtype.itemsize, ranks,
                              site="quantized_reduce_scatter")
        return lax.psum_scatter(x, axis, scatter_dimension=0, tiled=True)
    orig_dtype = x.dtype
    shard_shape = (x.shape[0] // ranks,) + tuple(x.shape[1:])
    row = int(np.prod(shard_shape)) if shard_shape else 1
    # chunk-align every rank's row so shard boundaries stay chunk boundaries
    row_pad = (-row) % quant_chunk
    flat = x.astype(jnp.float32).reshape(ranks, row)
    if row_pad:
        flat = jnp.concatenate(
            [flat, jnp.zeros((ranks, row_pad), jnp.float32)], axis=1)
    ccfg = CommConfig(comm_dtype=cd, quant_chunk=quant_chunk)
    shard, _ = reduce_scatter_flat(flat.reshape(-1), axis, ccfg,
                                   record=record)
    return shard[:row].reshape(shard_shape).astype(orig_dtype)


# ---------------------------------------------------------------------------
# Overlap measurement (profiler capture -> achieved comm/compute overlap)
# ---------------------------------------------------------------------------

# HLO opcode spellings, plus the jax primitive names the installed jax gives
# the instructions themselves (`%psum.7 = ... all-reduce(...)`: traces carry
# the instruction name, on the CPU client and in the compiled TPU HLO alike)
_COLLECTIVE_HLO_MARKERS = (
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute", "all_reduce", "all_gather", "reduce_scatter",
    "all_to_all", "collective_permute", "psum", "ppermute",
)


def _merge_intervals(iv: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    iv = sorted(iv)
    out: List[Tuple[float, float]] = []
    for s, e in iv:
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def _intersect_total(a: List[Tuple[float, float]],
                     b: List[Tuple[float, float]]) -> float:
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        s = max(a[i][0], b[j][0])
        e = min(a[i][1], b[j][1])
        if e > s:
            total += e - s
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def measure_overlap_fraction(trace_dir: str) -> Optional[Dict[str, float]]:
    """Read a profiler xplane capture and measure how much collective span
    time overlaps compute span time on the device execution lines.

    Returns {overlap_fraction, collective_ms, exposed_ms, compute_ms,
    source} or None when no capture / no collective events are present.
    ``source`` is "device_plane" (real accelerator timeline) or
    "cpu_thread_emulation" (host-thread lines: the virtual devices share
    one pool, so the fraction measures emulation concurrency, not ICI
    overlap — COMM_BENCH labels it so).
    """
    from ..utils.device_trace import _latest_xplane, _line_role, \
        profile_data_cls

    path = _latest_xplane(trace_dir)
    if path is None:
        return None
    pd = profile_data_cls().from_file(path)
    coll: List[Tuple[float, float]] = []
    comp: List[Tuple[float, float]] = []
    saw_device_plane = False
    for plane in pd.planes:
        device_plane = plane.name.startswith("/device:")
        for line in plane.lines:
            # device planes, the CPU runtime line, and the per-thread
            # Eigen compute-pool lines (where the CPU client's hlo events
            # actually land — intervals across threads union correctly)
            lname_str = str(line.name)
            if not (device_plane or "CpuClient" in lname_str
                    or "XLAEigen" in lname_str):
                continue
            if device_plane and _line_role(
                    str(line.name),
                    (str(ev.name) for ev in line.events)) in (
                        "steps", "modules"):
                continue
            for ev in line.events:
                try:
                    stats = dict(ev.stats)
                except Exception:
                    stats = {}
                name = str(stats.get("hlo_op") or ev.name)
                dur = float(getattr(ev, "duration_ns", 0.0) or 0.0)
                if dur <= 0:
                    continue
                start = float(getattr(ev, "start_ns", 0.0) or 0.0)
                lname = name.lower()
                if any(m in lname for m in _COLLECTIVE_HLO_MARKERS):
                    coll.append((start, start + dur))
                    saw_device_plane = saw_device_plane or device_plane
                else:
                    comp.append((start, start + dur))
    if not coll:
        return None
    coll_m = _merge_intervals(coll)
    comp_m = _merge_intervals(comp)
    coll_total = sum(e - s for s, e in coll_m)
    overlapped = _intersect_total(coll_m, comp_m)
    return {
        "overlap_fraction": overlapped / coll_total if coll_total else 0.0,
        "collective_ms": coll_total / 1e6,
        "exposed_ms": (coll_total - overlapped) / 1e6,
        "compute_ms": sum(e - s for s, e in comp_m) / 1e6,
        # off-TPU the 8 "devices" are host threads sharing one pool, so
        # cross-thread overlap is emulation concurrency, not ICI overlap —
        # labeled so COMM_BENCH readers don't mistake it for the real thing
        "source": ("device_plane" if saw_device_plane
                   else "cpu_thread_emulation"),
    }
