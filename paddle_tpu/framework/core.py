"""Core type system for the TPU-native framework.

Capability parity with the reference's ``paddle/fluid/framework/framework.proto``
(VarType enum at framework.proto:104-137) and ``platform/place.h`` — but instead
of an enum dispatched to per-device CUDA kernels, dtypes map straight to JAX
dtypes and Places map to JAX device sets.
"""
from __future__ import annotations

import dataclasses
import enum
import os as _os

import jax
import jax.numpy as jnp
import numpy as np


class VarType(enum.IntEnum):
    """Variable kinds — mirrors framework.proto:104-137 VarType.Type."""

    # value types (tensor dtypes)
    BOOL = 0
    INT16 = 1
    INT32 = 2
    INT64 = 3
    FP16 = 4
    FP32 = 5
    FP64 = 6
    UINT8 = 20
    INT8 = 21
    BF16 = 22
    # container / structural types
    LOD_TENSOR = 7
    SELECTED_ROWS = 8
    FEED_MINIBATCH = 9
    FETCH_LIST = 10
    STEP_SCOPES = 11
    LOD_RANK_TABLE = 12
    LOD_TENSOR_ARRAY = 13
    PLACE_LIST = 14
    READER = 15
    RAW = 17
    TUPLE = 18


_DTYPE_TO_VARTYPE = {
    "bool": VarType.BOOL,
    "int16": VarType.INT16,
    "int32": VarType.INT32,
    "int64": VarType.INT64,
    "float16": VarType.FP16,
    "float32": VarType.FP32,
    "float64": VarType.FP64,
    "uint8": VarType.UINT8,
    "int8": VarType.INT8,
    "bfloat16": VarType.BF16,
}
_VARTYPE_TO_DTYPE = {v: k for k, v in _DTYPE_TO_VARTYPE.items()}


def convert_dtype(dtype) -> str:
    """Normalize any dtype spec (str / np / jnp / VarType) to a canonical string."""
    if dtype is None:
        return "float32"
    if isinstance(dtype, VarType):
        return _VARTYPE_TO_DTYPE[dtype]
    if isinstance(dtype, int):   # raw proto enum value (framework.proto:91)
        return _VARTYPE_TO_DTYPE[VarType(dtype)]
    if isinstance(dtype, str):
        if dtype in _DTYPE_TO_VARTYPE:
            return dtype
        return np.dtype(dtype).name
    if dtype in (jnp.bfloat16,):
        return "bfloat16"
    name = np.dtype(dtype).name if not hasattr(dtype, "name") else dtype.name
    return name


_X64_NARROW = {"int64": "int32", "uint64": "uint32", "float64": "float32"}


def dtype_to_jax(dtype) -> jnp.dtype:
    """Compute dtype for a declared var dtype. Serialization keeps the
    declared width (VarType in the protobuf desc); compute canonicalizes
    64-bit types to what jax actually runs without x64 — silently, instead
    of per-op truncation warnings on every int64 astype."""
    s = convert_dtype(dtype)
    if s == "bfloat16":
        return jnp.bfloat16
    import jax

    if not jax.config.jax_enable_x64 and s in _X64_NARROW:
        s = _X64_NARROW[s]
    return jnp.dtype(s)


def int_index_dtype() -> jnp.dtype:
    """The int64-declared index dtype as jax will actually carry it."""
    return dtype_to_jax("int64")


def dtype_is_floating(dtype) -> bool:
    return convert_dtype(dtype) in ("float16", "float32", "float64", "bfloat16")


# ---------------------------------------------------------------------------
# Places — reference platform/place.h. On the TPU build a Place names a JAX
# backend; `XLAPlace` is the canonical accelerator place.
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Place:
    backend: str = "default"
    device_id: int = 0

    def jax_device(self):
        if self.backend == "default":
            return jax.devices()[self.device_id]
        return jax.devices(self.backend)[self.device_id]

    def __repr__(self):  # pragma: no cover
        return f"{type(self).__name__}({self.device_id})"


class CPUPlace(Place):
    def __init__(self, device_id: int = 0):
        super().__init__(backend="cpu", device_id=device_id)


class XLAPlace(Place):
    """The accelerator place: whatever JAX's default backend exposes (TPU)."""

    def __init__(self, device_id: int = 0):
        super().__init__(backend="default", device_id=device_id)


# Alias so reference scripts that say CUDAPlace keep working on TPU.
TPUPlace = XLAPlace


class BackwardStrategy:
    """Dygraph backward knobs — reference pybind/imperative.cc:491-519
    (``core.BackwardStrategy`` with the ``sort_sum_gradient`` property).

    ``sort_sum_gradient=True`` asks the reference's BasicEngine to sum a
    var's repeated gradients in a deterministic (sorted) order.  The tape
    engine here replays in reverse record order, which is already
    deterministic by construction, so the flag is accepted for API parity
    and does not change behavior."""

    def __init__(self):
        self.sort_sum_gradient = False


def is_compiled_with_tpu() -> bool:
    return any(d.platform not in ("cpu",) for d in jax.devices())


# ---------------------------------------------------------------------------
# Global flags registry — reference platform/flags.cc (gflags). Most reference
# flags control allocator/cudnn behavior that XLA owns; we keep the registry so
# `fluid.set_flags`/`get_flags` style code works and a few flags are live.
# ---------------------------------------------------------------------------

_GLOBAL_FLAGS = {
    "FLAGS_check_nan_inf": False,
    "FLAGS_check_nan_inf_level": "fetch",  # "fetch" | "op" (eager per-op scan)
    "FLAGS_benchmark": False,
    # steady-state dispatch record in Executor.run (framework/executor.py):
    # after the first step a (program, feed-sig, fetch) record skips feed
    # re-normalization and cache-key rebuild. False = always take the
    # full (pre-record) path; used for A/B in tools/dispatch_bench.py.
    "FLAGS_dispatch_fast_path": True,
    # opt-in flat-buffer fused optimizer sweep (optimizer.py
    # apply_gradients): one fused update op per (dtype, hparam-signature)
    # parameter group with moments in a flat megabuffer layout, instead of
    # one update op per parameter. Equivalent to passing fuse=True to the
    # optimizer constructor; see docs/memory_levers.md.
    "FLAGS_fuse_optimizer": False,
    # lower each fused flat-buffer optimizer group through ONE Pallas
    # megakernel launch (ops/pallas_kernels._opt_megakernel) instead of
    # the XLA elementwise-fusion stream the attribution ranks as the
    # optimizer residue tail. None = auto (on on TPU, off elsewhere —
    # interpret mode would only slow the CPU lane); True/False forces.
    # Only reached when the flat sweep itself is on (fuse=True /
    # FLAGS_fuse_optimizer). See docs/kernels.md.
    "FLAGS_fuse_optimizer_pallas": None,
    # persistent XLA compilation cache directory. Yields to the
    # JAX_COMPILATION_CACHE_DIR environment variable; '' = the fixed
    # in-checkout default (see ensure_compile_cache below).
    "FLAGS_compile_cache_dir": _os.environ.get("FLAGS_compile_cache_dir", ""),
    # program-report JSONL sink ('' = disabled): every compiled executable
    # writes one cost/memory introspection record under this directory
    # (observability/program_report.py; see docs/observability.md)
    "FLAGS_program_report_dir": _os.environ.get(
        "FLAGS_program_report_dir", ""),
    # quantized wire payload for fluid SUM-collectives ('' = off,
    # "bf16" | "int8"): c_allreduce_sum/avg and c_reducescatter reroute
    # through the chunk-scaled quantized exchange (f32 accumulation) in
    # paddle_tpu/parallel/comm_opt.py — the GradientMergeOptimizer k-step
    # tail reduction and transpiled dp gradient sync included. See
    # docs/comm_opt.md.
    "FLAGS_collective_comm_dtype": _os.environ.get(
        "FLAGS_collective_comm_dtype", ""),
    # Program IR static verifier (paddle_tpu/analysis/, see
    # docs/static_analysis.md): when on, Executor.run lints each program
    # once per version BEFORE compiling it — error-severity findings
    # raise, warnings log. Never touches the dispatch fast path.
    "FLAGS_check_program": bool(int(_os.environ.get(
        "FLAGS_check_program", "0") or 0)),
    "FLAGS_eager_delete_tensor_gb": 0.0,
    "FLAGS_allocator_strategy": "xla_managed",
    "FLAGS_paddle_num_threads": 1,
    "FLAGS_use_system_allocator": False,
    "FLAGS_executor_log_deps": False,
    # roi_align adaptive sampling: False = bounded uniform grid (fast
    # default), True = exact reference ceil(roi/pooled) per-ROI density
    # via a weighted static super-grid (ops/detection.py roi_align)
    "FLAGS_roi_align_exact": False,
    # multiplier on the exact-mode grid bound for ROIs larger than the
    # feature map (unclipped proposals); 1 = image-derived bound
    "FLAGS_roi_align_exact_scale": 1,
}


def set_flags(flags: dict):
    for k, v in flags.items():
        _GLOBAL_FLAGS[k] = v
    if "FLAGS_compile_cache_dir" in flags:
        ensure_compile_cache()


def get_flags(flags):
    if isinstance(flags, str):
        flags = [flags]
    return {k: _GLOBAL_FLAGS.get(k) for k in flags}


def get_flag(name, default=None):
    return _GLOBAL_FLAGS.get(name, default)


def flags_snapshot() -> dict:
    """Copy of the full flag state (anomaly forensics dumps record it)."""
    return dict(_GLOBAL_FLAGS)


# ---------------------------------------------------------------------------
# Persistent XLA compilation cache. Every process that compiles (Executor,
# make_train_step, DecodeEngine.warmup, the bench entry points) calls
# ensure_compile_cache(); `import paddle_tpu` does not. Where the cache
# lives, first match wins:
#   1. JAX_COMPILATION_CACHE_DIR — jax reads it itself; no directory is set
#      in code, so whoever launches the process places the cache;
#   2. FLAGS_compile_cache_dir (set_flags / env);
#   3. <checkout>/.jax_cache — a fixed path: the path is part of the cache
#      key, so a temp name, pid or timestamp would never hit.
# Hit/miss counters come from jax.monitoring events so the Executor can log
# and RecordEvent whether a compile was served from disk.
# ---------------------------------------------------------------------------

_DEFAULT_COMPILE_CACHE_DIR = _os.path.join(
    _os.path.dirname(_os.path.dirname(_os.path.dirname(
        _os.path.abspath(__file__)))), ".jax_cache")

_compile_cache_state = {"dir": None, "hits": 0, "misses": 0, "listener": False}


def _compile_cache_listener(event, **kwargs):
    if event == "/jax/compilation_cache/cache_hits":
        _compile_cache_state["hits"] += 1
    elif event == "/jax/compilation_cache/cache_misses":
        _compile_cache_state["misses"] += 1


def compile_cache_dir() -> str:
    """The directory the persistent compile cache uses (see above)."""
    return (_os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or _GLOBAL_FLAGS.get("FLAGS_compile_cache_dir")
            or _DEFAULT_COMPILE_CACHE_DIR)


def ensure_compile_cache() -> str:
    """Turn jax's persistent compilation cache on at compile_cache_dir().

    Idempotent; returns the directory. The size thresholds are dropped to
    zero so even small programs (which this framework compiles per
    (program, feed-sig, fetch) key) are cached across processes.
    """
    d = compile_cache_dir()
    if _compile_cache_state["dir"] != d:
        if not _compile_cache_state["listener"]:
            from jax import monitoring

            monitoring.register_event_listener(_compile_cache_listener)
            _compile_cache_state["listener"] = True
        if not _os.environ.get("JAX_COMPILATION_CACHE_DIR"):
            jax.config.update("jax_compilation_cache_dir", d)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        _compile_cache_state["dir"] = d
    return d


def compile_cache_counters():
    """(hits, misses) served by the persistent cache in this process."""
    return _compile_cache_state["hits"], _compile_cache_state["misses"]
