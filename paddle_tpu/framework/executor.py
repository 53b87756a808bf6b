"""Executor: compiles whole Blocks to single XLA computations.

The reference Executor (paddle/fluid/framework/executor.cc:432-494) is a per-op
interpreter: the hot loop calls op->Run per OpDesc with per-op kernel dispatch.
Here the SAME user API (``Executor.run(program, feed, fetch_list)`` — python
surface parity with fluid/executor.py:890) instead lowers the whole Block to one
jit-compiled JAX function per (program-fingerprint, feed-signature): forward,
backward and optimizer update fuse into one XLA module, parameters are donated
(buffer reuse ≙ the reference's inplace/memory passes for free).
"""
from __future__ import annotations

import logging
import os
import time
import weakref
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .core import (Place, XLAPlace, compile_cache_counters, dtype_to_jax,
                   ensure_compile_cache, get_flag)
from .program import Program, Variable, default_main_program
from .registry import LowerCtx, run_lowering, get_op_spec, has_op

logger = logging.getLogger("paddle_tpu.executor")

# ---------------------------------------------------------------------------
# Always-live metrics (observability/metrics.py). Children are resolved ONCE
# at import so the steady-state cost is a float add — unlike RecordEvents,
# these exist whether or not a profiling session is active (the "profiling
# started after the first step" dropped-compile-events satellite).
# ---------------------------------------------------------------------------
from ..observability import flight as _flight
from ..observability import goodput as _goodput
from ..observability import metrics as _obs_metrics
from ..observability import spans as _spans

_OBS = _obs_metrics.default_registry()
# the wall-clock ledger (docs/observability.md "Goodput & tracing"): run/
# train paths bracket themselves in exclusive-time category timers so the
# goodput report can attribute every second of a run
_gp = _goodput.ledger()
_m_dispatch = _OBS.counter(
    "paddle_executor_dispatch_total",
    "Executor.run dispatches by path (fast = dispatch-record hit)",
    ("path",))
_m_dispatch_fast = _m_dispatch.labels("fast")
_m_dispatch_slow = _m_dispatch.labels("slow")
_m_compile = _OBS.counter(
    "paddle_executor_compile_total",
    "Compiled (program, feed-sig, fetch) blocks built")
_m_compile_ms = _OBS.histogram(
    "paddle_executor_compile_ms",
    "Block build+trace wall time (ms); the XLA compile itself is lazy")
_m_compile_cache = _OBS.counter(
    "paddle_compile_cache_total",
    "Persistent XLA compile cache outcomes", ("verdict",))
_m_run_ms = _OBS.histogram(
    "paddle_executor_run_ms",
    "Executor.run host wall time per call (async dispatch, ms)")
_m_device_wait_ms = _OBS.histogram(
    "paddle_executor_device_wait_ms",
    "Blocking device->host fetch materialization time per run (ms)")
_m_fetch_stall = _OBS.counter(
    "paddle_fetch_sync_stall_ms_total",
    "train_from_dataset fetch-sync stall time at print/final boundaries (ms)")

# streaming datasets ride their batch-aligned resume token on each feed
# under this key (dataset.streaming.StreamingDataset.STATE_KEY); the
# dataset loop pops it before dispatch and serializes it into the elastic
# checkpoint's data_state
_STREAM_STATE_KEY = "__stream_state__"

_prof_mod = None


def _prof():
    """The profiler module, imported lazily once (avoids the package-init
    cycle) and cached so the steady-state path pays a global read, not an
    import-machinery lookup."""
    global _prof_mod
    if _prof_mod is None:
        from .. import profiler

        _prof_mod = profiler
    return _prof_mod


_health_mod = None


def _health():
    """The in-run health module (parallel/health.py), lazily cached like
    :func:`_prof`.  ``progress()`` stamps from the dispatch paths feed the
    hang watchdog — a single global read + None check until a watchdog is
    installed, so the fast path stays inside the dispatch-overhead gate."""
    global _health_mod
    if _health_mod is None:
        from ..parallel import health

        _health_mod = health
    return _health_mod


class Scope:
    """Host-side name -> device array map — parity with framework/scope.h:46.

    The reference Scope is a hierarchical C++ name->Variable table; here
    variables are jax.Arrays living in HBM, and the hierarchy collapses to
    parent chaining for sub-scopes (used by control flow at lowering time).
    """

    def __init__(self, parent: Optional["Scope"] = None):
        self._vars: Dict[str, Any] = {}
        self.parent = parent

    def var(self, name: str):
        return self._vars.setdefault(name, None)

    def set_var(self, name: str, value):
        self._vars[name] = value

    def find_var(self, name: str):
        s: Optional[Scope] = self
        while s is not None:
            if name in s._vars:
                return s._vars[name]
            s = s.parent
        return None

    def has_var(self, name: str) -> bool:
        s: Optional[Scope] = self
        while s is not None:
            if name in s._vars:
                return True
            s = s.parent
        return False

    def erase(self, names: Sequence[str]):
        for n in names:
            self._vars.pop(n, None)

    def local_var_names(self) -> List[str]:
        return list(self._vars)

    def new_scope(self) -> "Scope":
        return Scope(parent=self)


_scope_stack: List[Scope] = [Scope()]


def global_scope() -> Scope:
    return _scope_stack[-1]


class scope_guard:
    """fluid.executor.scope_guard parity: swap the ambient global scope so
    io/save/load and Executor.run default into ``scope``."""

    def __init__(self, scope: Scope):
        self._scope = scope

    def __enter__(self):
        _scope_stack.append(self._scope)
        return self._scope

    def __exit__(self, *exc):
        _scope_stack.pop()


import dataclasses


@dataclasses.dataclass(frozen=True)
class MeshPlan:
    """How a program maps onto a device mesh."""

    mode: str = "single"  # single | gspmd | shard_map
    axes: Tuple[Tuple[str, int], ...] = ()
    data_axis: Optional[str] = None
    # ring_id -> axis name (collective ops lower over these)
    ring_axes: Any = dataclasses.field(default_factory=dict)

    def signature(self):
        return (self.mode, self.axes, self.data_axis,
                tuple(sorted(self.ring_axes.items())) if self.ring_axes else ())


# weakref-keyed: entries die with their Program instead of pinning up to
# 4096 dead programs/executables; the compiled object is held by weakref and
# validated by identity on lookup so id() reuse can't alias entries
_plan_cache: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()


def plan_for_program(program: Program, compiled=None) -> Optional[MeshPlan]:
    """Derive the mesh plan from CompiledProgram state / program annotations.
    Memoized per (program, compiled identity, version) — Executor.run calls
    this once per step."""
    version = program._version_token()
    sub = _plan_cache.get(program)
    if sub is not None:
        hit = sub.get(version)
        if hit is not None:
            cref, cached_plan = hit
            if cref is None:
                if compiled is None:
                    return cached_plan
            else:
                # a dead weakref must NOT match compiled=None — the cached
                # plan belonged to a (now GC'd) CompiledProgram, while a plain
                # run must re-derive from program annotations
                target = cref()
                if target is not None and target is compiled:
                    return cached_plan

    plan: Optional[MeshPlan] = None
    ann = program._annotations
    if compiled is not None and compiled._is_data_parallel:
        ring_axes = dict(compiled._mesh_axes)
        has_collectives = any(
            op.type.startswith("c_")
            or op.type in ("allreduce", "broadcast", "dgc_momentum",
                           "sync_batch_norm", "sync_batch_norm_grad")
            for op in program.global_block().ops
        )
        mode = "shard_map" if has_collectives else "gspmd"
        dp_size = len(compiled._places) if compiled._places else -1
        plan = MeshPlan(mode=mode, axes=(("dp", dp_size),), data_axis="dp",
                        ring_axes=ring_axes or {0: "dp"})
    elif "mesh" in ann:
        m = ann["mesh"]
        plan = MeshPlan(
            mode=m.get("mode", "gspmd"),
            axes=tuple(tuple(a) for a in m.get("axes", ())),
            data_axis=m.get("data_axis"),
            ring_axes=dict(m.get("ring_axes", {})),
        )
    sub = _plan_cache.setdefault(program, {})
    if len(sub) > 64:  # bound per-program version history
        sub.clear()
    sub[version] = (weakref.ref(compiled) if compiled is not None else None,
                    plan)
    return plan


class _CompiledBlock:
    """One jit-compiled executable for (program, feed signature, fetch list).

    Three execution modes replace the reference's executor zoo
    (Executor / ParallelExecutor+SSA graph / NCCL rings):
      - single: one device, plain jit.
      - gspmd:  a jax.sharding.Mesh + NamedShardings on params/feeds; XLA's
        partitioner inserts gradient all-reduces etc. (subsumes
        ParallelExecutor's AllReduceOpHandle graph, details/build_strategy).
      - shard_map: per-rank program semantics for Fleet-transpiled programs
        that carry explicit c_allreduce_*/c_broadcast ops (ring_id -> mesh
        axis); matches the reference's collective-op execution model exactly.
    """

    def __init__(self, program: Program, feed_sig, fetch_names, param_names,
                 written_names, mesh_plan=None, donate: bool = True,
                 scope: Optional["Scope"] = None, report_name: str = ""):
        self.program = program
        self.feed_names = [n for n, _, _ in feed_sig]
        self.fetch_names = list(fetch_names)
        self.param_names = list(param_names)
        self.written_names = list(written_names)
        self.mesh_plan = mesh_plan
        self.report_name = report_name or (
            f"{fetch_names[0] if fetch_names else 'main'}"
            f"#{len(program.global_block().ops)}ops")
        # hang-watchdog progress site (docs/health.md): collective-carrying
        # shard_map blocks get their own label so paddle_hangs_total{site}
        # points at the comm path when a mismatched collective wedges
        self.progress_site = ("collective/shard_map"
                              if mesh_plan is not None
                              and mesh_plan.mode == "shard_map"
                              else "executor.run")
        # AOT compile state: the first call lowers + compiles explicitly and
        # keeps BOTH handles, so the executable that runs every step is the
        # same object that serves .as_text() for the profiler and
        # cost/memory analysis for the program report — no re-compile for
        # introspection (the old _hlo_text_getter paid a fresh
        # lower().compile() per block just for HLO text).
        self._executable = None
        self._aot_failed = False
        self.compile_ms: Optional[float] = None
        self.cache_verdict: Optional[str] = None
        self.report: Optional[Dict[str, Any]] = None
        self._in_summary = None
        mesh_axes = (mesh_plan.ring_axes if mesh_plan else {})
        block = program.global_block()
        written = set(written_names)
        # steady-state split, computed once instead of per __call__
        self._mutable_names = [n for n in self.param_names if n in written]
        self._const_names = [n for n in self.param_names if n not in written]
        # fetches that alias donated state: a fetch of a written persistable
        # may share its buffer with the new_state output, and the NEXT step
        # donates that scope array — an async (return_numpy=False) caller
        # would then hold a deleted buffer. These indices get a defensive
        # device-side copy after each call.
        self._fetch_copy_idx = [i for i, n in enumerate(self.fetch_names)
                                if n in written]
        # set during the first trace: did any lowering consume an rng key?
        self._rng_consumed = False

        def fn(mutable_params: Dict[str, Any], const_params: Dict[str, Any],
               feeds: Dict[str, Any], rng_key):
            env: Dict[str, Any] = {}
            env.update(const_params)
            env.update(mutable_params)
            env.update(feeds)
            rng_uses_before = LowerCtx.rng_use_count
            ctx = LowerCtx(program, block, env, rng_key=rng_key,
                           mesh_axes=mesh_axes)
            for op in block.ops:
                run_lowering(ctx, op)
            if LowerCtx.rng_use_count != rng_uses_before:
                self._rng_consumed = True
            fetches = [env[n] for n in self.fetch_names]
            # a declared persistable output may legitimately stay unbound
            # (bootstrap no-op lowerings, @EMPTY@ grads) — tolerate it
            new_state = {n: env[n] for n in self.written_names if n in env}
            return fetches, new_state

        donate_args = (0,) if donate else ()

        if mesh_plan is None or mesh_plan.mode == "single":
            self._jitted = jax.jit(fn, donate_argnums=donate_args)
            self.mesh = None
            return

        from ..parallel.mesh import build_mesh, named_sharding

        mesh = build_mesh(mesh_plan.axes)
        self.mesh = mesh
        n_dev = int(np.prod(mesh.devices.shape))
        data_axis = mesh_plan.data_axis
        block_vars = block.vars

        def param_spec(name):
            var = block_vars.get(name)
            return getattr(var, "sharding", None) if var is not None else None

        def feed_dims(shape):
            """Shard the batch (dim 0) only when it divides the mesh evenly;
            small feeds (lr tensors, flags) stay replicated."""
            if shape and shape[0] % n_dev == 0 and shape[0] > 0:
                return (data_axis,) + (None,) * (len(shape) - 1)
            return None

        if mesh_plan.mode == "gspmd":
            mutable_sh = {n: named_sharding(mesh, param_spec(n))
                          for n in self.param_names if n in written}
            const_sh = {n: named_sharding(mesh, param_spec(n))
                        for n in self.param_names if n not in written}
            # annotated feeds (sharding propagation, paddle_tpu/sharding/)
            # use their propagated spec; unannotated ones keep the
            # batch-dim heuristic
            feed_sh = {n: named_sharding(
                mesh, param_spec(n) if param_spec(n) is not None
                else feed_dims(shape))
                for n, shape, _ in feed_sig}
            rng_sh = named_sharding(mesh, None)
            self._jitted = jax.jit(
                fn,
                in_shardings=(mutable_sh, const_sh, feed_sh, rng_sh),
                donate_argnums=donate_args,
            )
            return

        # shard_map mode: per-rank execution, explicit collectives in program.
        # Fetches are concatenated along dim 0 across ranks — parity with
        # ParallelExecutor's fetch merge (a fetched scalar loss comes back as
        # one value per device, exactly like the reference).
        from jax.sharding import PartitionSpec as P

        from ..parallel.mesh import (aval_of, feed_aval, jit_shard_map,
                                     probe_produced_state)

        # discover which written names are actually produced (abstract-eval
        # probe, so the shard_map out_specs pytree is known before tracing)
        mutable_avals = {n: aval_of(scope.find_var(n)) for n in self.param_names
                         if n in written and scope is not None and scope.has_var(n)}
        const_avals = {n: aval_of(scope.find_var(n)) for n in self.param_names
                       if n not in written and scope is not None and scope.has_var(n)}
        feed_avals = {n: feed_aval(shape, dt) for n, shape, dt in feed_sig}
        produced = probe_produced_state(fn, mutable_avals, const_avals,
                                        feed_avals, self.written_names)
        self._produced_state = produced

        def per_rank(mutable_params, const_params, feeds, rng_key):
            fetches, new_state = fn(mutable_params, const_params, feeds, rng_key)
            fetches = [jnp.atleast_1d(f) for f in fetches]
            new_state = {n: new_state[n] for n in produced}
            return fetches, new_state

        mutable_specs = {n: P() for n in self.param_names if n in written}
        const_specs = {n: P() for n in self.param_names if n not in written}
        feed_specs = {
            n: P(*fd) if (fd := feed_dims(shape)) else P()
            for n, shape, _ in feed_sig
        }
        fetch_specs = [P(data_axis) for _ in fetch_names]
        state_specs = {n: P() for n in produced}

        self._jitted = jit_shard_map(
            per_rank, mesh,
            in_specs=(mutable_specs, const_specs, feed_specs, P()),
            out_specs=(fetch_specs, state_specs),
            donate_argnums=donate_args)

    def _hlo_text_getter(self, *call_args):
        """Deferred optimized-HLO-text fetch for profiler attribution.
        Abstracts the args immediately (shape/dtype only) so the getter
        stays valid after donation invalidates the live buffers."""
        import jax

        def absify(x):
            v = getattr(x, "value", x)
            return jax.ShapeDtypeStruct(jnp.shape(v), jnp.result_type(v))

        avals = jax.tree.map(absify, call_args)
        jitted = self._jitted

        def getter():
            # the steady-state executable IS the AOT-compiled object, so
            # HLO text is a free read off it; the fresh lower().compile()
            # survives only as the fallback for blocks where AOT dispatch
            # was unavailable (self._aot_failed).
            if self._executable is not None:
                return self._executable.as_text()
            return jitted.lower(*avals).compile().as_text()

        return getter

    # -- explicit AOT compile: one compile serves dispatch + introspection --
    def _aot_compile(self, mutable, const, feeds, rng_key) -> None:
        """Lower + compile the block explicitly and keep the executable.
        A compile the backend refuses raises here: compiling the same
        program a second time under implicit jit would only hide it."""
        h0, m0 = compile_cache_counters()
        t0 = time.perf_counter_ns()
        # a first-call XLA compile can legitimately run for minutes:
        # pause the hang-watchdog clock for its duration, and charge
        # the wall time to the ledger's compile category
        with _health().suspend(), _gp.timer("compile"), \
                _spans.span(f"compile/{self.report_name}"):
            lowered = self._jitted.lower(mutable, const, feeds, rng_key)
            executable = lowered.compile()
        self.compile_ms = (time.perf_counter_ns() - t0) / 1e6
        h1, m1 = compile_cache_counters()
        self.cache_verdict = ("hit" if h1 > h0
                              else "cold" if m1 > m0 else None)
        self._executable = executable
        # input avals summarized BEFORE the first call: donation will
        # invalidate the mutable buffers
        from ..observability import program_report as _prep

        self._in_summary = _prep._aval_rows((mutable, const, feeds))

    def _publish_report(self, fetches, new_state) -> None:
        """Emit the per-executable program report (once, after the first
        successful call so output avals are real)."""
        from ..observability import program_report as _prep

        self.report = _prep.capture(
            self.report_name,
            compiled=self._executable,
            compile_ms=self.compile_ms,
            cache=self.cache_verdict,
            donated=list(self._mutable_names),
            inputs=self._in_summary,
            outputs=(fetches, new_state),
            extra={
                "mode": self.mesh_plan.mode if self.mesh_plan else "single",
                "nops": len(self.program.global_block().ops),
                "feeds": list(self.feed_names),
                "fetches": list(self.fetch_names),
            })
        self._in_summary = None

    def __call__(self, scope: Scope, feed: Dict[str, Any], rng_key):
        feeds = {n: feed[n] for n in self.feed_names}
        return self.fast_call(scope, feeds, rng_key)

    def fast_call(self, scope: Scope, feeds: Dict[str, Any], rng_key):
        """Steady-state entry: ``feeds`` must already contain exactly
        ``feed_names`` (the dispatch record guarantees it)."""
        _health().progress(self.progress_site)
        find = scope.find_var
        mutable = {}
        const = {}
        for n in self._mutable_names:  # persistables read from scope
            v = find(n)
            if v is None:
                raise RuntimeError(
                    f"persistable var {n!r} is not initialized in scope — "
                    "run the startup program first"
                )
            mutable[n] = v  # donated: updated in place on device
        for n in self._const_names:
            v = find(n)
            if v is None:
                raise RuntimeError(
                    f"persistable var {n!r} is not initialized in scope — "
                    "run the startup program first"
                )
            const[n] = v
        prof = _prof()
        if prof.is_active():
            # owned token, not id(self): a GC'd block's reused address
            # would silently suppress registration of a new block
            key = self.__dict__.setdefault("_profile_key", object())
            if not prof.has_compiled(key):
                # capture avals BEFORE the call: mutable buffers are donated
                prof.register_compiled(
                    key, self._hlo_text_getter(mutable, const, feeds,
                                               rng_key))
        first_aot = False
        if self._executable is None and not self._aot_failed:
            self._aot_compile(mutable, const, feeds, rng_key)
            first_aot = self._executable is not None
        if self._executable is not None:
            try:
                fetches, new_state = self._executable(mutable, const, feeds,
                                                      rng_key)
            except TypeError as e:
                # signature drift the AOT call can't absorb (raised during
                # argument processing, before execution — no buffer was
                # donated yet); fall back to implicit jit for good
                logger.info("AOT dispatch mismatch for %s (%s); reverting "
                            "to jit dispatch", self.report_name, e)
                self._executable = None
                self._aot_failed = True
                first_aot = False
                fetches, new_state = self._jitted(mutable, const, feeds,
                                                  rng_key)
        else:
            fetches, new_state = self._jitted(mutable, const, feeds, rng_key)
        if first_aot:
            self._publish_report(fetches, new_state)
        for n, v in new_state.items():
            scope.set_var(n, v)
        for i in self._fetch_copy_idx:
            # detach written-persistable fetches from the donated state
            # buffer (async dispatch; no host sync)
            fetches[i] = jnp.copy(fetches[i])
        return fetches


# ---------------------------------------------------------------------------
# Host ops: ops that run Python-side between jitted device segments (the
# reference's RPC/PS ops — send/recv/listen_and_serv — execute on the host
# inside its per-op interpreter; here the Executor splits the block at host
# ops and jits the device spans around them).
# ---------------------------------------------------------------------------

_HOST_OPS: Dict[str, Any] = {}


def register_host_op(op_type: str):
    def deco(fn):
        _HOST_OPS[op_type] = fn
        return fn
    return deco


def is_host_op_type(t: str) -> bool:
    return t in _HOST_OPS


_FAST_MISS = object()


class _DispatchRecord:
    """Steady-state dispatch record for one (program, feed-sig, fetch) combo.

    ``Executor.run`` pays a per-step Python tax on the slow path: feed dict
    sort, ``np.asarray`` per feed, cache-key rebuild, host-op scan, mesh-plan
    lookup. After the first step all of that is invariant, so the record
    pins the compiled block plus a prebuilt feed flattener and the run goes
    straight from the user's feed dict to the jitted call. Any mismatch
    (program mutated, feed shape/dtype drift, flags) falls back to the full
    path, which re-derives and replaces the record.
    """

    __slots__ = ("key_obj", "compiled", "dp_flag", "program", "version",
                 "seed", "exe", "feed_checks", "nfeeds", "rng_base",
                 "rng_used")

    def __init__(self, key_obj, compiled, program, exe, feed_sig, raw_dtypes):
        self.key_obj = key_obj
        self.compiled = compiled
        self.dp_flag = (compiled._is_data_parallel
                        if compiled is not None else None)
        self.program = program
        self.version = program._version_token()
        self.seed = program.random_seed
        self.exe = exe
        self.rng_used = exe._rng_consumed
        # rng-free programs reuse one key; rng programs fold the step in,
        # bit-identical to the slow path's fold_in(PRNGKey(seed), step)
        self.rng_base = jax.random.PRNGKey(self.seed or 0)
        checks = []
        for name, shape, dt in feed_sig:
            # accept the normalized dtype and its x64-narrowed compute dtype
            # (a device-prefetched int64 feed arrives as int32)
            accepted = frozenset({dt, str(dtype_to_jax(dt))})
            raw = raw_dtypes.get(name)
            cast = None
            if raw is not None and raw not in accepted:
                cast = jnp.bfloat16 if dt == "bfloat16" else np.dtype(dt)
            checks.append((name, shape, accepted, raw, cast))
        self.feed_checks = checks
        self.nfeeds = len(checks)

    def prepare(self, feed: Dict[str, Any]):
        """Validate + flatten the user's feed dict against the recorded
        signature. Returns the dict to pass to the jitted call, or None when
        the feed doesn't match (caller falls back to the full path)."""
        if len(feed) != self.nfeeds:
            return None
        out = feed
        for name, shape, accepted, raw, cast in self.feed_checks:
            v = feed.get(name)
            if v is None or getattr(v, "shape", None) != shape:
                return None
            dt = str(getattr(v, "dtype", ""))
            if dt in accepted:
                continue
            if dt == raw and cast is not None:
                # same raw dtype as at record build: prebuilt cast (e.g. the
                # user feeds float64 into a float32 var every step)
                if out is feed:
                    out = dict(feed)
                out[name] = np.asarray(v).astype(cast)
            else:
                return None
        return out


class Executor:
    """User-facing executor — API parity with fluid/executor.py:890 Executor.run."""

    def __init__(self, place: Optional[Place] = None):
        self.place = place or XLAPlace(0)
        self._cache: Dict[Tuple, _CompiledBlock] = {}
        self._view_cache: Dict[Tuple, Program] = {}
        self._dispatch_records: Dict[Tuple, _DispatchRecord] = {}
        # per-program compile-signature history: the recompile explainer
        # diffs a fresh build against these siblings to name the cause
        self._compile_history: Dict[int, List[dict]] = {}
        # FLAGS_check_program: program versions already statically verified
        self._checked_programs: set = set()
        self._fast_hits = 0
        self._step = 0

    def close(self):
        self._cache.clear()
        self._dispatch_records.clear()
        self._compile_history.clear()
        self._checked_programs.clear()

    # ------------------------------------------------------------------
    def run(
        self,
        program: Optional[Program] = None,
        feed: Optional[Dict[str, Any]] = None,
        fetch_list: Optional[Sequence] = None,
        feed_var_name: str = "feed",
        fetch_var_name: str = "fetch",
        scope: Optional[Scope] = None,
        return_numpy: bool = True,
        use_program_cache: bool = True,
    ):
        # the whole call is step wall-time; nested timers re-bucket the
        # compile / device-wait shares out of it (exclusive accounting)
        with _gp.timer("productive_step"):
            return self._run_impl(program, feed, fetch_list, feed_var_name,
                                  fetch_var_name, scope, return_numpy,
                                  use_program_cache)

    def _run_impl(
        self,
        program: Optional[Program] = None,
        feed: Optional[Dict[str, Any]] = None,
        fetch_list: Optional[Sequence] = None,
        feed_var_name: str = "feed",
        fetch_var_name: str = "fetch",
        scope: Optional[Scope] = None,
        return_numpy: bool = True,
        use_program_cache: bool = True,
    ):
        from .compiler import CompiledProgram

        fetch_names = [
            v.name if isinstance(v, Variable) else str(v) for v in (fetch_list or [])
        ]

        # ---- steady-state fast path: dispatch record hit ----------------
        if (self._dispatch_records and use_program_cache
                and (feed is None or type(feed) is dict)
                and get_flag("FLAGS_dispatch_fast_path")
                and not get_flag("FLAGS_check_nan_inf")):
            pkey = (id(program) if program is not None
                    else id(default_main_program()))
            rec = self._dispatch_records.get((pkey, tuple(fetch_names)))
            if rec is not None:
                out = self._try_fast_run(rec, feed if feed else {}, scope,
                                         return_numpy)
                if out is not _FAST_MISS:
                    return out

        compiled = None
        if isinstance(program, CompiledProgram):
            compiled = program
            program = compiled.program
        if program is None:
            program = default_main_program()
        scope = scope or global_scope()
        feed = dict(feed or {})

        if get_flag("FLAGS_check_program"):
            self._check_program(program, feed, fetch_names)

        if any(op.type in _HOST_OPS for op in program.global_block().ops):
            return self._run_with_host_ops(
                program, feed, fetch_names, scope, return_numpy)

        if (get_flag("FLAGS_check_nan_inf")
                and get_flag("FLAGS_check_nan_inf_level") == "op"):
            return self._run_op_level_checked(
                program, feed, fetch_names, scope, return_numpy)

        # normalize feed values to jax arrays (device put happens inside jit)
        feed_arrays: Dict[str, Any] = {}
        feed_sig = []
        raw_dtypes: Dict[str, Optional[str]] = {}
        for name, value in sorted(feed.items()):
            raw_dtypes[name] = (str(value.dtype)
                                if isinstance(value, np.ndarray) else None)
            arr = _normalize_feed(program.global_block().vars.get(name),
                                  value)
            feed_arrays[name] = arr
            feed_sig.append((name, tuple(arr.shape), str(arr.dtype)))

        mesh_plan = plan_for_program(program, compiled)
        key = (
            id(program),
            program._version_token(),
            tuple(feed_sig),
            tuple(fetch_names),
            mesh_plan.signature() if mesh_plan else None,
        )
        prof = _prof()
        exe = self._cache.get(key)
        newly_built = exe is None
        if exe is None:
            block = program.global_block()
            param_names, written = _analyze_persistables(program)
            ensure_compile_cache()
            _m_compile.inc()
            report_name = str(
                program._annotations.get("report_name")
                or f"{fetch_names[0] if fetch_names else 'main'}"
                   f"#{len(block.ops)}ops")
            self._explain_rebuild(program, report_name, feed_sig,
                                  fetch_names, mesh_plan)
            with _m_compile_ms.time(), _gp.timer("compile"), \
                    prof.RecordEvent(f"compile/{len(block.ops)}ops"):
                if "pipeline" in program._annotations:
                    from ..parallel.pipeline_program import (
                        _CompiledPipelineBlock)
                    exe = _CompiledPipelineBlock(
                        program, feed_sig, fetch_names, param_names,
                        written, scope=scope, mesh_plan=mesh_plan)
                elif "grad_merge" in program._annotations:
                    from ..parallel.grad_merge import (
                        _CompiledGradMergeBlock)
                    exe = _CompiledGradMergeBlock(
                        program, feed_sig, fetch_names, param_names,
                        written, scope=scope, mesh_plan=mesh_plan)
                else:
                    exe = _CompiledBlock(
                        program, feed_sig, fetch_names, param_names, written,
                        mesh_plan=mesh_plan, scope=scope,
                        report_name=report_name,
                    )
            self._cache[key] = exe
            logger.info(
                "compiled program: %d ops, %d params, %d feeds, mesh=%s",
                len(block.ops), len(param_names), len(feed_sig),
                mesh_plan.mode if mesh_plan else "single",
            )

        seed = program.random_seed or 0
        rng_key = jax.random.fold_in(jax.random.PRNGKey(seed), self._step)
        self._step += 1
        # the XLA compile happens lazily at the first execution; when the
        # persistent cache is on, attribute it as served-from-disk vs cold
        if newly_built:
            hits0, misses0 = compile_cache_counters()
            t0 = time.perf_counter_ns()
        _m_dispatch_slow.inc()
        _health().progress(getattr(exe, "progress_site", "executor.run"))
        t_run0 = time.perf_counter_ns()
        with _gp.timer("productive_step"), prof.RecordEvent("executor_run"):
            fetches = exe(scope, feed_arrays, rng_key)
        t_run1 = time.perf_counter_ns()
        _m_run_ms.observe((t_run1 - t_run0) / 1e6)
        if _spans.tracing_enabled():
            _spans.record("executor/step", t_run0, t_run1 - t_run0,
                          attrs={"path": "slow"})
        if newly_built:
            hits1, misses1 = compile_cache_counters()
            if hits1 > hits0 or misses1 > misses0:
                verdict = "hit" if hits1 > hits0 else "cold"
                # counter is ALWAYS live; the trace event only exists while
                # a profiling session is active (prof.add_event guards)
                _m_compile_cache.labels(verdict).inc()
                prof.add_event(f"compile_cache/{verdict}", t0,
                               time.perf_counter_ns() - t0)
                logger.info(
                    "persistent compile cache %s for program (%d ops)",
                    verdict, len(program.global_block().ops))

        # pin the dispatch record so the next identical step skips all of
        # the normalization/keying work above
        if (use_program_cache and type(exe) is _CompiledBlock
                and get_flag("FLAGS_dispatch_fast_path")):
            key_obj = compiled if compiled is not None else program
            recs = self._dispatch_records
            if len(recs) > 256:
                recs.clear()
            recs[(id(key_obj), tuple(fetch_names))] = _DispatchRecord(
                key_obj, compiled, program, exe, feed_sig, raw_dtypes)

        if get_flag("FLAGS_check_nan_inf"):
            from ..utils.nan_inf import check_fetches

            check_fetches(fetch_names, fetches)
        if return_numpy:
            t_wait0 = time.perf_counter_ns()
            with _gp.timer("device_wait"):
                out = [np.asarray(f) for f in fetches]
            _m_device_wait_ms.observe((time.perf_counter_ns() - t_wait0) / 1e6)
            return out
        return fetches

    # ------------------------------------------------------------------
    def _check_program(self, program, feed, fetch_names) -> None:
        """FLAGS_check_program pre-compile hook: run the static verifier
        (paddle_tpu/analysis/) once per program version — errors raise
        before anything is traced, warnings go to the log. The dispatch
        fast path never reaches here (it only serves already-checked
        (program, feed, fetch) combinations)."""
        key = (id(program), program._version_token(), tuple(fetch_names))
        if key in self._checked_programs:
            return
        from .. import analysis

        result = analysis.analyze_program(
            program, feed_names=list(feed), fetch_names=fetch_names)
        for f in result.warnings:
            logger.warning("check_program: %s", f.format())
        if not result.ok:
            raise RuntimeError(
                "FLAGS_check_program: static verification failed:\n"
                + "\n".join(f.format() for f in result.errors))
        if len(self._checked_programs) > 512:
            self._checked_programs.clear()
        self._checked_programs.add(key)

    # ------------------------------------------------------------------
    # flags whose value changes the lowered computation: a rebuild whose
    # feed/fetch signature is unchanged but whose flags differ is blamed
    # on them by the recompile explainer
    _COMPILE_FLAGS = ("FLAGS_check_nan_inf", "FLAGS_check_nan_inf_level",
                      "FLAGS_fuse_optimizer", "FLAGS_roi_align_exact",
                      "FLAGS_roi_align_exact_scale")

    def _explain_rebuild(self, program, report_name, feed_sig, fetch_names,
                         mesh_plan) -> None:
        """Recompile explainer: when this program already compiled under a
        different (feed-sig, fetch, flags) signature, diff against the
        sibling history, count paddle_recompiles_total{cause=} and emit a
        rate-limited human-readable cause line."""
        from ..observability import program_report as _prep

        sig = _prep.make_sig(
            feed_sig, fetch_names,
            flags={k: get_flag(k) for k in self._COMPILE_FLAGS},
            version=program._version_token(),
            mesh=mesh_plan.signature() if mesh_plan else None)
        if len(self._compile_history) > 256:
            self._compile_history.clear()
        hist = self._compile_history.setdefault(id(program), [])
        if hist:
            cause, detail = _prep.explain_recompile(sig, hist)
            _prep.note_recompile(report_name, cause, detail)
        hist.append(sig)
        del hist[:-32]  # bound sibling history per program

    # ------------------------------------------------------------------
    def _try_fast_run(self, rec: _DispatchRecord, feed, scope, return_numpy):
        """Attempt the zero-rebuild dispatch; _FAST_MISS sends the caller
        down the full path (which re-derives and replaces the record)."""
        program = rec.program
        if (program._version_token() != rec.version
                or program.random_seed != rec.seed
                or (rec.compiled is not None
                    and rec.compiled._is_data_parallel != rec.dp_flag)):
            return _FAST_MISS
        feeds = rec.prepare(feed)
        if feeds is None:
            return _FAST_MISS
        if rec.rng_used:
            rng_key = jax.random.fold_in(rec.rng_base, self._step)
        else:
            rng_key = rec.rng_base
        self._step += 1
        self._fast_hits += 1
        _m_dispatch_fast.inc()
        # flight-recorder dispatch tick (ISSUE 19): ring-append only on
        # this path (no sidecar write unless one is attached) — the
        # <5% flight_overhead_pct A/B in tools/dispatch_bench.py holds
        # this to one global read when off, one event when on
        if _flight.flight_enabled():
            _flight.event("dispatch", path="fast", step=self._step)
        t_run0 = time.perf_counter_ns()
        prof = _prof()
        # no ledger timer here: the run() entry wrapper already brackets
        # this whole call as productive_step (fast-path overhead budget)
        if prof.is_active():
            with prof.RecordEvent("executor_run"):
                fetches = rec.exe.fast_call(scope or global_scope(),
                                            feeds, rng_key)
        else:
            fetches = rec.exe.fast_call(scope or global_scope(), feeds,
                                        rng_key)
        t_run1 = time.perf_counter_ns()
        _m_run_ms.observe((t_run1 - t_run0) / 1e6)
        # steady-state step spans: full fidelity while a profiler session
        # is live (they land on the merged-trace span plane), 1-in-64
        # sampled otherwise — a per-step record next to a ~50us jitted
        # call costs real cache locality (the <5% tracing gate in
        # tools/dispatch_bench.py)
        if _spans.tracing_enabled() and (prof.is_active()
                                         or (self._step & 63) == 0):
            _spans.record("executor/step", t_run0, t_run1 - t_run0,
                          attrs={"path": "fast"})
        if return_numpy:
            t_wait0 = time.perf_counter_ns()
            with _gp.timer("device_wait"):
                out = [np.asarray(f) for f in fetches]
            _m_device_wait_ms.observe((time.perf_counter_ns() - t_wait0) / 1e6)
            return out
        return fetches

    # ------------------------------------------------------------------
    def _run_op_level_checked(self, program, feed, fetch_names, scope,
                              return_numpy):
        """FLAGS_check_nan_inf_level=op: interpret the block EAGERLY one op
        lowering at a time, scanning every floating output on the host —
        the reference's per-op NaN/Inf localization
        (details/nan_inf_utils_detail.cc) with op attribution. Debug-only
        speed; see utils/nan_inf.py."""
        from ..utils.nan_inf import check_op_outputs

        block = program.global_block()
        env: Dict[str, Any] = {}
        for name, var in block.vars.items():
            if var.persistable and scope.has_var(name):
                env[name] = scope.find_var(name)
        for name, value in feed.items():
            env[name] = jnp.asarray(
                _normalize_feed(block.vars.get(name), value))
        seed = program.random_seed or 0
        rng_key = jax.random.fold_in(jax.random.PRNGKey(seed), self._step)
        self._step += 1
        ctx = LowerCtx(program, block, env, rng_key=rng_key)
        for op in block.ops:
            run_lowering(ctx, op)
            check_op_outputs(op, env)
        for name, var in block.vars.items():
            if var.persistable and name in env:
                scope.set_var(name, env[name])
        fetches = [env[n] for n in fetch_names]
        if return_numpy:
            return [np.asarray(f) for f in fetches]
        return fetches

    # ------------------------------------------------------------------
    def run_startup(self, startup_program: Program, scope: Optional[Scope] = None):
        """Convenience alias: startup programs run through the same path."""
        return self.run(program=startup_program, feed={}, fetch_list=[], scope=scope)

    # ------------------------------------------------------------------
    # host-op segmented execution
    # ------------------------------------------------------------------
    def _segment_ops(self, ops):
        """Split the op list into maximal (is_host, [lo, hi)) runs."""
        segs = []
        lo = 0
        while lo < len(ops):
            host = ops[lo].type in _HOST_OPS
            hi = lo
            while hi < len(ops) and (ops[hi].type in _HOST_OPS) == host:
                hi += 1
            segs.append((host, lo, hi))
            lo = hi
        return segs

    def _slice_view(self, program: Program, lo: int, hi: int,
                    promote: frozenset) -> Program:
        """A derived Program running ops[lo:hi] of block 0.  Vars crossing
        the segment boundary (``promote``) get persistable=True on *copied*
        Variable objects so the compiled block reads/writes them via scope.
        Sub-blocks (control flow) are shared by reference."""
        import copy as _copy

        key = (id(program), program._version_token(), lo, hi, promote)
        view = self._view_cache.get(key)
        if view is not None:
            return view
        src_block = program.global_block()
        view = Program()
        view.random_seed = program.random_seed
        vb = view.global_block()
        for name, var in src_block.vars.items():
            v = _copy.copy(var)
            if name in promote:
                v.persistable = True
            v.block = vb
            vb.vars[name] = v
        vb.ops = list(src_block.ops[lo:hi])
        view.blocks = [vb] + program.blocks[1:]
        self._view_cache[key] = view
        if len(self._view_cache) > 256:
            self._view_cache.clear()
        return view

    def _run_with_host_ops(self, program, feed, fetch_names, scope,
                           return_numpy):
        """Execute a block containing host ops (send/recv/listen_and_serv…):
        device spans are jitted via the normal cached path; host ops run in
        Python against the scope (the reference's per-op interpreter did the
        same, executor.cc op->Run — we only drop to it at host boundaries)."""
        block = program.global_block()
        ops = block.ops
        segs = self._segment_ops(ops)

        # host ops read inputs from scope — materialize any fed values they
        # consume (device segments keep taking feeds through jit args)
        host_inputs = {n for host, lo, hi in segs if host
                       for op in ops[lo:hi] for n in op.input_arg_names}
        for n in host_inputs & feed.keys():
            scope.set_var(n, jnp.asarray(feed[n]))

        results: Dict[str, Any] = {}
        from ..profiler import RecordEvent
        for si, (host, lo, hi) in enumerate(segs):
            if host:
                for op in ops[lo:hi]:
                    with RecordEvent(f"host_op/{op.type}"):
                        _HOST_OPS[op.type](scope, op, self)
                continue
            seg_ops = ops[lo:hi]
            produced = {n for op in seg_ops for n in op.output_arg_names}
            needed_later = set(fetch_names)
            for _, l2, h2 in segs[si + 1:]:
                for op in ops[l2:h2]:
                    needed_later.update(op.input_arg_names)
            consumed_here = {n for op in seg_ops for n in op.input_arg_names}
            produced_before = {n for _, l0, h0 in segs[:si]
                               for op in ops[l0:h0]
                               for n in op.output_arg_names}
            promote = frozenset(
                (produced & needed_later)
                | (consumed_here & produced_before))
            view = self._slice_view(program, lo, hi, promote)
            seg_feed = {n: v for n, v in feed.items()
                        if n in consumed_here and n not in produced_before
                        and n not in promote}
            seg_fetch = [n for n in fetch_names if n in produced]
            vals = self.run(program=view, feed=seg_feed,
                            fetch_list=seg_fetch, scope=scope,
                            return_numpy=return_numpy)
            results.update(dict(zip(seg_fetch, vals)))

        out = []
        for n in fetch_names:
            if n in results:
                out.append(results[n])
            else:
                v = scope.find_var(n)
                if v is None:
                    raise RuntimeError(f"fetch {n!r} was never produced")
                out.append(np.asarray(v) if return_numpy else v)
        return out

    # ------------------------------------------------------------------
    def train_from_dataset(self, program=None, dataset=None, scope=None,
                           thread: int = 0, debug: bool = False,
                           fetch_list=None, fetch_info=None,
                           print_period: int = 100, monitor=None,
                           checkpoint_dir=None, checkpoint_interval=None,
                           guardrails=None):
        """Dataset trainer path — parity with fluid/executor.py:1448.

        The reference hands the Dataset to C++ trainer threads
        (Executor::RunFromDataset → HogwildWorker loops); here each parsed
        batch feeds the SAME whole-program XLA computation as ``run``. With
        ``thread > 1`` (or dataset.set_thread), file parsing and batch
        assembly run in a worker pool with a bounded prefetch queue
        (dataset.iter_batches_threaded) so host-side data work overlaps the
        asynchronously dispatched device steps — the HogwildWorker/
        MultiTrainer capability on one dispatch stream.

        ``monitor``: an ``observability.TrainMonitor``; when given, every
        step emits one structured JSONL record (step time, host-dispatch vs
        device-wait split, throughput, loss, NaN/Inf flags). Monitored runs
        sync the first fetch each step — that per-step device wait is the
        quantity being measured; leave monitor=None for the fully-async
        fast path.

        ``checkpoint_dir`` + ``checkpoint_interval``: periodic async
        crash-safe checkpointing (docs/elastic.md).  Every ``interval``
        steps the program's persistable vars plus the dataset position
        ({"epoch", "offset"}) are committed through
        ``parallel.checkpoint.ElasticCheckpointer`` (write overlapped with
        the next steps); on entry, the latest committed step is restored
        and the already-consumed batches are skipped, so a preempted job
        resumes deterministically.  A SIGTERM/SIGINT mid-train triggers a
        final synchronous checkpoint and a clean return (the launcher's
        grace-period contract).

        ``guardrails``: a ``parallel.health.GuardrailConfig`` (or ``True``
        for the defaults) arms the divergence guardrail (docs/health.md):
        each step's loss (fetch[0]) is judged, a NaN/Inf or loss-spike step
        is *skipped* — the pre-step persistable state is restored, so the
        poisoned batch never lands (the full-precision generalization of
        AMP's overflow skip; the decision depends only on the already
        all-reduced loss, so dp ranks stay in lockstep) — and after K
        consecutive bad steps the loop rolls back to the latest valid
        checkpoint with an optional LR cooldown.  Guarded runs sync the
        loss and snapshot the persistables every step — a measured,
        documented cost; leave ``guardrails=None`` for the fully-async
        fast path.  Skips/rollbacks are metered as
        ``paddle_guardrail_skipped_steps_total{reason}`` /
        ``paddle_guardrail_rollbacks_total``.
        """
        return self._run_from_dataset(program, dataset, scope, fetch_list,
                                      fetch_info, print_period, train=True,
                                      thread=thread, monitor=monitor,
                                      checkpoint_dir=checkpoint_dir,
                                      checkpoint_interval=checkpoint_interval,
                                      guardrails=guardrails)

    def infer_from_dataset(self, program=None, dataset=None, scope=None,
                           thread: int = 0, debug: bool = False,
                           fetch_list=None, fetch_info=None,
                           print_period: int = 100, monitor=None):
        """Parity with fluid/executor.py:1381 (no optimizer side effects is
        the caller's responsibility, as in the reference)."""
        return self._run_from_dataset(program, dataset, scope, fetch_list,
                                      fetch_info, print_period, train=False,
                                      thread=thread, monitor=monitor)

    def _checkpoint_state(self, program, scope) -> Dict[str, Any]:
        """Persistable vars (the trainable state) as host arrays — the
        checkpoint payload.  Host conversion here is the snapshot point."""
        out: Dict[str, Any] = {}
        for name, v in program.global_block().vars.items():
            if not v.persistable or v.is_data:
                continue
            val = scope.find_var(name)
            if val is not None:
                out[name] = np.asarray(val)
        return out

    def _restore_checkpoint_state(self, program, scope, state) -> int:
        block = program.global_block()
        n = 0
        for name, arr in state.items():
            if name in block.vars and block.vars[name].persistable:
                scope.set_var(name, jnp.asarray(arr))
                n += 1
        return n

    def _guardrail_rollback(self, program, scope, ckpt, guard, step) -> None:
        """K consecutive bad steps: restore the latest valid checkpoint
        (skip-batch already rewound this step, which is all we can do
        without a checkpoint store), cool the learning rate, and charge the
        guard's rollback budget.  The data stream is NOT rewound —
        divergence is a state problem, not a data problem (docs/health.md).
        """
        restored = None
        if ckpt is not None:
            latest = ckpt.latest_valid_step()
            if latest is not None:
                state, _man = ckpt.restore(latest)
                self._restore_checkpoint_state(program, scope, state)
                restored = latest
        cool = guard.config.lr_cooldown
        if cool != 1.0:
            # fluid optimizers keep their rate in a persistable
            # learning_rate_N global var (optimizer.py _create_lr_var)
            for name, v in program.global_block().vars.items():
                if v.persistable and name.startswith("learning_rate"):
                    val = scope.find_var(name)
                    if val is not None:
                        scope.set_var(
                            name, jnp.asarray(np.asarray(val) * cool))
        logger.warning(
            "guardrail: rollback at step %d -> %s (lr cooldown x%s)",
            step,
            f"checkpoint step {restored}" if restored is not None
            else "pre-step snapshot (no valid checkpoint)",
            cool)
        guard.rolled_back()

    def _run_from_dataset(self, program, dataset, scope, fetch_list,
                          fetch_info, print_period, train: bool,
                          thread: int = 0, monitor=None,
                          checkpoint_dir=None, checkpoint_interval=None,
                          guardrails=None):
        # goodput run window (docs/observability.md): every wall-second of
        # the dataset loop is attributed to a ledger category; the window
        # remainder becomes `other`, and the per-rank report exports to
        # PADDLE_GOODPUT_DIR for the supervisor's gang aggregation
        opened = _gp.start_window()
        try:
            return self._run_from_dataset_inner(
                program, dataset, scope, fetch_list, fetch_info,
                print_period, train, thread=thread, monitor=monitor,
                checkpoint_dir=checkpoint_dir,
                checkpoint_interval=checkpoint_interval,
                guardrails=guardrails)
        finally:
            if opened:
                _goodput.maybe_export(_gp.end_window(
                    extra={"mode": "train" if train else "infer"}))

    def _run_from_dataset_inner(self, program, dataset, scope, fetch_list,
                                fetch_info, print_period, train: bool,
                                thread: int = 0, monitor=None,
                                checkpoint_dir=None,
                                checkpoint_interval=None,
                                guardrails=None):
        if dataset is None:
            raise ValueError("dataset must be provided")
        program = program or default_main_program()
        scope = scope or global_scope()
        fetch_list = fetch_list or []
        fetch_info = fetch_info or [
            (v.name if isinstance(v, Variable) else str(v)) for v in fetch_list
        ]
        # in-run health (docs/health.md): hang watchdog from the launcher
        # env contract, per-rank heartbeat onto the shared health dir, and
        # the optional divergence guardrail
        health = _health()
        health.maybe_install_from_env()
        hb_dir = os.environ.get(health.ENV_DIR)
        rank = int(os.environ.get("PADDLE_TRAINER_ID", "0") or "0")
        heartbeat = (health.RankHeartbeat(hb_dir, rank)
                     if hb_dir else None)
        # flight recorder + per-rank span sink (ISSUE 19): when the
        # launcher exports PADDLE_FLIGHT_DIR, the event ring mirrors to
        # a crash-surviving per-rank sidecar, and the span tracer writes
        # spans-train<R>-<pid>.jsonl into the same dir so
        # tools/trace_assemble.py stitches per-step training traces the
        # way it stitches serving requests
        flight_dir = os.environ.get(_flight.ENV_DIR)
        if flight_dir:
            _flight.maybe_attach_from_env()
            if _spans.tracing_enabled():
                try:
                    _spans.attach_process_sink(flight_dir, f"train{rank}")
                except OSError:
                    pass
        guard = None
        if train and guardrails is not None and guardrails is not False:
            if not fetch_list:
                raise ValueError(
                    "guardrails need a fetch_list (the loss is fetch[0])")
            guard = health.DivergenceGuard(
                guardrails if isinstance(guardrails, health.GuardrailConfig)
                else health.GuardrailConfig())
        # AMP visibility (docs/health.md): when the program carries the
        # mixed-precision loss-scaling state, mirror it into every monitor
        # row so guardrail decisions and AMP overflow-skips read off the
        # same JSONL stream
        amp_vars = None
        if monitor is not None:
            blk0 = program.global_block()
            amp_vars = {
                key: name for key, name in (
                    ("loss_scale", "loss_scaling_0"),
                    ("found_inf", "find_infinite_scale_0"),
                    ("bad_steps", "bad_steps_0"))
                if (v := blk0.vars.get(name)) is not None and v.persistable}
            if not amp_vars:
                amp_vars = None

        def _amp_fields():
            out = {}
            if amp_vars is None:
                return out
            # materializing the AMP scalars is a device sync
            with _gp.timer("device_wait"):
                return _amp_fields_inner()

        def _amp_fields_inner():
            out = {}
            v = scope.find_var(amp_vars.get("loss_scale", ""))
            if v is not None:
                out["loss_scale"] = float(np.asarray(v).ravel()[0])
            v = scope.find_var(amp_vars.get("found_inf", ""))
            if v is not None:
                out["bad_step"] = bool(np.asarray(v).ravel()[0])
            v = scope.find_var(amp_vars.get("bad_steps", ""))
            if v is not None:
                out["bad_steps"] = int(np.asarray(v).ravel()[0])
            return out
        feed_names = {v.name for v in getattr(dataset, "use_vars", [])}
        # stream-capable datasets (docs/data.md) run their own read/decode
        # worker pool — the threaded batch pipeline would bypass their
        # retry/quarantine/resume machinery
        streaming = hasattr(dataset, "stream_state") \
            and hasattr(dataset, "restore_stream_state")
        n_threads = int(thread) or int(getattr(dataset, "thread_num", 1) or 1)
        if n_threads > 1 and not streaming:
            from ..dataset import iter_batches_threaded

            batches = iter_batches_threaded(dataset, n_threads)
        else:
            batches = iter(dataset)

        def filtered():
            for batch_feed in batches:
                yield {k: v for k, v in batch_feed.items()
                       if not feed_names or k in feed_names
                       or k.endswith("__len") or k == _STREAM_STATE_KEY}

        # elastic checkpointing (docs/elastic.md): restore the latest
        # committed step into the scope, skip the consumed batches, and
        # save periodically / on preemption
        ckpt = preempt = None
        start_offset = 0
        stream_resumed = False
        if train and checkpoint_dir:
            # store bring-up (module import + committed-step scan) is
            # checkpoint machinery wall time
            with _gp.timer("checkpoint_save"):
                from ..parallel.checkpoint import ElasticCheckpointer
                from ..parallel.launch import install_preemption_handler

                scope = scope or global_scope()
                ckpt = ElasticCheckpointer(checkpoint_dir, keep_last=3)
                latest = ckpt.latest_valid_step()
            if latest is not None:
                with _gp.timer("restore"):
                    state, man = ckpt.restore(latest)
                    n_restored = self._restore_checkpoint_state(
                        program, scope, state)
                data_man = man.get("data") or {}
                start_offset = int(data_man.get("offset", 0))
                if streaming and data_man.get("stream"):
                    # a stream-capable dataset seeks to its saved per-shard
                    # offsets instead of replaying + discarding consumed
                    # batches (O(offset) parse work on every restart)
                    dataset.restore_stream_state(data_man["stream"])
                    stream_resumed = True
                logger.info(
                    "resumed %d persistables from checkpoint step %d "
                    "(%s)", n_restored, latest,
                    "stream state restored" if stream_resumed else
                    f"skipping {start_offset} consumed batches")
            preempt = install_preemption_handler()

        def _save_ckpt(step_no: int, sync: bool = False,
                       stream_state=None, span_ctx=None):
            # only the synchronous share burns main-thread wall: the host
            # snapshot + (for sync saves) the commit wait
            t_ck0 = time.perf_counter_ns()
            with _gp.timer("checkpoint_save"):
                data_state = {"epoch": 0, "offset": step_no}
                if stream_state is not None:
                    # the batch-aligned resume token of the sharded stream
                    # (docs/data.md StreamState schema)
                    data_state["stream"] = stream_state
                ckpt.save(step_no, self._checkpoint_state(program, scope),
                          data_state=data_state)
                if sync:
                    ckpt.wait()
            ck_dur = time.perf_counter_ns() - t_ck0
            _flight.event("ckpt_write", step=step_no, dur_ns=ck_dur,
                          sync=bool(sync))
            if span_ctx is not None:
                _spans.record("train/checkpoint", t_ck0, ck_dur,
                              trace=span_ctx[0], parent=span_ctx[1])

        # overlap host batch assembly + device transfer with the in-flight
        # (asynchronously dispatched) step; fetches stay on device between
        # print boundaries so the loop never blocks on the step it just
        # launched
        from ..reader import prefetch_to_device

        stream = filtered()
        if start_offset and not stream_resumed:
            import itertools

            stream = itertools.islice(stream, start_offset, None)
        step = start_offset
        last_fetch = None
        last_stream_state = None
        quarantined_fn = None
        if streaming:
            from ..dataset.streaming import quarantined_total

            quarantined_fn = quarantined_total
        batch_iter = prefetch_to_device(stream, size=2)
        while True:
            # the wait for the next staged batch is the step's input-side
            # stall; it rides every monitor row as input_wait_ms
            t_in = time.perf_counter_ns()
            try:
                feed = next(batch_iter)
            except StopIteration:
                break
            input_wait_ms = (time.perf_counter_ns() - t_in) / 1e6
            if isinstance(feed, dict):
                st = feed.pop(_STREAM_STATE_KEY, None)
                if st is not None:
                    last_stream_state = st
            # per-step flight events + a per-step root span (ISSUE 19):
            # the trace/root ids are minted up front so the dispatch /
            # data-wait / checkpoint children recorded along the way all
            # parent into the train/step root emitted at step end
            _flight.event("data_wait", dur_ns=int(input_wait_ms * 1e6),
                          step=step + 1)
            _flight.event("step_begin", step=step + 1)
            if _spans.tracing_enabled():
                step_trace, step_root = _spans.gen_id(), _spans.gen_id()
            else:
                step_trace = step_root = None
            t_disp0 = t_disp1 = None
            with _gp.timer("productive_step"):
                health.progress("train_from_dataset")
                if guard is not None:
                    # the skip-batch restore target: pre-step persistable
                    # state as host arrays (the same snapshot a checkpoint
                    # save takes — this sync + copy is guard mode's
                    # documented cost, charged to the step by the
                    # enclosing loop-body timer)
                    pre_state = self._checkpoint_state(program, scope)
                if monitor is not None:
                    if monitor.examples_per_step is None:
                        # infer the per-step example count from the batch dim
                        for v in feed.values():
                            shape = getattr(v, "shape", None)
                            if shape:
                                monitor.examples_per_step = int(shape[0])
                                break
                    # input-side context on every row (ISSUE 11 satellite):
                    # how long this step waited on the prefetch queue, and
                    # the cumulative quarantined-record count — anomaly
                    # dumps then show whether the input path was implicated
                    input_extra = {"input_wait_ms": round(input_wait_ms, 4)}
                    if quarantined_fn is not None:
                        input_extra["quarantined_records"] = \
                            int(quarantined_fn())
                    with monitor.step() as s:
                        # the dispatch IS the host-side train-step
                        # collective boundary: one monotone seq per step,
                        # agreed across ranks (identical step loops)
                        _fl_seq = _flight.collective_enter("train_step")
                        t_disp0 = time.perf_counter_ns()
                        last_fetch = self.run(program=program, feed=feed,
                                              fetch_list=fetch_list, scope=scope,
                                              return_numpy=False)
                        t_disp1 = time.perf_counter_ns()
                        _flight.collective_exit(_fl_seq, "train_step")
                        s.dispatched()
                        if fetch_list:
                            # materializing the first fetch IS the device wait;
                            # the full fetch list rides along (by reference, no
                            # sync) so an anomaly dump can summarize the
                            # offending step's values
                            extra = _amp_fields()
                            extra.update(input_extra)
                            if guard is not None:
                                with _gp.timer("device_wait"):
                                    loss_host = np.asarray(last_fetch[0])
                                verdict = guard.judge(loss_host)
                                if verdict != "ok":
                                    extra["bad_step"] = True
                            s.observe(loss=last_fetch[0], fetches=last_fetch,
                                      fetch_names=list(fetch_info), **extra)
                        else:
                            s.observe(**input_extra)
                else:
                    _fl_seq = _flight.collective_enter("train_step")
                    t_disp0 = time.perf_counter_ns()
                    last_fetch = self.run(program=program, feed=feed,
                                          fetch_list=fetch_list, scope=scope,
                                          return_numpy=False)
                    t_disp1 = time.perf_counter_ns()
                    _flight.collective_exit(_fl_seq, "train_step")
                    if guard is not None:
                        with _gp.timer("device_wait"):
                            loss_host = np.asarray(last_fetch[0])
                        verdict = guard.judge(loss_host)
                step += 1
                if heartbeat is not None:
                    heartbeat.beat(step)
                if guard is not None and verdict != "ok":
                    # skip-batch: the poisoned step's update never lands
                    with _gp.timer("rollback_replay"):
                        self._restore_checkpoint_state(program, scope, pre_state)
                        logger.warning(
                            "guardrail: step %d skipped (%s, consecutive bad "
                            "%d)", step, guard.last_reason,
                            guard.consecutive_bad)
                        if verdict == "rollback":
                            self._guardrail_rollback(program, scope, ckpt,
                                                     guard, step)
                if ckpt is not None:
                    if preempt is not None and preempt.triggered:
                        # the launcher's SIGTERM grace window: checkpoint
                        # synchronously and return cleanly
                        logger.info("preemption signal at step %d: "
                                    "checkpointing and exiting", step)
                        _save_ckpt(step, sync=True,
                                   stream_state=last_stream_state,
                                   span_ctx=(step_trace, step_root)
                                   if step_trace else None)
                        break
                    if checkpoint_interval and \
                            step % int(checkpoint_interval) == 0:
                        _save_ckpt(step, stream_state=last_stream_state,
                                   span_ctx=(step_trace, step_root)
                                   if step_trace else None)
                if fetch_list and print_period and step % print_period == 0:
                    # the only per-step host sync point (monitor excepted),
                    # and only when printing
                    t0 = time.perf_counter_ns()
                    with _gp.timer("device_wait"):
                        msg = ", ".join(
                            f"{name}={np.asarray(val).ravel()[:4]}"
                            for name, val in zip(fetch_info, last_fetch))
                    dev_ns = time.perf_counter_ns() - t0
                    _m_fetch_stall.inc(dev_ns / 1e6)
                    _flight.event("stream_fetch", step=step, dur_ns=dev_ns)
                    if step_trace is not None:
                        _spans.record("train/device", t0, dev_ns,
                                      trace=step_trace, parent=step_root)
                    logger.info("step %d: %s", step, msg)
                # step epilogue stays inside the productive_step window:
                # the flight/span sidecar flushes are framework cost of
                # the step, not unaccounted "other" in the goodput ledger
                _flight.event("step_end", step=step)
                if step_trace is not None:
                    tr = _spans.default_tracer()
                    tr.record("train/data_wait", t_in,
                              int(input_wait_ms * 1e6),
                              trace=step_trace, parent=step_root)
                    if t_disp0 is not None:
                        tr.record("train/dispatch", t_disp0,
                                  t_disp1 - t_disp0,
                                  trace=step_trace, parent=step_root)
                    tr.record("train/step", t_in,
                              time.perf_counter_ns() - t_in,
                              trace=step_trace, span_id=step_root,
                              attrs={"step": step, "rank": rank})
        if heartbeat is not None:
            heartbeat.flush()
        if ckpt is not None:
            if step > start_offset and not (preempt is not None
                                            and preempt.triggered):
                # the final save captures the dataset's CURRENT stream
                # state (epoch advanced, offsets cleared) so a relaunch
                # starts the next epoch instead of replaying the last batch
                _save_ckpt(step, sync=True,
                           stream_state=(dataset.stream_state()
                                         if streaming else None))
            ckpt.close()
        if last_fetch is not None:
            t0 = time.perf_counter_ns()
            with _gp.timer("device_wait"):
                last_fetch = [np.asarray(v) for v in last_fetch]
            _m_fetch_stall.inc((time.perf_counter_ns() - t0) / 1e6)
        return last_fetch


def _normalize_feed(var, value):
    """Cast a fed value to its declared var dtype (one rule for the jit and
    the op-level debug paths)."""
    arr = np.asarray(value)
    if var is not None and var.dtype != arr.dtype.name:
        arr = arr.astype(np.dtype(var.dtype)
                         if var.dtype != "bfloat16" else jnp.bfloat16)
    return arr


def _analyze_persistables(program: Program) -> Tuple[List[str], List[str]]:
    """Persistables read from scope vs. written back to scope by block-0 ops.

    A persistable read before any op produces it is an external input (must be
    in scope); any persistable produced by an op is written back after the run.
    Startup programs have write-only persistables (initializers) — they need no
    scope value beforehand.
    """
    block = program.global_block()
    persistable = {n for n, v in block.vars.items() if v.persistable}
    read, written = [], []
    produced: set = set()
    for op in block.ops:
        for n in op.input_arg_names:
            if n in persistable and n not in produced and n not in read:
                read.append(n)
        for n in op.output_arg_names:
            produced.add(n)
            if n in persistable and n not in written:
                written.append(n)
    return read, written
