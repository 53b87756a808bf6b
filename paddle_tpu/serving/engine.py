"""TPU-native decode engine: AOT prefill/decode executables over a
preallocated paged KV cache (docs/serving.md).

The training side already proved the ingredients — PR 1's cached dispatch,
PR 4's explicit ``lower()+compile()`` AOT executables and recompile
explainer, PR 5's chunk-scaled quantizer, PR 12's sharding plans. This
module assembles them into the serving shape:

- **Compiled once, shapes static forever.** Prefill is shape-bucketed
  (one executable per ladder rung, prompts padded up); decode is ONE
  executable over the static ``[max_batch]`` slot layout; the optional
  speculative-verify window is one more static ``[max_batch, W]``
  executable. Requests join and leave the batch by editing host-side slot
  state, never a shape. After :meth:`DecodeEngine.warmup`, steady-state
  serving performs zero compiles; ``paddle_recompiles_total`` is the
  guardrail.
- **KV cache as carried state.** One layout, the paged one
  (``serving/paged_kv.py``: a ``[L, num_pages, page_size, nh, hd]`` pool
  + per-slot page tables fed as device arrays, prefix-cache capable),
  threaded through every executable with buffer donation on TPU. The
  programs carry both pools through the layer loop in place
  (``models/gpt_serving.py:layers_over_pools``) and touch only the rows
  and pages they index;
  on a TPU the decode tick reads the live pages through the page table
  in a Pallas kernel (``kv_path``).
- **Sampling inside the executables** (``serving/sampling.py``):
  per-slot temperature/top-k/top-p/seed ride as batch inputs — changing
  them never changes a shape. ``temperature=0`` is bit-exact greedy,
  and a call pays only for the dearest sampler path its batch asks for
  (chosen inside the executable; counted and stamped by
  ``_note_sampler``).
- **Tensor-parallel lowering** (``EngineConfig(sharding="tp", tp=N)``):
  attention/MLP weights and the KV head axis shard over an N-chip mesh
  through PR 12's plan machinery (``sharding/plan.py`` suffix
  inheritance) + ``jax.jit`` ``in_shardings``/``out_shardings`` — the
  AOT warmup ladder, cache donation, and the zero-recompile gate all
  survive ``NamedSharding``.
- **Weights in serving precision.** ``weight_dtype="int8"|"bf16"``
  through serving/quant.py; dequantization happens inside the compiled
  functions. (int8's flat chunk layout cannot head-shard — ``tp`` engines
  take f32/bf16.)

The engine is single-threaded by contract: exactly one scheduler loop
calls it (serving/scheduler.py). Every program (prefill, decode, the
verify window) is ``cut the feed -> embed -> layers -> logits -> sample``
over ``(qparams, caches, feed)``, the layers those of a *model description*
(``serving/model.py`` states the protocol; the descriptions live beside
their models under ``models/``, and this package imports none of them).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from ..observability import program_report as _prep
from ..observability import spans as _spans
from ..ops import pallas_kernels as _pk
from . import metrics as smetrics
from . import model as _model
from . import sampling as samp
from .paged_kv import (PagedKVCache, PagePoolFullError, PrefixCache,
                       table_width as _table_width)
from .quant import QuantizedLeaf, quantized_nbytes
from .sampling import GREEDY, SamplingParams

__all__ = ["EngineConfig", "DecodeEngine", "PromptTooLongError",
           "default_bucket_ladder"]


class PromptTooLongError(ValueError):
    """Prompt exceeds the largest prefill bucket."""


def _note_sampler(program: str, temps, top_ks, top_ps) -> str:
    """Count one call of ``program`` (decode | prefill | verify) under the
    sampler path its executable will take for these host-side parameters,
    and return the path's name (the ``sampler`` attribute of the call's
    ``run`` span)."""
    path = samp.path_name(temps, top_ks, top_ps)
    smetrics.m_sampler_path.labels(path, program).inc()
    return path


# ---------------------------------------------------------------------------
# the feed: an engine call's host arguments, ONE int32 array
# ---------------------------------------------------------------------------
# Every host array a call hands its executable is a transfer of its own,
# 0.13 ms inside the call whatever its bytes (PERF.md section 6, PR 34), so
# each program takes one array behind the caches and cuts it apart
# (docs/serving.md "The tick's anatomy"). A row is
#
#     [ page-table row (M) | scalars | the sampler's four | token(s) ]
#
# a slot's in the decode tick's ``[max_batch, M + 7]`` and the verify
# window's ``[max_batch, M + 6 + W]`` (scalars: position, active), the
# request's in a prefill rung's ``[M + 7 + bucket]`` (scalars: length,
# prefix_len, slot). The sampler's four are ``samp.batch_arrays``' block,
# the floats as their bits.
_POSITION, _ACTIVE = 0, 1            # a slot's scalars, behind its table row
_SLOT_SCALARS = 2
_RUNG_SCALARS = 3
_SAMPLING = 4


def slot_feed_shape(B: int, M: int, W: int = 1) -> Tuple[int, int]:
    """Shape of a decode tick's feed, or with ``W`` a verify window's, for
    ``B`` slots whose table rows name ``M`` pages."""
    return B, M + _SLOT_SCALARS + _SAMPLING + W


def rung_feed_len(M: int, bucket: int) -> int:
    """Length of the feed of a prefill rung of ``bucket`` positions."""
    return M + _RUNG_SCALARS + _SAMPLING + bucket


def cut_slot_feed(feed, M: int):
    """Inside the tick's and the verify window's program: ``feed`` ->
    (tokens [B, W], positions [B], tables [B, M], actives [B], (temps,
    top_ks, top_ps, seeds)), each with the dtype and the bits the host
    wrote."""
    at = M + _SLOT_SCALARS
    return (feed[:, at + _SAMPLING:], feed[:, M + _POSITION], feed[:, :M],
            feed[:, M + _ACTIVE],
            samp.feed_columns(feed[:, at:at + _SAMPLING]))


def cut_rung_feed(feed, M: int):
    """Inside a prefill rung's program: ``feed`` -> (tokens [1, bucket],
    length, prefix_len, table_row [M], slot, (temp, top_k, top_p,
    seed))."""
    at = M + _RUNG_SCALARS
    return (feed[None, at + _SAMPLING:], feed[M], feed[M + 1], feed[:M],
            feed[M + 2], samp.feed_columns(feed[at:at + _SAMPLING]))


def _held_shapes(params, qparams) -> Dict[str, Tuple[int, ...]]:
    """``{"blocks/w_qkv": (L, d, 3·nh·hd), ..}``: the leaves of the serving
    storage whose shape is not the stored leaf's (a quantized leaf's shape
    is the one it dequantizes to)."""
    from ..sharding.plan import _path_str

    held = jax.tree_util.tree_flatten_with_path(
        qparams, is_leaf=lambda x: isinstance(x, QuantizedLeaf))[0]
    stored = jax.tree_util.tree_leaves(params)
    return {_path_str(path): tuple(q.shape)
            for (path, q), p in zip(held, stored)
            if tuple(q.shape) != tuple(np.shape(p))}


@dataclasses.dataclass
class _Tick:
    """A decode call that has been dispatched and not yet collected."""
    feed: Dict[int, int]         # slot -> input token, the riders
    # the call's cache outputs; None once they are the manager's arrays
    # (a tick dispatched ahead swaps them in at dispatch: its inputs were
    # donated to it, and whatever runs next must take its outputs)
    caches: Optional[tuple]
    logits: Any
    toks: Any
    report: list
    sampler: str
    t0: int                      # perf_counter_ns at dispatch
    call: Optional[int]          # its decode/call's span id, if traced


def default_bucket_ladder(max_seq: int, smallest: int = 16) -> Tuple[int, ...]:
    """Powers of two from ``smallest`` up to ``max_seq`` (inclusive as the
    last rung). Each rung is one AOT-compiled prefill executable — the
    ladder trades warmup compiles against padding waste."""
    out: List[int] = []
    b = smallest
    while b < max_seq:
        out.append(b)
        b *= 2
    out.append(max_seq)
    return tuple(sorted(set(out)))


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Static serving geometry — every field here is baked into executable
    shapes, so changing one means a new engine (and new compiles)."""
    max_batch: int = 8               # decode slots (the static batch)
    max_seq: int = 256               # per-slot prompt+generation bound
    prefill_buckets: Tuple[int, ...] = ()   # () -> default_bucket_ladder
    weight_dtype: str = "f32"        # "f32" | "bf16" | "int8"
    quant_chunk: int = 256           # int8 scale granularity
    cache_dtype: Any = None          # None -> the model's compute dtype
    eos_id: Optional[int] = None     # greedy decode stops on this token
    # -- paged KV (docs/serving.md "Paged KV") --------------------------
    # One value, "paged": the field is still here only because the
    # benchmark's configurations and its harness test pass it whole into
    # EngineConfig (ROADMAP D13); anything else is a ValueError.
    kv_layout: str = "paged"
    page_size: int = 16              # tokens per page (divides buckets)
    num_pages: int = 0               # 0 -> max_batch * max_seq / page_size
                                     #      pages (+1 scratch)
    prefix_cache: bool = True        # token-hash prefix cache
    prefix_cache_pages: int = 0      # 0 -> bounded by the pool itself
    # -- tensor parallelism over PR 12's sharding layer -----------------
    sharding: Optional[str] = None   # None | "tp"
    tp: int = 1                      # mesh size for sharding="tp"
    # -- phase disaggregation (ISSUE 17, docs/serving.md) ----------------
    # "prefill" | "decode" | "colocated": stamps the TTFT/TPOT metric
    # labels and tells the disagg router which fleet this engine serves
    role: str = "colocated"
    # -- speculative decoding (serving/spec_decode.py) ------------------
    verify_window: int = 0           # W>0 compiles the verify executable
    # -- fused decode step (ops/pallas_kernels.py, docs/kernels.md) -----
    # Pallas launches in place of the decode tick's small-fusion residue
    # ranked by ATTRIBUTION_DECODE.json: fused_ln for the tick's
    # layernorms and one launch for the final layernorm + LM-head
    # projection. Opt-in: interpret-mode Pallas is slower than XLA
    # off-TPU and no cell has measured these on the chip. NOT a switch
    # of the paged attention: an engine on a TPU reads its cache through
    # the page-table kernel either way (``DecodeEngine.kv_path``,
    # decided from platform and mesh); off the TPU this flag also asks
    # for that kernel, in interpret mode, which is how the CPU lane
    # drives it. Masked-lane / scratch-page write-guard semantics are
    # preserved (tests/test_pallas_fused.py).
    fused_decode: bool = False

    def resolved_buckets(self) -> Tuple[int, ...]:
        buckets = tuple(sorted(set(
            int(b) for b in (self.prefill_buckets
                             or default_bucket_ladder(self.max_seq)))))
        if not buckets:
            raise ValueError("prefill_buckets must not be empty")
        if buckets[-1] > self.max_seq:
            raise ValueError(
                f"largest prefill bucket {buckets[-1]} exceeds max_seq "
                f"{self.max_seq}")
        return buckets


class DecodeEngine:
    def __init__(self, params, cfg, ecfg: EngineConfig):
        """``cfg``: a model description (``serving/model.py``) or a config
        that names one (``cfg.serving_description()``)."""
        self.model = _model.describe(cfg)
        cfg = self.cfg = self.model.cfg
        if (self.model.max_positions is not None
                and ecfg.max_seq > self.model.max_positions):
            raise ValueError(
                f"EngineConfig.max_seq {ecfg.max_seq} exceeds the model's "
                f"positional table {self.model.max_positions}")
        self.ecfg = ecfg
        self._refuse_what_cannot_carry_state(ecfg)
        self.buckets = ecfg.resolved_buckets()
        if ecfg.kv_layout != "paged":
            raise ValueError(f"kv_layout {ecfg.kv_layout!r}: "
                             "expected 'paged'")
        bad = [b for b in self.buckets if b % ecfg.page_size]
        if bad:
            raise ValueError(
                f"paged engine: prefill buckets {bad} are not "
                f"multiples of page_size {ecfg.page_size}")
        if ecfg.role not in ("prefill", "decode", "colocated"):
            raise ValueError(f"role {ecfg.role!r}: expected 'prefill', "
                             "'decode' or 'colocated'")
        self.role = ecfg.role
        self._donate = jax.default_backend() != "cpu"
        self._ref_params = params                  # f32 truth for parity
        # -- tensor-parallel mesh + shardings (PR 12 plan machinery) ----
        self._mesh = None
        self._param_sh = None
        self._cache_sh = None
        self._pool_rows = None       # the model's own, unless the mesh's
        self._repl_sh = None
        if ecfg.sharding not in (None, "tp"):
            raise ValueError(f"sharding {ecfg.sharding!r}: expected None "
                             "or 'tp'")
        # the serving storage is the model's own: it may hold a leaf in
        # the layout its programs contract (the GPT block's w_qkv). A
        # sharded engine asks for the stored shapes, which its plan names.
        qparams = self.model.hold(params, ecfg.weight_dtype,
                                  ecfg.quant_chunk,
                                  sharded=ecfg.sharding is not None)
        if ecfg.sharding == "tp":
            self._init_tp(qparams)
        self.qparams = jax.device_put(qparams, self._param_sh)
        self.weight_nbytes = quantized_nbytes(self.qparams)
        # leaf path -> shape, for the leaves held in another shape than
        # they are stored (/health shows it)
        self.held_shapes = _held_shapes(params, qparams)
        cache_dtype = ecfg.cache_dtype or cfg.dtype
        pools = self.model.cache_pools
        # one manager for every kind of cache: pages for the attention
        # layers (keys and values of every head, or a latent model's one
        # row a token), a state row a slot for the recurrent ones
        # (a model whose layers keep different spans names several page
        # groups, each with its pools, free list and table a slot:
        # docs/serving.md "Window and global layers")
        num_heads, head_dim = pools.get("heads", (0, 0))
        self.cache = PagedKVCache(
            pools.get("layers", 0), ecfg.max_batch, ecfg.max_seq,
            num_heads=num_heads, head_dim=head_dim,
            dtype=cache_dtype, page_size=ecfg.page_size,
            num_pages=ecfg.num_pages, state=self.model.state_geometry,
            rows=self._pool_rows or pools.get("rows"),
            groups=pools.get("groups"))
        self.prefix = (PrefixCache(self.cache, ecfg.prefix_cache_pages)
                       if ecfg.prefix_cache else None)
        # pool pressure reclaims the pages only the prefix cache holds
        self.cache.prefix_cache = self.prefix
        if self._cache_sh is not None:
            self.cache.k = jax.device_put(self.cache.k, self._cache_sh)
            self.cache.v = jax.device_put(self.cache.v, self._cache_sh)
        # how the decode tick reads the cache (docs/serving.md): on a TPU
        # it reads the live pages through the page table in a Pallas
        # kernel, where Mosaic takes its page shape; under a mesh (a
        # Pallas call there needs shard_map) and off the TPU
        # (interpret-mode Pallas is slower than XLA) it gathers the
        # padded view. Off the TPU fused_decode asks for the kernel all
        # the same: the CPU lane's way to drive it.
        if self._mesh is not None:
            self.kv_path = "xla_gather"
        elif self.model.paged_kernel and (
                self.model.kernel_takes_pages(ecfg.page_size, cache_dtype)
                if _pk._on_tpu() else ecfg.fused_decode):
            self.kv_path = "pallas_paged"
        else:
            self.kv_path = "xla_gather"
        self._exec: Dict[str, Any] = {}
        self._sig_history: Dict[str, List[dict]] = {}
        self.compiles = 0
        self.steady_state_recompiles = 0
        self._warm = False
        # how much slot headroom a generation step needs (the spec-decode
        # wrapper raises this to its window size)
        self.min_headroom = 1
        # draft engines under spec_decode turn this off: their tokens
        # are proposals, not served output
        self.meter_tokens = True
        # set when an executable fails AFTER its cache buffers were donated
        # (the pools are invalidated by donation, so no later call can be
        # trusted) — every serving entrypoint refuses from then on
        self.poisoned: Optional[str] = None
        # Scheduler.steps of the step now running, stamped by the
        # scheduler so that serve/prefill names the step it ran in
        self.sched_step: Optional[int] = None
        # optional persistent prefix store (serving/prefix_store.py):
        # published pages survive restarts — attach_prefix_store()
        self.prefix_store = None
        self._tokens_window: List[Tuple[float, int]] = []  # (t, n) samples
        # what the last prefill / decode call's expert layers reported
        # (``_note_experts``); None for a model without experts
        self.last_expert_load: Optional[Dict[str, int]] = None
        # early dispatch (docs/serving.md "The tick's anatomy"): the hook
        # a Scheduler installs round its tick, asked for the next tick as
        # soon as a tick's sampled tokens are on the host: {slot: token
        # sampled} -> ({slot: input token}, {slot: SamplingParams}) or
        # None. A call from anyone else (a test, a resumed request's
        # replay, the speculative wrapper) finds none and never runs ahead.
        self.next_tick = None
        # the tick dispatched ahead of its decode_step_sampled call
        self._ahead: Optional[_Tick] = None

    def _beside_plain_pages(self):
        """``(what the model has, what a mechanism would have to carry)``
        for a model whose slots hold more or other than pages of keys and
        values, else None."""
        if self.model.recurrent:
            return "recurrent layers", "recurrent state"
        if getattr(self.model, "latent", False):
            return "a latent cache", "latent rows"
        if len(self.model.cache_pools.get("groups", ())) > 1:
            return "several page groups", "a table a group"
        return None

    def _refuse_what_cannot_carry_state(self, ecfg: EngineConfig) -> None:
        """The one place the rule is stated. A model whose layers keep
        different spans of the context (window and global layers) has a
        page table a group, and the mechanisms below name a slot's pages
        through one. A model with recurrent layers
        keeps, beside its pages, a state a slot that is advanced token by
        token and cannot be cut at a page boundary, rolled back, exported
        or split over chips by anything built so far. A model with latent
        attention keeps one compressed row a token in ONE pool, and the
        mechanisms below are written for pages of keys and values of equal
        heads. Each mechanism that would have to carry either is refused by
        name. The verify window's layers are the description's own
        (``verify_layers``): one without them is refused the window."""
        beside = self._beside_plain_pages()
        if beside is None:
            if ecfg.verify_window and not hasattr(self.model,
                                                  "verify_layers"):
                raise ValueError(
                    f"{type(self.model).__name__} has no verify_layers: the "
                    "verify window (verify_window > 0, speculative "
                    "decoding) runs the description's own window layers")
            return
        has, carry = beside
        recurrent = self.model.recurrent
        grouped = len(self.model.cache_pools.get("groups", ())) > 1

        def refuse(mechanism, why, why_latent=None, pages=True):
            if grouped and pages:
                # window and global layers under one manager: whatever
                # names a slot's pages names ONE table of them
                why = ("it is written for one page table a slot, every "
                       "layer holding every page"
                       + ("; pass prefix_cache=False"
                          if "prefix_cache" in mechanism else ""))
            elif not recurrent and why_latent:
                why = why_latent
            raise ValueError(
                f"{type(self.model).__name__} has {has}: {mechanism} cannot "
                f"carry {carry} ({why})")

        if ecfg.prefix_cache:
            refuse("the prefix cache (prefix_cache=True)",
                   "a cached page holds keys and values, not the state at "
                   "its boundary; pass prefix_cache=False",
                   "no continuation prefill attends a rung over cached "
                   "latent rows; pass prefix_cache=False")
        if ecfg.verify_window:
            refuse("the verify window (verify_window > 0, speculative "
                   "decoding)", "a rejected draft token cannot be taken "
                   "back out of the state",
                   "the verify program is the GPT block's, over a key and "
                   "a value pool")
        if ecfg.sharding is not None:
            refuse("the tensor-parallel engine (sharding='tp')",
                   "no plan shards the scan's channels",
                   "no plan shards the latent projections or the experts, "
                   "and the shared row has no head axis to split")
        if ecfg.weight_dtype not in ("bf16", "f32"):
            refuse(f"weight_dtype {ecfg.weight_dtype!r} (int8)",
                   "the quantiser's flat chunks are dequantised by the "
                   "GPT block's programs alone; use 'bf16' or 'f32'",
                   pages=False)
        if ecfg.role != "colocated":
            refuse(f"role {ecfg.role!r} (phase disaggregation, "
                   "kv_transfer)", "a hand-off ships pages only",
                   "a hand-off ships pages of keys and values")

    def _refuse_kv_transfer(self) -> None:
        beside = self._beside_plain_pages()
        if beside is not None:
            raise ValueError(
                f"{type(self.model).__name__} has {beside[0]}: "
                "kv_transfer (export_request_kv / adopt_request_kv) ships "
                "pages of keys and values of one table and cannot carry "
                f"{beside[1]}")

    def attach_prefix_store(self, store) -> int:
        """Arm warm restart (docs/serving.md "Resilience"): restore the
        store's committed prefix records into the pool + prefix cache
        NOW (call before :meth:`warmup`), and persist every later
        publish through it. Returns how many records were restored."""
        if self.prefix is None:
            raise ValueError("prefix store needs prefix_cache enabled")
        self.prefix_store = store
        return store.restore_into(self)

    # -- KV handoff surface (serving/kv_transfer.py, ISSUE 17) ----------
    def cache_fingerprint(self):
        """Geometry fingerprint of this engine's KV cache — the
        compatibility check on every handoff / prefix-store restore."""
        from .kv_transfer import cache_fingerprint

        return cache_fingerprint(self.cache)

    def export_request_kv(self, slot: int, tokens=None) -> dict:
        """Serialize a live slot's KV state for migration to a decode
        replica (chunked, CRC-stamped, fingerprinted). The slot stays
        live until the caller frees it."""
        from .kv_transfer import export_slot

        self._refuse_kv_transfer()
        return export_slot(self, slot, tokens=tokens)

    def adopt_request_kv(self, handoff: dict) -> int:
        """Materialize a migrated request's KV state into a fresh slot
        (the decode half of a handoff). Raises CacheConfigMismatch on
        geometry drift. Must run on the serving loop thread — it writes
        the cache arrays between executable calls."""
        from .kv_transfer import adopt_into_engine

        self._refuse_kv_transfer()
        return adopt_into_engine(self, handoff)

    def _init_tp(self, qparams) -> None:
        """Mesh + NamedShardings for the tp engine: KV heads and the
        attention/MLP weight split derive from the PR 12 GPT annotation
        set through plan-level suffix inheritance."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        from ..parallel.mesh import build_mesh
        from ..sharding.plan import (complete_pytree_specs,
                                     gpt_annotations, named_sharding_tree)

        tp = int(self.ecfg.tp)
        if tp < 2:
            raise ValueError("sharding='tp' needs tp >= 2")
        if self.ecfg.weight_dtype == "int8":
            raise ValueError(
                "sharding='tp' cannot head-shard int8's flat chunk "
                "layout — use weight_dtype 'f32' or 'bf16'")
        if len(jax.devices()) < tp:
            raise ValueError(
                f"tp={tp} needs {tp} devices, have {len(jax.devices())}")
        if self.cfg.num_heads % tp or self.cfg.d_ff % tp:
            raise ValueError(
                f"tp={tp} must divide num_heads {self.cfg.num_heads} and "
                f"d_ff {self.cfg.d_ff}")
        self._mesh = build_mesh([("tp", tp)], jax.devices()[:tp])
        ann = gpt_annotations("tp", tp_axis="tp")
        specs, self.tp_derived = complete_pytree_specs(
            qparams, ann, {"tp": tp})
        self._param_sh = named_sharding_tree(specs, self._mesh)
        # the plan splits the KV head axis, which flat rows no longer
        # show: a sharded engine's pools are [L, P, page, nh, hd], the
        # head axis at dim 3, and its tick gathers
        self._pool_rows = ((self.cfg.num_heads, self.cfg.head_dim),) * 2
        self._cache_sh = NamedSharding(
            self._mesh, P(None, None, None, "tp", None))
        self._repl_sh = NamedSharding(self._mesh, P())

    # ------------------------------------------------------------------
    # pure functions (traced once per executable)
    # ------------------------------------------------------------------
    def _prefill_fn_paged(self, qparams, caches, feed):
        """Paged (prefix-cache capable) prefill. ``feed``
        (:func:`cut_rung_feed`): tokens [1, T], the SUFFIX after
        ``prefix_len`` cached tokens, its ``length``, the slot and its
        table row, the request's sampling scalars; ``caches`` the cache
        manager's arrays (``PagedKVCache.arrays``: the two pools, and a
        recurrent model's two state arrays). ``embed -> layers -> final
        norm -> head``, the layers the model's own: they write the suffix
        K/V into the slot's pages (and the states after ``length - 1``
        into the slot's state rows). prefix_len == 0 is a plain paged
        prefill."""
        m = self.model
        tokens, length, prefix_len, table_row, slot, sp = cut_rung_feed(
            feed, self.table_width)
        T = tokens.shape[1]
        positions = prefix_len + jnp.arange(T)
        x = m.embed(qparams, tokens, positions[None])          # [1, T, D]
        x, caches, *report = m.prefill_layers(qparams, x, caches, _model.ctx(
            length=length, prefix_len=prefix_len, table_row=table_row,
            slot=slot, page_size=self.ecfg.page_size,
            table_widths=self.table_widths))
        h_last = jax.lax.dynamic_index_in_dim(x[0], length - 1, axis=0,
                                              keepdims=False)
        logits = m.logits(qparams, h_last)
        tok = samp.sample_token(logits, *sp, prefix_len + length - 1)
        return (caches, logits, tok, *report)

    def _decode_fn_paged(self, qparams, caches, feed):
        """``feed`` (:func:`cut_slot_feed`: a token, a position, ``actives``
        and the sampling knobs a slot, its page-table row) -> (caches,
        logits[B, V], tokens[B]): one token per slot, the layers the
        model's own (``serving/model.py``). Per-slot page tables
        [B, max_pages] route the one-row write and the attention read
        through the shared pool;
        lanes that do not ride have an all-zero table row (their write
        lands on the scratch page) and ``actives`` 0 (a recurrent model
        leaves their state as it is)."""
        m = self.model
        tokens, positions, tables, actives, sp = cut_slot_feed(
            feed, self.table_width)
        x = m.embed(qparams, tokens[:, 0], positions)
        x, caches, *report = m.decode_layers(qparams, x, caches, _model.ctx(
            positions=positions, tables=tables, actives=actives,
            page_size=self.ecfg.page_size, kv_path=self.kv_path,
            fused=self.ecfg.fused_decode, table_widths=self.table_widths))
        logits = m.logits(qparams, x, fused=self.ecfg.fused_decode)
        toks = samp.sample_batch(logits, *sp, positions)
        # a model with experts hands its layers' report out with the
        # logits: one small int32 array, no second program
        return (caches, logits, toks, *report)

    def _verify_fn_paged(self, qparams, caches, feed):
        """The verify window: ``feed`` is the tick's with W tokens a slot
        and the window's start for a position (``actives`` is not read: a
        lane that sits out has a zero table row) -> (caches, logits[B, W,
        V], tokens[B, W]), the layers the model's own ``verify_layers``:
        B*W rows written through the page tables at ``start + w``."""
        m = self.model
        tokens, starts, tables, _actives, sp = cut_slot_feed(
            feed, self.table_width)
        positions = starts[:, None] + jnp.arange(tokens.shape[1])  # [B, W]
        x = m.embed(qparams, tokens, positions)
        x, caches = m.verify_layers(qparams, x, caches, _model.ctx(
            starts=starts, positions=positions, tables=tables,
            page_size=self.ecfg.page_size))
        logits = m.logits(qparams, x)
        toks = samp.sample_window(logits, *sp, positions)
        return caches, logits, toks

    # ------------------------------------------------------------------
    # AOT compilation (PR 4 discipline: explicit lower+compile, program
    # report, recompile-explainer integration)
    # ------------------------------------------------------------------
    def _make_sig(self, example_args) -> dict:
        leaves = jax.tree_util.tree_leaves(example_args)
        feed_sig = [(f"arg{i}", tuple(np.shape(a)),
                     str(jnp.result_type(a))) for i, a in enumerate(leaves)]
        return _prep.make_sig(feed_sig, fetch_names=())

    def _shardings_for(self):
        """(in_shardings, out_shardings) pytrees for the tp mesh: params
        take the plan shardings, the pools the KV-head split, the feed
        and the two outputs behind the caches (logits, tokens) replicate.
        None/None off-mesh."""
        if self._mesh is None:
            return None, None
        caches = (self._cache_sh, self._cache_sh)
        return ((self._param_sh, caches, self._repl_sh),
                (caches, self._repl_sh, self._repl_sh))

    def _compile(self, name: str, fn, example_args,
                 donate_argnums: Tuple[int, ...]) -> Any:
        from ..parallel import health as _health

        sig = self._make_sig(example_args)
        hist = self._sig_history.setdefault(name, [])
        if hist:
            # a same-name rebuild is exactly what steady state must never
            # do: explain it through the PR 4 taxonomy and count it
            cause, detail = _prep.explain_recompile(sig, hist)
            _prep.note_recompile(f"serve/{name}", cause, detail)
            if self._warm:
                self.steady_state_recompiles += 1
        hist.append(sig)
        del hist[:-8]
        in_sh, out_sh = self._shardings_for()
        jit_kw: Dict[str, Any] = dict(
            donate_argnums=donate_argnums if self._donate else ())
        if in_sh is not None:
            jit_kw.update(in_shardings=in_sh, out_shardings=out_sh)
        jitted = jax.jit(fn, **jit_kw)
        t0 = time.perf_counter_ns()
        with _health.suspend():
            lowered = jitted.lower(*example_args)
            compiled = lowered.compile()
        compile_ms = (time.perf_counter_ns() - t0) / 1e6
        self.compiles += 1
        donated = [f"arg{i}" for i in donate_argnums] if self._donate else []
        _prep.capture(
            f"serve/{name}", compiled=compiled, compile_ms=compile_ms,
            donated=donated, inputs=example_args,
            extra={"engine": {
                "max_batch": self.ecfg.max_batch,
                "max_seq": self.ecfg.max_seq,
                "weight_dtype": self.ecfg.weight_dtype,
                "cache_dtype": str(jnp.dtype(self.cache.dtype).name),
                "sharding": self.ecfg.sharding or "none",
                "tp": self.ecfg.tp,
                "buckets": list(self.buckets),
            }})
        return compiled

    # -- the feed's host side (layout: the head of this file) -------------
    @property
    def table_width(self) -> int:
        """Pages a slot's table row names (the cache manager's
        ``max_pages_per_slot``; with several page groups their rows side
        by side), from the configuration: a program is traced for an
        engine that holds no cache too (tests/test_chip_compile.py)."""
        return sum(self.table_widths)

    @property
    def table_widths(self) -> Tuple[int, ...]:
        """Entries of a slot's table row a page group, in the groups'
        order (``paged_kv.table_width``)."""
        groups = self.model.cache_pools.get("groups") or ({"window": None},)
        return tuple(_table_width(g.get("window"), self.ecfg.max_seq,
                                  self.ecfg.page_size) for g in groups)

    def _slot_feed(self, width: int = 1, params_by_slot=None):
        """A tick's feed, or with ``width`` W a verify window's, every
        lane dead and the sampler's block written (greedy where
        ``params_by_slot`` names no slot: what the programs are compiled
        from and warmed with) -> (feed, ``samp.batch_arrays``' four
        vectors, views of its columns)."""
        B, M = self.ecfg.max_batch, self.table_width
        feed = np.zeros(slot_feed_shape(B, M, width), np.int32)
        at = M + _SLOT_SCALARS
        return feed, samp.batch_arrays(params_by_slot or {}, B,
                                       out=feed[:, at:at + _SAMPLING])

    def _rung_feed(self, bucket: int, suffix, prefix_len: int, slot: int,
                   table_row, params: SamplingParams):
        """A prefill rung's feed -> (feed, the request's four sampling
        values as one-element views of it)."""
        M = self.table_width
        at = M + _RUNG_SCALARS
        feed = np.zeros((rung_feed_len(M, bucket),), np.int32)
        if table_row is not None:
            feed[:M] = table_row
        feed[M:at] = (len(suffix), prefix_len, slot)
        sp = samp.batch_arrays({0: params}, 1,
                               out=feed[None, at:at + _SAMPLING])
        feed[at + _SAMPLING:at + _SAMPLING + len(suffix)] = suffix
        return feed, sp

    # Every program takes the manager's arrays as ONE argument (a tuple:
    # the pools, and a recurrent model's state arrays), donated whole.
    def _prefill_program(self, bucket: int):
        """(fn, example args) of one prefill rung, as _decode_program."""
        feed, _sp = self._rung_feed(bucket, (0,), 0, 0, None, GREEDY)
        return self._prefill_fn_paged, (
            self.qparams, self.cache.arrays(), feed)

    def _prefill_exec(self, bucket: int):
        name = f"prefill_b{bucket}"
        exe = self._exec.get(name)
        if exe is None:
            fn, example = self._prefill_program(bucket)
            exe = self._compile(name, fn, example, donate_argnums=(1,))
            self._exec[name] = exe
        return exe

    def _decode_program(self):
        """(fn, example args) of the decode tick — what _decode_exec
        compiles; tests/test_chip_compile.py lowers the same pair for a
        described chip from the example's shapes."""
        return self._decode_fn_paged, (
            self.qparams, self.cache.arrays(), self._slot_feed()[0])

    def _decode_exec(self):
        exe = self._exec.get("decode")
        if exe is None:
            fn, example = self._decode_program()
            exe = self._compile("decode", fn, example, donate_argnums=(1,))
            self._exec["decode"] = exe
        return exe

    def _call(self, exe, feed):
        """One prefill, decode or verify call on the live caches, its host
        arguments the one ``feed``: ``(caches, rest)`` back, the caches
        for ``self.cache.set_arrays`` once the call is known to have
        run."""
        out = exe(self.qparams, self.cache.arrays(), feed)
        return out[0], out[1:]

    def _verify_program(self):
        """(fn, example args) of the verify window, as _decode_program."""
        return self._verify_fn_paged, (
            self.qparams, self.cache.arrays(),
            self._slot_feed(self.ecfg.verify_window)[0])

    def _verify_exec(self):
        W = self.ecfg.verify_window
        if W < 2:
            raise ValueError("verify executable needs verify_window >= 2")
        name = f"verify_w{W}"
        exe = self._exec.get(name)
        if exe is None:
            fn, example = self._verify_program()
            exe = self._compile(name, fn, example, donate_argnums=(1,))
            self._exec[name] = exe
        return exe

    def warmup(self) -> Dict[str, float]:
        """Compile every executable the steady state will ever need (the
        decode program + one prefill per bucket + the verify window when
        configured) and run each once so the first real request pays no
        compile and no first-dispatch cost. Returns {executable_name:
        wall ms per warm call}."""
        from ..framework.core import ensure_compile_cache

        ensure_compile_cache()
        timings: Dict[str, float] = {}

        def _warm_call(label, exe, example):
            # the example arguments the executable was compiled from, on
            # the live caches: all-zero tables and ``actives``, so a warm
            # call writes the scratch page and slot 0's dead state alone
            t0 = time.perf_counter()
            caches, rest = self._call(exe, example[2])
            jax.block_until_ready(rest[0])
            self.cache.set_arrays(caches)
            timings[label] = (time.perf_counter() - t0) * 1e3

        _warm_call("decode", self._decode_exec(), self._decode_program()[1])
        for bucket in self.buckets:
            _warm_call(f"prefill_b{bucket}", self._prefill_exec(bucket),
                       self._prefill_program(bucket)[1])
        if self.ecfg.verify_window >= 2:
            _warm_call(f"verify_w{self.ecfg.verify_window}",
                       self._verify_exec(), self._verify_program()[1])
        # transfer-path gather/scatter (KV handoff + prefix store): one
        # compiled shape each — warmed here so a disagg handoff's first
        # export/adopt never pays a mid-request compile (~100ms). An
        # engine that refuses the transfer (_refuse_kv_transfer) warms
        # nothing: the scatter is not donated and would hold a second
        # copy of both pools beside the first
        t0 = time.perf_counter()
        if self._beside_plain_pages() is None:
            k0, v0 = self.cache.read_pages([0])
            self.cache.write_pages([0], k0, v0)
            timings["kv_transfer"] = (time.perf_counter() - t0) * 1e3
        self._warm = True
        return timings

    # ------------------------------------------------------------------
    # host-side serving API (one scheduler thread)
    # ------------------------------------------------------------------
    def _check_poisoned(self) -> None:
        if self.poisoned is not None:
            raise RuntimeError(f"engine poisoned: {self.poisoned}")

    def _poison_on_donation_failure(self, name: str, exc: Exception,
                                    swapped: bool = False) -> None:
        """An executable compiled with donate_argnums died mid-call: the
        cache pools it was handed are donation-invalidated, so cache.k/v
        can no longer be trusted. Mark the engine fatally poisoned rather
        than let later calls read freed buffers. (Without donation — CPU —
        the pools are untouched and the engine stays usable, unless the
        call's outputs were ``swapped`` in before it failed: a tick
        dispatched ahead.)"""
        if (self._donate or swapped) and self.poisoned is None:
            self.poisoned = (
                f"{name} failed after cache-buffer donation "
                f"({type(exc).__name__}: {exc}); KV pools invalidated — "
                f"rebuild the engine")

    def bucket_for(self, n: int) -> int:
        for b in self.buckets:
            if b >= n:
                return b
        raise PromptTooLongError(
            f"prompt length {n} exceeds the largest prefill bucket "
            f"{self.buckets[-1]}")

    def can_admit(self, prompt_len: int) -> bool:
        """Would a prompt admit RIGHT NOW (slot + page budget)? The
        scheduler's head-of-line check — never raises."""
        # conservative: require the full prompt's pages (a prefix hit
        # only makes admission cheaper; reclaimable cache pages count)
        return self.cache.can_admit(prompt_len)

    def _trim_prefix(self, n: int, prefix_len: int,
                     prefix_pages: Tuple[int, ...]):
        """Shrink a prefix hit until the suffix bucket fits behind it
        (prefix_len + bucket(suffix) <= max_seq keeps the page-write
        slice in range)."""
        while prefix_len > 0:
            try:
                bucket = self.bucket_for(n - prefix_len)
            except PromptTooLongError:
                bucket = None
            if bucket is not None and prefix_len + bucket <= self.ecfg.max_seq:
                return prefix_len, prefix_pages
            prefix_len -= self.cache.page_size
            prefix_pages = prefix_pages[:-1]
        return 0, ()

    def start_sequence(self, tokens: Sequence[int]) -> Tuple[int, np.ndarray]:
        """Claim a slot, prefill the prompt, return (slot, logits[V]) of
        the last prompt position — argmax of it is the first generated
        token. Raises CacheFullError when no slot is free,
        PagePoolFullError when the paged pool is dry, and
        PromptTooLongError above the ladder."""
        slot, logits, _tok = self.start_sequence_sampled(tokens, GREEDY)
        return slot, logits

    def start_sequence_sampled(
            self, tokens: Sequence[int], params: SamplingParams
    ) -> Tuple[int, np.ndarray, int]:
        """:meth:`start_sequence` plus in-executable sampling: returns
        (slot, last-position logits[V], first generated token)."""
        self._check_poisoned()
        n = len(tokens)
        if n < 1:
            raise ValueError("empty prompt")
        # a real open span, under the scheduler's per-request context
        # (the admit path wraps this call in the request's trace); its
        # four phases are its children
        # scan_tokens: what a recurrent model's scan has to process (the
        # prompt: such a model is never given a prefix); 0 where no layer
        # scans
        # delta_chunks: the chunks a chunked recurrence has to process
        # for the prompt (0 where the model has none)
        chunks = getattr(self.model, "delta_chunks", None)
        attrs = {"prompt_len": n, "step": self.sched_step,
                 "scan_tokens": n if self.model.recurrent else 0,
                 "delta_chunks": chunks(n) if chunks else 0}
        with _spans.span("serve/prefill", attrs=attrs):
            with _spans.span("prefill/prep") as prep:
                prefix_len, prefix_pages = 0, ()
                if self.prefix is not None:
                    # the prompt is hashed once: the publication below
                    # takes the keys the lookup was made with
                    keys = self.prefix.page_keys(tokens)
                    prep.set_attr("prefix_pages_hashed", len(keys))
                    prefix_len, prefix_pages = self.prefix.lookup(
                        tokens, keys)
                    prefix_len, prefix_pages = self._trim_prefix(
                        n, prefix_len, tuple(prefix_pages))
                suffix = list(tokens[prefix_len:])
                bucket = self.bucket_for(len(suffix))
                exe = self._prefill_exec(bucket)
                slot = self.cache.alloc(length=n, prefix_pages=prefix_pages)
                table_row = self.cache.table_row(slot)
                feed, sp = self._rung_feed(bucket, suffix, prefix_len, slot,
                                           table_row, params)
                attrs.update(bucket=bucket, prefix_len=prefix_len, slot=slot)
                if len(self.cache.groups) > 1:
                    # pages the prompt took, a page group
                    attrs.update({f"pages_{name}": n for name, n in
                                  self.cache.pages_held(slot).items()})
            caches, logits, tok = self._run_prefill(
                exe, bucket, slot, len(suffix), sp, feed)
            if self.last_expert_load is not None:
                attrs.update(
                    expert_tokens=self.last_expert_load["expert_tokens"],
                    experts_hit=self.last_expert_load["experts_hit"])
            with _spans.span("prefill/publish") as publish:
                self.cache.set_arrays(caches)
                if self.prefix is not None:
                    evicted = self.prefix.evicted
                    added = self.prefix.insert(tokens, table_row, keys)
                    publish.set_attr("prefix_added", added)
                    publish.set_attr("prefix_evicted",
                                     self.prefix.evicted - evicted)
                    if added and self.prefix_store is not None:
                        # persist at publish time: the pages just written are
                        # the ones a recycled replica restores (async,
                        # CRC-committed)
                        self.prefix_store.maybe_publish(tokens, table_row,
                                                        self.cache)
            return slot, logits, tok

    def _run_prefill(self, exe, bucket: int, slot: int, n_tokens: int,
                     sp, feed):
        """The prefill executable's call on ``feed`` (``sp``: the
        request's sampling values on the host): ``prefill/run`` until the
        sampled token is on the host (that waits for the program), then
        ``prefill/fetch_logits``, the transfer of the logits alone."""
        t0 = time.perf_counter_ns()
        sampler = _note_sampler("prefill", *sp[:3])
        try:
            with _spans.span("prefill/run",
                             attrs={"sampler": sampler}) as run:
                with _spans.span("prefill/call", attrs={
                        "exe": f"prefill_b{bucket}"}) as call:
                    caches, (logits, tok, *report) = self._call(exe, feed)
                run.set_attr("call", call.span_id)
                tok = int(tok)
            with _spans.span("prefill/fetch_logits"):
                logits = np.asarray(logits)
            self._note_experts(report, n_tokens)
        except Exception as e:
            self._poison_on_donation_failure(f"prefill_b{bucket}", e)
            self.cache.free(slot)
            raise
        smetrics.m_prefill_ms.observe((time.perf_counter_ns() - t0) / 1e6)
        smetrics.m_prefill_tokens.inc(n_tokens)
        return caches, logits, tok

    def resume_sequence_sampled(
            self, tokens: Sequence[int], params: SamplingParams
    ) -> Tuple[int, np.ndarray, int]:
        """Re-prefill a preempted request's prompt+generated stream —
        which may exceed the bucket ladder: the head prefills through
        the largest bucket and the tail replays through the decode
        executable (whose sampled outputs are discarded; the stream is
        already known). Returns (slot, last logits[V], next token) like
        :meth:`start_sequence_sampled` — same executables, zero new
        compiles."""
        n = len(tokens)
        if n <= self.buckets[-1]:
            return self.start_sequence_sampled(tokens, params)
        head = list(tokens[:self.buckets[-1]])
        slot, _logits, _tok = self.start_sequence_sampled(head, params)
        try:
            # the replayed tail is prefill work too: a second
            # serve/prefill beside the head's, the decode calls under it
            with _spans.span("serve/prefill", attrs={
                    "prompt_len": n, "replayed": n - len(head),
                    "slot": slot, "step": self.sched_step,
                    "scan_tokens": 0}):
                for i in range(len(head), n - 1):
                    self.decode_step_sampled({slot: int(tokens[i])}, None)
                out = self.decode_step_sampled(
                    {slot: int(tokens[n - 1])}, {slot: params})
        except Exception:
            if self.poisoned is None and self.cache.is_live(slot):
                self.cache.free(slot)
            raise
        tok, logits = out[slot]
        return slot, logits, tok

    def ensure_decode_capacity(self, slot: int, extra: int = 1) -> bool:
        """Make the next ``extra`` token positions of ``slot`` writable:
        maps pages on demand (False = pool dry even after prefix-cache
        reclaim — the scheduler preempts)."""
        return self.cache.ensure_capacity(
            slot, self.cache.length(slot) + extra)

    def live_pages(self, slots) -> int:
        """Pages a decode tick over ``slots`` reads: those holding each
        slot's rows up to the one the tick writes."""
        return sum(self.cache.pages_for(self.cache.length(s) + 1)
                   for s in slots)

    @property
    def latent_token_bytes(self) -> int:
        """Bytes of latent rows a cached token holds, all layers (the
        values, not the lanes a stored row is padded to): what a decode
        tick reads of it; 0 for a model whose pages hold keys and values."""
        if not getattr(self.model, "latent", False):
            return 0
        return (self.model.cfg.latent_width * self.cache.num_layers
                * jnp.dtype(self.cache.dtype).itemsize)

    def _note_experts(self, report, n_tokens: int) -> None:
        """Read the expert layers' report of the call that just ran
        (``[expert layers, G + 1]`` int32: tokens on each held expert, and
        last the held pairs that reached none) into ``last_expert_load``
        and the ``moe_*`` counters. ``n_tokens``: the tokens that were
        routed (a rung's valid ones, a tick's riders)."""
        if not report:
            return
        report = np.asarray(report[0])
        counts, dropped = report[:, :-1], int(report[:, -1].sum())
        here = int(counts.sum())
        routed = n_tokens * self.model.cfg.num_experts_per_tok \
            * counts.shape[0]
        load = {"expert_tokens": here,
                "experts_hit": int(np.count_nonzero(counts)),
                "expert_load_max": int(counts.max())}
        self.last_expert_load = load
        smetrics.m_moe_routed.labels("here").inc(here)
        smetrics.m_moe_routed.labels("elsewhere").inc(routed - here)
        smetrics.m_moe_load_max.set(load["expert_load_max"])
        smetrics.m_moe_dropped.inc(dropped)

    def state_bytes(self, slots) -> int:
        """Bytes the state rows of ``slots`` hold, every recurrent layer's
        conv row and state together: what a decode tick over those riders
        has to read and to write back, whatever the program moves (0 for a
        model without recurrent layers)."""
        return self.cache.state_bytes_per_slot * len(slots)

    def _decode_feed(self, slot_tokens: Dict[int, int], params_by_slot):
        """A tick's feed with its riders' tokens, positions and sampling
        knobs in -> (feed, the sampler's four vectors)."""
        is_live, length = self.cache.is_live, self.cache.length
        max_seq = self.ecfg.max_seq
        positions = []
        for slot in slot_tokens:
            if not is_live(slot):
                raise ValueError(f"slot {slot} is not live")
            n = length(slot)
            if n >= max_seq:                      # no headroom
                raise ValueError(f"slot {slot} is at max_seq {max_seq}")
            positions.append(n)
        feed, sp = self._slot_feed(params_by_slot=params_by_slot)
        riders = np.fromiter(slot_tokens, np.intp, len(slot_tokens))
        feed[riders, self.table_width + _POSITION] = positions
        feed[riders, -1] = list(slot_tokens.values())
        return feed, sp

    def _masked_tables(self, active_slots, feed: np.ndarray) -> None:
        """The riders' page-table rows and ``actives`` into ``feed``, by
        one row index: a lane that does not ride keeps its zero row, so
        its write lands in the scratch page — a live slot absent from
        this call keeps its pages untouched."""
        riders = np.fromiter(active_slots, np.intp, len(active_slots))
        M = self.table_width
        feed[riders, :M] = self.cache.table_rows(riders)
        feed[riders, M + _ACTIVE] = 1

    def decode_step(self, slot_tokens: Dict[int, int]) -> Dict[int, np.ndarray]:
        """One greedy-compatible decode step for the given
        {slot: input_token} map. Returns {slot: logits[V]} (PR 9 API —
        callers argmax host-side; :meth:`decode_step_sampled` returns the
        in-executable sampled tokens too)."""
        out = self.decode_step_sampled(slot_tokens, None)
        return {slot: logits for slot, (_tok, logits) in out.items()}

    def decode_step_sampled(
            self, slot_tokens: Dict[int, int],
            params_by_slot: Optional[Dict[int, SamplingParams]]
    ) -> Dict[int, Tuple[int, np.ndarray]]:
        """One decode step with per-slot sampling: {slot: input_token} ->
        {slot: (next_token, logits[V])}. Slots not in the map ride as
        masked lanes — same shapes, same executable, zero recompiles.

        Under a scheduler (``next_tick``) the call may find its tick
        already in flight, dispatched by the call before it, and may
        dispatch the next one before it returns (docs/serving.md "The
        tick's anatomy"); what it returns is the same either way."""
        if not slot_tokens:
            return {}
        self._check_poisoned()
        # phases, each a span under the scheduler's serve/decode_tick:
        # feed (host arrays), run (the call until the sampled tokens are
        # on the host: the small array first, it waits for the program),
        # plan (a scheduler's next tick, dispatched while this one's
        # logits are still on the device), fetch_logits (the transfer
        # alone), commit (host bookkeeping). The executable's call alone
        # is decode/call (_launch): under this run for a tick fed here,
        # under its predecessor's plan, with its feed, for a tick found
        # in flight.
        tick = self._claim_ahead(slot_tokens)
        was_ahead = tick is not None
        if was_ahead:
            sampler = tick.sampler
        else:
            with _spans.span("decode/feed"):
                feed, sampler = self._tick_args(slot_tokens, params_by_slot)
        try:
            with _spans.span("decode/run",
                             attrs={"sampler": sampler}) as run:
                if tick is None:
                    tick = self._launch(slot_tokens, feed, sampler)
                # the link across two steps: a tick found in flight was
                # called under the step before
                run.set_attr("call", tick.call)
                toks = np.asarray(tick.toks)
                sampled = {slot: int(toks[slot]) for slot in slot_tokens}
        except Exception as e:
            self._poison_on_donation_failure("decode", e, swapped=was_ahead)
            raise
        # dispatch to tokens on the host: the tick's round trip
        smetrics.m_decode_ms.observe(
            (time.perf_counter_ns() - tick.t0) / 1e6)
        plans = self.next_tick is not None and self._ahead is None
        if plans:
            with _spans.span("decode/plan"):
                self._advance(tick, slot_tokens)
                nxt = self.next_tick(sampled)
                if nxt is not None:
                    self.dispatch_ahead(*nxt)
        try:
            with _spans.span("decode/fetch_logits"):
                logits = np.asarray(tick.logits)
            self._note_experts(tick.report, len(slot_tokens))
        except Exception as e:
            self._poison_on_donation_failure("decode", e, swapped=was_ahead)
            raise
        with _spans.span("decode/commit"):
            if not plans:
                self._advance(tick, slot_tokens)
            out = {slot: (tok, logits[slot])
                   for slot, tok in sampled.items()}
            self.note_tokens(len(slot_tokens))
        return out

    def _tick_args(self, slot_tokens: Dict[int, int], params_by_slot):
        """The decode call's feed for these riders, their next rows'
        pages mapped: ``(feed, sampler path)``."""
        feed, sp = self._decode_feed(slot_tokens, params_by_slot)
        for slot in slot_tokens:
            if not self.ensure_decode_capacity(slot):
                raise PagePoolFullError(
                    f"slot {slot}: no free page for position "
                    f"{self.cache.length(slot)}")
        self._masked_tables(slot_tokens, feed)
        sampler = _note_sampler("decode", *sp[:3])
        return feed, sampler

    def _launch(self, slot_tokens: Dict[int, int], feed, sampler: str,
                ahead: bool = False) -> _Tick:
        """Dispatch the decode executable; nothing here waits for it.
        ``decode/call`` is the engine's boundary with the device: the
        executable's call and only that."""
        exe = self._decode_exec()
        t0 = time.perf_counter_ns()
        with _spans.span("decode/call", attrs={"exe": "decode"}) as call:
            caches, (logits, toks, *report) = self._call(exe, feed)
        if ahead:
            self.cache.set_arrays(caches)
            caches = None
        return _Tick(dict(slot_tokens), caches, logits, toks, report,
                     sampler, t0, call.span_id)

    def _advance(self, tick: _Tick, slots) -> None:
        """A collected tick's rows become part of its riders' sequences."""
        if tick.caches is not None:
            self.cache.set_arrays(tick.caches)
            tick.caches = None
        for slot in slots:
            self.cache.set_length(slot, self.cache.length(slot) + 1)

    def dispatch_ahead(self, feed: Dict[int, int], params_by_slot) -> None:
        """Dispatch the tick for ``feed`` now; the next
        ``decode_step_sampled`` call for these riders collects it."""
        if self._ahead is not None:
            raise RuntimeError(f"a tick is in flight for {self._ahead.feed}")
        host_feed, sampler = self._tick_args(feed, params_by_slot)
        try:
            self._ahead = self._launch(feed, host_feed, sampler, ahead=True)
        except Exception as e:
            self._poison_on_donation_failure("decode", e)
            raise

    def _claim_ahead(self, slot_tokens: Dict[int, int]) -> Optional[_Tick]:
        """The tick in flight, if this call is the one that collects it:
        its riders are the call's, less those freed since dispatch, whose
        lanes are dropped. A call for other slots altogether (the replay
        of a request resumed while it is in flight) runs beside it."""
        tick = self._ahead
        if tick is None or not tick.feed.keys() & slot_tokens.keys():
            return None
        if any(tick.feed.get(slot) != tok
               for slot, tok in slot_tokens.items()):
            raise RuntimeError(
                f"a tick is in flight for {tick.feed}, not for "
                f"{slot_tokens}")
        self._ahead = None
        gone = len(tick.feed) - len(slot_tokens)
        if gone:
            smetrics.m_early_dispatch.labels("dropped_lanes").inc(gone)
        return tick

    @property
    def ahead_feed(self) -> Optional[Dict[int, int]]:
        """{slot: input token} of the tick in flight, or None."""
        return None if self._ahead is None else self._ahead.feed

    def drop_ahead(self) -> None:
        """Forget the tick in flight: its riders are gone (an abort). The
        program runs to its end on the device; its caches are the
        manager's already and its tokens and logits are let go."""
        tick, self._ahead = self._ahead, None
        if tick is not None:
            smetrics.m_early_dispatch.labels("dropped_lanes").inc(
                len(tick.feed))

    def generate_step(
            self, slot_tokens: Dict[int, int],
            params_by_slot: Optional[Dict[int, SamplingParams]] = None
    ) -> Dict[int, List[int]]:
        """Uniform scheduler surface: one generation step -> {slot:
        [emitted tokens]}. The plain engine emits exactly one token per
        slot; the speculative wrapper (serving/spec_decode.py) emits up
        to its window."""
        return {slot: [tok] for slot, (tok, _logits) in
                self.decode_step_sampled(slot_tokens,
                                         params_by_slot).items()}

    def verify_step(
            self, windows: Dict[int, Sequence[int]],
            params_by_slot: Optional[Dict[int, SamplingParams]] = None
    ) -> Dict[int, Tuple[np.ndarray, np.ndarray]]:
        """Speculative verification: {slot: [W window tokens]} -> {slot:
        (logits[W, V], target_tokens[W])} in ONE batched call. Window
        rows are written into the cache at ``length + w``; slot lengths
        are NOT advanced — the caller decides how many window positions
        were accepted and calls :meth:`commit_window`."""
        if not windows:
            return {}
        self._check_poisoned()
        W = self.ecfg.verify_window
        if W < 2:
            raise RuntimeError("engine compiled without a verify window")
        feed, sp = self._slot_feed(W, params_by_slot)
        M = self.table_width
        for slot, win in windows.items():
            if len(win) != W:
                raise ValueError(
                    f"slot {slot}: window {len(win)} != {W}")
            if not self.cache.is_live(slot):
                raise ValueError(f"slot {slot} is not live")
            if self.cache.headroom(slot) < W:
                raise ValueError(f"slot {slot}: headroom < window {W}")
            feed[slot, -W:] = win
            feed[slot, M + _POSITION] = self.cache.length(slot)
        _note_sampler("verify", *sp[:3])
        exe = self._verify_exec()
        t0 = time.perf_counter_ns()
        try:
            for slot in windows:
                if not self.ensure_decode_capacity(slot, extra=W):
                    raise PagePoolFullError(
                        f"slot {slot}: no free pages for a {W}-token "
                        "verify window")
            self._masked_tables(windows, feed)
            with _spans.span("decode/call", attrs={"exe": f"verify_w{W}"}):
                caches, (logits, toks) = self._call(exe, feed)
            logits = np.asarray(logits)
            toks = np.asarray(toks)
        except PagePoolFullError:
            raise
        except Exception as e:
            self._poison_on_donation_failure(
                f"verify_w{W}", e)
            raise
        smetrics.m_decode_ms.observe((time.perf_counter_ns() - t0) / 1e6)
        self.cache.set_arrays(caches)
        return {slot: (logits[slot], toks[slot]) for slot in windows}

    def commit_window(self, slot: int, n_accepted_rows: int) -> None:
        """Advance ``slot`` past ``n_accepted_rows`` verified window rows
        (their K/V are already in the cache; rejected rows simply get
        overwritten by later writes)."""
        self.cache.set_length(slot, self.cache.length(slot)
                              + int(n_accepted_rows))

    def free_sequence(self, slot: int) -> None:
        self.cache.free(slot)

    # ------------------------------------------------------------------
    def note_tokens(self, n: int, window_s: float = 5.0) -> None:
        if not self.meter_tokens:
            return
        now = time.monotonic()
        smetrics.m_tokens.inc(n)
        w = self._tokens_window
        w.append((now, n))
        while w and w[0][0] < now - window_s:
            w.pop(0)
        span = now - w[0][0] if len(w) > 1 else 0.0
        if span > 0:
            smetrics.m_tokens_per_s.set(sum(x[1] for x in w) / span)

    # ------------------------------------------------------------------
    # reference / parity surface (tests + serve_bench quality bar)
    # ------------------------------------------------------------------
    def reference_logits(self, tokens: Sequence[int]) -> np.ndarray:
        """Full-forward f32-weight logits [T, V] for a prompt — the truth
        the cached decode path and the quantized weights are held to."""
        if self._ref_params is None:
            raise RuntimeError("reference params were dropped")
        toks = np.asarray(tokens, np.int32)[None]
        return np.asarray(
            self.model.forward(self._ref_params, toks)[0], np.float32)

    def drop_reference_params(self) -> None:
        self._ref_params = None

    @property
    def executables(self) -> List[str]:
        return sorted(self._exec)
