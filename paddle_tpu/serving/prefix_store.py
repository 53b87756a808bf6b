"""Persistent prefix-cache store: published pages survive engine restarts
(ISSUE 15, ROADMAP 2(c), docs/serving.md "Resilience").

The engine's :class:`~paddle_tpu.serving.paged_kv.PrefixCache`
makes a shared system prompt prefill ONCE — per engine *incarnation*.
A crash (or a gang recycle) used to throw the warmed pages away, so a
restarted replica re-paid every shared-prefix prefill. This module
closes that gap: at publish time the engine hands the store the
page-aligned prefix (token stream + the K/V page contents read off the
pool) and the store persists it through an :class:`ElasticCheckpointer`
— the same crash-safe format training checkpoints use (per-leaf CRC
manifests, atomic COMMIT marker, async writes, ``keep_last`` GC), so a
mid-save kill can never leave a half-written record that a restore
would trust. On boot :meth:`restore_into` replays committed records:
claims pages from the pool, writes their contents back, and re-registers
every nested page-boundary prefix in the prefix cache — the first
request after a recycle hits the cache exactly like the ten-thousandth
before it.

Contents are tied to the engine geometry (model hash is the caller's
concern). Records carry the writing pool's config fingerprint
(serving/kv_transfer.py — layout, layers, heads, head_dim, dtype,
page_size); restoring into a differently-configured engine raises
:class:`~paddle_tpu.serving.kv_transfer.CacheConfigMismatch` naming
every differing field instead of silently skipping (ISSUE 17 fix: the
old shape-tail check skipped quietly, hiding a misconfigured replica).
Legacy fingerprint-less records keep the skip-on-shape-drift behavior.

Metered by ``paddle_serve_prefix_store_total{op=save|restore|
restore_skipped}`` (gated by tools/metrics_check.py).
"""
from __future__ import annotations

import hashlib

import numpy as np

from ..parallel.checkpoint import CheckpointError, ElasticCheckpointer
from . import metrics as smetrics
from .kv_transfer import (CacheConfigMismatch, cache_fingerprint,
                          fingerprint_mismatch)

__all__ = ["PrefixStore"]


class PrefixStore:
    """One store directory per replica slot. Records are numbered
    ``step_<N>`` in publish order; ``max_records`` bounds the store with
    the checkpointer's ``keep_last`` GC (oldest published drop first —
    matching the prefix cache's own LRU bias toward fresh prefixes)."""

    def __init__(self, dirname: str, max_records: int = 64,
                 use_async: bool = True):
        self.dirname = str(dirname)
        self.max_records = int(max_records)
        self._ck = ElasticCheckpointer(self.dirname, use_async=use_async,
                                       keep_last=self.max_records)
        self.saved = 0
        self.restored = 0
        self.restore_skipped = 0
        # token-hash index of records already on disk (loaded lazily,
        # extended on publish) — a re-published prefix is not re-saved
        self._keys = None
        self._next_step = None

    @staticmethod
    def _key(tokens) -> str:
        return hashlib.sha1(
            np.asarray(tokens, np.int64).tobytes()).hexdigest()

    def _load_index(self) -> None:
        if self._keys is not None:
            return
        self._keys = set()
        steps = self._ck.all_steps()
        self._next_step = (steps[-1] + 1) if steps else 0
        for step in steps:
            try:
                man = self._ck.manifest(step)
            except CheckpointError:
                continue
            key = (man.get("extra") or {}).get("token_hash")
            if key:
                self._keys.add(key)

    # ------------------------------------------------------------------
    def maybe_publish(self, tokens, table_row: np.ndarray, pool) -> bool:
        """Persist the longest page-aligned prefix of ``tokens`` (its
        nested sub-prefixes restore for free — the page layout is
        nested by construction). No-op when nothing is page-aligned or
        the prefix is already stored. Returns True when a record was
        written (async; the checkpointer commits it atomically)."""
        self._load_index()
        ps = pool.page_size
        full = len(tokens) // ps
        if full < 1:
            return False
        prefix = [int(t) for t in tokens[:full * ps]]
        pages = [int(p) for p in table_row[:full]]
        if any(p == 0 for p in pages):
            return False                      # unmapped — nothing stored
        key = self._key(prefix)
        if key in self._keys:
            return False
        k_pages, v_pages = pool.read_pages(pages)
        step = self._next_step
        self._ck.save(step, {
            "tokens": np.asarray(prefix, np.int64),
            "k": np.asarray(k_pages),
            "v": np.asarray(v_pages),
        }, extra={"token_hash": key, "n_pages": len(pages),
                  "page_size": ps,
                  "fingerprint": cache_fingerprint(pool)})
        self._keys.add(key)
        self._next_step = step + 1
        self.saved += 1
        smetrics.m_prefix_store.labels("save").inc()
        return True

    def restore_into(self, engine) -> int:
        """Replay every committed record into ``engine``'s pool + prefix
        cache (boot time, before :meth:`DecodeEngine.warmup`). Records
        that no longer fit — pool pressure, token hash already live —
        are skipped, never half-applied. Returns how many records were
        restored.

        A record carrying a config fingerprint that does not match the
        receiving pool raises :class:`CacheConfigMismatch` naming every
        differing field — restoring KV bytes shaped for another config
        is an operator error, not something to paper over. Legacy
        records without a fingerprint fall back to the old silent
        shape-tail skip."""
        if engine.prefix is None:
            raise ValueError("prefix store needs prefix_cache enabled")
        pool, cache = engine.cache, engine.prefix
        fp_local = cache_fingerprint(pool)
        expect = (pool.num_layers, pool.page_size, pool.num_heads,
                  pool.head_dim)
        n = 0
        for step in self._ck.all_steps():
            try:
                rec, _man = self._ck.restore(step)
            except CheckpointError:
                self.restore_skipped += 1
                smetrics.m_prefix_store.labels("restore_skipped").inc()
                continue
            fp_rec = (_man.get("extra") or {}).get("fingerprint")
            if fp_rec is not None:
                diffs = fingerprint_mismatch(fp_local, fp_rec)
                if diffs:
                    raise CacheConfigMismatch(
                        f"prefix store {self.dirname!r} step_{step} was "
                        f"written for a different cache config — "
                        + "; ".join(diffs)
                        + " (point the replica at a store written by a "
                          "matching engine, or clear the store)")
            tokens = [int(t) for t in np.asarray(rec["tokens"])]
            k_pages = np.asarray(rec["k"])
            v_pages = np.asarray(rec["v"])
            shape_tail = (k_pages.shape[0],) + k_pages.shape[2:]
            n_pages = k_pages.shape[1]
            if (shape_tail != expect or k_pages.shape != v_pages.shape
                    or n_pages * pool.page_size != len(tokens)
                    or cache.has(tokens)
                    or pool.free_page_count() <= n_pages):
                # geometry drift / duplicate / pool too tight (leave at
                # least one free page for live traffic) — skip cleanly
                self.restore_skipped += 1
                smetrics.m_prefix_store.labels("restore_skipped").inc()
                continue
            pages = pool.claim_pages(n_pages)
            pool.write_pages(pages, k_pages, v_pages)
            cache.adopt_nested(tokens, pages)
            n += 1
            self.restored += 1
            smetrics.m_prefix_store.labels("restore").inc()
        return n

    def record_count(self) -> int:
        return len(self._ck.all_steps())

    def wait(self) -> None:
        """Join in-flight async publishes (tests / clean shutdown)."""
        self._ck.wait()

    def close(self) -> None:
        self._ck.close()
