"""KV handoff between serving replicas (ISSUE 17, docs/serving.md
"Disaggregation").

Disaggregated serving migrates a request from its PREFILL replica to a
DECODE replica at the first-token boundary. What actually moves is the
request's KV cache state: the live pages of its page table. This
module is that path — the prefix store's record discipline (content
CRC per payload, explicit COMMIT marker, config fingerprint) over an
in-memory channel instead of disk: either a handoff dict passed within
one process, or a length-prefixed frame stream over a TCP socket
between replicas
(:class:`KVTransferServer` / :func:`send_handoff`).

Layout redistribution rides the same path. A tp=2 prefill replica holds
the KV head axis sharded across its mesh; a tp=1 decode replica wants
the canonical unsharded layout. Following the chunk-wise discipline of
memory-efficient array redistribution (PAPERS.md arXiv:2112.01075), the
transfer never materializes both layouts for the full cache: pages move
in fixed-size chunks, each chunk is split into per-shard frames on the
source and merged along the head axis on the target, and a
:class:`TransferStats` residency meter ASSERTS in-path that the peak
transient canonical-layout footprint stays within the chunk budget —
orders of magnitude below the pool itself.

Fingerprinting is shared with ``serving/prefix_store.py``: a handoff
(or a persisted prefix record) carries the source cache's geometry and
the receiver refuses adoption with a field-by-field
:class:`CacheConfigMismatch` instead of silently writing mis-shaped
rows.
"""
from __future__ import annotations

import itertools
import json
import socket
import struct
import threading
import time
import zlib
from typing import Any, Dict, Iterator, List, Optional, Sequence, Tuple

import numpy as np

from ..observability import spans as _spans
from . import metrics as smetrics
from .paged_kv import CacheFullError

__all__ = [
    "CacheConfigMismatch", "TransferStats", "cache_fingerprint",
    "fingerprint_mismatch", "export_slot", "adopt_into_engine",
    "adopt_prefix", "export_prefix", "iter_frames", "KVTransferServer",
    "send_handoff", "last_stats", "handoff_to_jsonable",
    "handoff_from_jsonable", "DEFAULT_CHUNK_PAGES",
]

# chunk sizes for the staged transfer: small enough that the transient
# canonical-layout footprint is pages, not pools; large enough that the
# per-chunk host round trip amortizes
DEFAULT_CHUNK_PAGES = 4

_transfer_ids = itertools.count(1)


class CacheConfigMismatch(RuntimeError):
    """KV bytes shaped for one cache geometry were offered to another.
    The message names every differing field — the fix is config, not
    retry."""


def cache_fingerprint(cache) -> Dict[str, Any]:
    """The geometry that determines the shape of transferred KV bytes.
    Two caches with equal fingerprints can exchange pages byte for
    byte; anything else must be refused up front. ``layout`` has one
    value here; it stays on the wire because an older prefix-store file
    or peer may still send another, and that has to be refused
    (ROADMAP D14)."""
    return {
        "layout": "paged",
        "num_layers": int(cache.num_layers),
        "num_heads": int(cache.num_heads),
        "head_dim": int(cache.head_dim),
        "dtype": str(np.dtype(cache.dtype).name),
        "page_size": int(cache.page_size),
    }


def fingerprint_mismatch(expected: Dict[str, Any],
                         got: Dict[str, Any]) -> List[str]:
    """Human-readable list of differing fingerprint fields (empty =
    compatible)."""
    keys = sorted(set(expected) | set(got))
    return [f"{k}: expected {expected.get(k)!r}, got {got.get(k)!r}"
            for k in keys if expected.get(k) != got.get(k)]


class TransferStats:
    """Residency meter for the canonical (unsharded) layout during a
    transfer. ``note_alloc`` is called when a merged chunk is
    materialized, ``note_free`` when it is written/serialized and
    dropped — the in-path assertion is the arXiv:2112.01075 discipline
    made executable: at no point may the transient canonical footprint
    exceed the per-chunk budget (let alone approach the full cache)."""

    def __init__(self, budget_bytes: int, full_cache_bytes: int):
        self.budget_bytes = int(budget_bytes)
        self.full_cache_bytes = int(full_cache_bytes)
        self.inflight_bytes = 0
        self.peak_bytes = 0
        self.total_bytes = 0       # wire payload bytes moved
        self.chunks = 0
        self.elapsed_ms = 0.0

    def note_alloc(self, nbytes: int) -> None:
        self.inflight_bytes += int(nbytes)
        self.peak_bytes = max(self.peak_bytes, self.inflight_bytes)
        self.chunks += 1
        if self.inflight_bytes > self.budget_bytes:
            raise AssertionError(
                f"KV transfer residency {self.inflight_bytes}B exceeds "
                f"the chunk budget {self.budget_bytes}B — the transfer "
                f"must stay chunk-wise (full cache: "
                f"{self.full_cache_bytes}B)")

    def note_free(self, nbytes: int) -> None:
        self.inflight_bytes -= int(nbytes)


_stats_lock = threading.Lock()
_last_stats: Dict[str, TransferStats] = {}


def last_stats(kind: str = "adopt") -> Optional[TransferStats]:
    """The most recent transfer's residency stats (``kind`` is
    "export" or "adopt") — how tests assert the peak-residency
    contract held."""
    with _stats_lock:
        return _last_stats.get(kind)


def _note_stats(kind: str, stats: TransferStats) -> None:
    with _stats_lock:
        _last_stats[kind] = stats


def _shard_count(engine) -> int:
    ecfg = getattr(engine, "ecfg", None)
    if ecfg is not None and getattr(ecfg, "sharding", None) == "tp":
        return int(ecfg.tp)
    return 1


def _split_frames(arr: np.ndarray, proj: str, axis: int,
                  nshards: int) -> List[Dict[str, Any]]:
    """Serialize one merged chunk into per-shard wire frames. On a tp
    source each frame is one mesh shard's slice of the head axis — the
    canonical chunk lives only between read and this split."""
    parts = (np.split(arr, nshards, axis=axis) if nshards > 1 else [arr])
    frames = []
    for si, part in enumerate(parts):
        data = np.ascontiguousarray(part).tobytes()
        frames.append({"proj": proj, "shard": si, "nshards": nshards,
                       "shape": list(part.shape),
                       "dtype": str(part.dtype),
                       "crc": zlib.crc32(data), "data": data})
    return frames


def _assemble_chunk(chunk: Dict[str, Any], axis: int,
                    stats: TransferStats
                    ) -> Tuple[np.ndarray, np.ndarray]:
    """Verify CRCs and merge a chunk's shard frames back into the
    canonical layout (head-axis concat). Returns (k, v)."""
    out: Dict[str, np.ndarray] = {}
    for proj in ("k", "v"):
        frames = sorted((f for f in chunk["shards"]
                         if f["proj"] == proj),
                        key=lambda f: f["shard"])
        if not frames:
            raise ValueError(f"handoff chunk missing {proj!r} frames")
        parts = []
        for f in frames:
            data = f["data"]
            if zlib.crc32(data) != f["crc"]:
                raise ValueError(
                    f"KV transfer CRC mismatch on chunk "
                    f"{chunk['index']} {proj}/shard {f['shard']}")
            parts.append(np.frombuffer(data, np.dtype(f["dtype"]))
                         .reshape(f["shape"]))
        merged = (np.concatenate(parts, axis=axis)
                  if len(parts) > 1 else parts[0])
        stats.note_alloc(merged.nbytes)
        out[proj] = merged
    return out["k"], out["v"]


def _wire_bytes(handoff: Dict[str, Any]) -> int:
    return sum(len(f["data"]) for ch in handoff["chunks"]
               for f in ch["shards"])


# ----------------------------------------------------------------------
# export (prefill side)
# ----------------------------------------------------------------------
def export_slot(engine, slot: int,
                tokens: Optional[Sequence[int]] = None,
                chunk_pages: int = DEFAULT_CHUNK_PAGES) -> Dict[str, Any]:
    """Serialize a live slot's KV state into a handoff dict: config
    fingerprint + chunked, per-shard, CRC-stamped frames + COMMIT flag.
    The slot stays live — the caller frees it after the handoff is
    accepted (or keeps it on failure)."""
    cache = engine.cache
    fp = cache_fingerprint(cache)
    length = int(cache.length(slot))
    if length <= 0:
        raise ValueError(f"slot {slot} has no valid KV rows to export")
    t0 = time.perf_counter_ns()
    itemsize = np.dtype(cache.dtype).itemsize
    nshards = _shard_count(engine)
    chunks: List[Dict[str, Any]] = []
    n_pages = cache.pages_for(length)
    row = cache.table_row(slot)
    pages = [int(p) for p in row[:n_pages]]
    unit = (cache.num_layers * cache.page_size * cache.num_heads
            * cache.head_dim * itemsize)
    stats = TransferStats(2 * chunk_pages * unit, cache.nbytes)
    for ci, i in enumerate(range(0, len(pages), chunk_pages)):
        group = pages[i:i + chunk_pages]
        k_np, v_np = cache.read_pages(group)
        nbytes = k_np.nbytes + v_np.nbytes
        stats.note_alloc(nbytes)
        shards = (_split_frames(k_np, "k", 3, nshards)
                  + _split_frames(v_np, "v", 3, nshards))
        del k_np, v_np
        stats.note_free(nbytes)
        chunks.append({"index": ci, "n": len(group),
                       "shards": shards})
    handoff = {
        "version": 1,
        "transfer_id": f"t{next(_transfer_ids)}-{id(engine) & 0xffff:x}",
        "fingerprint": fp,
        "length": length,
        "tokens": ([int(t) for t in tokens]
                   if tokens is not None else None),
        "chunks": chunks,
        "committed": True,
    }
    stats.total_bytes = _wire_bytes(handoff)
    stats.elapsed_ms = (time.perf_counter_ns() - t0) / 1e6
    _note_stats("export", stats)
    smetrics.m_kv_transfer_bytes.labels("out").inc(stats.total_bytes)
    smetrics.m_kv_transfer_ms.observe(stats.elapsed_ms)
    return handoff


# ----------------------------------------------------------------------
# adopt (decode side)
# ----------------------------------------------------------------------
def adopt_into_engine(engine, handoff: Dict[str, Any]) -> int:
    """Materialize a handoff into the receiving engine's cache and
    return the slot it now lives in. Fingerprints are checked FIRST
    (:class:`CacheConfigMismatch` on any differing field); chunks are
    merged shard-by-shard and written page-wise so the canonical
    layout only ever exists chunk-sized."""
    cache = engine.cache
    diffs = fingerprint_mismatch(cache_fingerprint(cache),
                                 handoff["fingerprint"])
    if diffs:
        raise CacheConfigMismatch(
            "KV handoff rejected — cache config mismatch: "
            + "; ".join(diffs))
    if not handoff.get("committed"):
        raise ValueError("handoff was never committed — refusing "
                         "partial KV state")
    if cache.free_slot_count() == 0:
        # fail BEFORE claiming pages and scattering chunks: under
        # backlog the scheduler retries adoption every tick, and doing
        # the full transfer work just to hit CacheFullError in
        # adopt_slot taxes every decode gap (~2ms a tick)
        raise CacheFullError(
            f"no free decode slot for handoff "
            f"{handoff.get('transfer_id')!r}")
    t0 = time.perf_counter_ns()
    length = int(handoff["length"])
    max_chunk = max((int(ch["n"]) for ch in handoff["chunks"]),
                    default=1)
    itemsize = np.dtype(cache.dtype).itemsize
    unit = (cache.num_layers * cache.page_size * cache.num_heads
            * cache.head_dim * itemsize)
    stats = TransferStats(2 * max_chunk * unit, cache.nbytes)
    pages = cache.claim_pages(cache.pages_for(length))
    try:
        written = 0
        for ch in sorted(handoff["chunks"],
                         key=lambda c: c["index"]):
            k_np, v_np = _assemble_chunk(ch, 3, stats)
            cache.write_pages(pages[written:written + int(ch["n"])],
                              k_np, v_np)
            stats.note_free(k_np.nbytes + v_np.nbytes)
            written += int(ch["n"])
            del k_np, v_np
        if written != len(pages):
            raise ValueError(
                f"handoff covered {written} page(s), table needs "
                f"{len(pages)}")
        slot = cache.adopt_slot(length, pages)
    except Exception:
        cache.deref_pages(pages)
        raise
    stats.total_bytes = _wire_bytes(handoff)
    stats.elapsed_ms = (time.perf_counter_ns() - t0) / 1e6
    _note_stats("adopt", stats)
    smetrics.m_kv_transfer_bytes.labels("in").inc(stats.total_bytes)
    smetrics.m_kv_transfer_ms.observe(stats.elapsed_ms)
    # adoption runs under the request's span context (the scheduler's
    # handoff-ingest wrapper), so this lands inside the shared trace
    _spans.record("serve/kv_adopt", t0,
                  time.perf_counter_ns() - t0,
                  attrs={"transfer_id": handoff.get("transfer_id"),
                         "bytes": stats.total_bytes})
    return slot


def export_prefix(pool, tokens: Sequence[int], table_row,
                  chunk_pages: int = DEFAULT_CHUNK_PAGES
                  ) -> Optional[Dict[str, Any]]:
    """Serialize the longest page-aligned prefix of ``tokens`` (pages
    per ``table_row``) into a blob :func:`adopt_prefix` can replay on
    any same-fingerprint engine — the payload of the gang-shared prefix
    index (serving/disagg.py). Returns None when nothing page-aligned
    is mapped. Chunk-wise, same residency discipline as a slot
    export."""
    ps = pool.page_size
    full = len(tokens) // ps
    if full < 1:
        return None
    prefix = [int(t) for t in tokens[:full * ps]]
    pages = [int(p) for p in table_row[:full]]
    if any(p == 0 for p in pages):
        return None
    itemsize = np.dtype(pool.dtype).itemsize
    unit = (pool.num_layers * pool.page_size * pool.num_heads
            * pool.head_dim * itemsize)
    stats = TransferStats(2 * chunk_pages * unit, pool.nbytes)
    chunks: List[Dict[str, Any]] = []
    for ci, i in enumerate(range(0, len(pages), chunk_pages)):
        group = pages[i:i + chunk_pages]
        k_np, v_np = pool.read_pages(group)
        nbytes = k_np.nbytes + v_np.nbytes
        stats.note_alloc(nbytes)
        shards = (_split_frames(k_np, "k", 3, 1)
                  + _split_frames(v_np, "v", 3, 1))
        del k_np, v_np
        stats.note_free(nbytes)
        chunks.append({"index": ci, "n": len(group), "shards": shards})
    return {
        "version": 1,
        "transfer_id": f"p{next(_transfer_ids)}",
        "fingerprint": cache_fingerprint(pool),
        "length": len(prefix),
        "tokens": prefix,
        "chunks": chunks,
        "committed": True,
    }


def adopt_prefix(engine, blob: Dict[str, Any]) -> int:
    """Adopt a gang-shared prefix record (export_slot payload whose
    ``tokens`` cover exactly its page-aligned length) into the local
    pool + prefix cache, so the next prefill of those tokens hits
    locally. Returns prefix-cache entries registered (0 when the
    prefix is already cached). Engines with a prefix cache only."""
    cache = engine.cache
    if engine.prefix is None:
        raise ValueError("prefix adoption needs prefix_cache enabled")
    diffs = fingerprint_mismatch(cache_fingerprint(cache),
                                 blob["fingerprint"])
    if diffs:
        raise CacheConfigMismatch(
            "prefix record rejected — cache config mismatch: "
            + "; ".join(diffs))
    tokens = [int(t) for t in (blob.get("tokens") or [])]
    length = int(blob["length"])
    if not tokens or len(tokens) != length or length % cache.page_size:
        raise ValueError("prefix record must carry page-aligned tokens "
                         "matching its length")
    if engine.prefix.has(tokens):
        return 0
    max_chunk = max((int(ch["n"]) for ch in blob["chunks"]), default=1)
    itemsize = np.dtype(cache.dtype).itemsize
    unit = (cache.num_layers * cache.page_size * cache.num_heads
            * cache.head_dim * itemsize)
    stats = TransferStats(2 * max_chunk * unit, cache.nbytes)
    pages = cache.claim_pages(cache.pages_for(length))
    try:
        written = 0
        for ch in sorted(blob["chunks"], key=lambda c: c["index"]):
            k_np, v_np = _assemble_chunk(ch, 3, stats)
            cache.write_pages(pages[written:written + int(ch["n"])],
                              k_np, v_np)
            stats.note_free(k_np.nbytes + v_np.nbytes)
            written += int(ch["n"])
            del k_np, v_np
        if written != len(pages):
            raise ValueError(
                f"prefix record covered {written} page(s), need "
                f"{len(pages)}")
        # claim_pages' single reference becomes the cache's reference
        return engine.prefix.adopt_nested(tokens, pages)
    except Exception:
        cache.deref_pages(pages)
        raise


# ----------------------------------------------------------------------
# JSON-inline form (HTTP fallback channel, tests)
# ----------------------------------------------------------------------
def handoff_to_jsonable(handoff: Dict[str, Any]) -> Dict[str, Any]:
    """Base64 the shard payloads so a handoff can ride a JSON body —
    the fallback channel when the receiver runs no KVTransferServer.
    ~33% size overhead; the socket channel is the real path."""
    import base64

    out = {k: v for k, v in handoff.items() if k != "chunks"}
    out["chunks"] = [
        dict(ch, shards=[
            dict(f, data=base64.b64encode(f["data"]).decode())
            for f in ch["shards"]])
        for ch in handoff["chunks"]]
    return out


def handoff_from_jsonable(obj: Dict[str, Any]) -> Dict[str, Any]:
    import base64

    out = {k: v for k, v in obj.items() if k != "chunks"}
    out["chunks"] = [
        dict(ch, shards=[
            dict(f, data=base64.b64decode(f["data"]))
            for f in ch["shards"]])
        for ch in obj["chunks"]]
    return out


# ----------------------------------------------------------------------
# socket channel (between replica processes)
# ----------------------------------------------------------------------
# frame = [4B header length][header JSON][8B payload length][payload]
_HDR = struct.Struct(">I")
_PAY = struct.Struct(">Q")


def iter_frames(handoff: Dict[str, Any]
                ) -> Iterator[Tuple[Dict[str, Any], bytes]]:
    """The handoff as a frame stream: one meta frame, one frame per
    shard payload, one commit frame — the prefix store's record/COMMIT
    shape, on the wire."""
    meta = {k: v for k, v in handoff.items() if k != "chunks"}
    meta["kind"] = "meta"
    meta["committed"] = False       # commit is its own frame
    meta["n_chunks"] = len(handoff["chunks"])
    yield meta, b""
    for ch in handoff["chunks"]:
        base = {k: v for k, v in ch.items() if k != "shards"}
        for f in ch["shards"]:
            hdr = dict(base, kind="chunk",
                       transfer_id=handoff["transfer_id"],
                       **{k: v for k, v in f.items() if k != "data"})
            yield hdr, f["data"]
    yield {"kind": "commit", "transfer_id": handoff["transfer_id"]}, b""


def _send_frame(sock: socket.socket, header: Dict[str, Any],
                payload: bytes) -> None:
    hdr = json.dumps(header).encode()
    sock.sendall(_HDR.pack(len(hdr)) + hdr + _PAY.pack(len(payload)))
    if payload:
        sock.sendall(payload)


def _recv_exact(sock: socket.socket, n: int) -> bytes:
    buf = bytearray()
    while len(buf) < n:
        part = sock.recv(min(1 << 20, n - len(buf)))
        if not part:
            raise ConnectionError("KV transfer peer closed mid-frame")
        buf.extend(part)
    return bytes(buf)


def _recv_frame(sock: socket.socket) -> Tuple[Dict[str, Any], bytes]:
    hdr_len = _HDR.unpack(_recv_exact(sock, _HDR.size))[0]
    header = json.loads(_recv_exact(sock, hdr_len).decode())
    pay_len = _PAY.unpack(_recv_exact(sock, _PAY.size))[0]
    payload = _recv_exact(sock, pay_len) if pay_len else b""
    return header, payload


def send_handoff(host: str, port: int, handoff: Dict[str, Any],
                 timeout_s: float = 30.0) -> None:
    """Stream a handoff to a :class:`KVTransferServer` and wait for its
    post-commit ACK. Raises on any transport fault — the caller's cue
    to fall back to colocated dispatch (degrade, never drop)."""
    # the handoff's own trace context (stamped at export) parents the
    # send span — the wire hop shows up inside the request's timeline
    with _spans.default_tracer().context(_spans.extract(handoff)):
        with _spans.span("serve/kv_send",
                         attrs={"transfer_id": handoff["transfer_id"],
                                "length": int(handoff["length"])}):
            with socket.create_connection((host, int(port)),
                                          timeout=timeout_s) as sock:
                for header, payload in iter_frames(handoff):
                    _send_frame(sock, header, payload)
                ack = _recv_exact(sock, 2)
                if ack != b"OK":
                    raise ConnectionError(
                        f"KV transfer not acknowledged (got {ack!r})")


class KVTransferServer:
    """Per-replica TCP endpoint that buffers incoming handoffs until
    the serving loop adopts them. Frames for a transfer are staged
    under its transfer_id and become visible to :meth:`pop` only after
    the commit frame — a connection dying mid-stream leaves nothing
    behind (the record-or-nothing discipline)."""

    def __init__(self, host: str = "127.0.0.1", port: int = 0):
        self._sock = socket.create_server((host, port))
        self._sock.settimeout(0.5)
        self.host = host
        self.port = int(self._sock.getsockname()[1])
        self._ready: Dict[str, Dict[str, Any]] = {}
        self._cv = threading.Condition()
        self._stop = False
        self._thread = threading.Thread(
            target=self._serve, daemon=True, name="kv-transfer-server")

    def start(self) -> "KVTransferServer":
        self._thread.start()
        return self

    def _serve(self) -> None:
        while not self._stop:
            try:
                conn, _ = self._sock.accept()
            except socket.timeout:
                continue
            except OSError:
                return
            threading.Thread(target=self._handle, args=(conn,),
                             daemon=True,
                             name="kv-transfer-conn").start()

    def _handle(self, conn: socket.socket) -> None:
        staged: Dict[str, Any] = {}
        chunks: Dict[int, Dict[str, Any]] = {}
        try:
            with conn:
                conn.settimeout(30.0)
                while True:
                    header, payload = _recv_frame(conn)
                    kind = header.get("kind")
                    if kind == "meta":
                        staged = {k: v for k, v in header.items()
                                  if k not in ("kind", "n_chunks")}
                        chunks = {}
                    elif kind == "chunk":
                        ci = int(header["index"])
                        ch = chunks.setdefault(ci, {
                            "index": ci, "n": header["n"],
                            "shards": []})
                        if "start" in header:
                            ch["start"] = header["start"]
                        ch["shards"].append({
                            "proj": header["proj"],
                            "shard": header["shard"],
                            "nshards": header["nshards"],
                            "shape": header["shape"],
                            "dtype": header["dtype"],
                            "crc": header["crc"], "data": payload})
                    elif kind == "commit":
                        handoff = dict(
                            staged, committed=True,
                            chunks=[chunks[i]
                                    for i in sorted(chunks)])
                        n = _wire_bytes(handoff)
                        smetrics.m_kv_transfer_bytes.labels("in").inc(n)
                        with self._cv:
                            self._ready[handoff["transfer_id"]] = handoff
                            self._cv.notify_all()
                        conn.sendall(b"OK")
                        return
                    else:
                        raise ValueError(f"unknown frame kind {kind!r}")
        except (ConnectionError, OSError, ValueError, KeyError):
            # mid-stream death: nothing was published — the sender's
            # missing ACK triggers its colocated fallback
            return

    def pop(self, transfer_id: str,
            timeout_s: float = 30.0) -> Dict[str, Any]:
        """Block until the transfer committed, then hand it over
        (exactly once). TimeoutError when it never lands."""
        with self._cv:
            ok = self._cv.wait_for(
                lambda: transfer_id in self._ready or self._stop,
                timeout=timeout_s)
            if not ok or transfer_id not in self._ready:
                raise TimeoutError(
                    f"KV transfer {transfer_id!r} never committed")
            return self._ready.pop(transfer_id)

    def close(self) -> None:
        self._stop = True
        with self._cv:
            self._cv.notify_all()
        try:
            self._sock.close()
        except OSError:
            pass
        if self._thread.is_alive():
            self._thread.join(timeout=5)
