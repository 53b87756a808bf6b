"""Lightweight end-to-end span tracer (ISSUE 10).

The metrics registry answers "how much / how often"; the span tracer
answers "which request / which step, and what happened inside it".  A span
is one timed operation with identity:

    {trace, span, parent, name, start_ns, dur_ns, tid, thread, attrs}

- ``trace`` groups every span of one logical unit (a serving request, a
  training step) so a user-visible p99 can be walked back to the exact
  prefill/decode tick that caused it;
- ``parent`` links spans into a tree *across threads*: a worker thread
  (async fetch, ``prefetch_to_device``, the serving ``EngineLoop``, the
  checkpoint async-save writer) attaches the submitting thread's context
  with :meth:`SpanTracer.context` and its spans parent correctly instead
  of orphaning;
- timestamps are :data:`clock_ns` (``time.perf_counter_ns``) — the SAME
  clock profiler.py host events use, so spans drop into the merged chrome
  trace (trace_merge.py) as their own plane with no cross-clock
  alignment.  Code that holds a ``time.monotonic()`` instant (a request's
  stamps, a benchmark's window) turns it into these units with
  :func:`monotonic_to_ns` and never assumes the two clocks are one;
- a span opened with :meth:`SpanTracer.span` is also a
  ``jax.profiler.TraceAnnotation`` called ``paddle/<name>`` while it is
  open, with one stat, ``span``: the id its record carries.  Without a
  profiler session that is a no-op inside jax (the stat is not encoded);
  with one (profiler.py, a benchmark's traced run, an operator's capture)
  the span lies on the xplane's host plane, on the profiler's clock,
  beside the device operations, and its id fetches the record, with its
  attributes and its parent, from the ring or the JSONL sink.  A process
  that has not loaded jax (the stub replica worker) is never made to.

Cost model (the dispatch-overhead gate in tools/dispatch_bench.py holds
tracing to <5% of the fast path): a disabled tracer is one global read;
an enabled :func:`record` builds one tuple and appends it under a lock;
the :meth:`span` context manager adds two clock reads and the
annotation.  Records land in a bounded ring of :data:`RING` — old ones
fall off and ``dropped`` counts them, so a reader can tell a window it
still holds whole from one it does not — and, when a JSONL sink is set,
one flushed line per span.
"""
from __future__ import annotations

import collections
import contextlib
import itertools
import json
import sys
import threading
import time
from typing import Any, Dict, IO, List, Optional, Tuple, Union

__all__ = [
    "SpanTracer", "default_tracer", "span", "record", "current_context",
    "gen_id", "set_tracing_enabled", "tracing_enabled",
    "WIRE_KEY", "inject", "extract", "attach_process_sink",
    "process_sink_path", "clock_ns", "monotonic_to_ns", "RING",
    "ANNOTATION_PREFIX",
]

# the one clock every record is stamped with
clock_ns = time.perf_counter_ns
RING = 65536
ANNOTATION_PREFIX = "paddle/"

_monotonic_offset_ns = None


def monotonic_to_ns(t: float) -> int:
    """A ``time.monotonic()`` instant in :data:`clock_ns` units.  The
    offset between the two clocks is read once (zero where both are
    CLOCK_MONOTONIC, which no caller should count on)."""
    global _monotonic_offset_ns
    if _monotonic_offset_ns is None:
        a = clock_ns()
        m = time.monotonic()
        b = clock_ns()
        _monotonic_offset_ns = (a + b) // 2 - int(m * 1e9)
    return int(t * 1e9) + _monotonic_offset_ns


_annotation = None


def _annotation_class():
    """``jax.profiler.TraceAnnotation`` once this process has loaded jax,
    else None: tracing never imports it."""
    global _annotation
    if _annotation is None:
        jax = sys.modules.get("jax")
        profiler = getattr(jax, "profiler", None)
        _annotation = getattr(profiler, "TraceAnnotation", None)
    return _annotation

# process-wide kill switch, mirroring metrics.set_metrics_enabled — the
# tracing on/off A/B in tools/dispatch_bench.py throws this
_ENABLED = True


def tracing_enabled() -> bool:
    return _ENABLED


def set_tracing_enabled(on: bool) -> None:
    global _ENABLED
    _ENABLED = bool(on)


_ids = itertools.count(1)
_pid_salt = None


def gen_id() -> int:
    """Process-unique span/trace id (monotone counter salted with the pid
    so ids from different gang ranks never collide in a merged view)."""
    global _pid_salt
    if _pid_salt is None:
        import os

        _pid_salt = (os.getpid() & 0xFFFF) << 40
    return _pid_salt | next(_ids)


Context = Tuple[int, int]  # (trace_id, span_id)

# -- trace-context wire format (ISSUE 18) -----------------------------------
# One request = ONE trace across processes: the gang front door mints a
# context, injects it into every replica-bound JSON body (and the KV
# handoff frame), and each hop extracts + re-injects.  The wire shape is
# a plain JSON object under the ``trace`` key:
#
#     {"trace": {"trace_id": <int>, "parent_span": <int>}}
#
# ints, not hex strings, so stdlib-only workers (serving/replica.py stub
# mode) round-trip it with nothing but ``json``.

WIRE_KEY = "trace"


def inject(ctx: Optional[Context]) -> Optional[Dict[str, int]]:
    """Serialize a (trace_id, span_id) context for a JSON body / frame.
    The receiving side's spans parent under ``parent_span``."""
    if ctx is None:
        return None
    return {"trace_id": int(ctx[0]), "parent_span": int(ctx[1])}


def extract(obj: Any) -> Optional[Context]:
    """Inverse of :func:`inject`.  Accepts the wire dict itself or any
    mapping carrying it under :data:`WIRE_KEY`; returns None on anything
    malformed (a request with a garbled trace still serves — it just
    starts a fresh trace)."""
    if not isinstance(obj, dict):
        return None
    wire = obj.get(WIRE_KEY, obj)
    if not isinstance(wire, dict):
        return None
    try:
        return (int(wire["trace_id"]), int(wire["parent_span"]))
    except (KeyError, TypeError, ValueError):
        return None


def process_sink_path(trace_dir: str, role: str = "proc") -> str:
    """Per-process span file inside a shared trace dir.  The pid keeps
    sibling replicas (and restarted incarnations) from clobbering each
    other; tools/trace_assemble.py globs ``spans-*.jsonl``."""
    import os

    return os.path.join(trace_dir, f"spans-{role}-{os.getpid()}.jsonl")


def attach_process_sink(trace_dir: str, role: str = "proc") -> str:
    """Point the default tracer's JSONL sink at this process's file in
    ``trace_dir`` (created if missing).  Append-at-record with per-line
    flush — a SIGKILLed process leaves every finished span on disk for
    post-mortem assembly."""
    import os

    os.makedirs(trace_dir, exist_ok=True)
    path = process_sink_path(trace_dir, role)
    _default.set_sink(path)
    return path


# a finished span in the ring: a plain tuple in this order, turned into the
# documented dict only where it is handed out (spans(), the JSONL sink)
_FIELDS = ("name", "trace", "span", "parent", "start_ns", "dur_ns", "tid",
           "thread", "attrs")
_NAME, _TRACE, _START, _DUR, _ATTRS = 0, 1, 4, 5, 8


def _as_dict(rec: tuple) -> dict:
    d = dict(zip(_FIELDS, rec))
    if not d["attrs"]:
        del d["attrs"]
    return d


class _OpenSpan:
    __slots__ = ("tracer", "name", "trace", "span_id", "parent", "attrs",
                 "prev", "t0", "ann")

    def __init__(self, tracer, name, trace, span_id, parent, attrs, prev):
        self.tracer = tracer
        self.name = name
        self.trace = trace
        self.span_id = span_id
        self.parent = parent
        self.attrs = attrs
        self.prev = prev            # the thread's context to go back to

    def __enter__(self):
        cls = _annotation_class()
        if cls is not None:
            self.ann = cls(ANNOTATION_PREFIX + self.name, span=self.span_id)
            self.ann.__enter__()
        else:
            self.ann = None
        self.t0 = clock_ns()
        if self.tracer._sink is not None:
            # announce the open span (dur 0, attrs.open; the full record
            # supersedes it, tools/trace_assemble.py): children flush at
            # their own end, before this one's, and a process SIGKILLed
            # in between must leave their parent on disk
            self.tracer._to_sink((
                self.name, self.trace, self.span_id, self.parent, self.t0,
                0, threading.get_ident(), threading.current_thread().name,
                {"open": True}))
        return self

    def set_attr(self, key: str, value) -> None:
        if self.attrs is None:
            self.attrs = {}
        self.attrs[key] = value

    def __exit__(self, exc_type, exc, tb):
        t1 = clock_ns()
        if self.ann is not None:
            self.ann.__exit__(exc_type, exc, tb)
        tr = self.tracer
        tr._tls.ctx = self.prev
        if exc_type is not None:
            self.set_attr("error", exc_type.__name__)
        tr._append((self.name, self.trace, self.span_id, self.parent,
                    self.t0, t1 - self.t0, threading.get_ident(),
                    threading.current_thread().name, self.attrs or None))
        return False


class _NullSpan:
    """Shared no-op handle returned while tracing is disabled."""

    __slots__ = ()
    span_id = None

    def __enter__(self):
        return self

    def set_attr(self, key, value):
        pass

    def __exit__(self, *exc):
        return False


_NULL = _NullSpan()


class SpanTracer:
    """Bounded-ring span recorder with thread-local context propagation."""

    def __init__(self, ring: int = RING,
                 sink: Optional[Union[str, IO]] = None):
        self._ring = collections.deque(maxlen=int(ring))
        self._ring_lock = threading.Lock()
        # records that fell off the ring's old end since the tracer was
        # made: a reader whose window may reach back past the oldest
        # record left must not take what remains for the whole
        self.dropped = 0
        self._tls = threading.local()
        self._sink: Optional[IO] = None
        self._own_sink = False
        self._sink_lock = threading.Lock()
        if sink is not None:
            self.set_sink(sink)

    # -- context propagation ----------------------------------------------
    def current_context(self) -> Optional[Context]:
        """(trace_id, span_id) of the innermost open span on this thread,
        or an attached cross-thread context; None outside any span."""
        return getattr(self._tls, "ctx", None)

    @contextlib.contextmanager
    def context(self, ctx: Optional[Context]):
        """Adopt ``ctx`` (captured on another thread via
        :meth:`current_context`) for the duration of the block: spans
        opened inside parent into it.  ``None`` is a no-op block."""
        prev = getattr(self._tls, "ctx", None)
        if ctx is not None:
            self._tls.ctx = ctx
        try:
            yield
        finally:
            self._tls.ctx = prev

    # -- recording --------------------------------------------------------
    def span(self, name: str, trace: Optional[int] = None,
             attrs: Optional[Dict[str, Any]] = None,
             parent: Optional[int] = None):
        """Context manager timing one span.  Inherits trace + parent from
        the thread-local context unless ``trace`` starts a new one (or,
        with ``parent``, names the span of that trace to hang under).
        Leaving it puts the thread back in the context it was opened
        in, whatever trace that was."""
        if not _ENABLED:
            return _NULL
        ctx = getattr(self._tls, "ctx", None)
        if trace is not None:
            trace_id = trace
            if parent is None and ctx and ctx[0] == trace:
                parent = ctx[1]
        elif ctx is not None:
            trace_id, parent = ctx
        else:
            trace_id, parent = gen_id(), None
        span_id = gen_id()
        self._tls.ctx = (trace_id, span_id)
        return _OpenSpan(self, name, trace_id, span_id, parent, attrs, ctx)

    def record(self, name: str, start_ns: int, dur_ns: int,
               trace: Optional[int] = None, parent: Optional[int] = None,
               span_id: Optional[int] = None,
               attrs: Optional[Dict[str, Any]] = None) -> Optional[int]:
        """Append an already-timed span (the timing happened elsewhere —
        e.g. queue wait measured between submit and admit).  With
        ``trace=None`` both trace and parent come from the thread-local
        context; an explicit ``trace`` leaves ``parent`` exactly as given
        (``None`` = a root span of that trace).  Returns the span id, or
        None while tracing is disabled."""
        if not _ENABLED:
            return None
        if trace is None:
            ctx = getattr(self._tls, "ctx", None)
            if ctx is not None:
                trace = ctx[0]
                if parent is None:
                    parent = ctx[1]
            else:
                trace = gen_id()
        if span_id is None:
            span_id = gen_id()
        self._append((name, trace, span_id, parent, int(start_ns),
                      int(dur_ns), threading.get_ident(),
                      threading.current_thread().name, attrs or None))
        return span_id

    def _append(self, rec: tuple) -> None:
        ring = self._ring
        with self._ring_lock:
            if len(ring) == ring.maxlen:
                self.dropped += 1
            ring.append(rec)
        if self._sink is not None:
            self._to_sink(rec)

    def _to_sink(self, rec: tuple) -> None:
        with self._sink_lock:
            sink = self._sink
            if sink is not None:
                sink.write(json.dumps(_as_dict(rec)) + "\n")
                sink.flush()

    # -- sinks / introspection --------------------------------------------
    def set_sink(self, path_or_file: Optional[Union[str, IO]]) -> None:
        """JSONL sink: one flushed line per finished span (None detaches).
        The ring keeps recording either way."""
        with self._sink_lock:
            if self._own_sink and self._sink is not None:
                self._sink.close()
            if path_or_file is None:
                self._sink, self._own_sink = None, False
            elif hasattr(path_or_file, "write"):
                self._sink, self._own_sink = path_or_file, False
            else:
                self._sink = open(path_or_file, "a")
                self._own_sink = True

    def spans(self) -> List[dict]:
        """Snapshot of the ring, oldest first."""
        return [_as_dict(r) for r in list(self._ring)]

    def clear(self) -> None:
        self._ring.clear()

    def summary(self) -> Dict[str, dict]:
        """Per-name percentile rollup over the ring:
        {name: {count, total_ms, p50_ms, p90_ms, p99_ms, max_ms}}."""
        by_name: Dict[str, List[float]] = {}
        for r in list(self._ring):
            by_name.setdefault(r[_NAME], []).append(r[_DUR] / 1e6)
        out: Dict[str, dict] = {}
        for name, vals in sorted(by_name.items()):
            vals.sort()
            n = len(vals)

            def pct(q):
                return vals[min(n - 1, max(0, int(round(q / 100.0
                                                        * (n - 1)))))]

            out[name] = {
                "count": n, "total_ms": round(sum(vals), 3),
                "p50_ms": round(pct(50), 3), "p90_ms": round(pct(90), 3),
                "p99_ms": round(pct(99), 3), "max_ms": round(vals[-1], 3),
            }
        return out

    def trace_spans(self, trace_id: int) -> List[dict]:
        """Every ring span of one trace, in start order (the p99->cause
        walk: feed it the trace id stamped on a slow request)."""
        return [_as_dict(r) for r in sorted(
            (r for r in list(self._ring) if r[_TRACE] == trace_id),
            key=lambda r: r[_START])]

    def attr_range(self, name: str, key: str, lo, hi) -> List[dict]:
        """Ring spans called ``name`` whose ``attrs[key]`` lies in
        [lo, hi], in start order: from a request's ``first_step`` and
        ``last_step`` to the ``serve/decode_tick`` records it rode."""
        out = []
        for r in list(self._ring):
            if r[_NAME] == name and r[_ATTRS] is not None:
                v = r[_ATTRS].get(key)
                if v is not None and lo <= v <= hi:
                    out.append(r)
        return [_as_dict(r) for r in sorted(out, key=lambda r: r[_START])]


_default = SpanTracer()


def default_tracer() -> SpanTracer:
    return _default


def span(name: str, trace: Optional[int] = None,
         attrs: Optional[Dict[str, Any]] = None,
         parent: Optional[int] = None):
    """Module-level :meth:`SpanTracer.span` on the default tracer."""
    return _default.span(name, trace=trace, attrs=attrs, parent=parent)


def record(name: str, start_ns: int, dur_ns: int, **kw) -> Optional[int]:
    return _default.record(name, start_ns, dur_ns, **kw)


def current_context() -> Optional[Context]:
    return _default.current_context()
