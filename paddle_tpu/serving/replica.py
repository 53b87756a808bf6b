"""Serving replica worker: one engine incarnation under the gang
supervisor (ISSUE 15, docs/serving.md "Resilience").

Run as a SCRIPT (``python paddle_tpu/serving/replica.py --config X``) by
:class:`~paddle_tpu.serving.gang.ReplicaGang` — one subprocess per
replica slot. The worker:

- builds the model + :class:`DecodeEngine` from the JSON config
  (deterministic ``init_params(PRNGKey(seed))`` — every replica serves
  identical weights, so a failed-over greedy request returns the same
  tokens its first replica would have),
- restores the persistent prefix store (``prefix_store_dir``) BEFORE
  warmup, so a recycled replica serves the shared-system-prompt workload
  prefill-once from its very first request,
- serves through the standard :class:`FrontDoor` on an ephemeral port,
  reported back through ``ready.json`` (port, pid, restored record
  count),
- arms the hang watchdog from the ``PADDLE_HEALTH_*`` env contract the
  gang exports (the engine loop stamps ``serve/tick`` progress; a wedged
  loop exits :data:`~paddle_tpu.parallel.health.HANG_EXIT_CODE` = 43),
  and writes a liveness heartbeat file the supervisor probes,
- maps a POISONED engine to a fail-fast exit with
  :data:`POISONED_EXIT_CODE` = 44 (the gang recycles with
  ``cause=poisoned``) instead of 500ing every request forever,
- drains gracefully on SIGTERM and exits 0.

``{"stub": {...}}`` configs run a stdlib-only protocol stub (no jax
import — sub-second startup) implementing the same HTTP surface
(``/generate``, ``/health``, ``/metrics``) with deterministic fake
tokens; gang unit tests use it to exercise failover/recycle mechanics
without paying engine warmup per test.

Top-level imports here are stdlib-only on purpose: the gang imports
this module for the exit-code contract, and the stub path must not drag
jax in.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

#: Exit code for a poisoned engine (donation invalidated the KV pools —
#: engine.py). Distinct from health.HANG_EXIT_CODE (43): the gang maps
#: 44 -> ``paddle_serve_replica_restarts_total{cause="poisoned"}``.
POISONED_EXIT_CODE = 44

READY_NAME = "ready.json"
HEARTBEAT_NAME = "heartbeat.json"


class ReplicaRole:
    """Phase role of a replica in a disaggregated gang (ISSUE 17,
    docs/serving.md "Disaggregation"). Plain string constants — this
    module must stay stdlib-only (no enum import cost matters, but the
    gang JSON-serializes roles into replica configs, so str is the
    native type)."""

    PREFILL = "prefill"      # serves /prefill, ships KV handoffs out
    DECODE = "decode"        # serves /resume, adopts KV handoffs
    COLOCATED = "colocated"  # serves /generate end to end (default)
    ALL = (PREFILL, DECODE, COLOCATED)


def _atomic_json(path: str, obj: dict) -> None:
    tmp = f"{path}.tmp.{os.getpid()}"
    try:
        with open(tmp, "w") as f:
            json.dump(obj, f)
        os.replace(tmp, path)
    except OSError:
        pass  # liveness files are advisory, never fatal


def _heartbeat_loop(run_dir: str, status_fn, stop: threading.Event,
                    interval_s: float = 0.5) -> None:
    path = os.path.join(run_dir, HEARTBEAT_NAME)
    # first beat lands IMMEDIATELY: staleness detection needs a baseline
    # even when the worker wedges right after coming up
    _atomic_json(path, {"ts": time.time(), "pid": os.getpid(),
                        "status": "starting"})
    while not stop.wait(interval_s):
        try:
            status = status_fn()
        except Exception as e:
            status = f"error: {e}"
        _atomic_json(path, {"ts": time.time(), "pid": os.getpid(),
                            "status": status})


# ---------------------------------------------------------------------------
# Stub worker: protocol-faithful, engine-free (gang unit tests)
# ---------------------------------------------------------------------------

def _stub_tokens(prompt, n):
    return [(sum(prompt) * 31 + i * 7) % 97 for i in range(n)]


_stub_span_lock = threading.Lock()
_stub_span_n = [0]


def _stub_span_append(path: str, name: str, start_ns: int, dur_ns: int,
                      trace: int, parent, attrs: dict) -> None:
    """Append ONE span record (same JSONL shape observability/spans.py
    writes — tools/trace_assemble.py stitches both) with write+flush per
    record, so a SIGKILLed stub's completed spans survive. Stdlib-only
    on purpose: the stub path must not import the observability
    package."""
    with _stub_span_lock:
        _stub_span_n[0] += 1
        span_id = ((os.getpid() & 0xFFFF) << 40) | _stub_span_n[0]
        rec = {"name": name, "trace": int(trace), "span": span_id,
               "parent": None if parent is None else int(parent),
               "start_ns": int(start_ns), "dur_ns": int(dur_ns),
               "tid": threading.get_ident(),
               "thread": threading.current_thread().name,
               "attrs": attrs}
        try:
            with open(path, "a") as f:
                f.write(json.dumps(rec) + "\n")
                f.flush()
        except OSError:
            pass


def run_stub(cfg: dict) -> int:
    from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

    stub = cfg.get("stub") or {}
    run_dir = cfg["run_dir"]
    os.makedirs(run_dir, exist_ok=True)
    role = cfg.get("role", ReplicaRole.COLOCATED)
    state = {"served": 0, "hung": False}
    hb_frozen = threading.Event()
    span_path = None
    if cfg.get("trace_dir"):
        # same per-process sink naming as spans.process_sink_path —
        # assembled together with the supervisor's and siblings' files
        os.makedirs(cfg["trace_dir"], exist_ok=True)
        span_path = os.path.join(
            cfg["trace_dir"], f"spans-{role}-{os.getpid()}.jsonl")

    def status():
        if stub.get("poison_after") and \
                state["served"] >= stub["poison_after"]:
            return "poisoned"
        return "ok"

    class H(BaseHTTPRequestHandler):
        protocol_version = "HTTP/1.1"

        def log_message(self, fmt, *args):
            pass

        def _json(self, code, obj):
            body = json.dumps(obj).encode()
            self.send_response(code)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            try:
                self.wfile.write(body)
            except (BrokenPipeError, ConnectionResetError):
                pass

        def do_GET(self):
            if self.path == "/health":
                return self._json(200, {
                    "status": status(), "loop_alive": not state["hung"],
                    "stub": True, "served": state["served"],
                    "role": role})
            if self.path == "/metrics":
                text = (f"paddle_serve_prefill_tokens_total "
                        f"{state['served']}\n").encode()
                self.send_response(200)
                self.send_header("Content-Type",
                                 "text/plain; version=0.0.4")
                self.send_header("Content-Length", str(len(text)))
                self.end_headers()
                self.wfile.write(text)
                return
            self._json(404, {"error": "unknown path"})

        def do_POST(self):
            if self.path not in ("/generate", "/prefill", "/resume"):
                return self._json(404, {"error": "unknown path"})
            n = int(self.headers.get("Content-Length", 0))
            body = json.loads(self.rfile.read(n).decode() or "{}")
            # trace participation (ISSUE 18): adopt the router's wire
            # context and append one span per handled request — flushed
            # at record, so a killed stub's completed spans survive
            wire = body.get("trace")
            t0 = time.perf_counter_ns()
            try:
                self._post_inner(body)
            finally:
                if span_path and isinstance(wire, dict) \
                        and "trace_id" in wire:
                    _stub_span_append(
                        span_path, "stub" + self.path, t0,
                        time.perf_counter_ns() - t0,
                        trace=wire["trace_id"],
                        parent=wire.get("parent_span"),
                        attrs={"pid": os.getpid(), "role": role})

        def _post_inner(self, body):
            if self.path == "/resume" and stub.get("die_on_resume"):
                # mid-transfer kill: the decode replica dies while the
                # migrated request is in its hands (gang failover test)
                os._exit(int(stub.get("die_code", 1)))
            if stub.get("hang_after") is not None and \
                    state["served"] >= stub["hang_after"]:
                state["hung"] = True
                hb_frozen.set()           # heartbeat goes stale too
                time.sleep(600)
            if stub.get("die_after") is not None and \
                    state["served"] >= stub["die_after"]:
                os._exit(int(stub.get("die_code", 1)))
            delay = float(body.get("stub_delay_s",
                                   stub.get("delay_s", 0.0)))
            if delay:
                time.sleep(delay)
            if status() == "poisoned":
                return self._json(503, {"error": "engine poisoned (stub)"})
            if self.path == "/prefill":
                prompt = body.get("prompt") or []
                if not prompt:
                    return self._json(400, {"error": "empty prompt"})
                state["served"] += 1
                # inline fake handoff: checksum lets /resume verify the
                # blob actually travelled router -> decode intact
                return self._json(200, {
                    "first_token": _stub_tokens(prompt, 1)[0],
                    "ttft_ms": delay * 1e3,
                    "transfer_id": body.get("transfer_id") or "stub",
                    "kv": {"stub": True, "checksum": sum(prompt),
                           "prompt_len": len(prompt),
                           "tokens": list(prompt)},
                    "pid": os.getpid()})
            if self.path == "/resume":
                kv = body.get("kv") or {}
                prompt = kv.get("tokens") or body.get("prompt") or []
                if not prompt or kv.get("checksum") != sum(prompt):
                    return self._json(400, {
                        "error": "stub handoff checksum mismatch"})
                toks = _stub_tokens(prompt,
                                    int(body.get("max_new_tokens", 4)))
                if int(body.get("first_token", toks[0])) != toks[0]:
                    return self._json(400, {
                        "error": "stub first-token mismatch"})
                state["served"] += 1
                return self._json(200, {
                    "tokens": toks, "num_tokens": len(toks),
                    "tpot_ms": 0.0, "pid": os.getpid()})
            prompt = body.get("prompt") or []
            toks = _stub_tokens(prompt,
                                int(body.get("max_new_tokens", 4)))
            state["served"] += 1
            self._json(200, {"tokens": toks, "num_tokens": len(toks),
                             "ttft_ms": delay * 1e3, "tpot_ms": 0.0,
                             "pid": os.getpid()})

    httpd = ThreadingHTTPServer(("127.0.0.1", 0), H)
    httpd.daemon_threads = True
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    stop_hb = threading.Event()

    def hb_status():
        if hb_frozen.is_set():
            time.sleep(600)               # freeze: supervisor sees stale
        return status()

    threading.Thread(target=_heartbeat_loop,
                     args=(run_dir, hb_status, stop_hb, 0.2),
                     daemon=True).start()
    _atomic_json(os.path.join(run_dir, READY_NAME),
                 {"port": httpd.server_address[1], "pid": os.getpid(),
                  "stub": True, "role": role,
                  "restored_prefix_records": 0})
    import signal

    done = threading.Event()
    signal.signal(signal.SIGTERM, lambda *_: done.set())
    done.wait()
    httpd.shutdown()
    return 0


# ---------------------------------------------------------------------------
# Real worker: DecodeEngine + FrontDoor + prefix-store warm restart
# ---------------------------------------------------------------------------

def run_engine(cfg: dict) -> int:
    import signal

    import jax

    from paddle_tpu import serving
    from paddle_tpu.models import gpt
    from paddle_tpu.parallel import health

    run_dir = cfg["run_dir"]
    os.makedirs(run_dir, exist_ok=True)
    if cfg.get("trace_dir"):
        # per-process span sink under the gang's shared trace dir: every
        # span this replica records (serve/request, serve/prefill,
        # serve/kv_send, ...) appends to spans-<role>-<pid>.jsonl,
        # flushed per record so a SIGKILL loses at most the in-flight
        # span; tools/trace_assemble.py stitches the fleet's files
        from paddle_tpu.observability import spans as ospans

        ospans.attach_process_sink(cfg["trace_dir"],
                                   cfg.get("role", "engine"))
    m = cfg["model"]
    mcfg = gpt.GPTConfig(
        vocab_size=int(m["vocab_size"]),
        max_seq_len=int(m.get("max_seq_len", 64)),
        num_layers=int(m["num_layers"]), num_heads=int(m["num_heads"]),
        d_model=int(m["d_model"]), d_ff=int(m["d_ff"]), remat=False)
    params = gpt.init_params(jax.random.PRNGKey(int(m.get("seed", 0))),
                             mcfg)
    ekw = dict(cfg.get("engine") or {})
    if "prefill_buckets" in ekw:
        ekw["prefill_buckets"] = tuple(int(b)
                                       for b in ekw["prefill_buckets"])
    engine = serving.DecodeEngine(params, mcfg,
                                  serving.EngineConfig(**ekw))
    restored = 0
    store = None
    if cfg.get("prefix_store_dir"):
        from paddle_tpu.serving.kv_transfer import CacheConfigMismatch
        from paddle_tpu.serving.prefix_store import PrefixStore

        store = PrefixStore(cfg["prefix_store_dir"])
        try:
            restored = engine.attach_prefix_store(store)
        except CacheConfigMismatch as e:
            # a mismatched store must not crash-loop the replica under
            # the gang supervisor: log loudly, serve with a cold cache,
            # and DETACH the store so this incarnation neither trusts
            # nor overwrites records shaped for another config
            sys.stderr.write(f"[replica] prefix store rejected — "
                             f"serving cold: {e}\n")
            sys.stderr.flush()
            engine.prefix_store = None
            try:
                store.close()
            except Exception:
                pass
            store = None
    engine.warmup()
    kv_server = None
    if cfg.get("kv_server"):
        from paddle_tpu.serving.kv_transfer import KVTransferServer

        kv_server = KVTransferServer().start()
    skw = dict(cfg.get("scheduler") or {})
    sched = serving.Scheduler(engine, serving.SchedulerConfig(**skw))

    inject = cfg.get("inject") or {}
    if inject:
        orig_step = sched.step

        def step():
            done = sched.completed
            if inject.get("hang_after") is not None \
                    and done >= inject["hang_after"]:
                # wedge the loop: progress stamps stop, the watchdog
                # (armed from the gang's PADDLE_HEALTH_* env) exits 43
                sys.stderr.write("[replica] injected hang\n")
                sys.stderr.flush()
                time.sleep(3600)
            if inject.get("poison_after") is not None and \
                    done >= inject["poison_after"] and \
                    engine.poisoned is None:
                # stand-in for an executable dying after cache donation
                engine.poisoned = ("injected poison "
                                   "(serve_fault_bench)")
            if inject.get("die_after") is not None \
                    and done >= inject["die_after"]:
                os._exit(int(inject.get("die_code", 1)))
            return orig_step()

        sched.step = step

    def on_poison(reason):
        sys.stderr.write(f"[replica] engine poisoned ({reason}) — "
                         f"exiting {POISONED_EXIT_CODE} for the gang\n")
        sys.stderr.flush()
        os._exit(POISONED_EXIT_CODE)

    front = serving.FrontDoor(
        scheduler=sched, port=int(cfg.get("port", 0)),
        max_queue=int(cfg.get("max_queue", 64)),
        request_timeout_s=float(cfg.get("request_timeout_s", 30.0)),
        on_poison=on_poison, kv_server=kv_server).start()
    # the gang's env contract arms the hang watchdog AFTER warmup (the
    # engine's own compiles ran under health.suspend regardless)
    health.maybe_install_from_env()
    front.install_signal_handlers(
        drain_timeout_s=float(cfg.get("drain_timeout_s", 30.0)))

    stop_hb = threading.Event()
    threading.Thread(
        target=_heartbeat_loop,
        args=(run_dir, lambda: front.health()["status"], stop_hb),
        daemon=True).start()
    _atomic_json(os.path.join(run_dir, READY_NAME),
                 {"port": front.port, "pid": os.getpid(),
                  "role": engine.role,
                  "kv_port": (kv_server.port if kv_server is not None
                              else None),
                  "restored_prefix_records": int(restored)})
    sys.stderr.write(f"[replica] ready on port {front.port} "
                     f"role={engine.role} "
                     f"(restored {restored} prefix records)\n")
    sys.stderr.flush()
    try:
        while front._thread is not None and front._thread.is_alive():
            time.sleep(0.2)
    finally:
        stop_hb.set()
        if kv_server is not None:
            try:
                kv_server.close()
            except Exception:
                pass
        if store is not None:
            try:
                store.close()
            except Exception:
                pass
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--config", required=True,
                    help="path to the replica's JSON config")
    args = ap.parse_args(argv)
    with open(args.config) as f:
        cfg = json.load(f)
    if cfg.get("stub") is not None:
        return run_stub(cfg)
    return run_engine(cfg)


if __name__ == "__main__":
    if __package__ in (None, ""):
        # executed as a file by the gang supervisor: make the package
        # importable without requiring an installed paddle_tpu
        sys.path.insert(0, os.path.abspath(os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "..", "..")))
    sys.exit(main())
