"""Production HTTP front door for the serving engine (docs/serving.md).

One server class, two backends:

- **engine backend** (``FrontDoor(scheduler=...)``): ``POST /generate``
  with ``{"prompt": [token ids], "max_new_tokens": N, "timeout_s": T}`` —
  requests queue into the continuous-batching scheduler and stream through
  the AOT decode engine. A dedicated loop thread ticks the scheduler; the
  handler thread blocks on the request's completion event.
- **predictor backend** (``FrontDoor(predictor=...)``): ``POST /predict``
  with ``{"inputs": {name: nested-list}}`` — the PR-era StableHLO /
  save_inference_model artifact path, now behind the same admission
  control.

Shared production semantics (the ISSUE 9 robustness satellite):

- bounded admission: queue-full -> **429** with a JSON error body;
- per-request deadlines: blown -> **504** (a queued generate request whose
  deadline passes is expired by the scheduler at the token boundary);
- error taxonomy: malformed/mismatched client input -> **400**, internal
  handler failure -> **500**, always with a JSON body (never a raw
  traceback or an empty 500);
- graceful drain: SIGTERM (``install_signal_handlers()``) flips the server
  to *draining* — new work is refused with **503**, in-flight requests
  finish, then the listener closes. ``/health`` reports the phase.
- every response increments ``paddle_serve_requests_total{code}``;
  ``GET /metrics`` serves the Prometheus exposition of the shared
  registry.
"""
from __future__ import annotations

import json
import signal
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Any, Dict, Optional

import numpy as np

from ..observability import spans as _ospans
from . import metrics as smetrics
from .engine import PromptTooLongError
from .scheduler import QueueFullError, Scheduler

__all__ = ["FrontDoor", "EngineLoop", "shed_decision"]


def shed_decision(scheduler: Scheduler, timeout_s: float,
                  retry_after_cap_s: float = 60.0):
    """Deadline-aware admission check (docs/serving.md "Resilience"):
    when the measured queue-drain ETA already exceeds the request's
    deadline, admitting it only guarantees a 504 after the client waited
    the full timeout — shed NOW with a Retry-After computed from the
    drain rate instead. Returns ``None`` (admit) or ``(reason,
    retry_after_s)``; counts ``paddle_serve_shed_total{reason}``."""
    eta = scheduler.queue_eta_s()
    if eta is None or eta <= timeout_s:
        return None
    smetrics.m_shed.labels("deadline").inc()
    return "deadline", scheduler.retry_after_s(retry_after_cap_s)


class EngineLoop:
    """Background thread ticking ``scheduler.step()``; parks on an event
    when idle so an empty server burns no CPU.

    A ``step()`` exception must never kill this thread silently while the
    HTTP server keeps accepting work (every handler would then block to
    504 with no operator-visible signal): the loop catches it, fails every
    queued/active request so their waiters wake with an error, records the
    fault (``faults``/``last_fault``, surfaced through ``/health``), and
    keeps ticking.

    A POISONED engine is different: no later step can ever succeed
    (donated KV pools are invalid — engine.py), so instead of 500ing
    every request forever the loop fails fast — it aborts everything
    with ``refuse_new`` (late submits get a clean error), records
    ``poison_reason``, invokes ``on_poison`` (a supervised replica exits
    with :data:`~paddle_tpu.serving.replica.POISONED_EXIT_CODE` here so
    the gang recycles it with ``cause=poisoned``), and stops ticking.
    ``/health`` reports status ``poisoned``.

    Every iteration stamps hang-watchdog progress (``serve/tick``), so a
    replica armed via the ``PADDLE_HEALTH_*`` env contract exits 43 when
    the loop wedges — the same contract training workers follow.

    Beside the loop a second thread, the beat, keeps the host's side of
    the transfers awake while requests are pending (``_beat``)."""

    # The beat's period. An engine call hands over one host array (PR 38),
    # and with ticks of 20 ms between prefills of 40-250 ms that is too
    # few transfers to keep whatever waits under them from going to
    # sleep: every wait for a transfer, in either direction, then takes
    # 0.5-0.8 ms longer (PERF.md section 6, PR 38). A 64-byte transfer
    # every 4 ms while the loop has work keeps it awake.
    BEAT_S = 0.004

    def __init__(self, scheduler: Scheduler, idle_sleep_s: float = 0.002,
                 on_poison=None):
        self.scheduler = scheduler
        self.idle_sleep_s = idle_sleep_s
        self.beats = 0
        self.faults = 0
        self.last_fault: Optional[str] = None
        self.on_poison = on_poison
        self.poison_reason: Optional[str] = None
        self._stop = threading.Event()
        self._wake = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._beat_thread: Optional[threading.Thread] = None

    def start(self) -> "EngineLoop":
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="serve-engine-loop")
        self._thread.start()
        self._beat_thread = threading.Thread(target=self._beat, daemon=True,
                                             name="serve-engine-beat")
        self._beat_thread.start()
        return self

    @property
    def alive(self) -> bool:
        return self._thread is not None and self._thread.is_alive()

    def wake(self) -> None:
        self._wake.set()

    def stop(self, timeout: float = 5.0) -> None:
        self._stop.set()
        self._wake.set()
        for thread in (self._thread, self._beat_thread):
            if thread:
                thread.join(timeout=timeout)

    def _beat(self) -> None:
        """While requests are pending, one tiny host-to-device transfer
        every ``BEAT_S``: nothing reads it, no program waits for it (it
        is no argument of any), and an idle server sends none."""
        import jax

        word = np.zeros((16,), np.int32)
        while not self._stop.wait(self.BEAT_S):
            if self.scheduler.pending():
                jax.device_put(word)
                self.beats += 1

    def _run(self) -> None:
        from ..parallel import health as _health

        idle = None     # the open serve/loop_idle span, while parked
        while not self._stop.is_set():
            _health.progress("serve/tick")
            if self._check_poisoned():
                break
            worked = False
            if self.scheduler.pending():
                try:
                    # a step that can do nothing is counted and parked
                    # under the idle span that is open: no record a
                    # step, none a park
                    if not self.scheduler.stalled_step():
                        if idle is not None:
                            idle.__exit__(None, None, None)
                            idle = None
                        worked = self.scheduler.step()
                except Exception as e:
                    self.faults += 1
                    self.last_fault = f"{type(e).__name__}: {e}"
                    try:
                        self.scheduler.abort_all(
                            f"engine loop fault: {self.last_fault}")
                    except Exception:
                        pass  # never let cleanup kill the loop either
                    if self._check_poisoned():
                        break
            if not worked:
                # one span for the whole stretch with nothing to do, not
                # one a park: an empty server must not wash its requests'
                # records out of the ring
                if idle is None:
                    idle = _ospans.span(
                        "serve/loop_idle",
                        trace=getattr(self.scheduler, "loop_trace", None))
                    idle.__enter__()
                self._wake.wait(timeout=self.idle_sleep_s)
                self._wake.clear()
        if idle is not None:
            idle.__exit__(None, None, None)
        try:
            # a tick dispatched ahead is collected before the thread ends
            self.scheduler.settle()
        except Exception as e:
            self.faults += 1
            self.last_fault = f"{type(e).__name__}: {e}"

    def _check_poisoned(self) -> bool:
        """Fail-fast on a poisoned engine: abort + refuse, fire
        ``on_poison``, stop the loop. Returns True when poisoned."""
        reason = getattr(self.scheduler.engine, "poisoned", None)
        if reason is None:
            return False
        if self.poison_reason is None:
            self.poison_reason = str(reason)
            try:
                self.scheduler.abort_all(
                    f"engine poisoned: {reason}", refuse_new=True)
            except Exception:
                pass
            if self.on_poison is not None:
                try:
                    self.on_poison(self.poison_reason)
                except Exception:
                    pass
        self._stop.set()
        return True


class _Server(ThreadingHTTPServer):
    # the stdlib default listen backlog (5) resets connections under a
    # burst of simultaneous connects — exactly the overload moment the
    # shedding path exists for; shed with a 429, not a TCP reset
    request_queue_size = 128


class _Handler(BaseHTTPRequestHandler):
    protocol_version = "HTTP/1.1"

    def log_message(self, fmt, *args):
        if self.server.front.verbose:
            super().log_message(fmt, *args)

    # -- plumbing ----------------------------------------------------------
    def _json(self, code: int, obj: Dict[str, Any],
              retry_after: Optional[int] = None) -> None:
        if retry_after is not None:
            # both the header (standard clients) and a JSON field
            # (the gang router + simple SDKs read the body only)
            obj = dict(obj, retry_after_s=int(retry_after))
        body = json.dumps(obj).encode()
        self.send_response(code)
        self.send_header("Content-Type", "application/json")
        if retry_after is not None:
            self.send_header("Retry-After", str(int(retry_after)))
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        try:
            self.wfile.write(body)
        except (BrokenPipeError, ConnectionResetError):
            pass  # client went away; the count below still records it
        smetrics.request_code(code)

    def _read_json(self) -> Optional[Dict[str, Any]]:
        n = int(self.headers.get("Content-Length", 0))
        if n > self.server.front.max_body_bytes:
            self._json(413, {"error": "body too large"})
            return None
        try:
            return json.loads(self.rfile.read(n).decode())
        except (ValueError, UnicodeDecodeError) as e:
            self._json(400, {"error": f"malformed JSON body: {e}"})
            return None

    # -- routes ------------------------------------------------------------
    def do_GET(self):
        front = self.server.front
        if self.path == "/health":
            return self._json(200, front.health())
        if self.path == "/metrics":
            from ..observability import prom

            text = prom.render().encode()
            self.send_response(200)
            self.send_header("Content-Type",
                             "text/plain; version=0.0.4")
            self.send_header("Content-Length", str(len(text)))
            self.end_headers()
            self.wfile.write(text)
            smetrics.request_code(200)
            return
        self._json(404, {"error": f"unknown path {self.path!r}"})

    def do_POST(self):
        front = self.server.front
        if self.path == "/generate":
            return self._generate(front)
        if self.path == "/prefill":
            return self._prefill(front)
        if self.path == "/resume":
            return self._resume(front)
        if self.path == "/predict":
            return self._predict(front)
        self._json(404, {"error": f"unknown path {self.path!r}"})

    @staticmethod
    def _parse_sampling(req_obj):
        if not any(k in req_obj for k in ("temperature", "top_k",
                                          "top_p", "seed")):
            return None
        from .sampling import SamplingParams

        return SamplingParams(
            temperature=float(req_obj.get("temperature", 0.0)),
            top_k=int(req_obj.get("top_k", 0)),
            top_p=float(req_obj.get("top_p", 1.0)),
            seed=int(req_obj.get("seed", 0)))

    # -- disaggregated phases (ISSUE 17) -----------------------------------
    def _prefill(self, front: "FrontDoor"):
        """Prefill-only: run to the first token, then either push the
        KV handoff to the caller-named decode replica's transfer
        endpoint (``kv_target``) or return it inline (base64)."""
        if front.scheduler is None:
            return self._json(400, {"error": "no generation engine loaded"})
        if front.draining:
            return self._json(503, {"error": "server is draining"},
                              retry_after=front._retry_after())
        req_obj = self._read_json()
        if req_obj is None:
            return
        prompt = req_obj.get("prompt") or req_obj.get("tokens")
        if not isinstance(prompt, list) or not prompt:
            return self._json(
                400, {"error": "body must carry a non-empty token list "
                               "under 'prompt'"})
        timeout_s = req_obj.get("timeout_s")
        timeout_s = (front.request_timeout_s if timeout_s is None
                     else float(timeout_s))
        try:
            request = front.scheduler.submit(
                prompt, max_new_tokens=int(req_obj.get(
                    "max_new_tokens", 16)),
                timeout_s=timeout_s,
                sampling=self._parse_sampling(req_obj),
                prefill_only=True,
                trace_ctx=_ospans.extract(req_obj))
        except QueueFullError as e:
            smetrics.m_shed.labels("queue_full").inc()
            return self._json(429, {"error": str(e)},
                              retry_after=front._retry_after())
        except PromptTooLongError as e:
            return self._json(400, {"error": str(e)})
        except (TypeError, ValueError) as e:
            return self._json(400, {"error": f"{type(e).__name__}: {e}"})
        except RuntimeError as e:
            return self._json(503, {"error": str(e)},
                              retry_after=front._retry_after())
        front.loop.wake()
        request.wait(timeout=timeout_s + 1.0)
        if request.state != "done" or request.handoff is None:
            if request.state in ("expired", "queued", "active"):
                return self._json(504, {
                    "error": request.error or "deadline exceeded"})
            return self._json(500, {"error": request.error
                                    or f"request {request.state}"})
        from . import kv_transfer as kvt

        handoff = request.handoff
        resp = {"first_token": int(request.tokens[0]),
                "ttft_ms": round(request.ttft_ms, 3),
                "transfer_id": handoff["transfer_id"]}
        kv_target = req_obj.get("kv_target")
        if kv_target:
            handoff = dict(handoff, transfer_id=str(
                kv_target.get("transfer_id") or handoff["transfer_id"]))
            try:
                kvt.send_handoff(kv_target["host"],
                                 int(kv_target["port"]), handoff,
                                 timeout_s=timeout_s)
            except Exception as e:
                # the prefill itself succeeded; the handoff channel did
                # not — 502 tells the router to degrade to colocated
                return self._json(502, {
                    "error": f"KV push failed: {type(e).__name__}: {e}",
                    "first_token": int(request.tokens[0])})
            resp["transfer_id"] = handoff["transfer_id"]
            resp["transferred"] = True
        else:
            resp["kv"] = kvt.handoff_to_jsonable(handoff)
        return self._json(200, resp)

    def _resume(self, front: "FrontDoor"):
        """Decode a migrated request: adopt its KV handoff (socket
        transfer by id, or inline) and generate from the first token."""
        if front.scheduler is None:
            return self._json(400, {"error": "no generation engine loaded"})
        if front.draining:
            return self._json(503, {"error": "server is draining"},
                              retry_after=front._retry_after())
        req_obj = self._read_json()
        if req_obj is None:
            return
        if "first_token" not in req_obj:
            return self._json(400, {"error": "body must carry "
                                             "'first_token'"})
        prompt = req_obj.get("prompt") or []
        timeout_s = req_obj.get("timeout_s")
        timeout_s = (front.request_timeout_s if timeout_s is None
                     else float(timeout_s))
        from . import kv_transfer as kvt

        if req_obj.get("transfer_id"):
            if front.kv_server is None:
                return self._json(400, {
                    "error": "no KV transfer server on this replica"})
            try:
                handoff = front.kv_server.pop(
                    str(req_obj["transfer_id"]),
                    timeout_s=min(timeout_s, 10.0))
            except TimeoutError as e:
                return self._json(504, {"error": str(e)})
        elif req_obj.get("kv"):
            try:
                handoff = kvt.handoff_from_jsonable(req_obj["kv"])
            except Exception as e:
                return self._json(400, {
                    "error": f"malformed inline handoff: {e}"})
        else:
            return self._json(400, {"error": "body must carry "
                                             "'transfer_id' or 'kv'"})
        try:
            request = front.scheduler.submit_handoff(
                handoff, int(req_obj["first_token"]),
                max_new_tokens=int(req_obj.get("max_new_tokens", 16)),
                timeout_s=timeout_s,
                sampling=self._parse_sampling(req_obj),
                prompt=prompt or None,
                trace_ctx=_ospans.extract(req_obj))
        except QueueFullError as e:
            smetrics.m_shed.labels("queue_full").inc()
            return self._json(429, {"error": str(e)},
                              retry_after=front._retry_after())
        except (TypeError, ValueError) as e:
            return self._json(400, {"error": f"{type(e).__name__}: {e}"})
        except RuntimeError as e:
            return self._json(503, {"error": str(e)},
                              retry_after=front._retry_after())
        front.loop.wake()
        request.wait(timeout=timeout_s + 1.0)
        if request.state == "done":
            return self._json(200, {
                "tokens": request.tokens,
                "num_tokens": len(request.tokens),
                "tpot_ms": (round(request.tpot_ms, 3)
                            if request.tpot_ms is not None else None),
            })
        if request.state in ("expired", "queued", "active"):
            return self._json(504, {
                "error": request.error or "deadline exceeded",
                "partial_tokens": request.tokens})
        return self._json(500, {"error": request.error
                                or f"request {request.state}"})

    # -- engine backend ----------------------------------------------------
    def _generate(self, front: "FrontDoor"):
        if front.scheduler is None:
            return self._json(400, {"error": "no generation engine loaded"})
        if front.draining:
            return self._json(503, {"error": "server is draining"},
                              retry_after=front._retry_after())
        req_obj = self._read_json()
        if req_obj is None:
            return
        prompt = req_obj.get("prompt") or req_obj.get("tokens")
        if not isinstance(prompt, list) or not prompt:
            return self._json(
                400, {"error": "body must carry a non-empty token list "
                               "under 'prompt'"})
        timeout_s = req_obj.get("timeout_s")
        timeout_s = (front.request_timeout_s if timeout_s is None
                     else float(timeout_s))
        if front.shed_deadline_aware:
            shed = shed_decision(front.scheduler, timeout_s,
                                 front.retry_after_cap_s)
            if shed is not None:
                reason, after = shed
                return self._json(429, {
                    "error": f"queue drain ETA exceeds the request "
                             f"deadline ({timeout_s:.1f}s) — shed "
                             f"({reason})"}, retry_after=after)
        try:
            sampling = self._parse_sampling(req_obj)
            request = front.scheduler.submit(
                prompt, max_new_tokens=int(req_obj.get(
                    "max_new_tokens", 16)),
                timeout_s=timeout_s, sampling=sampling,
                trace_ctx=_ospans.extract(req_obj))
        except QueueFullError as e:
            smetrics.m_shed.labels("queue_full").inc()
            return self._json(429, {"error": str(e)},
                              retry_after=front._retry_after())
        except PromptTooLongError as e:
            return self._json(400, {"error": str(e)})
        except (TypeError, ValueError) as e:
            return self._json(400, {"error": f"{type(e).__name__}: {e}"})
        except RuntimeError as e:
            # draining raced the check above, or a poisoned engine's
            # refusal — either way: clean 503, come back later/elsewhere
            return self._json(503, {"error": str(e)},
                              retry_after=front._retry_after())
        front.loop.wake()
        # the scheduler owns the deadline; +1s of slack covers loop wakeup
        request.wait(timeout=timeout_s + 1.0)
        if request.state == "done":
            return self._json(200, {
                "tokens": request.tokens,
                "num_tokens": len(request.tokens),
                "ttft_ms": round(request.ttft_ms, 3),
                "tpot_ms": (round(request.tpot_ms, 3)
                            if request.tpot_ms is not None else None),
            })
        if request.state in ("expired", "queued", "active"):
            return self._json(504, {
                "error": request.error or "deadline exceeded",
                "partial_tokens": request.tokens})
        return self._json(500, {"error": request.error
                                or f"request {request.state}"})

    # -- predictor backend -------------------------------------------------
    def _predict(self, front: "FrontDoor"):
        if front.predictor is None:
            return self._json(400, {"error": "no predictor loaded"})
        if front.draining:
            return self._json(503, {"error": "server is draining"})
        req_obj = self._read_json()
        if req_obj is None:
            return
        if "inputs" not in req_obj or not isinstance(req_obj["inputs"],
                                                     dict):
            return self._json(400, {"error": "body must carry 'inputs'"})
        if not front._predict_slots.acquire(blocking=False):
            smetrics.m_shed.labels("queue_full").inc()
            return self._json(429, {
                "error": f"predict queue at capacity "
                         f"({front.max_queue})"}, retry_after=1)
        t0 = time.monotonic()
        deadline = t0 + front.request_timeout_s
        try:
            feed = {k: np.asarray(v) for k, v in req_obj["inputs"].items()}
            # predictor calls are serialized (one device queue); waiting
            # for the run lock IS the queueing — bounded by the deadline
            if not front._run_lock.acquire(
                    timeout=max(0.0, deadline - time.monotonic())):
                return self._json(504, {
                    "error": "deadline exceeded while queued"})
            try:
                front._inflight += 1
                outs = front.predictor.run(feed)
            finally:
                front._inflight -= 1
                front._run_lock.release()
        except (KeyError, ValueError, TypeError) as e:
            # client-shaped failure: wrong names, shapes, dtypes
            return self._json(400, {"error": f"{type(e).__name__}: {e}"})
        except Exception as e:
            return self._json(500, {"error": f"{type(e).__name__}: {e}"})
        finally:
            front._predict_slots.release()
        smetrics.m_ttft_ms.labels("predict", "colocated").observe(
            (time.monotonic() - t0) * 1e3)
        return self._json(200, {"outputs": [np.asarray(o).tolist()
                                            for o in outs]})


class FrontDoor:
    """The serving HTTP server. Construct with exactly one backend:
    ``scheduler=`` (generation) or ``predictor=`` (artifact inference);
    both may be present (generation servers usually also expose their
    tokenizer-side artifact — not required)."""

    def __init__(self, scheduler: Optional[Scheduler] = None,
                 predictor=None, host: str = "127.0.0.1", port: int = 0,
                 max_queue: int = 64, request_timeout_s: float = 30.0,
                 max_body_bytes: int = 256 << 20, verbose: bool = False,
                 shed_deadline_aware: bool = True,
                 retry_after_cap_s: float = 60.0, on_poison=None,
                 kv_server=None):
        if scheduler is None and predictor is None:
            raise ValueError("FrontDoor needs a scheduler or a predictor")
        self.scheduler = scheduler
        self.predictor = predictor
        # KVTransferServer for the socket handoff channel (decode-role
        # replicas in a disaggregated gang; None = inline handoffs only)
        self.kv_server = kv_server
        self.max_queue = int(max_queue)
        self.request_timeout_s = float(request_timeout_s)
        self.max_body_bytes = int(max_body_bytes)
        self.verbose = verbose
        # adaptive overload control (docs/serving.md "Resilience"):
        # reject requests whose measured queue-drain ETA already exceeds
        # their deadline, with a Retry-After from the drain rate
        self.shed_deadline_aware = bool(shed_deadline_aware)
        self.retry_after_cap_s = float(retry_after_cap_s)
        self._draining = False
        self._inflight = 0
        self._run_lock = threading.Lock()
        self._predict_slots = threading.BoundedSemaphore(self.max_queue)
        self.loop = (EngineLoop(scheduler, on_poison=on_poison).start()
                     if scheduler is not None else None)
        self.httpd = _Server((host, port), _Handler)
        self.httpd.daemon_threads = True
        self.httpd.front = self
        self._thread: Optional[threading.Thread] = None
        self._old_handlers: Dict[int, Any] = {}

    # -- lifecycle ---------------------------------------------------------
    @property
    def port(self) -> int:
        return self.httpd.server_address[1]

    @property
    def draining(self) -> bool:
        return self._draining

    def _retry_after(self) -> int:
        """Retry-After seconds for 429/503 responses, from the measured
        scheduler drain rate (1 when no scheduler / no rate yet)."""
        if self.scheduler is None:
            return 1
        try:
            return self.scheduler.retry_after_s(self.retry_after_cap_s)
        except Exception:
            return 1

    def start(self) -> "FrontDoor":
        self._thread = threading.Thread(target=self.httpd.serve_forever,
                                        daemon=True, name="serve-http")
        self._thread.start()
        return self

    def stop(self) -> None:
        if self.loop is not None:
            self.loop.stop()
        self.httpd.shutdown()
        self.httpd.server_close()
        if self._thread:
            self._thread.join(timeout=5)

    def health(self) -> Dict[str, Any]:
        out: Dict[str, Any] = {
            "status": "draining" if self._draining else "ok",
        }
        if self.scheduler is not None:
            out["role"] = getattr(self.scheduler.engine, "role",
                                  "colocated")
        if self.predictor is not None:
            out["inputs"] = self.predictor.get_input_names()
            out["outputs"] = self.predictor.get_output_names()
        if self.scheduler is not None:
            # per-span-name percentile rollups (queue wait, prefill,
            # decode ticks, evictions, whole requests) off the tracer ring
            from ..observability import spans as _ospans

            out["span_rollups_ms"] = {
                k: v for k, v in _ospans.default_tracer().summary().items()
                if k.startswith("serve/")}
            out["queue_depth"] = self.scheduler.queue_depth()
            out["active"] = len(self.scheduler._active)
            out["max_batch"] = self.scheduler.engine.ecfg.max_batch
            out["buckets"] = list(self.scheduler.engine.buckets)
            out["weight_dtype"] = self.scheduler.engine.ecfg.weight_dtype
            out["kv_path"] = getattr(self.scheduler.engine, "kv_path", None)
            # decode ticks by whether their successor was dispatched ahead
            # of the step that collects it, or held and why; the share
            out["early_dispatch"] = dict(
                self.scheduler.early_dispatch,
                ahead_share=self.scheduler.early_dispatch_share())
            out["held_shapes"] = {
                k: list(v) for k, v in getattr(
                    self.scheduler.engine, "held_shapes", {}).items()}
            cache = self.scheduler.engine.cache
            if getattr(cache, "state_bytes_per_slot", 0):
                # a hybrid model: recurrent state the live slots hold, and
                # how many slots' states have been born
                out["state_bytes"] = cache.live_state_bytes()
                out["state_resets"] = cache.state_resets
            if self.loop is not None:
                out["loop_alive"] = self.loop.alive
                out["loop_faults"] = self.loop.faults
                if self.loop.last_fault is not None:
                    out["loop_last_fault"] = self.loop.last_fault
                if not self.loop.alive and not self._draining:
                    out["status"] = "degraded"
            # a poisoned engine outranks everything: donation invalidated
            # its KV pools, no request will ever succeed again — the gang
            # supervisor recycles the replica on this status
            poisoned = getattr(self.scheduler.engine, "poisoned", None)
            if self.loop is not None and self.loop.poison_reason:
                poisoned = poisoned or self.loop.poison_reason
            if poisoned:
                out["status"] = "poisoned"
                out["engine_poisoned"] = str(poisoned)
        return out

    # -- graceful drain ----------------------------------------------------
    def drain(self, timeout_s: float = 60.0) -> bool:
        """Refuse new work, finish what is in flight, then stop. Returns
        True when everything completed inside the timeout."""
        from ..observability import goodput as _goodput

        with _goodput.timer("drain"):
            return self._drain_inner(timeout_s)

    def _drain_inner(self, timeout_s: float) -> bool:
        self._draining = True
        ok = True
        if self.scheduler is not None:
            with self.scheduler._lock:
                self.scheduler._draining = True
            if self.loop is not None:
                self.loop.wake()
            end = time.monotonic() + timeout_s
            while time.monotonic() < end and self.scheduler.pending():
                time.sleep(0.01)
            ok = self.scheduler.pending() == 0
        end = time.monotonic() + max(0.1, timeout_s / 10)
        while time.monotonic() < end and self._inflight > 0:
            time.sleep(0.01)
        ok = ok and self._inflight == 0
        self.stop()
        return ok

    def install_signal_handlers(self, drain_timeout_s: float = 60.0) -> None:
        """SIGTERM/SIGINT -> graceful drain in a helper thread (the
        handler itself must return immediately — it may run on the main
        thread mid-request)."""

        def _on_signal(signum, frame):
            threading.Thread(target=self.drain,
                             kwargs={"timeout_s": drain_timeout_s},
                             daemon=True,
                             name="serve-drain").start()

        for sig in (signal.SIGTERM, signal.SIGINT):
            self._old_handlers[sig] = signal.signal(sig, _on_signal)

    def restore_signal_handlers(self) -> None:
        for sig, h in self._old_handlers.items():
            signal.signal(sig, h)
        self._old_handlers.clear()
