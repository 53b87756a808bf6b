"""Multi-process launcher — parity with python/paddle/distributed/launch.py
(:193 launch, utils.py:338-375 env contract): spawns one worker process per
device/host slot, sets the PADDLE_* env, and supervises the gang.

The reference's TrainerProc watch loop aborts the whole job on any failure;
this launcher is the elastic superset (ROADMAP item 4, docs/elastic.md):

- **Graceful shutdown**: a dying gang gets SIGTERM, a grace period to
  checkpoint-and-exit (workers install :func:`install_preemption_handler`),
  then SIGKILL.  The first failing child's exit code propagates (signal
  deaths map to the shell convention 128+N).
- **Preemption tolerance**: SIGTERM/SIGINT on the launcher is trapped and
  forwarded to the children, which checkpoint and exit cleanly; the
  launcher then returns 0 so an external scheduler sees a clean preemption.
- **Supervised restarts**: ``max_restarts > 0`` restarts the whole gang
  after a worker failure (collective jobs cannot survive a lone member —
  every rank restarts together and resumes from the latest committed
  checkpoint), with exponential backoff between attempts.  Restarts count
  into ``paddle_restarts_total{cause=hang|crash|preempt}`` through the
  PR 3 registry: a worker exiting with ``health.HANG_EXIT_CODE`` (its own
  hang watchdog fired) is ``hang``, an untrapped SIGTERM death is
  ``preempt``, and every other failure — any signal or nonzero exit — is
  ``crash``.
- **In-run health** (ISSUE 8, docs/health.md): ``hang_deadline_s`` /
  ``health_dir`` export the :mod:`.health` env contract to every worker
  (each installs a hang watchdog that stack-dumps and exits with the
  ``hang`` code when no dispatch progress lands inside the deadline), and
  the supervisor polls the shared heartbeat dir for stragglers —
  ``paddle_straggler_detected_total{rank}`` plus a rate-limited warning
  naming the slow rank.

On TPU the normal deployment is one process per HOST (all local chips in one
process), so --nproc_per_node defaults to 1; the per-GPU spawning of the
reference maps to per-host here.
"""
from __future__ import annotations

import os
import signal
import subprocess
import sys
import threading
import time
from typing import Callable, List, Optional

from ..observability import flight as _flight
from ..observability import goodput as _goodput
from ..observability import metrics as _obs_metrics
from . import health as _health

_m_restarts = _obs_metrics.default_registry().counter(
    "paddle_restarts_total",
    "Supervised gang restarts by cause (hang, crash, preempt)",
    ("cause",))
_m_input_stalls = _obs_metrics.default_registry().counter(
    "paddle_input_stall_reports_total",
    "Input-stall reports surfaced by the supervisor, by rank", ("rank",))


def _poll_input_stall_reports(health_dir: str, seen: dict) -> list:
    """Surface workers' input-stall reports (docs/data.md): a stalled
    sharded stream writes ``input_stall.rank<R>.json`` into the shared
    health dir; the supervisor polls it alongside the straggler check so a
    slow/corrupt shard is visible at the JOB level, not just in one
    worker's log.  ``seen`` maps path -> last-surfaced mtime; returns the
    new reports."""
    import glob
    import json as _json

    out = []
    for path in sorted(glob.glob(
            os.path.join(health_dir, "input_stall.rank*.json"))):
        try:
            mtime = os.path.getmtime(path)
            if seen.get(path) == mtime:
                continue
            with open(path) as f:
                rep = _json.load(f)
        except (OSError, ValueError):
            continue
        seen[path] = mtime
        _m_input_stalls.labels(str(rep.get("rank", "?"))).inc()
        sys.stderr.write(
            f"launch: rank {rep.get('rank')} input stalled "
            f"{rep.get('waited_s')}s on shard {rep.get('shard')!r} "
            "(slow storage or a stuck decode worker — see docs/data.md "
            "runbook)\n")
        out.append(rep)
    return out


def get_cluster_endpoints(node_ips: List[str], nproc_per_node: int,
                          start_port: int = 6070) -> List[str]:
    eps = []
    for ip in node_ips:
        for i in range(nproc_per_node):
            eps.append(f"{ip}:{start_port + i}")
    return eps


# ---------------------------------------------------------------------------
# Worker-side helpers
# ---------------------------------------------------------------------------

class PreemptionSignal:
    """Process-wide preemption flag set by SIGTERM/SIGINT.  Training loops
    poll :attr:`triggered` (or :meth:`check`) at step boundaries, save a
    checkpoint, and exit cleanly — the launcher's grace period exists
    exactly for this."""

    def __init__(self):
        self.triggered = False
        self.signum: Optional[int] = None
        self._callbacks: List[Callable[[], None]] = []

    def check(self) -> bool:
        return self.triggered

    def reset(self) -> None:
        """Clear the flag (tests, or a loop that handled the preemption
        itself and decided to continue)."""
        self.triggered = False
        self.signum = None

    def add_callback(self, fn: Callable[[], None]) -> None:
        self._callbacks.append(fn)

    def _fire(self, signum):
        self.triggered = True
        self.signum = signum
        for fn in list(self._callbacks):
            try:
                fn()
            except Exception:
                pass


_preemption: Optional[PreemptionSignal] = None


def install_preemption_handler(
        signals=(signal.SIGTERM, signal.SIGINT)) -> PreemptionSignal:
    """Install (or return the already-installed) preemption trap.  Safe to
    call repeatedly; outside the main thread (where signal handlers cannot
    be installed) the returned flag simply never fires."""
    global _preemption
    if _preemption is not None:
        return _preemption
    sig = PreemptionSignal()

    def handler(signum, frame):
        sig._fire(signum)

    if threading.current_thread() is threading.main_thread():
        for s in signals:
            signal.signal(s, handler)
    _preemption = sig
    return sig


def preemption_signal() -> Optional[PreemptionSignal]:
    """The installed preemption trap, if any (None before install)."""
    return _preemption


def init_collective_with_retry(init_fn: Callable[[], None],
                               retries: int = 5, backoff_s: float = 0.5,
                               backoff_max_s: float = 8.0,
                               log=None) -> None:
    """Retry-with-backoff around collective/backend bring-up
    (``jax.distributed.initialize`` or a custom bootstrap): a slow-starting
    peer raises a connect error on the fast ranks — retrying with
    exponential backoff instead of failing the job lets the gang converge.
    Re-raises the last error after ``retries`` failed attempts."""
    delay = backoff_s
    for attempt in range(1, max(1, retries) + 1):
        try:
            init_fn()
            return
        except Exception as e:
            if attempt >= retries:
                raise
            if log is not None:
                log(f"collective init attempt {attempt}/{retries} failed "
                    f"({e!r}); retrying in {delay:.1f}s")
            time.sleep(delay)
            delay = min(delay * 2, backoff_max_s)


# ---------------------------------------------------------------------------
# Launcher / supervisor
# ---------------------------------------------------------------------------

def _exit_code(ret: int) -> int:
    """Popen returncode -> propagated exit code (signal death N -> 128+N,
    the shell convention)."""
    return 128 - ret if ret < 0 else ret


def _restart_cause(ret: int) -> str:
    """Popen returncode -> paddle_restarts_total cause label.

    ``hang``: the worker's own watchdog declared it stuck and exited with
    the distinct :data:`health.HANG_EXIT_CODE`.  ``preempt``: an untrapped
    SIGTERM death (an external scheduler pulled the node before the worker
    could checkpoint — a trapped preemption exits 0 and never restarts).
    Everything else — SIGKILL/segfault/any nonzero exit — is ``crash``.
    """
    if ret == _health.HANG_EXIT_CODE:
        return "hang"
    if ret < 0:
        return "preempt" if -ret == signal.SIGTERM else "crash"
    return "crash"


def _stop_gang(procs, grace_period_s: float, sig=signal.SIGTERM):
    """Graceful shutdown: ``sig`` to every live child, wait up to the grace
    period for them to checkpoint-and-exit, then SIGKILL stragglers."""
    for _, p, _ in procs:
        if p.poll() is None:
            try:
                p.send_signal(sig)
            except OSError:
                pass
    deadline = time.time() + max(0.0, grace_period_s)
    for _, p, _ in procs:
        if p.poll() is not None:
            continue
        remaining = deadline - time.time()
        try:
            p.wait(timeout=max(0.1, remaining))
        except subprocess.TimeoutExpired:
            p.kill()
    for _, p, _ in procs:
        if p.poll() is None:
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                pass


def _assemble_blame(flight_dir: str, attempt: int) -> Optional[dict]:
    """Run the blame engine (tools/flight_assemble.py) over the dead
    incarnation's flight files: write ``blame.attempt<K>.json`` next to
    them (the restart record), publish ``paddle_blamed_rank`` /
    ``paddle_step_skew_ms``, and return the verdict.  Forensics must
    never fail the restart — any error returns None."""
    try:
        import importlib.util
        import json as _json

        tool = os.path.join(
            os.path.dirname(os.path.dirname(
                os.path.dirname(os.path.abspath(__file__)))),
            "tools", "flight_assemble.py")
        spec = importlib.util.spec_from_file_location(
            "paddle_flight_assemble", tool)
        if spec is None or spec.loader is None:
            return None
        fa = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(fa)
        report = fa.assemble_dir(flight_dir, attempt=attempt)
        verdict = report.get("verdict") or {}
        out = os.path.join(flight_dir, f"blame.attempt{attempt}.json")
        with open(out, "w") as f:
            _json.dump(report, f, indent=1)
        blamed = verdict.get("blamed_ranks") or []
        _flight.note_blame(blamed[0] if blamed else None,
                           verdict.get("step_skew_ms"))
        if blamed:
            sys.stderr.write(
                f"launch: blame verdict (attempt {attempt}): rank(s) "
                f"{blamed} {verdict.get('blame_mode')} at collective seq "
                f"{verdict.get('missed_seq')}"
                + (f" [{verdict['missed_name']}]"
                   if verdict.get("missed_name") else "")
                + f" — {out}\n")
        else:
            sys.stderr.write(
                f"launch: blame assembly (attempt {attempt}): no rank "
                f"blamed — {out}\n")
        return verdict
    except Exception as e:
        sys.stderr.write(f"launch: blame assembly failed: {e}\n")
        return None


def launch(training_script: str, script_args: Optional[List[str]] = None,
           cluster_node_ips: str = "127.0.0.1", node_ip: str = "127.0.0.1",
           nproc_per_node: int = 1, started_port: int = 6070,
           log_dir: Optional[str] = None, perf_flags: bool = True,
           max_restarts: int = 0, restart_backoff_s: float = 1.0,
           restart_backoff_max_s: float = 30.0,
           grace_period_s: float = 15.0,
           hang_deadline_s: Optional[float] = None,
           health_dir: Optional[str] = None,
           straggler_ratio: float = 2.0,
           straggler_warn_cooldown_s: float = 30.0,
           goodput_dir: Optional[str] = None,
           flight_dir: Optional[str] = None) -> int:
    """Spawn and supervise the worker gang; returns the job's exit code
    (0 on success or clean preemption; otherwise the FIRST failing child's
    exit code, with signal deaths mapped to 128+N).

    ``hang_deadline_s``/``health_dir`` arm the in-run health layer
    (docs/health.md): workers install a hang watchdog from the exported
    env contract, write per-rank heartbeats into ``health_dir``, and the
    supervisor polls that dir for stragglers (EWMA step time beyond
    ``straggler_ratio`` x the gang median).

    ``goodput_dir`` (defaults to ``<log_dir>/goodput``) arms gang-wide
    wall-clock accounting (docs/observability.md): workers export their
    per-rank goodput ledgers + Prometheus textfiles there via the
    ``PADDLE_GOODPUT_DIR`` env contract, the supervisor times every
    failure-detect -> respawn window as ``restart_downtime``, and at job
    end it merges everything into ``GOODPUT.json`` (gang goodput
    fraction) plus one merged gang exposition.

    ``flight_dir`` (defaults to ``<log_dir>/flight``, or
    ``<health_dir>/flight`` without a log dir) arms the per-rank flight
    recorder (ISSUE 19, docs/health.md): workers mirror their event
    rings to crash-surviving sidecars via ``PADDLE_FLIGHT_DIR``, and on
    a hang-cause restart the supervisor runs the blame engine
    (tools/flight_assemble.py) over the dead incarnation's files,
    writes ``blame.attempt<K>.json`` next to them, and publishes the
    ``paddle_blamed_rank`` / ``paddle_step_skew_ms`` metric pair.
    """
    from ..sysconfig import tpu_perf_flags

    node_ips = [ip.strip() for ip in cluster_node_ips.split(",")]
    endpoints = get_cluster_endpoints(node_ips, nproc_per_node, started_port)
    node_rank = node_ips.index(node_ip)
    if log_dir:
        os.makedirs(log_dir, exist_ok=True)
    if health_dir is None and (hang_deadline_s is not None) and log_dir:
        health_dir = os.path.join(log_dir, "health")
    if health_dir:
        os.makedirs(health_dir, exist_ok=True)
    if goodput_dir is None and log_dir:
        goodput_dir = os.path.join(log_dir, "goodput")
    if goodput_dir:
        os.makedirs(goodput_dir, exist_ok=True)
    if flight_dir is None:
        if log_dir:
            flight_dir = os.path.join(log_dir, "flight")
        elif health_dir:
            flight_dir = os.path.join(health_dir, "flight")
    if flight_dir:
        os.makedirs(flight_dir, exist_ok=True)
    straggler_mon = (_health.StragglerMonitor(
        health_dir, ratio=straggler_ratio,
        warn_cooldown_s=straggler_warn_cooldown_s)
        if health_dir else None)

    def spawn_gang(attempt: int):
        procs = []
        for local_rank in range(nproc_per_node):
            rank = node_rank * nproc_per_node + local_rank
            env = dict(os.environ)
            env.update({
                "PADDLE_TRAINER_ID": str(rank),
                "PADDLE_TRAINERS_NUM": str(len(endpoints)),
                "PADDLE_TRAINER_ENDPOINTS": ",".join(endpoints),
                "PADDLE_CURRENT_ENDPOINT": endpoints[rank],
                "PADDLE_RESTART_ATTEMPT": str(attempt),
            })
            # health env contract: workers self-install the hang watchdog
            # and heartbeat writer (health.maybe_install_from_env)
            if hang_deadline_s is not None:
                env[_health.ENV_DEADLINE] = str(float(hang_deadline_s))
            if health_dir:
                env[_health.ENV_DIR] = health_dir
            if goodput_dir:
                # goodput env contract: workers export their per-rank
                # ledger + exposition here at run-window exit
                env[_goodput.ENV_DIR] = goodput_dir
            if flight_dir:
                # flight env contract: workers sidecar their event
                # rings here (flight.maybe_attach_from_env)
                env[_flight.ENV_DIR] = flight_dir
            if perf_flags:
                # comm/compute-overlap preset into each worker's
                # LIBTPU_INIT_ARGS BEFORE its backend init (read by libtpu
                # only: harmless for a worker that loads none)
                tpu_perf_flags(env=env)
            # append mode: a restarted worker's log continues the file
            out = (open(os.path.join(log_dir, f"worker.{rank}.log"), "a")
                   if log_dir else None)
            p = subprocess.Popen(
                [sys.executable, training_script] + list(script_args or []),
                env=env, stdout=out,
                stderr=subprocess.STDOUT if out else None,
            )
            procs.append((rank, p, out))
        return procs

    # preemption trap: forward to children, give them the grace period to
    # checkpoint, then return cleanly (main thread only — signal handlers
    # cannot install elsewhere, e.g. under pytest workers calling us from
    # a thread)
    preempted = {"flag": False}
    old_handlers = {}
    in_main = threading.current_thread() is threading.main_thread()
    if in_main:
        def _trap(signum, frame):
            preempted["flag"] = True
        for s in (signal.SIGTERM, signal.SIGINT):
            old_handlers[s] = signal.signal(s, _trap)

    all_procs: List = []
    exit_code = 0
    restarts = 0
    restart_downtime_s = 0.0
    backoff = restart_backoff_s
    last_straggler_poll = 0.0
    stall_seen: dict = {}
    try:
        procs = spawn_gang(0)
        all_procs = list(procs)
        while True:
            if preempted["flag"]:
                sys.stderr.write("launch: preemption signal — forwarding "
                                 "SIGTERM to workers\n")
                _stop_gang(procs, grace_period_s)
                # a clean preemption (children checkpointed and exited 0)
                # is a clean job exit; a child that died badly propagates
                codes = [_exit_code(p.poll()) for _, p, _ in procs
                         if p.poll() not in (0, None)]
                exit_code = codes[0] if codes else 0
                break
            alive, failed = [], None
            for rank, p, out in procs:
                ret = p.poll()
                if ret is None:
                    alive.append((rank, p, out))
                elif ret != 0 and failed is None:
                    failed = (rank, ret)
            if failed is not None:
                rank, ret = failed
                t_fail = time.monotonic()
                code = _exit_code(ret)
                cause = _restart_cause(ret)
                sys.stderr.write(
                    f"launch: worker {rank} exited with {ret} "
                    f"(code {code}, cause {cause})\n")
                _stop_gang(procs, grace_period_s)
                if cause == "hang" and flight_dir:
                    # gang is quiesced: every surviving sidecar is
                    # flushed — name the rank that wedged the gang and
                    # the collective seq it missed (restart record)
                    _assemble_blame(flight_dir, attempt=restarts)
                if restarts < max_restarts:
                    restarts += 1
                    _m_restarts.labels(cause).inc()
                    sys.stderr.write(
                        f"launch: restarting gang (attempt {restarts}/"
                        f"{max_restarts}) in {backoff:.1f}s\n")
                    time.sleep(backoff)
                    backoff = min(backoff * 2, restart_backoff_max_s)
                    for _, _, out in procs:
                        if out:
                            out.close()
                    procs = spawn_gang(restarts)
                    all_procs.extend(procs)
                    # failure detection -> gang respawned: the whole gang
                    # was idle for this window (goodput restart_downtime,
                    # attributed at the job level — a SIGKILL'd worker
                    # cannot report its own death)
                    dt = time.monotonic() - t_fail
                    restart_downtime_s += dt
                    _goodput.attribute("restart_downtime", dt)
                    continue
                exit_code = code
                break
            procs = alive
            if not procs:
                break       # every worker exited 0
            if health_dir is not None and \
                    time.monotonic() - last_straggler_poll >= 2.0:
                last_straggler_poll = time.monotonic()
                if straggler_mon is not None:
                    straggler_mon.poll()
                _poll_input_stall_reports(health_dir, stall_seen)
            time.sleep(0.2)
    finally:
        if in_main:
            for s, h in old_handlers.items():
                signal.signal(s, h)
        # terminate, then reap every child and close its log handle so a
        # failed job leaves no zombies and no buffered log tail unflushed
        for _, p, out in all_procs:
            if p.poll() is None:
                p.terminate()
        for _, p, out in all_procs:
            try:
                p.wait(timeout=15)
            except subprocess.TimeoutExpired:
                p.kill()
                p.wait()
            if out and not out.closed:
                out.close()
    if goodput_dir:
        # gang aggregation: merge the per-rank ledgers + expositions the
        # workers exported, charge the supervisor's restart-downtime
        # windows, and write GOODPUT.json with the gang goodput fraction
        try:
            path = _goodput.write_gang_report(
                goodput_dir, restart_downtime_s=restart_downtime_s,
                nranks=len(endpoints),
                extra={"exit_code": exit_code, "restarts": restarts})
            if path:
                sys.stderr.write(f"launch: gang goodput report: {path}\n")
        except Exception as e:   # accounting must never fail the job
            sys.stderr.write(f"launch: goodput aggregation failed: {e}\n")
    return exit_code


def main():  # CLI: python -m paddle_tpu.parallel.launch script.py args...
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--cluster_node_ips", default="127.0.0.1")
    ap.add_argument("--node_ip", default="127.0.0.1")
    ap.add_argument("--nproc_per_node", type=int, default=1)
    ap.add_argument("--started_port", type=int, default=6070)
    ap.add_argument("--log_dir", default=None)
    ap.add_argument("--max_restarts", type=int, default=0,
                    help="restart the gang up to N times after a worker "
                         "failure (exponential backoff)")
    ap.add_argument("--restart_backoff", type=float, default=1.0)
    ap.add_argument("--grace_period", type=float, default=15.0,
                    help="seconds between SIGTERM and SIGKILL at shutdown")
    ap.add_argument("--hang_deadline", type=float, default=None,
                    help="arm each worker's hang watchdog: no dispatch "
                         "progress for this many seconds dumps stacks and "
                         "restarts the gang with cause=hang")
    ap.add_argument("--health_dir", default=None,
                    help="shared dir for hang dumps + per-rank heartbeats "
                         "(default: <log_dir>/health when the watchdog is "
                         "armed)")
    ap.add_argument("--straggler_ratio", type=float, default=2.0,
                    help="flag ranks whose step-time EWMA exceeds this "
                         "multiple of the gang median")
    ap.add_argument("--goodput_dir", default=None,
                    help="shared dir for per-rank goodput ledgers; the "
                         "supervisor merges them (plus its restart-"
                         "downtime windows) into GOODPUT.json (default: "
                         "<log_dir>/goodput)")
    ap.add_argument("--flight_dir", default=None,
                    help="shared dir for per-rank flight-recorder "
                         "sidecars; on a hang-cause restart the "
                         "supervisor writes blame.attempt<K>.json here "
                         "(default: <log_dir>/flight)")
    ap.add_argument("--no_perf_flags", action="store_true",
                    help="skip the sysconfig.tpu_perf_flags XLA preset")
    ap.add_argument("training_script")
    ap.add_argument("script_args", nargs=argparse.REMAINDER)
    args = ap.parse_args()
    sys.exit(launch(args.training_script, args.script_args,
                    args.cluster_node_ips, args.node_ip, args.nproc_per_node,
                    args.started_port, args.log_dir,
                    perf_flags=not args.no_perf_flags,
                    max_restarts=args.max_restarts,
                    restart_backoff_s=args.restart_backoff,
                    grace_period_s=args.grace_period,
                    hang_deadline_s=args.hang_deadline,
                    health_dir=args.health_dir,
                    straggler_ratio=args.straggler_ratio,
                    goodput_dir=args.goodput_dir,
                    flight_dir=args.flight_dir))


if __name__ == "__main__":
    main()
