"""The program's spans joined to the profiler's trace, and a device program
to the span that called it and the span that waited for it.

A span of the program exists twice: as a record in the tracer's ring, on
``spans.clock_ns``, with its attributes and its parent; and, while a
profiler session runs, as a ``paddle/<name>`` annotation on the profiler's
host clock whose one stat, ``span``, is the record's id. Three joins are
made here, once a traced run:

- record <-> annotation, by id, exactly. The joined pairs give the constant
  between the two host clocks (they run at one rate), and with it every
  record is placed on the profiler's clock, annotated or not: what
  ``record()`` wrote (``serve/queue_wait``, ``serve/request``), and a span
  that was open when the session began.
- call <-> device program. ``decode/call`` and ``prefill/call`` are the
  engine's boundary with the device, made on one thread, and the device
  runs what it is handed in that order, one program at a time: the
  programs of the first device plane's "XLA Modules" line follow one
  another as their calls do (a prefill called while a tick is in flight
  queues behind it), so the two sequences are merged in order. The
  trace's shared timeline is trusted to 2 ms and no finer (programs lie
  5 ms and more apart, the skew in doubt is one) for the one thing the
  order cannot say, which side lacks an entry: a program begun more than
  2 ms before the next call left is nobody's here (its call was before
  the ring's window), and a call whose ``*/run`` had its tokens more than
  2 ms before the next program left was over has no program in the trace.
  A pair is then held to its kinds (``exe`` against the program's name)
  and to its durations: a program is shorter than the time from its
  call's start to the end of the ``*/run`` that waited for it. What fails
  is counted, printed and left out.
- call <-> ``*/run``, by the run's attribute ``call``: a tick found in
  flight was called one step before the step that collects it.

Per joined program three quantities that no skew between the host's and
the device's clock can move, each a difference on ONE clock:
``round_trip`` (the call's start to the run's end, host), ``program`` (the
program's duration, device), ``call`` (the call's length, host);
**``overhead`` = round_trip - program**, what the call cost beyond its
program, and **``beyond_call`` = overhead - call**, what is left when the
host's own work of dispatching is taken out: the program's start latency,
the tokens' transfer, the runtime's notification, the thread's wake-up.
A pair whose round trip holds another's program (a prefill called while a
tick was in flight, and that tick, collected behind the prefill) is
counted and kept out of the medians: its numbers are true and say nothing
of a call's cost.

The skew itself is bounded by causality: with ``lag`` = program start -
call start and ``wake`` = run end - program end as the shared timeline
gives them, the device's events lie between ``-min(wake)`` and
``+min(lag)`` too late. ``trace_reduce.idle_gaps`` divides idle time at
the edge of a program by that timeline, so its division of one gap
between the span before a program and the span after it moves with the
session's skew; the lines printed here show by how much.

A program without the stat or without the call spans gives ``None``
everywhere, and a reader that gets ``None`` reports nothing.
"""
import glob
import os

from benchmark import program_spans, stats, trace_reduce

ANCHOR_NS = 2_000_000
CALL_SPANS = {"decode/call": "decode/run", "prefill/call": "prefill/run"}
# ``exe`` as the engine keys its executables -> what the program's name holds
PROGRAM_OF = (("decode", "decode_fn"), ("prefill_b", "prefill_fn"),
              ("verify_w", "verify_fn"))


def program_kind(exe):
    return next(needle for key, needle in PROGRAM_OF if exe.startswith(key))


def load_annotations(path):
    """[(name, start_ns, dur_ns, span id or None)] of the ``paddle/``
    annotations on the host planes of an ``.xplane.pb``, by start."""
    from jax.profiler import ProfileData

    prefix = trace_reduce.PROGRAM_PREFIX
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith(trace_reduce.DEVICE_PLANE_PREFIX):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(prefix):
                    span = next((v for k, v in ev.stats if k == "span"),
                                None)
                    out.append((ev.name[len(prefix):], int(ev.start_ns),
                                int(ev.duration_ns), span))
    out.sort(key=lambda a: a[1])
    return out


def ring_records(spans_module, window):
    """(the default tracer's records that overlap ``window``, the window
    in the ring's units), ``window`` being ``time.monotonic()`` seconds;
    None where the tracer cannot vouch for it
    (``program_spans.window_records``)."""
    if program_spans.window_records(spans_module, window) is None:
        return None
    w0, w1 = (spans_module.monotonic_to_ns(t) for t in window)
    return [r for r in spans_module.default_tracer().spans()
            if r["start_ns"] <= w1 and _end(r) >= w0], (w0, w1)


def _end(rec):
    return rec["start_ns"] + rec["dur_ns"]


class Join:
    """``records``: ring records that overlap the traced window ``(w0,
    w1)`` (ring clock); ``annotations``: ``load_annotations``;
    ``modules``: the first device plane's programs ``[(name, start_ns,
    dur_ns)]``, or nothing where there is no device plane."""

    def __init__(self, records, annotations, modules, window):
        self.records, self.window = records, window
        self.by_id = {r["span"]: r for r in records}
        self._clock(annotations)
        self.pairs, self.lone_calls, self.lone_programs = [], [], []
        self.refused = []
        if self.offset_ns is not None and modules:
            self._pair(sorted(
                (m for m in modules
                 if any(needle in m[0] for _, needle in PROGRAM_OF)),
                key=lambda m: m[1]))

    # -- record <-> annotation ----------------------------------------------
    def _clock(self, annotations):
        found = {a[3]: a for a in annotations if a[3] in self.by_id}
        self.found = found
        diffs = sorted(a[1] - self.by_id[i]["start_ns"]
                       for i, a in found.items())
        self.offset_ns = int(stats.median(diffs)) if diffs else None
        self.offset_spread_ns = diffs[-1] - diffs[0] if diffs else None
        # the gauge: of the records inside the window that are annotated
        # at all (``record()`` writes none: no annotation has their name),
        # the share that found theirs
        names = {a[0] for a in annotations}
        w0, w1 = self.window
        inside = [r for r in self.records
                  if w0 <= r["start_ns"] and _end(r) <= w1]
        self.annotated = [r for r in inside if r["name"] in names]
        self.matched = [r for r in self.annotated if r["span"] in found]
        self.unannotated = [r for r in self.records
                            if r["span"] not in found]

    @property
    def share(self):
        if not self.annotated:
            return None
        return 100.0 * len(self.matched) / len(self.annotated)

    def placed(self, rec):
        """(start, end) of a ring record on the profiler's clock."""
        return (rec["start_ns"] + self.offset_ns, _end(rec) + self.offset_ns)

    # -- call <-> program <-> run ---------------------------------------------
    def _pair(self, programs):
        calls = sorted((r for r in self.records if r["name"] in CALL_SPANS
                        and "exe" in r.get("attrs", {})),
                       key=lambda r: r["start_ns"])
        runs = {r["attrs"]["call"]: r for r in self.records
                if r["name"] in CALL_SPANS.values()
                and r.get("attrs", {}).get("call") is not None}
        i = j = 0
        while i < len(programs) and j < len(calls):
            prog, call = programs[i], calls[j]
            run = runs.get(call["span"])
            if call["start_ns"] + self.offset_ns > prog[1] + ANCHOR_NS:
                # begun before the first call that is left: nobody's here
                self.lone_programs.append(prog)
                i += 1
            elif run is not None and (prog[1] + prog[2] > _end(run)
                                      + self.offset_ns + ANCHOR_NS):
                # the wait was over before this program was: another's,
                # and the call's own is not in the trace
                self.lone_calls.append(call)
                j += 1
            else:
                i, j = i + 1, j + 1
                if (program_kind(call["attrs"]["exe"]) not in prog[0]
                        or (run is not None
                            and prog[2] >= _end(run) - call["start_ns"])):
                    self.refused.append((call, prog))
                else:
                    self.pairs.append(_Pair(self, call, prog, run))
        self.lone_programs += programs[i:]
        self.lone_calls += calls[j:]
        # a call made while its predecessor was still waited for (a prefill
        # behind a tick in flight, the tick collected behind that prefill):
        # its round trip holds the other's program too
        over = [_end(runs[c["span"]]) if c["span"] in runs else None
                for c in calls]
        shared = set()
        for k in range(1, len(calls)):
            if over[k - 1] is None or over[k - 1] > calls[k]["start_ns"]:
                shared |= {calls[k - 1]["span"], calls[k]["span"]}
        for pair in self.pairs:
            pair.queued = pair.call["span"] in shared

    @property
    def joined(self):
        """The pairs whose run is known: call, program and the wait."""
        return [p for p in self.pairs if p.run is not None]

    @property
    def alone(self):
        """The joined pairs whose round trip holds no program but their
        own: what the medians are taken over."""
        return [p for p in self.joined if not p.queued]

    def ticks(self, ahead=None):
        """The decode ticks joined and alone; ``ahead``: only those
        dispatched ahead (True) or fed by their own step (False), as
        their ``serve/decode_tick`` says."""
        return [p for p in self.alone if p.exe == "decode"
                and (ahead is None or p.ahead is ahead)]

    def skew_interval_ns(self):
        """(lo, hi): how far too late the device's events may lie on the
        shared timeline; None without a joined program."""
        joined = self.joined
        if not joined:
            return None
        return (-min(p.wake_ns for p in joined),
                min(p.lag_ns for p in joined))


class _Pair:
    def __init__(self, join, call, prog, run):
        self.call, self.prog, self.run = call, prog, run
        self.exe = call["attrs"]["exe"]
        self.program_ns, self.call_ns = prog[2], call["dur_ns"]
        # on the shared timeline, so only as good as it is
        self.lag_ns = prog[1] - join.placed(call)[0]
        if run is not None:
            self.round_trip_ns = _end(run) - call["start_ns"]
            self.overhead_ns = self.round_trip_ns - self.program_ns
            self.beyond_call_ns = self.overhead_ns - self.call_ns
            self.wake_ns = join.placed(run)[1] - (prog[1] + prog[2])
            tick = join.by_id.get(run["parent"], {})
            self.ahead = tick.get("attrs", {}).get("ahead") is True


# ---------------------------------------------------------------------------
# once a run
# ---------------------------------------------------------------------------

def _ms(values):
    return stats.median(values) / 1e6


def lines(join, profile=None):
    """What a traced run prints of its join."""
    out = []
    if join.offset_ns is None:
        return out
    bare = {}
    for r in join.unannotated:
        bare[r["name"]] = bare.get(r["name"], 0) + 1
    out.append(
        f"span join: {len(join.matched)} of {len(join.annotated)} annotated "
        f"records inside the traced window found their annotation by id; "
        f"ring clock + {join.offset_ns} ns = profiler's host clock (spread "
        f"{join.offset_spread_ns} ns over {len(join.found)} pairs); placed "
        "by it without an annotation: " + (", ".join(
            f"{k} {v}" for k, v in sorted(bare.items())) or "none"))
    if not join.pairs:
        return out
    n_calls = len(join.pairs) + len(join.lone_calls) + len(join.refused)
    out.append(
        f"calls and programs: {len(join.pairs)} of {n_calls} calls paired "
        f"with their program, {len(join.lone_calls)} calls inside the window "
        f"with no program, {len(join.lone_programs)} programs with no call, "
        f"{len(join.refused)} pairs refused, "
        f"{len(join.pairs) - len(join.joined)} calls that no run names, "
        f"{len(join.joined) - len(join.alone)} queued behind another's "
        "program or collected behind it (left out of the medians)")
    joined = join.joined
    if not joined:
        return out
    lo, hi = join.skew_interval_ns()
    lag, wake = [p.lag_ns for p in joined], [p.wake_ns for p in joined]

    def at(s):
        return (f"lag {_ms(lag) - s / 1e6:.4f} wake {_ms(wake) + s / 1e6:.4f}"
                " ms")

    out.append(
        f"skew interval: the device's events lie [{lo / 1e6:.4f}, "
        f"{hi / 1e6:.4f}] ms too late on the trace's timeline (width "
        f"{(hi - lo) / 1e6:.4f}, {len(joined)} programs); medians as "
        f"observed {at(0)}, at the ends {at(lo)} and {at(hi)}")
    if profile is not None:
        out.append("idle under decode/run : decode/plan + decode/call, s: "
                   + ", ".join(f"{label} {a:.6f} : {b:.6f}" for label, (a, b)
                               in (("observed", _division(profile, 0)),
                                   (f"at {lo / 1e6:.4f} ms",
                                    _division(profile, lo)),
                                   (f"at {hi / 1e6:.4f} ms",
                                    _division(profile, hi)))))
    by_exe = {}
    for p in join.alone:
        by_exe.setdefault(p.exe, []).append(p)
    out.append("device programs by calling span: " + "; ".join(
        f"{exe} n={len(ps)} "
        f"program_ms={_ms([p.program_ns for p in ps]):.4f} "
        f"call_ms={_ms([p.call_ns for p in ps]):.4f} "
        f"overhead_ms={_ms([p.overhead_ns for p in ps]):.4f} "
        f"beyond_call_ms={_ms([p.beyond_call_ns for p in ps]):.4f}"
        # the tick, then the rungs from the smallest up
        for exe, ps in sorted(by_exe.items(),
                              key=lambda kv: (len(kv[0]), kv[0]))))
    return out


def _division(profile, skew_ns):
    """``idle_gaps``' seconds under ``decode/run`` and under ``decode/plan``
    + ``decode/call`` with the device's events taken ``skew_ns`` earlier."""
    moved = trace_reduce.Profile(
        {plane: [(n, s - skew_ns, d) for n, s, d in evs]
         for plane, evs in profile.devices.items()}, {}, profile.spans)
    idle = dict(trace_reduce.idle_by_span(moved))
    return (idle.get("decode/run", 0.0),
            idle.get("decode/plan", 0.0) + idle.get("decode/call", 0.0))


def _read(run):
    from paddle_tpu.observability import spans

    ring = ring_records(spans, getattr(run, "trace_window", None))
    found = sorted(glob.glob(os.path.join(
        run.trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if ring is None or not found:
        return None
    annotations = load_annotations(found[-1])
    profile = getattr(run, "profile", None)
    modules = []
    if profile is not None and profile.devices:
        modules = profile.modules.get(sorted(profile.devices)[0], [])
    else:
        profile = None
    join = Join(ring[0], annotations, modules, ring[1])
    for line in lines(join, profile):
        print("[bench] " + line, flush=True)
    return join if join.offset_ns is not None else None


def read(run):
    """The run's ``Join``, made once; None where the run has no trace, the
    ring cannot vouch for the traced window, or no annotation carries an
    id the ring knows."""
    cached = getattr(run, "_span_join", None)
    if cached is None:
        cached = run._span_join = (_read(run),)
    return cached[0]
