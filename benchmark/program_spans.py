"""The program's own spans, for the per-layer readers that rest on them.

The program times itself with one tracer (``paddle_tpu/observability/
spans.py``): every finished span is a record in its ring, on the tracer's
clock, and while a span is open it is a profiler annotation ``paddle/<name>``
on the profiler's clock. Two things are read here, once a run:

- ``named(run, name)``: the ring's records of that name that lie inside
  ``run.window``. The window is ``time.monotonic()``; the tracer's own
  helper turns it into the ring's units. Where the ring may have lost a
  record of the window (its ``dropped`` is up and its oldest record is no
  older than the window), nothing is handed out: no number beats a median
  over what happened to be left.
- ``traced(run)``: a ``trace_reduce.Profile`` of the traced window whose
  spans are the ``paddle/`` annotations, read from the newest
  ``*.xplane.pb`` under ``run.trace_dir`` (``trace_reduce.load_xplane``
  keeps the benchmark's own ``bench/`` spans only); the device planes are
  those the harness has loaded. From it: the idle gaps by the program span
  the host was in (printed once, ``[bench] idle by program span: ...``),
  the host's part of each decode tick, and the share of the idle time that
  no leaf span of the program covers.

A program from before its tracer could be read (no ``dropped``, no clock
helper, no annotation) gives ``None`` everywhere, and a reader that gets
``None`` reports nothing.
"""
import glob
import os

from benchmark import trace_reduce

PREFIX = "paddle/"
# the spans with no span of the program inside them: idle time under one of
# these has a name; under their parents alone (serve/step, serve/admit,
# serve/decode_tick, serve/prefill) it has not
LEAF_PREFIXES = ("decode/", "prefill/")
LEAF_NAMES = ("serve/emit", "serve/evict", "serve/loop_idle")


def is_leaf(name):
    return name.startswith(LEAF_PREFIXES) or name in LEAF_NAMES


# ---------------------------------------------------------------------------
# the ring
# ---------------------------------------------------------------------------

def window_records(spans_module, window):
    """{name: [record]} of the default tracer's records inside ``window``
    (``time.monotonic()`` seconds), or None where the tracer cannot vouch
    for the window."""
    tracer = spans_module.default_tracer()
    to_ns = getattr(spans_module, "monotonic_to_ns", None)
    dropped = getattr(tracer, "dropped", None)
    if to_ns is None or dropped is None or window is None:
        return None
    w0, w1 = to_ns(window[0]), to_ns(window[1])
    records = tracer.spans()
    if dropped and (not records or
                    records[0]["start_ns"] + records[0]["dur_ns"] >= w0):
        return None
    by_name = {}
    for r in records:
        if w0 <= r["start_ns"] and r["start_ns"] + r["dur_ns"] <= w1:
            by_name.setdefault(r["name"], []).append(r)
    return by_name


def _read_once(run):
    cached = getattr(run, "_program_spans", None)
    if cached is None:
        from paddle_tpu.observability import spans as spans_module

        ring = window_records(spans_module, run.window)
        profile = _load_traced(run)
        if profile is not None:
            print("[bench] " + idle_line(profile), flush=True)
        cached = run._program_spans = (ring, profile)
    return cached


def named(run, name):
    """The ring's records called ``name`` inside the run's window, oldest
    first; None where the ring cannot be read."""
    ring = _read_once(run)[0]
    return None if ring is None else ring.get(name, [])


def ms(records):
    return [r["dur_ns"] / 1e6 for r in records]


def by_step(records):
    """{attrs.step: [record]}."""
    out = {}
    for r in records:
        out.setdefault(r.get("attrs", {}).get("step"), []).append(r)
    return out


# ---------------------------------------------------------------------------
# the profiler's trace
# ---------------------------------------------------------------------------

def annotations(path):
    """[(name without the prefix, start_ns, dur_ns)] of the ``paddle/``
    host events of an ``.xplane.pb``, by start."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith(trace_reduce.DEVICE_PLANE_PREFIX):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PREFIX):
                    out.append((ev.name[len(PREFIX):], int(ev.start_ns),
                                int(ev.duration_ns)))
    out.sort(key=lambda e: e[1])
    return out


def _load_traced(run):
    base = getattr(run, "profile", None)
    if base is None or not base.devices:
        return None
    found = sorted(glob.glob(os.path.join(
        run.trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        return None
    spans = annotations(found[-1])
    if not spans:
        return None
    return trace_reduce.Profile(base.devices, base.modules, spans)


def traced(run):
    """The traced window with the program's annotations as its spans, or
    None: no device plane (a rehearsal on the CPU), or no annotation."""
    return _read_once(run)[1]


def idle_by_span(profile):
    """[(span name, idle seconds)] over the traced window, the innermost
    program span at each gap's middle, most first."""
    out = {}
    for name, _, d in trace_reduce.idle_gaps(profile):
        out[name] = out.get(name, 0) + d / 1e9
    return sorted(out.items(), key=lambda kv: -kv[1])


def idle_line(profile):
    return "idle by program span: " + ", ".join(
        f"{name} {seconds:.6f}" for name, seconds in idle_by_span(profile))


def host_gaps_ms(profile, name):
    """Per annotation called ``name``: its length less the time the first
    device was busy inside it, in ms."""
    plane = sorted(profile.devices)[0]
    busy = trace_reduce.busy_intervals(profile, plane)
    out = []
    for n, s, d in profile.spans:
        if n != name:
            continue
        inside = sum(min(e, s + d) - max(b, s) for b, e in busy
                     if b < s + d and e > s)
        out.append((d - inside) / 1e6)
    return out


def unattributed_idle_share(profile):
    """Share (%) of the traced window's device idle time whose gap has its
    middle under no leaf span of the program."""
    gaps = trace_reduce.idle_gaps(profile)
    total = sum(d for _, _, d in gaps)
    if not total:
        return None
    return 100.0 * sum(d for name, _, d in gaps
                       if not is_leaf(name)) / total
