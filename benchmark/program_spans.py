"""The program's own spans, for the per-layer readers that rest on them.

The program times itself with one tracer (``paddle_tpu/observability/
spans.py``): every finished span is a record in its ring, on the tracer's
clock, and while a span is open it is a profiler annotation ``paddle/<name>``
on the profiler's clock. Two things are read here, once a run:

- ``named(run, name)``: the ring's records of that name that lie inside
  ``run.window`` (or inside another window of the same clock, such as
  ``run.trace_window``). The window is ``time.monotonic()``; the tracer's
  own helper turns it into the ring's units. Where the ring may have lost a
  record of the window (its ``dropped`` is up and its oldest record is no
  older than the window), nothing is handed out: no number beats a median
  over what happened to be left.
- ``traced(run)``: the ``trace_reduce.Profile`` the harness loaded from the
  run's xplane, where it holds a device plane and any span
  (``trace_reduce.load_xplane`` keeps the ``paddle/`` annotations). From
  it: the idle time by the span the host was in (printed once, ``[bench]
  idle by program span: ...``, the same division the result line's
  ``breakdown.idle_gaps`` carries), the host's part of each decode tick,
  and the share of the idle time that no leaf span of the program covers.

A program from before its tracer could be read (no ``dropped``, no clock
helper, no annotation) gives ``None`` everywhere, and a reader that gets
``None`` reports nothing.
"""
from benchmark import trace_reduce

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
    for r in _within(records, w0, w1):
        by_name.setdefault(r["name"], []).append(r)
    return by_name


def _within(records, w0, w1):
    return [r for r in records
            if w0 <= r["start_ns"] and r["start_ns"] + r["dur_ns"] <= w1]


def _spans_module():
    from paddle_tpu.observability import spans

    return spans


def _read_once(run):
    cached = getattr(run, "_program_spans", None)
    if cached is None:
        ring = window_records(_spans_module(), run.window)
        profile = getattr(run, "profile", None)
        if profile is None or not profile.devices or not profile.spans:
            profile = None
        else:
            print("[bench] " + idle_line(profile), flush=True)
        cached = run._program_spans = (ring, profile)
    return cached


def named(run, name, window=None):
    """The ring's records called ``name`` inside the run's window (and
    inside ``window`` too, ``time.monotonic()`` seconds, where one is
    given), oldest first; None where the ring cannot be read."""
    ring = _read_once(run)[0]
    if ring is None:
        return None
    records = ring.get(name, [])
    if window is not None:
        to_ns = _spans_module().monotonic_to_ns
        records = _within(records, to_ns(window[0]), to_ns(window[1]))
    return records


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

def traced(run):
    """The traced window with the host's spans on the device's clock, or
    None: no device plane (a rehearsal on the CPU), or no span."""
    return _read_once(run)[1]


def idle_line(profile):
    return "idle by program span: " + ", ".join(
        f"{name} {seconds:.6f}"
        for name, seconds in trace_reduce.idle_by_span(profile))


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
    """Share (%) of the traced window's device idle time that lies under
    no leaf span of the program."""
    gaps = trace_reduce.idle_gaps(profile)
    total = sum(d for _, _, d in gaps)
    if not total:
        return None
    return 100.0 * sum(d for name, _, d in gaps
                       if not is_leaf(name)) / total
