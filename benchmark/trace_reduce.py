"""From the profiler's trace to numbers: the union of device-busy
intervals, durations by operation name, and idle gaps divided over the
spans the host was in.

The reduction works on a ``Profile``: per device plane the leaf operations
as ``(name, start_ns, dur_ns)``, and the host's spans on the same clock:
the program's own (``TraceAnnotation`` events ``paddle/<name>``, written by
its tracer while a span is open) beside the few a traffic kind still puts
round its own calls (``bench/<name>``). ``load_xplane`` makes one from the
``.xplane.pb`` the JAX profiler writes; ``Profile.from_json`` from the
compact recording kept under ``benchmark/fixtures/``.
"""
import gzip
import heapq
import json
import re

SPAN_PREFIX = "bench/"              # what ``harness.Spans`` writes
PROGRAM_PREFIX = "paddle/"          # what the program's tracer writes
DEVICE_PLANE_PREFIX = "/device:TPU:"
# the line of a TPU plane that holds the leaf operations (one event per
# executed HLO instruction or fusion); "XLA Modules" holds whole programs
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
OUTSIDE = "outside_every_span"


class Profile:
    def __init__(self, devices, modules, spans):
        self.devices = devices      # {plane: [(name, start_ns, dur_ns)]}
        self.modules = modules      # {plane: [(name, start_ns, dur_ns)]}
        self.spans = spans          # [(name, start_ns, dur_ns)], no prefix

    def to_json(self):
        return {"devices": self.devices, "modules": self.modules,
                "spans": self.spans}

    @classmethod
    def from_json(cls, doc):
        def tup(evs):
            return [(str(n), int(s), int(d)) for n, s, d in evs]
        return cls({p: tup(e) for p, e in doc["devices"].items()},
                   {p: tup(e) for p, e in doc["modules"].items()},
                   tup(doc["spans"]))

    @classmethod
    def from_file(cls, path):
        opener = gzip.open if path.endswith(".gz") else open
        with opener(path, "rt") as f:
            return cls.from_json(json.load(f))


_OP = re.compile(
    r"^%(\S+) = \(?([a-z0-9]+\[[0-9,]*\])?.*? ([a-z][a-z0-9\-]*)\(")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def short_name(text):
    """The trace names a device operation by its whole HLO instruction.
    Keep what identifies it: name, opcode (with a custom call's target) and
    the first result's type and shape."""
    m = _OP.match(text)
    if not m:
        return text[:96]
    name, shape, opcode = m.groups()
    target = _TARGET.search(text)
    if target:
        opcode += "/" + target.group(1)
    return f"{name} {opcode} {shape or ''}".strip()


def load_xplane(path):
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, modules, spans = {}, {}, []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            for line in plane.lines:
                if line.name in (OPS_LINE, MODULES_LINE):
                    evs = [(short_name(ev.name), int(ev.start_ns),
                            int(ev.duration_ns)) for ev in line.events]
                    evs.sort(key=lambda e: (e[1], -e[2]))
                    (devices if line.name == OPS_LINE
                     else modules)[plane.name] = evs
        else:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith((SPAN_PREFIX, PROGRAM_PREFIX)):
                        spans.append((ev.name.split("/", 1)[1],
                                      int(ev.start_ns),
                                      int(ev.duration_ns)))
    spans.sort(key=lambda e: e[1])
    return Profile(devices, modules, spans)


# ---------------------------------------------------------------------------

def merge_intervals(intervals):
    """Sorted, disjoint union of ``(start, end)`` intervals."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def window(profile):
    """The traced window on the profiler's clock: from the first to the
    last thing the trace holds, spans and device operations alike."""
    starts, ends = [], []
    for evs in list(profile.devices.values()) + [profile.spans]:
        if evs:
            starts.append(min(s for _, s, _ in evs))
            ends.append(max(s + d for _, s, d in evs))
    if not starts:
        raise ValueError("the trace holds no event")
    return min(starts), max(ends)


def busy_intervals(profile, plane):
    return merge_intervals((s, s + d) for _, s, d in profile.devices[plane])


def busy_seconds(profile, chips=None):
    """(seconds in which an operation ran on the device, averaged over the
    device planes; length of the traced window in seconds)."""
    w0, w1 = window(profile)
    planes = sorted(profile.devices)
    if chips is not None and len(planes) != chips:
        raise ValueError(f"the trace holds {len(planes)} device planes, "
                         f"the cell used {chips} chips")
    busy = [sum(e - s for s, e in busy_intervals(profile, p))
            for p in planes]
    busy_s = sum(busy) / len(busy) / 1e9
    window_s = (w1 - w0) / 1e9
    if busy_s > window_s * (1 + 1e-6):
        raise ValueError(f"busy {busy_s}s exceeds the window {window_s}s")
    return busy_s, window_s


def idle_share(profile, chips=None):
    busy_s, window_s = busy_seconds(profile, chips)
    return 100.0 * (1.0 - busy_s / window_s)


def self_times(events):
    """The operations line nests: a ``while`` spans the operations of its
    body. Give every event its own time, its duration less that of the
    events directly inside it. ``events`` sorted by (start, -duration)."""
    out, stack = [], []             # stack of [end, index into out]
    for name, s, d in events:
        while stack and s >= stack[-1][0]:
            stack.pop()
        if stack and s + d <= stack[-1][0]:
            out[stack[-1][1]][1] -= d
        out.append([name, d])
        stack.append([s + d, len(out) - 1])
    return out


def durations_by_name(profile):
    """{operation name: seconds of its own}, summed over events and
    averaged over the device planes."""
    out = {}
    for evs in profile.devices.values():
        for name, d in self_times(evs):
            out[name] = out.get(name, 0) + d
    n = max(len(profile.devices), 1)
    return {k: v / n / 1e9 for k, v in out.items()}


def seconds_matching(profile, *needles, head=""):
    """Summed device seconds of the operations whose name starts with
    ``head`` and holds every one of ``needles``, averaged over planes, and
    how many events a plane."""
    total, count = 0, 0
    for evs in profile.devices.values():
        for name, d in self_times(evs):
            if name.startswith(head) and all(n in name for n in needles):
                total += d
                count += 1
    n = max(len(profile.devices), 1)
    return total / n / 1e9, count // n


def innermost_timeline(spans):
    """``[(start, end, name)]``, disjoint and by start: over every stretch
    that any span covers, the innermost span there (the shortest of those
    that cover it, as nested spans go)."""
    edges = sorted({x for _, s, d in spans if d > 0 for x in (s, s + d)})
    starts = sorted((s, d, name) for name, s, d in spans if d > 0)
    out, live, k = [], [], 0         # live: heap of (dur, end, name)
    for a, b in zip(edges, edges[1:]):
        while k < len(starts) and starts[k][0] <= a:
            s, d, name = starts[k]
            heapq.heappush(live, (d, s + d, name))
            k += 1
        while live and live[0][1] <= a:
            heapq.heappop(live)
        if not live:
            continue
        name = live[0][2]
        if out and out[-1][2] == name and out[-1][1] == a:
            out[-1] = (out[-1][0], b, name)
        else:
            out.append((a, b, name))
    return out


def idle_gaps(profile):
    """Every gap between busy intervals inside the window, on the first
    device plane, divided over the innermost spans that cover it, each
    getting the part it covers (what no span covers goes to ``OUTSIDE``):
    [(span name, start_ns, dur_ns)], by start."""
    w0, w1 = window(profile)
    plane = sorted(profile.devices)[0]
    busy = busy_intervals(profile, plane)
    edges = [w0] + [x for iv in busy for x in iv] + [w1]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    line = innermost_timeline(profile.spans)
    out, k = [], 0
    for g0, g1 in gaps:
        at = g0
        while k < len(line) and line[k][1] <= g0:
            k += 1
        j = k
        while j < len(line) and line[j][0] < g1:
            a, b, name = line[j]
            a, b = max(a, g0), min(b, g1)
            if a > at:
                out.append((OUTSIDE, at, a - at))
            out.append((name, a, b - a))
            at = b
            j += 1
        if g1 > at:
            out.append((OUTSIDE, at, g1 - at))
    return out


def idle_by_span(profile):
    """[(span name, idle seconds)] over the traced window, most first."""
    by_span = {}
    for name, _, d in idle_gaps(profile):
        by_span[name] = by_span.get(name, 0) + d / 1e9
    return sorted(by_span.items(), key=lambda kv: -kv[1])


def breakdown(profile, top=10):
    ops = sorted(durations_by_name(profile).items(), key=lambda kv: -kv[1])
    return {"device_ops": [[k, v] for k, v in ops[:top]],
            "idle_gaps": [[k, v] for k, v in idle_by_span(profile)[:top]]}
