"""The benchmark's harness: one cell, once.

Nothing here names a cell, a configuration, a family, a traffic kind or a
per-layer metric. ``BENCHMARK.json`` names them, and each is a file of its
own that the harness finds by that name:

    benchmark/workloads/<cell>.json        the cell: config, traffic, limits
    benchmark/configs/<config>.json        the sizes, as run
    benchmark/families/<family>.py         program builder + plain reference
    benchmark/traffic/<traffic>.json       parameters of the traffic mix
    benchmark/traffic_kinds/<kind>.py      the generator and the window
    benchmark/layer_metrics/<metric>.py    one reader of spans/counters/trace

``benchmark/checks.py`` holds what has to be true of all of them together.
``main`` is the measuring path and refuses anything but a TPU. ``rehearse``
runs the same code at the tiny sizes a cell's file gives under
``rehearsal``, on whatever backend there is, and stamps the device it ran on.
"""
import argparse
import contextlib
import glob
import importlib.util
import json
import os
import shutil
import sys
import time

from benchmark import trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
RESULT_KEYS = ("correct", "attempted", "failed", "metrics", "device",
               "checks")


class Refused(Exception):
    """The run cannot be a measurement: no result line, exit code 2."""


# ---------------------------------------------------------------------------
# finding things by name
# ---------------------------------------------------------------------------

def load_json(path):
    with open(path) as f:
        return json.load(f)


def load_module(path):
    if not os.path.isfile(path):
        raise Refused(f"no such file: {os.path.relpath(path, ROOT)}")
    name = "benchmark_file_" + os.path.relpath(path, HERE).replace(
        os.sep, "_").replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _merge(base, over):
    """``over`` laid on ``base``, nested groups key by key."""
    out = dict(base)
    for k, v in over.items():
        out[k] = _merge(out[k], v) if (isinstance(v, dict) and
                                       isinstance(out.get(k), dict)) else v
    return out


class Cell:
    """One entry of ``workloads`` with every file it names, resolved."""

    def __init__(self, root, name, rehearsal=False):
        self.root = root
        bench = os.path.join(root, "benchmark")
        self.manifest = load_json(os.path.join(root, "BENCHMARK.json"))
        entry = [w for w in self.manifest["workloads"] if w["name"] == name]
        if not entry:
            raise Refused(f"BENCHMARK.json has no workload {name!r}")
        self.entry = entry[0]
        self.name, self.chips = name, int(self.entry["chips"])
        self.spec = load_json(os.path.join(bench, "workloads",
                                           name + ".json"))
        cfg = [c for c in self.manifest["configs"]
               if c["name"] == self.entry["config"]][0]
        self.config = load_json(os.path.join(root, cfg["file"]))
        self.traffic = load_json(os.path.join(
            bench, "traffic", self.entry["traffic"] + ".json"))
        self.limits = dict(self.spec["limits"])
        self.control_precision = self.spec.get("control_precision")
        self.control_also = self.spec.get("control_also", [])
        self.rehearsal = rehearsal
        if rehearsal:
            over = self.spec["rehearsal"]
            self.control_precision = over.get("control_precision",
                                              self.control_precision)
            self.control_also = over.get("control_also", [])
            self.config = _merge(self.config, over.get("config", {}))
            self.traffic = _merge(self.traffic, over.get("traffic", {}))
            self.limits = _merge(self.limits, over.get("limits", {}))
        self.family = load_module(os.path.join(
            bench, "families", self.config["family"] + ".py"))
        self.kind = load_module(os.path.join(
            bench, "traffic_kinds", self.traffic["kind"] + ".py"))

    def metrics(self, group):
        """The manifest's ``end_to_end`` or ``per_layer`` metrics that this
        cell reports: those that list it, and of those that list no cell,
        every end-to-end metric and every per-layer metric that moves one
        the cell reports."""
        mine = [m for m in self.manifest["end_to_end"]
                if self.name in m.get("workloads", [self.name])]
        if group == "end_to_end":
            return mine
        reported = {m["name"] for m in mine}
        return [m for m in self.manifest["per_layer"]
                if (self.name in m["workloads"] if "workloads" in m
                    else m["moves"] in reported)]


def load_peaks(device_kind):
    table = load_json(os.path.join(HERE, "peaks.json"))["devices"]
    if device_kind not in table:
        raise Refused(f"device kind {device_kind!r} is not in "
                      "benchmark/peaks.json: add it with its source")
    return table[device_kind]


# ---------------------------------------------------------------------------
# spans and the device trace
# ---------------------------------------------------------------------------

class Spans:
    """Host-clock spans recorded from the benchmark's own files around the
    calls into each layer. While the profiler runs, the same span is also
    a ``TraceAnnotation``, which puts it on the profiler's clock."""

    def __init__(self):
        self.records = []            # (name, t0, t1, attrs), time.monotonic
        self.annotate = False

    @contextlib.contextmanager
    def span(self, name, **attrs):
        ann = None
        if self.annotate:
            import jax

            ann = jax.profiler.TraceAnnotation(trace_reduce.SPAN_PREFIX
                                               + name)
            ann.__enter__()
        t0 = time.monotonic()
        try:
            yield attrs
        finally:
            t1 = time.monotonic()
            if ann is not None:
                ann.__exit__(None, None, None)
            self.records.append((name, t0, t1, attrs))

    def named(self, name):
        return [r for r in self.records if r[0] == name]


class Run:
    """What a traffic kind is handed, and what the per-layer readers read
    afterwards."""

    def __init__(self, cell, devices, seed, seconds, trace, control,
                 started):
        self.cell, self.devices = cell, devices
        self.seed, self.seconds = int(seed), float(seconds)
        self.trace, self.control = bool(trace), bool(control)
        self.started = started       # time.monotonic at process start
        self.spans = Spans()
        self.marks = []
        self.setup_s = None
        self.window = None           # (t0, t1), time.monotonic
        # a directory of this process's own: two runs of one cell at once
        # (the tests' workers) must not empty each other's trace
        self.trace_dir = os.path.join(cell.root, ".bench_trace",
                                      f"{cell.name}.{os.getpid()}")
        self.trace_window = None
        self.profile = None          # trace_reduce.Profile after the run
        self.counters = {}
        self.end_to_end = {}
        self.checks = []             # (name, value, limit)
        self.attempted = self.failed = 0
        self.memory_peak_bytes = None
        self.peaks = None

    # -- set by the kind ----------------------------------------------------
    def mark(self, stage):
        """A stage of set-up reached, in seconds since the process began."""
        self.marks.append((stage, time.monotonic() - self.started))

    def open_window(self):
        """Set-up ends here: the next thing is measured."""
        t = time.monotonic()
        self.setup_s = t - self.started
        self.mark("window")
        print("[bench] set-up reached " + ", ".join(
            f"{k} {v:.2f}s" for k, v in self.marks), flush=True)
        return t

    def read_memory_peak(self):
        """Peak on the fullest chip, read before the reference runs so that
        it stays the program's."""
        peaks = []
        for d in self.devices:
            st = d.memory_stats() or {}
            peaks.append(st.get("peak_bytes_in_use", 0))
        self.memory_peak_bytes = int(max(peaks)) if peaks else 0

    def check(self, name, value, limit):
        self.checks.append((name, float(value), float(limit)))

    # -- the profiler -------------------------------------------------------
    def start_trace(self):
        if not self.trace:
            return
        import jax

        shutil.rmtree(self.trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        self.spans.annotate = True
        self._trace_t0 = time.monotonic()

    def stop_trace(self):
        if not self.trace:
            return
        import jax

        t1 = time.monotonic()
        self.spans.annotate = False
        jax.profiler.stop_trace()
        self.trace_window = (self._trace_t0, t1)

    def load_profile(self):
        found = sorted(glob.glob(os.path.join(
            self.trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
        if not found:
            return None
        self.profile = trace_reduce.load_xplane(found[-1])
        return self.profile


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def pick_devices(chips, rehearsal):
    import jax

    devices = jax.devices()
    if not rehearsal and devices[0].platform != "tpu":
        raise Refused(f"backend is {devices[0].platform!r}, not a TPU: "
                      "nothing is measured off the chip")
    if len(devices) < chips:
        raise Refused(f"the cell asks for {chips} chips, jax finds "
                      f"{len(devices)}")
    return devices[:chips]


def setup_environment(root, rehearsal=False):
    """Before jax loads: the flag preset the program documents for the
    chip (libtpu reads it once, at load; a rehearsal loads none), and the
    persistent compile cache at a fixed path inside the checkout (or where
    JAX_COMPILATION_CACHE_DIR says)."""
    if not os.path.isdir(os.path.join(root, "paddle_tpu")):
        raise Refused("the program (paddle_tpu/) is not in this checkout")
    if root not in sys.path:
        sys.path.insert(0, root)
    if not rehearsal:
        from paddle_tpu.sysconfig import tpu_perf_flags

        tpu_perf_flags()
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from paddle_tpu.framework.core import ensure_compile_cache

    return ensure_compile_cache()


def read_layer_metrics(run):
    out = {}
    bench = os.path.join(run.cell.root, "benchmark")
    for m in run.cell.metrics("per_layer"):
        reader = load_module(os.path.join(bench, "layer_metrics",
                                          m["name"] + ".py"))
        if reader.META["name"] != m["name"]:
            raise Refused(f"layer_metrics/{m['name']}.py names itself "
                          f"{reader.META['name']!r}")
        value = reader.read(run)
        if value is None:
            continue
        if reader.META.get("share_of_peak") and value > 100:
            raise Refused(
                f"{m['name']} reads {value:.2f}% of a peak: the count of "
                "operations or bytes is too high or the time leaves work out")
        out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def run_cell(root, workload, seed, seconds, trace, rehearsal=False,
             control=False, started=None, out=sys.stdout):
    """Run one cell once and print the result line. Returns the result."""
    started = time.monotonic() if started is None else started
    cell = Cell(root, workload, rehearsal=rehearsal)
    cache_dir = setup_environment(root, rehearsal)
    devices = pick_devices(cell.chips, rehearsal)
    run = Run(cell, devices, seed, seconds, trace, control, started)
    run.mark("devices")
    if not rehearsal or devices[0].platform == "tpu":
        run.peaks = load_peaks(devices[0].device_kind)
    print(f"[bench] {workload} seed={seed} seconds={seconds} trace="
          f"{int(bool(trace))} on {devices[0].device_kind} x{len(devices)}; "
          f"compile cache {cache_dir}", file=out, flush=True)

    cell.kind.run(run)

    check_lines = "".join(
        f"[bench] check {name}: {value:.6g} (limit {limit:.6g}) "
        f"{'ok' if value <= limit else 'FAILED'}\n"
        for name, value, limit in run.checks)
    print(check_lines, end="", file=out, flush=True)
    correct = bool(run.checks) and all(v <= lim for _, v, lim in run.checks)
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": run.memory_peak_bytes}
    result = {"correct": correct, "attempted": int(run.attempted),
              "failed": int(run.failed)}
    if trace:
        profile = run.load_profile()
        metrics = read_layer_metrics(run)
        if profile is not None and profile.devices:
            busy = trace_reduce.busy_seconds(profile, len(devices))
            device["busy_s"], device["window_s"] = busy
            result["breakdown"] = trace_reduce.breakdown(profile)
        shutil.rmtree(run.trace_dir, ignore_errors=True)
    else:
        metrics = {}
        if run.setup_s is not None:
            run.end_to_end["setup_s"] = run.setup_s
        for m in cell.metrics("end_to_end"):
            if m["name"] not in run.end_to_end:
                if control:         # a control times nothing it need not
                    continue
                raise Refused(f"the cell did not report {m['name']}")
            metrics[m["name"]] = {"value": float(run.end_to_end[m["name"]]),
                                  "unit": m["unit"]}
    result["metrics"] = metrics
    result["device"] = device
    # every number compared beside its limit: last in the line, and the
    # last lines on standard error
    result["checks"] = {name: {"value": value, "limit": limit}
                        for name, value, limit in run.checks}
    print(json.dumps(result), file=out, flush=True)
    print(check_lines, end="", file=sys.stderr, flush=True)
    return result


def _args(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None, rehearsal=False, control=False, started=None):
    a = _args(argv)
    try:
        run_cell(ROOT, a.workload, a.seed, a.seconds, a.trace,
                 rehearsal=rehearsal, control=control, started=started)
    except Refused as e:
        print(f"[bench] refused: {e}", file=sys.stderr)
        return 2
    return 0
