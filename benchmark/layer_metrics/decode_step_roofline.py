"""Share of its roofline the compiled decode step reaches. The step is
bound by memory: the least time is the bytes it has to read (every
multiplied weight once, and the keys and values of the live slots up to
their lengths at that tick: the family's count from the shapes) over the
HBM bandwidth of ``benchmark/peaks.json``, averaged over the traced ticks:
``cached_tokens`` of the program's ``serve/decode_tick`` records
(``Scheduler._decode``) that lie inside the traced window. The time is the
mean device duration of the decode step's program in the trace (the "XLA
Modules" line, by the jitted function's name)."""
from benchmark import program_spans

META = {"name": "decode_step_roofline", "layer": "kernels", "unit": "%",
        "share_of_peak": True, "better": "higher", "source": "device_trace",
        "moves": "gap_p90_ms"}
PROGRAM = "decode_fn"


def read(run):
    if (run.profile is None or not run.profile.modules
            or run.peaks is None or run.trace_window is None):
        return None
    runs = [d for evs in run.profile.modules.values()
            for name, _, d in evs if PROGRAM in name]
    ticks = [r["attrs"]["cached_tokens"] for r in program_spans.named(
        run, "serve/decode_tick", window=run.trace_window) or []]
    if not runs or not ticks:
        return None
    sv = run.cell.config["serving"]
    width = {"bf16": 2, "f32": 4, "int8": 1}[sv["engine"]["weight_dtype"]]
    bytes_mean = sum(run.cell.family.bytes_per_decode_step(
        run.cell.config, [n], weight_bytes=width) for n in ticks) / len(ticks)
    least_s = bytes_mean / run.peaks["hbm_bytes_per_s"]
    measured_s = sum(runs) / len(runs) / 1e9
    return 100.0 * least_s / measured_s
