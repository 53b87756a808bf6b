"""Share of its roofline the compiled decode step of a model with delta-rule
layers whose gate is a channel's AND sparse experts reaches. The step is
bound by memory, and its least bytes depend on its riders: every non-expert
matrix and the untied head once, the float32 leaves (routers, gains, taps,
decay constants), the held experts that got a token (``experts_hit`` of the
program's ``serve/decode_tick`` records, ``Scheduler._decode``), the riders'
embedding rows, the riders' recurrent state read and written back
(``state_bytes``: matrix states and conv taps), and the keys and values of
the riders' cached tokens (``cached_tokens``): the family's count, over the
HBM bandwidth of ``benchmark/peaks.json``, averaged over the ticks inside the
traced window. The time is the mean device duration of the decode step's
program in the trace (the "XLA Modules" line, by the jitted function's name).
A family without the count or a program whose ticks carry not both
``state_bytes`` and ``experts_hit`` gives nothing."""
from benchmark import program_spans

META = {"name": "kda_moe_decode_step_roofline", "layer": "kernels",
        "unit": "%", "share_of_peak": True, "better": "higher",
        "source": "device_trace", "moves": "gap_p90_ms"}
PROGRAM = "decode_fn"


def read(run):
    if (run.profile is None or not run.profile.modules
            or run.peaks is None or run.trace_window is None):
        return None
    count = getattr(run.cell.family, "bytes_per_decode_step", None)
    runs = [d for evs in run.profile.modules.values()
            for name, _, d in evs if PROGRAM in name]
    ticks = [r["attrs"] for r in program_spans.named(
        run, "serve/decode_tick", window=run.trace_window) or []
        if r.get("attrs", {}).get("state_bytes")
        and "experts_hit" in r["attrs"]]
    if count is None or not runs or not ticks:
        return None
    sv = run.cell.config["serving"]
    width = {"bf16": 2, "f32": 4}[sv["engine"]["weight_dtype"]]
    bytes_mean = sum(count(run.cell.config, t["experts_hit"],
                           t["state_bytes"], t["cached_tokens"], t["batch"],
                           weight_bytes=width)
                     for t in ticks) / len(ticks)
    least_s = bytes_mean / run.peaks["hbm_bytes_per_s"]
    measured_s = sum(runs) / len(runs) / 1e9
    return 100.0 * least_s / measured_s
