"""Share of its roofline the compiled decode step of a model with sparse
experts and window and global attention layers reaches. The step is bound
by memory, and its least bytes depend on what its riders routed and on how
much of each rider's context a layer still sees, not on the cached tokens
alone (which is all ``decode_step_roofline`` hands a family): every
non-expert matrix once, the held experts that got a token once each
(``experts_hit`` of the program's ``serve/decode_tick`` records,
``Scheduler._decode``), the tied head's rows, and the keys and values its
riders have live in each page group (``rows_full`` and ``rows_window`` of the
same records: a window group's rows clipped to the window), a layer of the
group each: the family's count, over the HBM bandwidth of
``benchmark/peaks.json``, averaged over the ticks inside the traced window.
The time is the mean device duration of the decode step's program in the
trace (the "XLA Modules" line, by the jitted function's name). A program
that writes no ``rows_window`` (a parent from before it) gives nothing."""
from benchmark import program_spans

META = {"name": "swa_moe_decode_step_roofline", "layer": "kernels",
        "unit": "%", "share_of_peak": True, "better": "higher",
        "source": "device_trace", "moves": "gap_p90_ms"}
PROGRAM = "decode_fn"


def read(run):
    if (run.profile is None or not run.profile.modules
            or run.peaks is None or run.trace_window is None):
        return None
    count = getattr(run.cell.family, "bytes_per_swa_moe_decode_step", None)
    runs = [d for evs in run.profile.modules.values()
            for name, _, d in evs if PROGRAM in name]
    ticks = [r["attrs"] for r in program_spans.named(
        run, "serve/decode_tick", window=run.trace_window) or []
        if "experts_hit" in r.get("attrs", {})
        and "rows_window" in r.get("attrs", {})]
    if count is None or not runs or not ticks:
        return None
    sv = run.cell.config["serving"]
    width = {"bf16": 2, "f32": 4}[sv["engine"]["weight_dtype"]]
    bytes_mean = sum(count(run.cell.config, t["experts_hit"],
                           t["rows_full"], t["rows_window"], t["batch"],
                           weight_bytes=width)
                     for t in ticks) / len(ticks)
    least_s = bytes_mean / run.peaks["hbm_bytes_per_s"]
    measured_s = sum(runs) / len(runs) / 1e9
    return 100.0 * least_s / measured_s
