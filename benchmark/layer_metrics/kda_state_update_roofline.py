"""Share of its roofline the one-token delta-rule kernel with a gate a
channel reaches in the decode tick. The kernel is bound by memory: the least
time is the bytes of the riders' matrix states, read once and written once
(``state_slots`` of the program's ``serve/decode_tick`` records inside the
traced window times a sequence's float32 matrix states over all KDA layers:
the family's count; a slot that does not ride costs nothing), over the HBM
bandwidth of ``benchmark/peaks.json``. The time is the summed device time of
the operations whose short name starts with ``kda_update``
(``pl.pallas_call(name="kda_update_rows")``, one launch a KDA layer and
tick). A program with no such kernel or no ``state_slots`` gives nothing."""
from benchmark import program_spans, trace_reduce

META = {"name": "kda_state_update_roofline", "layer": "kernels", "unit": "%",
        "share_of_peak": True, "better": "higher", "source": "device_trace",
        "moves": "gap_p90_ms"}
NAME_HEAD = "kda_update"


def read(run):
    if (run.profile is None or not run.profile.devices
            or run.peaks is None or run.trace_window is None):
        return None
    count = getattr(run.cell.family, "state_update_bytes", None)
    seconds, events = trace_reduce.seconds_matching(run.profile,
                                                    head=NAME_HEAD)
    riders = [r["attrs"]["state_slots"] for r in program_spans.named(
        run, "serve/decode_tick", window=run.trace_window) or []
        if r.get("attrs", {}).get("state_slots")]
    if count is None or not events or not riders:
        return None
    least_s = count(run.cell.config, sum(riders)) \
        / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / seconds
