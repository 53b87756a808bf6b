"""Share of its roofline the grouped-query paged decode-attention kernel
reaches. The kernel is bound by memory: the least time is the keys and
values its riders have live inside each layer's span (``rows_full`` and
``rows_window`` of the program's ``serve/decode_tick`` records inside the
traced window, a window group's rows clipped to the window; a layer of the
group each, counted as values: 2 x key/value heads x head size a row, the
family's count) over the HBM bandwidth of ``benchmark/peaks.json``. The
time is the summed device time of the operations whose short name starts
with ``gqa_paged_decode`` (``pl.pallas_call(name="gqa_paged_decode")``, one
launch a layer and tick). A page is copied whole, so the rows of a window's
first page that lie before the bound are time the kernel took and no bytes
the algorithm requires. A program with no such kernel (its tick gathers) or
no ``rows_window`` gives nothing."""
from benchmark import program_spans, trace_reduce

META = {"name": "gqa_window_paged_decode_roofline", "layer": "kernels",
        "unit": "%", "share_of_peak": True, "better": "higher",
        "source": "device_trace", "moves": "gap_p90_ms"}
NAME_HEAD = "gqa_paged_decode"


def read(run):
    if (run.profile is None or not run.profile.devices
            or run.peaks is None or run.trace_window is None):
        return None
    count = getattr(run.cell.family, "kv_bytes_per_decode_step", None)
    seconds, events = trace_reduce.seconds_matching(run.profile,
                                                    head=NAME_HEAD)
    ticks = [r["attrs"] for r in program_spans.named(
        run, "serve/decode_tick", window=run.trace_window) or []
        if "rows_window" in r.get("attrs", {})]
    if count is None or not events or not ticks:
        return None
    least = sum(count(run.cell.config, t["rows_full"], t["rows_window"])
                for t in ticks)
    return 100.0 * least / run.peaks["hbm_bytes_per_s"] / seconds
