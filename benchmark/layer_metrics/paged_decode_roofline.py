"""Share of its roofline the equal-heads paged decode-attention kernel
reaches. The kernel is bound by memory: the least time is the keys and
values of the riders' cached tokens (``cached_tokens`` of the program's
``serve/decode_tick`` records inside the traced window, times what a cached
token holds in every attention layer at the model's own heads: the family's
count, 2 x heads x head size x the cache's width a layer) over the HBM
bandwidth of ``benchmark/peaks.json``. The time is the summed device time of
the operations whose short name starts with ``paged_decode_attention``
(``pl.pallas_call(name="paged_decode_attention")``, one launch an attention
layer and tick). It counts values, not a page's rows past a slot's length
nor a pool's padded head rows: those are time the kernel took and no bytes
the algorithm requires. A program with no such kernel (its tick gathers, or
reads its pages through another kernel) gives nothing."""
from benchmark import program_spans, trace_reduce

META = {"name": "paged_decode_roofline", "layer": "kernels", "unit": "%",
        "share_of_peak": True, "better": "higher", "source": "device_trace",
        "moves": "gap_p90_ms"}
NAME_HEAD = "paged_decode_attention"


def kv_bytes_per_token(cell):
    """Bytes of keys and values one cached token holds, all attention
    layers: the family's own count where it has one, else what its count
    of a decode step's bytes grows by a cached token."""
    family = cell.family
    count = getattr(family, "kv_bytes_per_token", None)
    if count is not None:
        return count(cell.config)
    step = getattr(family, "bytes_per_decode_step", None)
    if step is None:
        return None
    return step(cell.config, [1]) - step(cell.config, [0])


def read(run):
    if (run.profile is None or not run.profile.devices
            or run.peaks is None or run.trace_window is None):
        return None
    seconds, events = trace_reduce.seconds_matching(run.profile,
                                                    head=NAME_HEAD)
    ticks = [r["attrs"]["cached_tokens"] for r in program_spans.named(
        run, "serve/decode_tick", window=run.trace_window) or []
        if "cached_tokens" in r.get("attrs", {})]
    row = kv_bytes_per_token(run.cell)
    if not events or not ticks or not row:
        return None
    least = sum(ticks) * row
    return 100.0 * least / run.peaks["hbm_bytes_per_s"] / seconds
