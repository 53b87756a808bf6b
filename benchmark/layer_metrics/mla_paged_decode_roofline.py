"""Share of its roofline the latent decode-attention kernel reaches. The
kernel is bound by memory: the least time is the latent rows of the riders'
cached tokens (``latent_bytes`` of the program's ``serve/decode_tick``
records inside the traced window: cached tokens x ``kv_lora_rank +
qk_rope_head_dim`` values x layers, the values alone: the spare lanes of a
stored row are the program's choice) over the HBM bandwidth of
``benchmark/peaks.json``. The time is the summed device time of the
operations whose short name starts with ``mla_paged_decode``
(``pl.pallas_call(name="mla_paged_decode")``). A program with no such
kernel (its tick gathers) or no ``latent_bytes`` gives nothing."""
from benchmark import program_spans, trace_reduce

META = {"name": "mla_paged_decode_roofline", "layer": "kernels", "unit": "%",
        "share_of_peak": True, "better": "higher", "source": "device_trace",
        "moves": "gap_p90_ms"}
NAME_HEAD = "mla_paged_decode"


def read(run):
    if (run.profile is None or not run.profile.devices
            or run.peaks is None or run.trace_window is None):
        return None
    seconds, events = trace_reduce.seconds_matching(run.profile,
                                                    head=NAME_HEAD)
    ticks = [r["attrs"]["latent_bytes"] for r in program_spans.named(
        run, "serve/decode_tick", window=run.trace_window) or []
        if r.get("attrs", {}).get("latent_bytes")]
    if not events or not ticks:
        return None
    return 100.0 * sum(ticks) / run.peaks["hbm_bytes_per_s"] / seconds
