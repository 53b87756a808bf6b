"""Share of its roofline the grouped, windowed flash-attention kernel
reaches in prefill. The kernel is bound by compute at these shapes: the
least time is the operations of the (query, key) pairs inside each layer's
causal band alone, for the prompts the window's prefills processed
(``prompt_len`` of the program's ``serve/prefill`` records that carry
``pages_window``, inside the traced window; the family's count: a full layer
``j <= i``, a sliding layer ``i - window < j <= i``, 4 x head size a pair
and query head) over the bfloat16 peak of ``benchmark/peaks.json``. The
time is the summed device time of the operations whose short name starts
with ``window_flash`` (``pl.pallas_call(name="window_flash_fwd")``, one
launch a layer and prefill). The padding of a rung and the masked part of a
block on the band's edge are time the kernel took and no work the algorithm
requires. A program with no such kernel or no ``pages_window`` gives
nothing."""
from benchmark import program_spans, trace_reduce

META = {"name": "window_flash_prefill_roofline", "layer": "kernels",
        "unit": "%", "share_of_peak": True, "better": "higher",
        "source": "device_trace", "moves": "ttft_p50_ms"}
NAME_HEAD = "window_flash"


def read(run):
    if (run.profile is None or not run.profile.devices
            or run.peaks is None or run.trace_window is None):
        return None
    flops = getattr(run.cell.family, "band_attention_flops", None)
    seconds, events = trace_reduce.seconds_matching(run.profile,
                                                    head=NAME_HEAD)
    prompts = [r["attrs"]["prompt_len"] for r in program_spans.named(
        run, "serve/prefill", window=run.trace_window) or []
        if "pages_window" in r.get("attrs", {})
        and not r["attrs"].get("replayed")]
    if flops is None or not events or not prompts:
        return None
    least = sum(flops(run.cell.config, n) for n in prompts)
    return 100.0 * least / run.peaks["bf16_flops_per_s"] / seconds
