"""Share of its roofline the chunkwise delta-rule kernel with a gate a
channel reaches in prefill. The least time is the larger of the chunked
form's matrix products over the bf16 peak and its bytes over the HBM
bandwidth of ``benchmark/peaks.json`` (the family's two counts; the log-gate
is ``dk`` float32 values a token and head, so the bytes lead), for the
prompt tokens the window's prefills had to process (``scan_tokens`` of the
program's ``serve/prefill`` records that carry ``delta_chunks``, inside the
traced window). The time is the summed device time of the operations whose
short name starts with ``kda_chunk`` (``pl.pallas_call(name=
"kda_chunk_fwd")``, one launch a KDA layer and prefill). The padding of a
rung is time the kernel took and no work the algorithm requires; the
triangular solve and the diagonal sub-blocks' pairwise differences are the
VPU's and are not counted. A program with no such kernel or no
``delta_chunks`` gives nothing."""
from benchmark import program_spans, trace_reduce

META = {"name": "kda_chunk_prefill_roofline", "layer": "kernels",
        "unit": "%", "share_of_peak": True, "better": "higher",
        "source": "device_trace", "moves": "ttft_p50_ms"}
NAME_HEAD = "kda_chunk"


def read(run):
    if (run.profile is None or not run.profile.devices
            or run.peaks is None or run.trace_window is None):
        return None
    flops = getattr(run.cell.family, "chunk_prefill_flops", None)
    moved = getattr(run.cell.family, "chunk_prefill_bytes", None)
    seconds, events = trace_reduce.seconds_matching(run.profile,
                                                    head=NAME_HEAD)
    prefills = [r["attrs"]["scan_tokens"] for r in program_spans.named(
        run, "serve/prefill", window=run.trace_window) or []
        if r.get("attrs", {}).get("delta_chunks")]
    if flops is None or moved is None or not events or not prefills:
        return None
    tokens = sum(prefills)
    least_s = max(
        flops(run.cell.config, tokens) / run.peaks["bf16_flops_per_s"],
        moved(run.cell.config, tokens, len(prefills))
        / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / seconds
