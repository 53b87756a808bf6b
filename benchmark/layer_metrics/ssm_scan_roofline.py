"""Share of its roofline the selective-scan kernel reaches in prefill. The
scan is bound by memory on paper: the least time is the bytes it has to
move for the prompt tokens it had to process (``scan_tokens`` of the
program's ``serve/prefill`` records inside the traced window,
``DecodeEngine.start_sequence_sampled``: a token and Mamba layer ``xs``,
``delta``, ``z`` read and ``y`` written, ``B`` and ``C`` read; a state
written once a sequence: the family's count) over the HBM bandwidth of
``benchmark/peaks.json``. The time is the summed device time of the
operations whose short name starts with ``selective_scan``:
``pl.pallas_call(name="selective_scan_fwd")`` is the HLO instruction's own
``%name``, and on the v5e XLA wraps the Mosaic call (``tpu_custom_call``)
together with the in-place write of the state it returns into one fusion
that carries that name, so the trace shows ``selective_scan_fwd.N fusion
f32[Lm, slots, N, Di]`` and no custom call of its own (my chip run, PR 29).
The padding of a rung is time the kernel took and no work the algorithm
requires. A program with no such kernel or no ``scan_tokens`` gives
nothing."""
from benchmark import program_spans, trace_reduce

META = {"name": "ssm_scan_roofline", "layer": "kernels", "unit": "%",
        "share_of_peak": True, "better": "higher", "source": "device_trace",
        "moves": "ttft_p50_ms"}
NAME_HEAD = "selective_scan"


def read(run):
    if (run.profile is None or not run.profile.devices
            or run.peaks is None or run.trace_window is None):
        return None
    count = getattr(run.cell.family, "scan_bytes", None)
    seconds, events = trace_reduce.seconds_matching(run.profile,
                                                    head=NAME_HEAD)
    prefills = [r["attrs"]["scan_tokens"] for r in program_spans.named(
        run, "serve/prefill", window=run.trace_window) or []
        if r.get("attrs", {}).get("scan_tokens")]
    if count is None or not events or not prefills:
        return None
    least_s = count(run.cell.config, sum(prefills), len(prefills)) \
        / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / seconds
