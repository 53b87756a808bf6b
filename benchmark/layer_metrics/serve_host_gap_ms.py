"""The host's part of a decode tick, on the profiler's clock: per
``paddle/serve/decode_tick`` annotation of the traced window, its length
less the time the device was busy inside it (the union of the "XLA Ops"
intervals); median. What is left is feed building, dispatch, the fetch of
the logits and the commit, with the device waiting."""
from benchmark import program_spans, stats

META = {"name": "serve_host_gap_ms", "layer": "device", "unit": "ms",
        "better": "lower", "source": "device_trace",
        "moves": "serve_tokens_per_s"}


def read(run):
    profile = program_spans.traced(run)
    if profile is None:
        return None
    gaps = program_spans.host_gaps_ms(profile, "serve/decode_tick")
    return stats.median(gaps) if gaps else None
