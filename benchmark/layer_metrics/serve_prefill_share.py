"""Prefill's share of the loop's time: the summed ``serve/prefill`` spans
(``DecodeEngine.start_sequence_sampled`` and the resume path) over the
summed ``serve/step`` spans (``Scheduler.step``) inside the window."""
from benchmark import program_spans

META = {"name": "serve_prefill_share", "layer": "decode engine", "unit": "%",
        "better": "lower", "source": "program_span", "moves": "gap_p90_ms"}


def read(run):
    steps = program_spans.named(run, "serve/step")
    prefills = program_spans.named(run, "serve/prefill")
    if not steps:
        return None
    return 100.0 * sum(r["dur_ns"] for r in prefills) / sum(
        r["dur_ns"] for r in steps)
