"""The program's ``serve/decode_tick`` span (``Scheduler._decode`` round
``engine.generate_step``: feed, the device's tick, the fetch of the logits,
the commit) of the steps inside the window that ran no prefill; median."""
from benchmark import program_spans, stats

META = {"name": "serve_tick_ms", "layer": "decode engine", "unit": "ms",
        "better": "lower", "source": "program_span", "moves": "gap_p90_ms"}


def read(run):
    steps = program_spans.named(run, "serve/step")
    ticks = program_spans.named(run, "serve/decode_tick")
    if not steps or not ticks:
        return None
    plain = {r["attrs"]["step"] for r in steps
             if not r["attrs"].get("prefills")}
    ticks = [r for r in ticks if r["attrs"]["step"] in plain]
    return stats.median(program_spans.ms(ticks)) if ticks else None
