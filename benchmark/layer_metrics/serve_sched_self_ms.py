"""The scheduler's own time in a step: the program's ``serve/step`` span
(``Scheduler.step``) less the ``serve/prefill`` and ``serve/decode_tick``
spans of the same step (the engine's time), over the steps inside the
window that did any work; median."""
from benchmark import program_spans, stats

META = {"name": "serve_sched_self_ms", "layer": "front door and scheduler",
        "unit": "ms", "better": "lower", "source": "program_span",
        "moves": "serve_tokens_per_s"}


def read(run):
    steps = program_spans.named(run, "serve/step")
    if not steps:
        return None
    engine_ns = {}
    for name in ("serve/prefill", "serve/decode_tick"):
        for step, records in program_spans.by_step(
                program_spans.named(run, name)).items():
            engine_ns[step] = engine_ns.get(step, 0) + sum(
                r["dur_ns"] for r in records)
    own = [(r["dur_ns"] - engine_ns.get(r["attrs"]["step"], 0)) / 1e6
           for r in steps if r["attrs"].get("worked")]
    return stats.median(own) if own else None
