"""The program's ``decode/plan`` span (``DecodeEngine.decode_step_sampled``
for a tick found in flight, ``Scheduler._decode`` after the emit for a tick
the step fed itself: the riders' advance, the scheduler's answer, the next
tick's feed and its dispatch, the critical section between a tick's tokens
and its successor's start) inside the window; median. A program without
the span reports nothing."""
from benchmark import program_spans, stats

META = {"name": "serve_plan_ms", "layer": "decode engine",
        "unit": "ms", "better": "lower", "source": "program_span",
        "moves": "serve_tokens_per_s"}


def read(run):
    plans = program_spans.named(run, "decode/plan")
    return stats.median(program_spans.ms(plans)) if plans else None
