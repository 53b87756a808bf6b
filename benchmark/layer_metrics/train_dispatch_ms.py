"""The host's part of a training step: the program's ``train/step`` span
(``parallelize._wrap_step_with_report``: the dispatch of the compiled
step, which returns before the device has run it) inside the window;
median."""
from benchmark import program_spans, stats

META = {"name": "train_dispatch_ms", "layer": "train step", "unit": "ms",
        "better": "lower", "source": "program_span",
        "moves": "train_tokens_per_s"}


def read(run):
    steps = program_spans.named(run, "train/step")
    return stats.median(program_spans.ms(steps)) if steps else None
