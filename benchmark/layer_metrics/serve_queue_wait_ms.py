"""The program's ``serve/queue_wait`` span (``Scheduler._admit_queued``:
``submit`` to the moment the request is taken off the queue for its
prefill) of the requests submitted and admitted inside the window;
median."""
from benchmark import program_spans, stats

META = {"name": "serve_queue_wait_ms", "layer": "front door and scheduler",
        "unit": "ms", "better": "lower", "source": "program_span",
        "moves": "ttft_p50_ms"}


def read(run):
    waits = program_spans.named(run, "serve/queue_wait")
    return stats.median(program_spans.ms(waits)) if waits else None
