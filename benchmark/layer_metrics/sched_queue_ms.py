"""The benchmark's span from ``Scheduler.submit`` to the start of the
``Scheduler.step`` that admitted the request (took it out of the queue and
ran its prefill); median over the requests admitted in the run."""
from benchmark import stats

META = {"name": "sched_queue_ms", "layer": "front door and scheduler",
        "unit": "ms", "better": "lower", "source": "program_span",
        "moves": "ttft_p50_ms"}


def read(run):
    waits = run.counters.get("queue_ms")
    return stats.median(waits) if waits else None
