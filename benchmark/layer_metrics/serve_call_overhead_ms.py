"""What a decode tick cost beyond its device program: per tick of the
traced window whose ``decode/call``, device program and ``decode/run`` are
joined (``benchmark/span_join.py``), the call's start to the run's end on
the host's clock less the program's duration on the device's; median. Each
term is a difference on one clock, so the skew between the two cannot move
it. A program without the call spans, and a run without a device plane,
report nothing."""
from benchmark import span_join, stats

META = {"name": "serve_call_overhead_ms", "layer": "device", "unit": "ms",
        "better": "lower", "source": "device_trace",
        "moves": "serve_tokens_per_s"}


def read(run):
    join = span_join.read(run)
    ticks = join.ticks() if join is not None else []
    return stats.median([p.overhead_ns / 1e6 for p in ticks]) \
        if ticks else None
