"""What is left of a tick's overhead when the host's own work of
dispatching is taken out: per joined tick of the traced window that was
dispatched ahead (``ahead`` on its ``serve/decode_tick``), the round trip
less the device program less the length of ``decode/call``; median. It
holds the program's start latency after the call returned, the tokens'
transfer, the runtime's notification and the loop thread's wake-up, free
of the skew between the host's and the device's clock
(``benchmark/span_join.py``)."""
from benchmark import span_join, stats

META = {"name": "serve_wakeup_ms", "layer": "device", "unit": "ms",
        "better": "lower", "source": "device_trace",
        "moves": "serve_tokens_per_s"}


def read(run):
    join = span_join.read(run)
    ticks = join.ticks(ahead=True) if join is not None else []
    return stats.median([p.beyond_call_ns / 1e6 for p in ticks]) \
        if ticks else None
