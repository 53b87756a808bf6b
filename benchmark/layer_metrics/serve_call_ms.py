"""The program's ``decode/call`` span (``DecodeEngine._launch``: the decode
executable's call and only that, the host's own work of dispatching a
tick) inside the window; median. A program without the span reports
nothing."""
from benchmark import program_spans, stats

META = {"name": "serve_call_ms", "layer": "decode engine", "unit": "ms",
        "better": "lower", "source": "program_span",
        "moves": "serve_tokens_per_s"}


def read(run):
    calls = [r for r in program_spans.named(run, "decode/call") or []
             if r.get("attrs", {}).get("exe") == "decode"]
    return stats.median(program_spans.ms(calls)) if calls else None
