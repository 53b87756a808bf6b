"""The program's ``decode/fetch_logits`` span
(``DecodeEngine.decode_step_sampled``: ``np.asarray(logits)`` after the
sampled tokens have reached the host, so the transfer of the tick's
``[slots, vocabulary]`` float32 logits alone) inside the window; median."""
from benchmark import program_spans, stats

META = {"name": "serve_logits_fetch_ms", "layer": "decode engine",
        "unit": "ms", "better": "lower", "source": "program_span",
        "moves": "serve_tokens_per_s"}


def read(run):
    fetches = program_spans.named(run, "decode/fetch_logits")
    return stats.median(program_spans.ms(fetches)) if fetches else None
