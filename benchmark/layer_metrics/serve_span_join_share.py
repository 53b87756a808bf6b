"""The gauge of the join between the program's ring and the profiler's
trace: of the ring's records inside the traced window that are annotated
at all, the share whose ``paddle/<name>`` annotation was found by the span
id it carries (``benchmark/span_join.py``). 100 is whole; a program whose
annotations carry no id reports nothing."""
from benchmark import span_join

META = {"name": "serve_span_join_share", "layer": "decode engine",
        "unit": "%", "better": "higher", "source": "program_span",
        "moves": "serve_tokens_per_s"}


def read(run):
    join = span_join.read(run)
    return join.share if join is not None else None
