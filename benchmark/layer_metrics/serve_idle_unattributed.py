"""The gauge of the program's own span coverage: the share of the traced
window's device idle time whose gap has its middle under no leaf span of
the program (``decode/*``, ``prefill/*``, ``serve/emit``, ``serve/evict``,
``serve/loop_idle``): idle time the program cannot put a name to."""
from benchmark import program_spans

META = {"name": "serve_idle_unattributed", "layer": "device", "unit": "%",
        "better": "lower", "source": "device_trace",
        "moves": "serve_tokens_per_s"}


def read(run):
    profile = program_spans.traced(run)
    if profile is None:
        return None
    return program_spans.unattributed_idle_share(profile)
