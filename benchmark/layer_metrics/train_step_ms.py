"""Host clock around the steps between two loss fetches (each group ends
when the fetched loss has reached the host, so the device has finished
them), over the steps in the group; median over the window's groups."""
from benchmark import stats

META = {"name": "train_step_ms", "layer": "train step", "unit": "ms",
        "better": "lower", "source": "host_clock",
        "moves": "train_tokens_per_s"}


def read(run):
    groups = run.counters.get("fetch_groups")
    if not groups:
        return None
    return stats.median([(t1 - t0) / n * 1e3 for t0, t1, n in groups])
