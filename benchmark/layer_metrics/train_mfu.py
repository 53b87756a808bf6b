"""Trained tokens per second times the operations training requires per
token (the family's count from the shapes: 6 per multiplied parameter,
causal attention at half the square, recomputation not counted), over
chips times the bf16 peak of ``benchmark/peaks.json``. The rate is a step's
tokens over the median time of a step between two loss fetches, which the
profiler's start and stop inside a traced window do not touch."""
from benchmark import stats


META = {"name": "train_mfu", "layer": "model", "unit": "%",
        "share_of_peak": True, "better": "higher", "source": "host_clock",
        "moves": "train_tokens_per_s"}


def read(run):
    groups = run.counters.get("fetch_groups")
    if not groups or run.peaks is None:
        return None
    rate = run.counters["tokens_per_step"] / stats.median(
        [(t1 - t0) / n for t0, t1, n in groups])
    flops = run.cell.family.flops_per_token(run.cell.config,
                                            run.counters["seq_len"])
    peak = len(run.devices) * run.peaks["bf16_flops_per_s"]
    return 100.0 * rate * flops / peak
