"""The benchmark's span around each ``Scheduler.step`` inside the window
that decoded and ran no prefill; median."""
from benchmark import stats

META = {"name": "decode_tick_ms", "layer": "decode engine", "unit": "ms",
        "better": "lower", "source": "program_span",
        "moves": "gap_p90_ms"}


def read(run):
    if run.window is None:
        return None
    t0, t1 = run.window
    ticks = [(b - a) * 1e3 for _, a, b, attrs in run.spans.named("sched_step")
             if t0 <= a and b <= t1 and attrs.get("worked")
             and not attrs.get("prefills")]
    return stats.median(ticks) if ticks else None
