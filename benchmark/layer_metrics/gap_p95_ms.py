"""The gap between consecutive tokens of one request, 95th percentile over
all gaps that end in the window: the stall a running answer feels when a
long prompt's prefill shares its tick. In the closed loop of 14 it sits on
the edge between two kinds of tick (4.3 % of gaps wait for a prefill of the
1024 rung or above) and moved by 13 % from seed to seed (my chip runs, PR
25), so the 90th percentile is the end-to-end metric and this one is read
beside it."""
META = {"name": "gap_p95_ms", "layer": "decode engine", "unit": "ms",
        "better": "lower", "source": "host_clock", "moves": "gap_p90_ms"}


def read(run):
    return run.counters.get("gap_p95_ms")
