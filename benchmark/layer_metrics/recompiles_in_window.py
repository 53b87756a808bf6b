"""``paddle_recompiles_total`` plus ``engine.steady_state_recompiles``,
after the window minus before it. Nothing may compile in the window."""

META = {"name": "recompiles_in_window", "layer": "decode engine",
        "unit": "count", "better": "lower", "source": "program_counter",
        "moves": "gap_p90_ms"}


def read(run):
    return run.counters.get("recompiles")
