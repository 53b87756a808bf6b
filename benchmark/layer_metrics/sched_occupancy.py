"""``Scheduler.mean_occupancy`` over the window: the share of the engine's
slots that held a live request, averaged over the scheduler's steps between
the window's opening and its close."""

META = {"name": "sched_occupancy", "layer": "front door and scheduler",
        "unit": "%", "better": "higher", "source": "program_counter",
        "moves": "serve_tokens_per_s"}


def read(run):
    occ = run.counters.get("occupancy")
    return None if occ is None else 100.0 * occ
