"""1 - union of device-busy intervals / traced window, serving."""
from benchmark import trace_reduce

META = {"name": "device_idle.serve", "layer": "device", "unit": "%",
        "better": "lower", "source": "device_trace",
        "moves": "serve_tokens_per_s"}


def read(run):
    if run.profile is None or not run.profile.devices:
        return None
    return trace_reduce.idle_share(run.profile, len(run.devices))
