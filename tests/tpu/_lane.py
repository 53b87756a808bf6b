"""Shared TPU-lane helpers: records what the lane measured under
chiprun_out/ (the one directory the chip tool brings back)."""
import json
import os

_PATH = os.path.join(os.path.dirname(__file__), "..", "..",
                     "chiprun_out", "TPU_LANE.json")


def record(key, value):
    data = {}
    if os.path.exists(_PATH):
        with open(_PATH) as f:
            data = json.load(f)
    data[key] = value
    os.makedirs(os.path.dirname(_PATH), exist_ok=True)
    with open(_PATH, "w") as f:
        json.dump(data, f, indent=1)
