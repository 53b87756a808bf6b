#!/usr/bin/env python3
"""python3 benchmark/run.py --workload W --seed N --seconds S --trace 0|1

Runs one cell of BENCHMARK.json once, on the TPU this process finds, and
prints one JSON object as the last line of its output. Without a TPU, or
with fewer chips than the cell asks for, it prints no result and exits 2.
"""
import time

STARTED = time.monotonic()

import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

if __name__ == "__main__":
    from benchmark import harness

    sys.exit(harness.main(started=STARTED))
