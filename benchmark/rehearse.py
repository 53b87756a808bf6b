#!/usr/bin/env python3
"""The rehearsal entry: the harness's whole path at the tiny sizes each
cell's file gives under ``rehearsal``, on whatever backend jax finds. Its
result line names the device it ran on; a number from it is never a record.
Same arguments as ``run.py``."""
import time

STARTED = time.monotonic()

import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

if __name__ == "__main__":
    from benchmark import harness

    sys.exit(harness.main(rehearsal=True, started=STARTED))
