#!/usr/bin/env python3
"""The control of a cell: the same run with the precision below the stated
one switched on (the program's own lower-precision path where it has one,
else the reference at that precision in the program's place). ``correct``
has to come out false. Never part of a measurement; same arguments as
``run.py``, and like it refuses anything but a TPU."""
import time

STARTED = time.monotonic()

import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

if __name__ == "__main__":
    from benchmark import harness

    sys.exit(harness.main(control=True, started=STARTED))
