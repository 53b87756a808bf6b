#!/usr/bin/env python3
"""Repeat one cell and report what the contract sets bounds from.

    python3 benchmark/sweep.py --workload W --seeds 1,2,3,4,5,6 --sets 2 \
        --seconds 51 --out chiprun_out/W.jsonl

Runs ``run.py`` (``--entry control`` for ``control.py``) once per seed and
set, one process after another (this parent never touches jax, so each
child has the chip to itself), keeps every result line in ``--out`` and
prints, for each metric, each set's median and quartile spread
(``statistics.quantiles(n=4)`` over the median), how far the second set's
median lies from the first's, the bound the contract's rule gives and the
window in which the driver admits a bound. ``--report`` prints the same
from a file of rows written earlier.
"""
import argparse
import json
import os
import statistics
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from benchmark.stats import quartile_spread as spread  # noqa: E402


def one(entry, workload, seed, seconds, trace):
    cmd = [sys.executable, os.path.join(HERE, entry + ".py"),
           "--workload", workload, "--seed", str(seed),
           "--seconds", str(seconds), "--trace", str(trace)]
    proc = subprocess.run(cmd, capture_output=True, text=True)
    lines = [x for x in proc.stdout.splitlines() if x.strip()]
    checks = [x for x in lines if x.startswith("[bench] ")][1:]
    try:
        result = json.loads(lines[-1])
    except (IndexError, ValueError):
        result = None
    if proc.returncode != 0 or result is None:
        sys.stderr.write(proc.stderr[-3000:])
    return proc.returncode, result, checks


def trimmed(values):
    """The set without its run farthest from the median, as the driver
    leaves it out when it asks whether a bound is too tight."""
    med = statistics.median(values)
    far = max(range(len(values)), key=lambda i: abs(values[i] - med))
    return [v for i, v in enumerate(values) if i != far]


def report(rows):
    """Per metric: each set's median and quartile spread, the wider spread,
    the bound the contract's rule gives (five times the widest, never under
    1 %), and the window a bound has to lie in to be admitted: over twice
    the mean of the sets' trimmed spreads, under eight times the widest
    (1 % is never too loose)."""
    sets = sorted({r["set"] for r in rows})
    names = sorted({m for r in rows if r["result"]
                    for m in r["result"]["metrics"]})
    for m in names:
        per_set = [[r["result"]["metrics"][m]["value"] for r in rows
                    if r["set"] == k and r["result"]
                    and m in r["result"]["metrics"]] for k in sets]
        line = [m]
        if m == "setup_s" and len(per_set[0]) > 1:
            # the first run of the first set compiles: its set-up apart
            line.append(f"first {per_set[0][0]:.3f}")
            per_set[0] = per_set[0][1:]
        usable = [v for v in per_set if len(v) >= 2]
        for k, v in zip(sets, per_set):
            if len(v) >= 2:
                line.append(f"set{k} median {statistics.median(v):.6g} "
                            f"spread {100 * spread(v):.3f}%")
        if len(usable) == 2:
            m0, m1 = (statistics.median(v) for v in usable)
            line.append(f"set1 vs set0 {100 * (m1 - m0) / m0:+.3f}%")
        if usable and all(len(v) >= 3 for v in usable):
            widest = max(spread(v) for v in usable)
            tight = statistics.mean(spread(trimmed(v)) for v in usable)
            line.append(f"rule 5x widest = {max(500 * widest, 1):.2f}%; "
                        f"admitted {200 * tight:.2f}%.."
                        f"{max(800 * widest, 1):.2f}%")
        print("  ".join(line), flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload")
    ap.add_argument("--seeds")
    ap.add_argument("--sets", type=int, default=1)
    ap.add_argument("--seconds", type=float)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--entry", default="run", choices=("run", "control"))
    ap.add_argument("--out", required=True)
    ap.add_argument("--report", action="store_true",
                    help="run nothing: report on the rows --out holds")
    a = ap.parse_args()
    if a.report:
        with open(a.out) as f:
            report([json.loads(x) for x in f])
        return
    seeds = [int(s) for s in a.seeds.split(",")]
    os.makedirs(os.path.dirname(os.path.abspath(a.out)), exist_ok=True)
    rows = []
    with open(a.out, "a") as f:
        for k in range(a.sets):
            for seed in seeds:
                rc, result, checks = one(a.entry, a.workload, seed,
                                         a.seconds, a.trace)
                row = {"set": k, "seed": seed, "rc": rc, "result": result,
                       "checks": checks, "seconds": a.seconds,
                       "entry": a.entry, "workload": a.workload}
                f.write(json.dumps(row) + "\n")
                f.flush()
                rows.append(row)
                vals = {m: v["value"] for m, v in
                        (result or {}).get("metrics", {}).items()}
                print(f"set {k} seed {seed} rc {rc} correct "
                      f"{(result or {}).get('correct')} failed "
                      f"{(result or {}).get('failed')}/"
                      f"{(result or {}).get('attempted')} {vals}",
                      flush=True)
                for c in checks:
                    print("    " + c, flush=True)
    report(rows)


if __name__ == "__main__":
    main()
