"""The arithmetic between samples and reported numbers."""
import math
import statistics


def percentile(values, q):
    """The ``q``-th percentile (0-100) by linear interpolation between the
    closest ranks, as numpy's default does. ``values`` need not be sorted."""
    xs = sorted(float(v) for v in values)
    if not xs:
        raise ValueError("percentile of no samples")
    if not 0 <= q <= 100:
        raise ValueError(f"percentile {q} outside 0..100")
    pos = (len(xs) - 1) * q / 100.0
    lo, hi = math.floor(pos), math.ceil(pos)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def median(values):
    return percentile(values, 50)


def quartile_spread(values):
    """Distance between the first and third quartile as a share of the
    median, with the quartiles of ``statistics.quantiles(values, n=4)``:
    the spread the contract sets bounds from."""
    q1, _, q3 = statistics.quantiles([float(v) for v in values], n=4)
    return (q3 - q1) / abs(statistics.median(values))


def worst_leaf_gap(program, reference):
    """Largest gap between a leaf's norm in the program and in the
    reference, measured against the reference's norm of that leaf or of
    the median leaf, whichever is larger (some leaves are all but zero).
    Returns (gap, leaf)."""
    floor = statistics.median(reference.values())
    worst, at = -1.0, None
    for leaf, ref in reference.items():
        gap = abs(program[leaf] - ref) / max(ref, floor)
        if gap > worst:
            worst, at = gap, leaf
    return worst, at
