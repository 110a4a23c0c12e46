"""Percentile, throughput and spread arithmetic: plain Python."""
import math
import statistics


def percentile(values, q):
    """The q-th percentile (0..100), linear between closest ranks."""
    if not values:
        raise ValueError("percentile of no values")
    s = sorted(values)
    pos = (len(s) - 1) * q / 100.0
    lo, hi = math.floor(pos), math.ceil(pos)
    return s[lo] + (s[hi] - s[lo]) * (pos - lo)


def tokens_per_s_per_chip(steps, tokens_per_step, first_dispatch_s,
                          last_completion_s, chips):
    """Tokens of the whole steps completed in the window over the time
    from the first dispatch to the last completion, per chip."""
    span = last_completion_s - first_dispatch_s
    if steps <= 0 or span <= 0:
        raise ValueError("no completed step in the window")
    return steps * tokens_per_step / span / chips


def quartile_spread(values):
    """Distance between the first and third quartile as a share of the
    median (statistics.quantiles, n=4): the contract's spread."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def worst_leaf_gap(program, reference, difference=None):
    """Worst leaf's gap between the program's norm and the reference's,
    against the reference's norm of that leaf or of the median leaf,
    whichever is larger. With `difference` ({leaf: norm of program minus
    reference}) the gap is that norm instead of the norms' gap. Returns
    (gap, leaf)."""
    if set(program) != set(reference):
        raise ValueError("leaves differ: %s" % sorted(
            set(program) ^ set(reference)))
    floor = statistics.median(reference.values())
    worst, at = -1.0, None
    for name, ref in reference.items():
        gap = (abs(program[name] - ref) if difference is None
               else difference[name]) / max(ref, floor, 1e-30)
        if gap != gap:              # a NaN gap is the worst there is
            return gap, name
        if gap > worst:
            worst, at = gap, name
    return worst, at
