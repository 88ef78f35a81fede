"""Order statistics on plain lists."""

import math


def quantile(values, q):
    """The ``q`` quantile (0..1) by linear interpolation between order
    statistics; ``nan`` for an empty list."""
    if not values:
        return math.nan
    xs = sorted(values)
    pos = q * (len(xs) - 1)
    lo = int(math.floor(pos))
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def median(values):
    return quantile(values, 0.5)
