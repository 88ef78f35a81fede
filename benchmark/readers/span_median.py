"""Median host-clock duration of a benchmark span, in ``scale`` units."""

from benchmark.harness.stats import median


def read(ctx, span, scale=1000.0):
    xs = ctx["spans"].get(span)
    return scale * median(xs) if xs else None
