"""A number the runner counted: ``counters[key] * scale``."""


def read(ctx, key, scale=1.0):
    value = ctx["counters"].get(key)
    return None if value is None else value * scale
