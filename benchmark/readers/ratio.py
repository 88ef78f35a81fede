"""One count over another."""


def read(ctx, num, den, scale=1.0):
    c = ctx["counters"]
    if not c.get(den):
        return None
    return scale * c[num] / c[den]
