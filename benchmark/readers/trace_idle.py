"""Share of the traced window in which no operation ran on the first device."""


def read(ctx):
    t = ctx["trace"]
    if not t or not t["window_s"]:
        return None
    return 100.0 * (1.0 - t["busy_first_s"] / t["window_s"])
