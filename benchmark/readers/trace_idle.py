"""Share of the traced window in which no operation ran on the first device.
The window is what the trace accounts for (``covered.accounted_s``): a part
whose device events the profiler lost is not idle time."""

from benchmark.readers import covered


def read(ctx):
    t = ctx["trace"]
    if not t or not t["window_s"]:
        return None
    return 100.0 * (1.0 - t["busy_first_s"] / covered.accounted_s(ctx))
