"""Share of the window the first device spends in collective operations on
its operation line. That line is serial: while a collective (or the ``-done``
half of an asynchronous one) occupies it, no compute operation runs."""

from benchmark.harness.trace import is_collective
from benchmark.readers import covered


def read(ctx):
    t = ctx["trace"]
    if not t or not t["window_s"]:
        return None
    secs = sum(s for name, (s, _) in t["ops"].items() if is_collective(name))
    return 100.0 * secs / covered.accounted_s(ctx)
