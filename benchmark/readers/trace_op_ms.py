"""Device time of the operations of one kind (``custom-call``...), in
milliseconds per dispatch of the program that holds them."""

from benchmark.harness.trace import op_kind
from benchmark.readers import covered


def read(ctx, kind, per):
    t = ctx["trace"]
    if not t:
        return None
    secs = sum(s for name, (s, _) in t["ops"].items() if op_kind(name) == kind)
    n = covered.per(ctx, per)
    return 1e3 * secs / n if secs and n else None
