"""Median duration of one of the program's own spans, in milliseconds; with
``minus``, of the duration less the spans of that name below it (a scheduler
step less the engine's device wait is the host's share of the step). Prints
the sample count, and the benchmark's own span of the same name beside it
where it has one (that one also covers the ramp and the drain, which the
recorder, on only inside the traced window, does not)."""

from benchmark.harness.stats import median
from benchmark.readers.program_spans import fetch_ns, spans


def read(ctx, span, minus=None):
    found = spans()
    if found is None:
        return None
    mine = [s for s in found if s.name == span]
    if not mine:
        return None
    whole = [(s.end - s.start) / 1e6 for s in mine]
    line = f"[program_span] {span}: {len(mine)} spans, median {median(whole):.3f} ms"
    outside = ctx["spans"].get(span)
    if outside:
        line += (f" (the benchmark's own: {len(outside)} spans, median "
                 f"{1e3 * median(outside):.3f} ms)")
    if minus is None:
        print(line, flush=True)
        return median(whole)
    below = [fetch_ns(s, found, minus) / 1e6 for s in mine]
    rest = [w - b for w, b in zip(whole, below)]
    print(f"{line}; '{minus}' below it median {median(below):.3f} ms, the rest "
          f"{median(rest):.3f} ms", flush=True)
    return median(rest)
