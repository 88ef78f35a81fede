"""Sum of one attribute over sum of another, across the program's spans of
one name (rows over padded rows of ``engine.dispatch``: how full the compiled
shapes ran), times ``scale``."""

from benchmark.readers.program_spans import spans


def read(ctx, span, num, den, scale=100.0):
    found = spans(span)
    if not found:
        return None
    top = sum(s.attrs.get(num, 0) for s in found)
    bottom = sum(s.attrs.get(den, 0) for s in found)
    if not bottom:
        return None
    print(f"[span_attr_ratio] {span}: {len(found)} spans, {num} {top}, "
          f"{den} {bottom}", flush=True)
    return scale * top / bottom
