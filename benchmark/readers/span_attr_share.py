"""Share of the program's spans of one name whose attribute ``attr`` is above
zero, in percent (``seg_tokens`` of ``engine.dispatch``: the dispatches that
carried a prefill chunk's segment, and so the share of a live sequence's token
gaps that a mixed step, not a decode round, set)."""

from benchmark.readers.program_spans import spans


def read(ctx, span, attr):
    found = spans(span)
    if not found or not any(attr in s.attrs for s in found):
        return None
    hit = sum(s.attrs.get(attr, 0) > 0 for s in found)
    print(f"[span_attr_share] {span}: {hit} of {len(found)} spans with "
          f"{attr} > 0", flush=True)
    return 100.0 * hit / len(found)
