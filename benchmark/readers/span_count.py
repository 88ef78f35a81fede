"""How many spans of one name the program recorded in the traced window
(``compile``: a program compiled, or loaded from the compile cache, where
every shape should have been warm)."""

from benchmark.readers.program_spans import spans


def read(ctx, span):
    found = spans(span)
    if found is None:
        return None
    for s in found:
        print(f"[span_count] {span}: {s.attrs} {(s.end - s.start) / 1e6:.1f} ms",
              flush=True)
    return float(len(found))
