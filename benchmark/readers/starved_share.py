"""Share of the run-ahead rounds that the device had to wait for, in
percent: ``engine.dispatch`` spans with ``ahead`` 1 (enqueued with the round
before them unfetched) whose ``starved`` is 1 (that round had already
finished when this one's launch returned, so the device went dry waiting for
the host). ``span_attr_share`` divides by every span of the name; here the
denominator is the ``ahead`` 1 spans alone: a mixed step or a pipe restart
has no predecessor to find finished. A program without the attribute (the
parent of the PR that added it) reads nothing."""

from benchmark.readers.program_spans import spans


def read(ctx):
    ahead = [s for s in spans("engine.dispatch") or ()
             if s.attrs.get("ahead") and "starved" in s.attrs]
    if not ahead:
        return None
    starved = sum(s.attrs["starved"] for s in ahead)
    print(f"[starved_share] {starved} of {len(ahead)} run-ahead rounds found "
          f"their predecessor finished", flush=True)
    return 100.0 * starved / len(ahead)
