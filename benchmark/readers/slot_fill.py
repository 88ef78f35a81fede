"""State slots in use over the slots the cell's engine has: the mean of one
attribute of the program's ``engine.dispatch`` spans (``state_slots``: slots
that have an owner when the step is built) over ``max_seqs`` of the traffic
file's ``engine`` (a slot a sequence), in %. A program that sets no such
attribute says nothing."""

from benchmark.readers.program_spans import spans


def read(ctx, attr="state_slots"):
    found = [s for s in spans("engine.dispatch") or () if attr in s.attrs]
    if not found:
        return None
    cap = ctx["cell"].traffic["engine"]["max_seqs"]
    used = sum(s.attrs[attr] for s in found)
    print(f"[slot_fill] {len(found)} dispatches, {attr} {used}, {cap} slots",
          flush=True)
    return 100.0 * used / (len(found) * cap)
