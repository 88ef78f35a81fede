"""How full one class of KV blocks ran at its peak: the largest, over the
program's ``engine.dispatch`` spans, of the class's blocks in use
(``window_blocks`` | ``full_blocks``) over the class's usable blocks, in %.
The usable blocks are the span's own ``used + free``, which is the traffic
file's ``engine.num_blocks`` of the class less its trash block. A program
that sets no such attributes says nothing."""

from benchmark.readers.program_spans import spans


def read(ctx, used, free):
    found = [s for s in spans("engine.dispatch") or ()
             if used in s.attrs and free in s.attrs]
    if not found:
        return None
    peak = max(found, key=lambda s: s.attrs[used])
    cap = peak.attrs[used] + peak.attrs[free]
    print(f"[class_fill] {len(found)} dispatches, peak {used} "
          f"{peak.attrs[used]} of {cap} usable blocks", flush=True)
    return 100.0 * peak.attrs[used] / cap if cap else None
