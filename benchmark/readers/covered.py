"""The host-side work that belongs to the device seconds a trace holds.

The profiler can lose the device events of part of the window, at either end
(``harness/trace.py``), while the program's spans and the runners' counters
cover all of it: a reader that divides the one by the other then reads too
much work for the time (a roofline share over 100) or too little time for the
count (a kernel's milliseconds a dispatch at half their size). Every reader
that pairs the two asks here: ``inside`` keeps the program's spans that touch
the covered interval, ``per`` scales a counter by its spans' share inside, and
``accounted_s`` is the length of the window less the part whose device events
are lost: what busy seconds are a share of.

Nothing is clamped and nothing is assumed of a whole trace: its covered
interval reaches from the first launch to the last, every span touches it, and
each function returns what it was given. A trace without a clock anchor or a
program without the recorder reads as whole."""

from benchmark.harness.cell import BenchmarkError
from benchmark.readers.program_spans import spans

#: the program's span that stands for one of each counter of the runners
COUNTED_BY = {"dispatches": "engine.dispatch", "steps": "engine.train_batch"}
#: the span around every launch of a program, training and serving alike
LAUNCH = "engine.enqueue"


def interval(trace):
    """The covered interval on the recorder's clock, (lo, hi) in ns; None
    where the trace has none or no anchor to place it by."""
    if not trace or trace.get("covered_ns") is None \
            or trace.get("clock_offset_ns") is None:
        return None
    lo, hi = trace["covered_ns"]
    return lo - trace["clock_offset_ns"], hi - trace["clock_offset_ns"]


def inside(ctx, found):
    """Those of the program's spans ``found`` that touch the covered
    interval: the ones whose device work the trace can hold."""
    iv = interval(ctx["trace"])
    if iv is None or not found:
        return found
    return [s for s in found if s.end >= iv[0] and s.start <= iv[1]]


def per(ctx, counter):
    """``counters[counter]`` as far as the covered interval goes: scaled by
    the share of its spans (``COUNTED_BY``) that touch the interval. A
    counter whose span is not known here cannot be cut with the device's
    seconds: that is an error, not a whole count."""
    if counter not in COUNTED_BY:
        raise BenchmarkError(
            f"no span stands for the counter '{counter}': a reader cannot "
            f"divide device time by it (readers/covered.py COUNTED_BY has "
            f"{sorted(COUNTED_BY)})")
    n = ctx["counters"].get(counter)
    found = spans(COUNTED_BY[counter])
    if not n or not found:
        return n
    return n * len(inside(ctx, found)) / len(found)


def lost_s(ctx):
    """Seconds of the window whose device events are lost: from the first
    launch that ended before the first device event to that event, and from
    the last device event to the end of the last launch that began after it.
    A part of the window without a launch lost nothing: the device idled."""
    trace = ctx["trace"]
    iv = interval(trace)
    launches = spans(LAUNCH)
    if iv is None or not launches or not trace.get("window_ns"):
        return 0.0
    (lo, hi), (opened, closed) = iv, trace["window_ns"]
    before = [s.start for s in launches if opened <= s.start and s.end < lo]
    after = [s.end for s in launches if hi < s.start <= closed]
    lost = (lo - min(before) if before else 0) \
        + (min(max(after), closed) - hi if after else 0)
    return lost / 1e9


def accounted_s(ctx):
    """The window less what ``lost_s`` counts: the seconds the busy time is
    a share of."""
    return ctx["trace"]["window_s"] - lost_s(ctx)


def line(ctx):
    """The ``[trace]`` line of a traced run."""
    trace = ctx["trace"]
    lo, hi = trace["covered_ns"] or (0, 0)
    out = (f"[trace] device events cover {(hi - lo) / 1e9:.4f} of "
           f"{trace['window_s']:.4f} s")
    iv = interval(trace)
    if iv is None:
        return out + "; no clock anchor, every reader takes the window whole"
    lost = lost_s(ctx)
    for name in COUNTED_BY.values():
        found = spans(name)
        if found:
            out += (f"; {len(inside(ctx, found))} of {len(found)} {name} "
                    "spans inside")
    return out + (f"; {lost:.4f} s of launches without device events, "
                  f"{trace['window_s'] - lost:.4f} s accounted")
