"""Share of the traced window in which the serving engine had handed the
device nothing and the server was not empty, in percent: the summed
``engine.bubble`` events (from the return of the last fetch with no round
unfetched to the return of the next launch) of every cause but ``empty``.
It is the program's own account of ``breakdown.idle_gaps["sched.step"]``, and
a lower bound on it (a launch lands after its call returns). Prints seconds
and counts by cause, ``empty`` among them, and the harness's figure beside
them. A program without the event (the parent of the PR that added it) reads
nothing."""

from benchmark.harness.trace import CONTAINERS, parse_op
from benchmark.readers.program_spans import spans


def by_cause(found):
    """{cause: (seconds, count)} of ``engine.bubble`` events."""
    out = {}
    for s in found:
        sec, n = out.get(s.attrs.get("cause"), (0.0, 0))
        out[s.attrs.get("cause")] = (sec + (s.end - s.start) / 1e9, n + 1)
    return out


def read(ctx):
    found = spans("engine.bubble")
    trace = ctx["trace"]
    if not found or not trace or not trace["window_s"]:
        return None
    causes = by_cause(found)
    waited = sum(sec for cause, (sec, _) in causes.items() if cause != "empty")
    print("[bubble_share] " + ", ".join(
        f"{cause} {sec:.4f} s in {n}" for cause, (sec, n) in
        sorted(causes.items(), key=lambda kv: -kv[1][0]))
        + f"; not empty {waited:.4f} s of a {trace['window_s']:.3f} s window",
        flush=True)
    outside = trace.get("idle_by_span", {}).get("sched.step")
    if outside is not None:
        # the harness's figure also holds the gaps between one program's ops
        # and between a round and the one queued behind it: no bubble, the
        # device had been given the work
        events = sum(n for name, (_, n) in trace["ops"].items()
                     if parse_op(name)[1] not in CONTAINERS)
        print(f"[bubble_share] the device's idle under the benchmark's "
              f"sched.step: {outside:.4f} s; beyond the bubbles "
              f"{outside - waited:.4f} s, {1e6 * (outside - waited) / events:.3f}"
              f" us a device op event ({events} events)", flush=True)
    return 100.0 * waited / trace["window_s"]
