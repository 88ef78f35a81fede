"""Profiler trace -> device busy/idle, an operation table, idle gaps by host
annotation, and the part of the window the device's events cover.

``Tracer`` wraps the measured window of a ``--trace 1`` run in
``jax.profiler`` and marks the benchmark's own host spans with
``TraceAnnotation`` so that they land on the trace's clock. ``reduce_xplane``
is the reduction; it reads the ``.xplane.pb`` with ``jax.profiler.ProfileData``
and nothing else, and is tested against ``fixtures/``.

The profiler can lose the device events of part of a window (the check of
PR 58 read 40.8% idle in a cell that idles 4%: the events covered five of the
eight seconds) while the host's spans and counters cover all of it. So the
reduction returns the covered interval, and the tracer an anchor that puts it
on the host's clock: ``readers/covered.py`` pairs device seconds with the
host-side work of the same interval.
"""

import bisect
import contextlib
import glob
import os
import re
import shutil
import tempfile
import time

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute", "collective-broadcast")
_NULL = contextlib.nullcontext()
#: the annotation the tracer writes at each edge of the window, its instant
#: read on the host's clock beside it
ANCHOR = "bench.clock"


_TEXT = re.compile(r"^%(\S+) = (.*)$", re.S)
_OPCODE = re.compile(r"(?:^|[\s)])([a-z][a-z0-9\-]*)\(")
KERNEL = 'custom_call_target="tpu_custom_call"'


def parse_op(name):
    """(instruction, opcode) of a device operation event. A TPU trace names an
    event by its whole HLO line, ``%fusion.12 = bf16[8,128]{...} fusion(...)``;
    the CPU client by the instruction alone, ``dot_general.1``. A Pallas
    kernel is a ``custom-call`` whose target is ``tpu_custom_call`` and reads
    as opcode ``kernel``; XLA's own custom calls (``ConcatBitcast``...) stay
    ``custom-call``."""
    m = _TEXT.match(name)
    if not m:
        return name, re.sub(r"[.\d]+$", "", name)
    instr, rhs = m.groups()
    found = _OPCODE.search(re.sub(r"\{[^}]*\}", "", rhs))
    opcode = found.group(1) if found else "?"
    if opcode == "custom-call" and KERNEL in rhs:
        opcode = "kernel"
    return instr, opcode


def op_kind(name):
    return parse_op(name)[1]


def is_collective(name):
    return op_kind(name).startswith(COLLECTIVES)


def label(name):
    """A short, stable label for the ledger: instruction, opcode, result
    shape."""
    instr, opcode = parse_op(name)
    shape = re.search(r"= \(?([a-z0-9]+\[[0-9,]*\])", name)
    return " ".join(filter(None, (instr, opcode, shape and shape.group(1))))


def _union(intervals):
    """Merged, sorted (start, end) list."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _device_op_lines(profile):
    """{device plane name: [events]} of the lines that hold the operations
    the device ran. A TPU plane has an ``XLA Ops`` line; the CPU client (the
    rehearsal's stand-in) marks its operations with an ``hlo_op`` stat."""
    planes = {}
    for plane in profile.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    planes[plane.name] = [
                        (e.name, e.start_ns, e.duration_ns) for e in line.events]
        elif plane.name == "/host:CPU":
            ops = [(e.name, e.start_ns, e.duration_ns)
                   for line in plane.lines if "PjRtCpuClient" in line.name
                   for e in line.events
                   if any(k == "hlo_op" for k, _ in e.stats)]
            if ops:
                planes.setdefault("/host:CPU", ops)
    tpu = {k: v for k, v in planes.items() if k != "/host:CPU"}
    return tpu or planes


def _host_spans(profile, names):
    """[(name, start_ns, end_ns)] of the benchmark's own annotations."""
    out = []
    for plane in profile.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in names:
                    out.append((e.name, e.start_ns, e.start_ns + e.duration_ns))
    return sorted(out, key=lambda s: s[1])


def clock_offset(found, anchor_ns):
    """Profiler clock minus host clock, in ns: from the ``ANCHOR`` annotation
    among the host spans ``found`` and the host-clock instant ``anchor_ns``
    the tracer read as it wrote it. None where the trace holds no anchor."""
    mine = [s for name, s, _ in found if name == ANCHOR]
    if not mine or anchor_ns is None:
        return None
    return mine[0] - anchor_ns


def reduce_xplane(path, span_names=(), window_s=None, anchor_ns=None):
    """The numbers the per-layer readers use, from one ``.xplane.pb``.

    ``busy_s``: union of operation intervals per device, averaged over
    devices. ``ops``: per instruction name on the first device, (seconds,
    count). ``idle_by_span``: the first device's idle seconds attributed to the
    host annotation open at each gap's midpoint (``none`` where there is none).
    ``window_s``: given, or first operation start to last operation end.
    ``covered_ns``: the first device's first operation start and last operation
    end, on the profiler's clock. ``clock_offset_ns``: what to take from a
    profiler instant to get the host clock's (``anchor_ns``: the host-clock
    instant of the tracer's ``ANCHOR`` annotation), None without an anchor.
    """
    from jax.profiler import ProfileData

    profile = ProfileData.from_file(path)
    lines = _device_op_lines(profile)
    if not lines:
        return None
    busy, first = [], None
    for name in sorted(lines):
        merged = _union((s, s + d) for _, s, d in lines[name])
        busy.append(sum(e - s for s, e in merged) / 1e9)
        if first is None:
            first = (lines[name], merged)
    events, merged = first
    ops = {}
    for name, _, dur in events:
        sec, cnt = ops.get(name, (0.0, 0))
        ops[name] = (sec + dur / 1e9, cnt + 1)
    span_s = (merged[-1][1] - merged[0][0]) / 1e9 if merged else 0.0
    found = _host_spans(profile, set(span_names) | {ANCHOR})
    spans = [s for s in found if s[0] != ANCHOR]
    idle = {}
    starts = [s for _, s, _ in spans]
    for (_, a), (b, _) in zip(merged, merged[1:]):
        mid, who = (a + b) / 2, "none"
        i = bisect.bisect_right(starts, mid)
        # annotations nest (sched.step inside nothing, sync inside nothing):
        # the latest one started that still covers the midpoint
        for name, s, e in reversed(spans[max(0, i - 4):i]):
            if s <= mid <= e:
                who = name
                break
        idle[who] = idle.get(who, 0.0) + (b - a) / 1e9
    return {
        "devices": len(lines),
        "busy_s": sum(busy) / len(busy),
        "busy_first_s": busy[0],
        "window_s": window_s if window_s is not None else span_s,
        "covered_ns": (merged[0][0], merged[-1][1]) if merged else None,
        "clock_offset_ns": clock_offset(found, anchor_ns),
        "ops": ops,
        "idle_by_span": idle,
    }


#: operations that only contain others (their bodies are listed themselves)
CONTAINERS = ("while", "conditional", "call")


def breakdown(summary, top=10):
    ops = sorted(((n, v) for n, v in summary["ops"].items()
                  if op_kind(n) not in CONTAINERS),
                 key=lambda kv: -kv[1][0])[:top]
    gaps = sorted(summary["idle_by_span"].items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[label(n), s] for n, (s, _) in ops],
            "idle_gaps": [[n, s] for n, s in gaps]}


class Tracer:
    """``start()``/``stop()`` bracket the traced window; ``span(name)`` marks
    host work inside it and keeps its host-clock durations in ``spans``. With
    ``on`` false every call is free and ``stop()`` returns None."""

    def __init__(self, on):
        self.on = bool(on)
        self.spans = {}
        self._dir = None
        self._t0 = None
        self.window_ns = None       # the window's edges on the host's clock

    def start(self):
        if not self.on:
            return
        import jax

        self._dir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0      # the Python tracer slows the host loop
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self._dir, profiler_options=opts)
        self._t0 = time.perf_counter()
        # the anchor: one annotation, with the host's clock read as it opens.
        # time.monotonic_ns is the clock the program's recorder stamps its
        # spans with (tracing.clock_ns), which the covered interval is
        # compared with
        self.window_ns = (time.monotonic_ns(), None)
        with jax.profiler.TraceAnnotation(ANCHOR):
            pass

    @contextlib.contextmanager
    def _span(self, name):
        import jax

        t = time.perf_counter()
        with jax.profiler.TraceAnnotation(name):
            yield
        self.spans.setdefault(name, []).append(time.perf_counter() - t)

    def span(self, name):
        return self._span(name) if self.on else _NULL

    def stop(self):
        """End the traced window (the profiler writes its file)."""
        if self.on:
            import jax

            self.window_ns = (self.window_ns[0], time.monotonic_ns())
            self.window_s = time.perf_counter() - self._t0
            jax.profiler.stop_trace()

    def summary(self):
        """Reduce the trace written by ``stop()``; None when tracing is off
        or the profiler wrote nothing."""
        if not self.on:
            return None
        try:
            found = glob.glob(os.path.join(self._dir, "**", "*.xplane.pb"),
                              recursive=True)
            if not found:
                return None
            keep = os.environ.get("BENCH_KEEP_TRACE")   # how fixtures/ is recorded
            if keep:
                shutil.copy(found[0], keep)
            out = reduce_xplane(found[0], self.spans.keys(),
                                window_s=self.window_s,
                                anchor_ns=self.window_ns[0])
            if out is not None:
                out["window_ns"] = self.window_ns
            return out
        finally:
            shutil.rmtree(self._dir, ignore_errors=True)
