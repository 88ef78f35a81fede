"""What set-up cost inside the program, from the recorder's always-on account
(``deepspeed_tpu.utils.tracing.builds`` / ``inits``, docs/TRACING.md "Set-up
and recompiles"): every program the process made runnable, with its trace,
lowering and compile-or-load seconds, the package function that asked for it
and the kernels bound while it was traced, and each engine's constructor.

*Before the window* are the records that end before the first instant the
recorder wrote at (the earliest end among ``tracing.snapshot()``: the same
clock). ``what``:

- ``init_s``: summed duration of the ``engine.init`` records;
- ``trace_s`` / ``lower_s`` / ``load_s``: that field summed over the records
  with a package ``site`` (``load_s`` is a compile when cold, a load from the
  compile cache when warm);
- ``programs``: how many such records;
- ``kernel_trace_s``: seconds of kernel-body tracing summed over every
  record.

A program without the account (the parent of the PR that added it) reads
None, and so does a run that recorded no span (no window to split at). The
first call prints the account as one table, to lay beside the ``[setup]``
line."""

from benchmark.readers.program_spans import spans

FIELDS = ("trace_s", "lower_s", "load_s")
_printed = []


def account():
    """(package builds, the caller's own builds, inits), all before the
    window; None where the program keeps no such account or no window was
    recorded."""
    recorded = spans()
    if recorded is None:
        return None
    from deepspeed_tpu.utils import tracing

    if not hasattr(tracing, "builds"):
        return None
    opened = min(s.end for s in recorded)
    before = [b for b in tracing.builds() if b.end < opened]
    return ([b for b in before if b.attrs["site"]],
            [b for b in before if not b.attrs["site"]],
            [r for r in tracing.inits() if r.end < opened])


def _kernels(records):
    total = {}
    for b in records:
        for name, (calls, seconds) in b.attrs["kernels"].items():
            into = total.setdefault(name, [0, 0.0])
            into[0] += calls
            into[1] += seconds
    return total


def _cost(b):
    return sum(b.attrs[f] for f in FIELDS)


def table(ours, theirs, inits):
    """The account as lines: the constructor, the ten costliest package
    programs (``at``: seconds from the constructor's start to the instant the
    program was runnable), the programs that were compiled and not loaded, the
    caller's own by module and the grand total."""
    from deepspeed_tpu.utils import tracing

    origin = inits[0].start if inits else 0
    lines = []
    for r in inits:
        phases = ", ".join(f"{k[:-2]} {v:.2f}" for k, v in r.attrs.items()
                           if k.endswith("_s"))
        lines.append(f"engine.init ({r.attrs.get('engine')}): "
                     f"{(r.end - r.start) / 1e9:.2f} s ({phases})")
    sums = {f: sum(b.attrs[f] for b in ours) for f in FIELDS}
    inside = sum(_cost(b) for b in ours
                 if any(r.start <= b.end <= r.end for r in inits))
    cold = [b for b in ours if not b.attrs["cached"]]
    lines.append(
        f"{len(ours)} package programs before the window: trace "
        f"{sums['trace_s']:.2f} + lower {sums['lower_s']:.2f} + load "
        f"{sums['load_s']:.2f} = {sum(sums.values()):.2f} s, {inside:.2f} s of "
        f"it inside engine.init; {len(cold)} not cached "
        f"({sum(b.attrs['load_s'] for b in cold):.2f} s of compile)")
    lines.append("  at_s  trace_s  lower_s  load_s  cached  program  site  "
                 "kernels name:binds:seconds")
    for b in sorted(ours, key=_cost, reverse=True)[:10]:
        a = b.attrs
        kernels = " ".join(f"{k}:{n}:{s:.3f}"
                           for k, (n, s) in sorted(a["kernels"].items()))
        lines.append(
            f"  {(b.end - origin) / 1e9:+.2f}  {a['trace_s']:.3f}  "
            f"{a['lower_s']:.3f}  {a['load_s']:.3f}  {int(a['cached'])}  "
            f"{a['program']}  {a['site']}  {kernels or '-'}")
    kernels = _kernels(ours + theirs)
    lines.append("kernel bodies: " + (", ".join(
        f"{k} {n} binds {s:.3f} s" for k, (n, s) in sorted(
            kernels.items(), key=lambda kv: -kv[1][1])) or "none"))
    by_module = {}
    for b in theirs:
        into = by_module.setdefault(b.attrs["caller"], [0, 0.0])
        into[0] += 1
        into[1] += _cost(b)
    for module, (n, *parts) in tracing.small_builds().items():
        into = by_module.setdefault(module, [0, 0.0])
        into[0] += n
        into[1] += sum(parts)
    lines.append("the caller's own (short builds counted over the whole "
                 "process): " + (", ".join(
                     f"{m} {n} programs {s:.2f} s" for m, (n, s) in sorted(
                         by_module.items(), key=lambda kv: -kv[1][1]))
                     or "none"))
    lines.append(
        f"all programs: {len(ours) + sum(n for n, _ in by_module.values())}, "
        f"{sum(sums.values()) + sum(s for _, s in by_module.values()):.2f} s; "
        f"the listener itself {tracing.listener_seconds():.4f} s")
    return lines


def read(ctx, what):
    found = account()
    if found is None:
        return None
    ours, theirs, inits = found
    if not _printed:
        _printed.append(True)
        for line in table(ours, theirs, inits):
            print(f"[setup_builds] {line}", flush=True)
    if what == "init_s":
        return sum(r.end - r.start for r in inits) / 1e9 if inits else None
    if what == "programs":
        return float(len(ours))
    if what == "kernel_trace_s":
        return float(sum(s for _, s in _kernels(ours + theirs).values()))
    if what in FIELDS:
        return sum(b.attrs[what] for b in ours)
    raise ValueError(f"setup_builds: no quantity '{what}'")
