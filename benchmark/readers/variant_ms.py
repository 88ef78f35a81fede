"""Device time by the program that spent it: the serving engine's ragged
program runs as two compiled variants, the **decode round** (``max_seqs``
rows) and the **mixed step** (the rows of a token budget), which differ five-
to tenfold, and ``scope_ms`` divides their summed time by all dispatches.

``tracing.device_programs()`` says which noted programs hold each
instruction-and-shape of the trace's op table; the ``engine.dispatch`` spans
say how often each variant ran (``padded_rows``). An op only one variant holds
is that variant's; one both hold under one name and shape (a layer's slice of
a stacked weight, the head over ``max_seqs`` final rows, an expert tile of 128
rows) is *shared* and goes to neither, so a variant's number is what that
variant spent **alone**: a lower bound, short by its part of the shared time.
``variant``: ``round`` (rows = the traffic file's ``max_seqs``) or ``mixed``
(every other ragged variant that ran, by the spans' ``padded_rows``);
``scope``: one ``jax.named_scope`` class of it, or the whole variant. In
milliseconds a dispatch of that variant.

The first call prints the account: each kind's seconds and dispatches, how
they add up to the busy time, the shared ops' share of it with the largest of
them, and for every scope over 5% of a variant its three largest ops."""

from benchmark.harness.trace import CONTAINERS, label, parse_op
from benchmark.readers.covered import inside
from benchmark.readers.program_spans import spans

RAGGED = "engine_v2.ragged"

#: the one trace's account, computed once: (trace, account or None)
_last = (None, None)


def round_rows(cell, seen):
    """Rows of the decode round: ``max_seqs`` of the traffic file's engine,
    or of its rehearsal preset, whichever ran."""
    for mix in (cell.traffic, cell.traffic.get("rehearsal", {})):
        rows = mix.get("engine", {}).get("max_seqs")
        if rows in seen:
            return rows
    return None


def split(ops, op_key, programs, scopes, kind_of):
    """{kind: {scope: [(seconds, op name)]}} of an op table. ``kind_of``:
    {(name, key): kind} for the programs told apart; an op held by programs
    of two kinds is ``shared``, by none of them ``other``."""
    out = {}
    for name, (sec, _) in ops.items():
        if parse_op(name)[1] in CONTAINERS:
            continue
        key = op_key(name)
        kinds = {kind_of[p] for p in programs.get(key, ()) if p in kind_of}
        kind = kinds.pop() if len(kinds) == 1 else \
            ("shared" if kinds else "other")
        out.setdefault(kind, {}).setdefault(
            scopes.get(key, "unscoped"), []).append((sec, name))
    return out


def account(ctx):
    """The trace's split by variant, computed once per trace; None where the
    program keeps no by-program table (the parent of the PR that added it),
    noted no ragged program or recorded no dispatch."""
    global _last
    if _last[0] is not ctx["trace"]:
        _last = (ctx["trace"], _account(ctx))
    return _last[1]


def _account(ctx):
    try:
        from deepspeed_tpu.utils import tracing
    except ImportError:
        return None
    if not hasattr(tracing, "device_programs"):
        return None
    runs = {}
    for s in inside(ctx, spans("engine.dispatch")) or ():
        if s.attrs.get("program") == "ragged" and "padded_rows" in s.attrs:
            rows = s.attrs["padded_rows"]
            runs[rows] = runs.get(rows, 0) + 1
    programs = tracing.device_programs()
    first = round_rows(ctx["cell"], runs)
    kind_of = {p: "round" if p[1][0] == first else "mixed"
               for held in programs.values() for p in held
               if p[0] == RAGGED and p[1][0] in runs}
    if not kind_of:
        return None
    by_kind = split(ctx["trace"]["ops"], tracing.op_key, programs,
                    tracing.device_scopes(), kind_of)
    n = {"round": runs.get(first, 0),
         "mixed": sum(c for rows, c in runs.items() if rows != first)}
    sec = {kind: {scope: sum(s for s, _ in ops)
                  for scope, ops in scoped.items()}
           for kind, scoped in by_kind.items()}
    total = {kind: sum(sec.get(kind, {}).values())
             for kind in ("round", "mixed", "shared", "other")}
    busy = ctx["trace"]["busy_first_s"]
    print(f"[variant_ms] rows {sorted(runs.items())}, round = {first}; "
          f"busy {busy:.4f} s = " + " + ".join(
              f"{k} {v:.4f}" for k, v in total.items())
          + f" ({100 * sum(total.values()) / busy:.2f}%); of busy: mixed "
          f"alone {100 * total['mixed'] / busy:.2f}%, shared "
          f"{100 * total['shared'] / busy:.2f}%", flush=True)
    both = sorted((op for ops in by_kind.get("shared", {}).values()
                   for op in ops), reverse=True)[:3]
    if both:
        print(f"[variant_ms] shared: {1e3 * total['shared'] / sum(n.values()):.3f}"
              " ms a dispatch of either kind, if each paid alike; largest: "
              + "; ".join(f"{label(name)} {t:.4f} s" for t, name in both),
              flush=True)
    for kind in ("round", "mixed"):
        if not n[kind] or not total[kind]:
            continue
        print(f"[variant_ms] {kind}: {n[kind]} dispatches, "
              f"{1e3 * total[kind] / n[kind]:.3f} ms each", flush=True)
        for scope, s in sorted(sec[kind].items(), key=lambda kv: -kv[1]):
            if s < 0.05 * total[kind]:
                continue
            top = sorted(by_kind[kind][scope], reverse=True)[:3]
            print(f"[variant_ms]   {scope} {1e3 * s / n[kind]:.3f} ms "
                  f"({100 * s / total[kind]:.1f}%): " + "; ".join(
                      f"{label(name)} {1e3 * t / n[kind]:.3f}"
                      for t, name in top), flush=True)
    return {"n": n, "sec": sec, "total": total}


def read(ctx, variant, scope=None):
    if not ctx["trace"]:
        return None
    acc = account(ctx)
    if acc is None or not acc["n"][variant]:
        return None
    # where the table cannot tell the variants apart (the CPU client names
    # an event by the instruction alone) every op is shared and this reads 0
    sec = acc["total"][variant] if scope is None \
        else acc["sec"].get(variant, {}).get(scope, 0.0)
    return 1e3 * sec / acc["n"][variant]
