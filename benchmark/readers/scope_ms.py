"""Device time of the operations traced under one ``jax.named_scope`` class
(``deepspeed_tpu.utils.tracing.classify``: ``fwd``, ``bwd``, ``remat``,
``optimizer``; serving ``kv_write``, ``paged_attn``, ``sample``, ``model``),
in milliseconds per ``per`` (a counter: steps, dispatches).

The trace's events carry no scope, so the map from instruction to scope
comes from the program: ``tracing.device_scopes()`` compiles (a cache hit)
the programs that ran in the window and reads ``op_name`` from their
optimized HLO. Operations that contain others (``while``...) are left out,
as in the breakdown. The first call prints every class's share of the first
device's busy time, ``unscoped`` among them."""

from benchmark.harness.trace import CONTAINERS, label, parse_op
from benchmark.readers import covered

#: the one trace's table, computed once: (trace, {scope: seconds} or None)
_last = (None, None)


def table(trace):
    """{scope: seconds} over the first device's operations, computed once
    per trace; None where the program has no recorder or noted no program."""
    global _last
    if _last[0] is not trace:
        _last = (trace, _table(trace))
    return _last[1]


def _table(trace):
    try:
        from deepspeed_tpu.utils import tracing
    except ImportError:
        return None
    scopes = tracing.device_scopes()
    if not scopes:
        return None
    by_scope, loose = {}, []
    for name, (sec, _) in trace["ops"].items():
        if parse_op(name)[1] in CONTAINERS:
            continue
        scope = scopes.get(tracing.op_key(name), "unscoped")
        by_scope[scope] = by_scope.get(scope, 0.0) + sec
        if scope in ("unscoped", "mixed"):
            loose.append((sec, label(name)))
    total = sum(by_scope.values())
    if not total:
        return None
    print("[scope_ms] share of device time by scope: " + ", ".join(
        f"{k} {100 * v / total:.1f}%" for k, v in
        sorted(by_scope.items(), key=lambda kv: -kv[1])), flush=True)
    print("[scope_ms] largest unscoped: " + "; ".join(
        f"{n} {1e3 * s:.1f} ms" for s, n in sorted(loose, reverse=True)[:6]),
        flush=True)
    return by_scope


def read(ctx, scope, per):
    trace, n = ctx["trace"], covered.per(ctx, per)
    if not trace or not n:
        return None
    by_scope = table(trace)
    if by_scope is None:
        return None
    return 1e3 * by_scope.get(scope, 0.0) / n
