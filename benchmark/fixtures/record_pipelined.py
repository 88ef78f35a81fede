"""Record ``fixtures/pipelined_tpu.xplane.pb`` and ``pipelined_tpu.spans.json``
on one TPU chip:

    python3 benchmark/fixtures/record_pipelined.py <directory>

A host loop that runs one round ahead of the device, as the serving scheduler
does (``ahead`` 1): it launches step ``k`` inside an ``engine.dispatch`` span
and only then waits for step ``k - 1``, so the device works on a step while
the host's span of the *next* one is open. Every twelfth step is a long one
(a mixed step beside the decode rounds). The window is the benchmark's own
``Tracer``, so the trace holds its ``bench.clock`` anchor; the spans are the
program's recorder's, written beside it with the window's edges on the same
clock. ``tests/benchmark/test_cut_trace.py`` cuts the device events of this
trace at instants of its choosing and holds the readers of
``readers/covered.py`` to what the whole trace reads.

The program is made for the purpose (two matrix products and one Pallas
kernel named ``round_kernel`` a step): what the fixture records is the timing
of a pipelined loop and the profiler's clocks, not a model.
"""

import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

STEPS = 240
LONG_EVERY = 12            # every twelfth step is a long one
ROUND_ROWS, MIXED_ROWS = 32, 512
KEPT = ("engine.dispatch", "engine.enqueue", "engine.fetch")


def programs(interpret):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    def body(x_ref, o_ref):
        o_ref[...] = x_ref[...] * 0.5 + 1.0

    def kernel(x):
        return pl.pallas_call(
            body, grid=(x.shape[0] // 256,),
            in_specs=[pl.BlockSpec((256, 1024), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((256, 1024), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
            interpret=interpret, name="round_kernel")(x)

    def step(x, w, products):
        for _ in range(products):
            x = jnp.tanh(x @ w).astype(x.dtype)
        return x, kernel(x[:2048, :1024].astype(jnp.float32)).sum()

    return (jax.jit(lambda x, w: step(x, w, 2)),
            jax.jit(lambda x, w: step(x, w, 16)))


def main(out_dir, tiny=False):
    import jax
    import jax.numpy as jnp

    from benchmark.harness.trace import Tracer
    from deepspeed_tpu.utils import tracing

    os.makedirs(out_dir, exist_ok=True)
    pb = os.path.join(out_dir, "pipelined_tpu.xplane.pb")
    os.environ["BENCH_KEEP_TRACE"] = pb
    n = 2048 if tiny else 4096
    steps = 36 if tiny else STEPS
    a_round, a_mixed = programs(interpret=jax.default_backend() != "tpu")
    x = jnp.ones((n, n), jnp.bfloat16) * 0.01
    w = jnp.eye(n, dtype=jnp.bfloat16)
    for f in (a_round, a_mixed):               # warm: nothing compiles inside
        jax.block_until_ready(f(x, w))

    tracer = Tracer(True)
    tracer.start()
    prev, kinds = None, []
    for k in range(steps):
        long = k % LONG_EVERY == LONG_EVERY - 1
        kinds.append("mixed" if long else "round")
        with tracer.span("sched.step"):
            rows = 13 + (128 if long else 0)
            with tracing.span(
                    "engine.dispatch", program="ragged", ahead=int(prev is not None),
                    rows=rows, decode_rows=13,
                    padded_rows=MIXED_ROWS if long else ROUND_ROWS,
                    ctx_tokens=50_000 + 13 * k, ctx_tokens_by_row=50_000 + 13 * k,
                    sel_blocks=10_000 + k):
                with tracing.span("engine.enqueue"):
                    x, out = (a_mixed if long else a_round)(x, w)
            if prev is not None:
                with tracing.span("engine.fetch"):
                    prev.block_until_ready()
            prev = out
    with tracer.span("sched.step"), tracing.span("engine.fetch"):
        prev.block_until_ready()
    tracer.stop()
    recorded = [list(s) for s in tracing.snapshot() if s.name in KEPT]
    summary = tracer.summary()
    with open(os.path.join(out_dir, "pipelined_tpu.spans.json"), "w") as f:
        json.dump({"window_ns": list(tracer.window_ns), "kinds": kinds,
                   "device": jax.devices()[0].device_kind,
                   "fields": list(tracing.Record._fields), "spans": recorded},
                  f, separators=(",", ":"))
    lo, hi = summary["covered_ns"]
    print(f"recorded {steps} steps, {len(recorded)} spans, "
          f"{sum(c for _, c in summary['ops'].values())} device events; "
          f"covered {(hi - lo) / 1e9:.4f} of {summary['window_s']:.4f} s, "
          f"busy {summary['busy_s']:.4f} s, anchor "
          f"{summary['clock_offset_ns'] is not None}; "
          f"{os.path.getsize(pb)} bytes", flush=True)


if __name__ == "__main__":
    main(sys.argv[1], tiny=os.environ.get("RECORD_TINY") == "1")
