"""The served head alone: a step in miniature over ``gpt2-medium.serve-chat``'s
tied table, XLA's form beside ``head_logits`` (``ops/transformer/fused_ce.py``).

A step here is one program, as a decode round is: its first op gathers the
step's rows from the ``(50304, 1024)`` table, its last ops multiply them by the
same table and take each row's argmax. Left to XLA the table is the program's
cross-program prefetch: copied to VMEM whole, then multiplied out of VMEM; with
the kernel it stays in HBM and is streamed once under the products. The program
is dispatched ``--calls`` times under a profiler trace, and a line says what
the device did a call: its busy time, its largest ops, the table's copy
(``table_copy_us``), the kernel's time and its share of the table's bytes at the
chip's 819 GB/s. PERF.md 5's table of the served head was made so (PR 68); no
benchmark cell runs this.

**What XLA's line shows here and what it cannot** (my chip runs, PR 68): a
program this small keeps the prefetched table in VMEM from one execution to the
next, so after the first call the copy costs nothing and the line is the
product out of VMEM alone (36.6 us at 64 rows, 91% of the MXU's peak). A served
round has other uses for VMEM: its program frees the table after the head and
copies it again at its end, in every dispatch, with nothing left to hide it
(``copy-done.1 bf16[50304,1024]`` 136.5 us in a traced ``serve-chat`` run of
PR 67's tree, beside the product's 43.6). The kernel's line is the same in
both places (137 us): it reads HBM whatever VMEM holds.

    python examples/kernels/head_alone.py                  # rows 1, 64, 96; XLA | block_v 128 | 384
    python examples/kernels/head_alone.py --tree <dir>     # another checkout's kernel
    python examples/kernels/head_alone.py --rows 64 --ablate fetch,product

``--ablate`` takes a part of the kernel out to show what binds it: ``fetch``
keeps the products and fetches one table tile once (every grid step names
tile 0), ``product`` keeps the fetches and writes a tile of zeros. Both leave
Pallas's own pipeline in place, so neither can hang the chip. Times mean
something on a TPU only; ``JAX_PLATFORMS=cpu`` with ``--tiny`` rehearses the
flow (interpreted).
"""

import argparse
import functools
import glob
import json
import os
import sys
import tempfile
import time

HBM_BYTES_PER_S = 819e9
TABLE = (50304, 1024)
TINY = (1536, 256)


def device_ops(trace_dir, calls):
    """{op label: us a call} of the traced device operations."""
    from jax.profiler import ProfileData

    from benchmark.harness import trace as T

    path, = glob.glob(os.path.join(trace_dir, "plugins/profile/*/*.xplane.pb"))
    lines = T._device_op_lines(ProfileData.from_file(path))
    ops = {}
    for name, _, ns in next(iter(lines.values()), []):
        label = T.label(name)
        if T.op_kind(name) not in ("while", "conditional", "call"):
            ops[label] = ops.get(label, 0.0) + ns / 1e3 / calls
    return ops


def measure(table, rows, form, block_v, ablate, calls, dtype):
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.ops.transformer import fused_ce
    from deepspeed_tpu.utils import tracing

    V, H = table
    keys = jax.random.split(jax.random.PRNGKey(rows), 2)
    w = (jax.random.normal(keys[0], table, jnp.float32) * 0.02).astype(dtype)
    ids = jax.random.randint(keys[1], (rows,), 0, V)

    def step(w, ids):
        x = w[ids]                                  # the embedding's gather
        x = (x.astype(jnp.float32) * 1.5).astype(x.dtype)
        lg = x @ w.T if form == "xla" else fused_ce.head_logits(
            x, w, vocab_major=True, block_v=block_v)
        return jnp.argmax(lg.astype(jnp.float32), axis=-1), lg[:2]

    real = fused_ce._w_spec, fused_ce._logits_kernel
    if ablate == "fetch":
        from jax.experimental import pallas as pl
        fused_ce._w_spec = lambda H, block_v, vocab_major: pl.BlockSpec(
            (block_v, H), lambda j, i: (0, 0))
    elif ablate == "product":
        def zeros(x_ref, w_ref, lg_ref, *, vocab_major):
            lg_ref[...] = jnp.zeros(lg_ref.shape, lg_ref.dtype)
        fused_ce._logits_kernel = zeros
    mark = tracing.clock_ns()
    try:
        if ablate:      # the call is traced once a shape: trace this one anew
            fused_ce._logits_call.clear_cache()
        run = jax.jit(step)
        toks, some = jax.block_until_ready(run(w, ids))        # compiled
    finally:
        fused_ce._w_spec, fused_ce._logits_kernel = real
        if ablate:
            fused_ce._logits_call.clear_cache()
    bind = {}
    for rec in tracing.builds():
        if rec.end > mark:
            bind.update(rec.attrs.get("kernel_attrs", {}).get("head_logits", {}))
    err = None
    if not ablate:
        want = jnp.dot((w[ids[:2]].astype(jnp.float32) * 1.5).astype(dtype)
                       .astype(jnp.float32), w.T.astype(jnp.float32),
                       precision="highest")
        err = float(jnp.max(jnp.abs(some.astype(jnp.float32) - want))
                    / jnp.max(jnp.abs(want)))
    start = time.perf_counter()
    for _ in range(calls):
        last = run(w, ids)
    jax.block_until_ready(last)
    wall_us = (time.perf_counter() - start) / calls * 1e6
    with tempfile.TemporaryDirectory() as trace_dir:
        jax.profiler.start_trace(trace_dir)
        try:
            for _ in range(calls):
                last = run(w, ids)
            jax.block_until_ready(last)
        finally:
            jax.profiler.stop_trace()
        ops = device_ops(trace_dir, calls)
    table_bytes = V * H * jnp.dtype(dtype).itemsize
    head_us = sum(us for op, us in ops.items() if op.startswith("head_logits"))
    copy_us = sum(us for op, us in ops.items()
                  if op.startswith("copy-done") and f"[{V},{H}]" in op)
    device = jax.devices()[0]
    return {"rows": rows, "form": form, "block_v": block_v, "ablate": ablate,
            "table": list(table), "dtype": jnp.dtype(dtype).name,
            "calls": calls, "wall_us_call": wall_us,
            "busy_us_call": sum(ops.values()),
            "table_copy_us": copy_us or None,
            "head_logits_us": head_us or None,
            "table_us_at_819": table_bytes / HBM_BYTES_PER_S * 1e6,
            "roofline_share": (100 * table_bytes / HBM_BYTES_PER_S * 1e6
                               / head_us) if head_us and not ablate else None,
            "ops": sorted(((op, round(us, 2)) for op, us in ops.items()),
                          key=lambda kv: -kv[1])[:6],
            "bind": bind, "max_rel_err": err,
            "device": [device.platform, device.device_kind]}


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tree", default=os.path.join(os.path.dirname(__file__),
                                                   "..", ".."),
                    help="the checkout whose deepspeed_tpu is measured")
    ap.add_argument("--rows", default="1,64,96",
                    help="rows of a step, comma separated")
    ap.add_argument("--block-v", default="128,384",
                    help="vocabulary tiles of the kernel, comma separated")
    ap.add_argument("--ablate", default="",
                    help="parts of the kernel to take out, each a run of its "
                    "own at the largest tile: fetch,product")
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--calls", type=int, default=200)
    ap.add_argument("--tiny", action="store_true",
                    help="a test-sized table (a CPU rehearsal)")
    args = ap.parse_args()
    sys.path.insert(0, os.path.abspath(args.tree))

    import jax.numpy as jnp

    from deepspeed_tpu.utils.xla_env import enable_compile_cache

    enable_compile_cache()
    table = TINY if args.tiny else TABLE
    tiles = [int(v) for v in args.block_v.split(",")]
    line = functools.partial(measure, table, calls=args.calls,
                             dtype=jnp.dtype(args.dtype))
    for rows in (int(r) for r in args.rows.split(",")):
        runs = [("xla", None, "")] + [("kernel", v, "") for v in tiles] + [
            ("kernel", max(tiles), part)
            for part in filter(None, args.ablate.split(","))]
        for form, block_v, ablate in runs:
            print(json.dumps({"tree": os.path.abspath(args.tree), **line(
                rows=rows, form=form, block_v=block_v, ablate=ablate)}),
                flush=True)


if __name__ == "__main__":
    main()
