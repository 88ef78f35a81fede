"""The latent decode kernel alone: us a call of ``mla_decode``'s one-token
form at a latent serving cell's pool, and the share of the live rows' bytes at
the chip's 819 GB/s.

A round is one call a layer of the pool, all in one jitted ``fori_loop`` (the
layer index varies), timed on the host's clock over ``--rounds``
rounds, the best of ``--repeats``. PERF.md 5's table "``mla_decode`` alone,
parent against PR 67" was made so; no benchmark cell runs this.

    python examples/kernels/mla_alone.py                      # both pools
    python examples/kernels/mla_alone.py --tree <dir>         # another checkout's kernel
    python examples/kernels/mla_alone.py --pool longout --live 0,39,96 --rounds 32

The pools are the two latent cells' (``--pool``), 64 heads against rank 512 +
rope 64, blocks of 64 tokens:

- ``longout``: ``longcat-flash-chat.serve-longout``, (8, 1, 2560, 64, 640), a
  round of 96 rows, tables 48 wide, a live row's context log-uniform over
  300-3,000 tokens;
- ``longdoc``: ``gigachat3.1-702b-a36b.serve-longdoc``, (5, 1, 3072, 64, 640),
  32 rows, tables 140 wide, contexts log-uniform over 1,000-9,000.

``--live`` counts the live rows of a step; each count is run with the live
rows as the step's first rows (``prefix``) and spread over it
(``scattered``), the same contexts either way. One JSON line a (pool, live
rows, layout): ``us_call``, the bytes the live rows' blocks hold a call,
``roofline_share`` (those bytes at 819 GB/s over the time, %), the kernel's
bind record (``tracing.builds()`` ``kernel_attrs``: ``rows_per_cell``,
``slots``, ``blocks_per_trip``, ``trip_bytes``, ``operand_dtype``, where the
tree's kernel says them; ``--set NAME=INT`` gives a constant of
``paged_attention`` another value for the run, to size it by) and the largest
error against ``mla_attend_xla``, relative to its largest value (dead rows
must read zeros). Times mean something on a TPU only;
``JAX_PLATFORMS=cpu DSTPU_FORCE_PAGED_KERNEL=1`` with ``--tiny`` rehearses
the flow (interpreted).
"""

import argparse
import functools
import json
import os
import sys
import time

HBM_BYTES_PER_S = 819e9
HEADS, RANK, ROPE = 64, 512, 64
#: pool (L, 1, NB, BS, row), rows of a step, table width, a live row's
#: context from .. to (log-uniform)
POOLS = {
    "longout": ((8, 1, 2560, 64, 640), 96, 48, 300, 3000),
    "longdoc": ((5, 1, 3072, 64, 640), 32, 140, 1000, 9000),
}
#: live rows of a step measured by default: none, the cell's mean, half, all
LIVE = {"longout": "0,39,48,96", "longdoc": "6,13,32"}
TINY = {
    "longout": ((2, 1, 96, 16, 640), 12, 12, 20, 190),
    "longdoc": ((2, 1, 96, 16, 640), 8, 24, 60, 380),
}


def spread(rows, n):
    """``n`` row numbers spread over a step of ``rows``."""
    return sorted({round(i * (rows - 1) / max(n - 1, 1)) for i in range(n)})


def measure(name, spec, live, layout, rounds, repeats, dtype):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.ops.transformer import paged_attention as pa
    from deepspeed_tpu.utils import tracing

    shape, rows, maxb, lo, hi = spec
    L, _, NB, BS, row = shape
    at = list(range(live)) if layout == "prefix" else spread(rows, live)
    rng = np.random.default_rng(live)
    contexts = np.exp(rng.uniform(np.log(lo), np.log(hi), live)).astype(int)
    tables, limits = np.zeros((rows, maxb), np.int32), np.zeros(rows, np.int32)
    ids, used = rng.permutation(np.arange(1, NB)), 0
    for b, ctx in zip(at, contexts):
        n = -(-int(ctx) // BS)
        # the call only reads: past the pool's blocks rows share some
        tables[b, :n] = ids[(used + np.arange(n)) % len(ids)]
        limits[b], used = ctx, used + n
    tables, limits = jnp.asarray(tables), jnp.asarray(limits)
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    q_lat = jax.random.normal(keys[0], (rows, HEADS, RANK), dtype)
    q_rope = jax.random.normal(keys[1], (rows, HEADS, ROPE), dtype)
    pool = jax.random.normal(keys[2], shape, dtype)
    scale = (128 + ROPE) ** -0.5

    def call(kernel, q_lat, q_rope, pool, layer):
        return kernel(q_lat, q_rope, pool, layer, tables, limits, scale=scale)

    def rounds_of(q_lat, q_rope, pool):
        def body(i, acc):
            # the queries as they lie: a slice a call would be a copy a call
            out = call(pa.mla_decode, q_lat, q_rope, pool, i % L)
            # a corner of the result keeps the call alive: 8 heads x 128 lanes
            return acc + out[:, :8, :128].astype(jnp.float32)
        return jax.lax.fori_loop(
            0, L * rounds, body, jnp.zeros((rows, 8, 128), jnp.float32))

    mark = tracing.clock_ns()
    got = jax.jit(functools.partial(call, pa.mla_decode))(
        q_lat, q_rope, pool, jnp.int32(1)).astype(jnp.float32)
    want = jax.jit(functools.partial(call, pa.mla_attend_xla))(
        q_lat, q_rope, pool, jnp.int32(1)).astype(jnp.float32)
    dead = np.asarray(limits) == 0
    assert not np.asarray(got)[dead].any(), "a dead row does not read zeros"
    err = None
    if live:
        alive = jnp.asarray(at)
        err = float(jnp.max(jnp.abs(got[alive] - want[alive]))
                    / jnp.max(jnp.abs(want[alive])))
    del got, want
    run = jax.jit(rounds_of)
    best = float("inf")
    for _ in range(repeats + 1):                       # the first compiles
        start = time.perf_counter()
        jax.block_until_ready(run(q_lat, q_rope, pool))
        best = min(best, time.perf_counter() - start)
    attrs = {}
    for rec in tracing.builds():
        if rec.end > mark:
            attrs.update(rec.attrs.get("kernel_attrs", {}).get("mla_decode", {}))
    call_bytes = int(sum(-(-int(c) // BS) for c in contexts)
                     * BS * row * jnp.dtype(dtype).itemsize)
    s_call = best / (rounds * L)
    device = jax.devices()[0]
    return {"pool": name, "shape": list(shape), "dtype": jnp.dtype(dtype).name,
            "rows": rows, "live": live, "layout": layout,
            "tokens": int(contexts.sum()), "us_call": 1e6 * s_call,
            "bytes_call": call_bytes,
            "roofline_share": 100 * call_bytes / HBM_BYTES_PER_S / s_call,
            "bind": attrs, "max_rel_err": err,
            "device": [device.platform, device.device_kind]}


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tree", default=os.path.join(os.path.dirname(__file__),
                                                   "..", ".."),
                    help="the checkout whose deepspeed_tpu is measured")
    ap.add_argument("--pool", action="append", choices=sorted(POOLS),
                    help="default: both")
    ap.add_argument("--live", default=None,
                    help="live rows of a step, comma separated (default: "
                    "0,39,48,96 of longout's 96 and 6,13,32 of longdoc's 32)")
    ap.add_argument("--layout", action="append",
                    choices=("prefix", "scattered"), help="default: both")
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--rounds", type=int, default=16)
    ap.add_argument("--repeats", type=int, default=5)
    ap.add_argument("--set", action="append", default=[], metavar="NAME=INT",
                    help="a constant of paged_attention for this run, to size "
                    "it by: DECODE_SLOTS=2")
    ap.add_argument("--tiny", action="store_true",
                    help="test-sized pools (a CPU rehearsal)")
    args = ap.parse_args()
    sys.path.insert(0, os.path.abspath(args.tree))

    import jax.numpy as jnp

    from deepspeed_tpu.utils.xla_env import enable_compile_cache

    enable_compile_cache()
    from deepspeed_tpu.ops.transformer import paged_attention as pa

    for text in args.set:
        constant, value = text.split("=")
        assert hasattr(pa, constant), constant
        setattr(pa, constant, int(value))
    pools = TINY if args.tiny else POOLS
    for name in args.pool or list(POOLS):
        spec = pools[name]
        rows = spec[1]
        counts = [int(n) for n in (args.live or LIVE[name]).split(",")]
        if args.tiny and not args.live:     # the same shares of a small step
            counts = [n * rows // POOLS[name][1] for n in counts]
        for live in sorted({min(n, rows) for n in counts}):
            for layout in args.layout or ["prefix", "scattered"]:
                if layout == "scattered" and live in (0, rows) \
                        and not args.layout:
                    continue                # the prefix is all there is
                print(json.dumps({"tree": os.path.abspath(args.tree),
                                  "set": args.set, **measure(
                    name, spec, live, layout, args.rounds, args.repeats,
                    jnp.dtype(args.dtype))}), flush=True)


if __name__ == "__main__":
    main()
