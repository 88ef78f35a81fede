"""The paged decode kernel alone: ms a round of ``paged_decode`` at a serving
cell's pool, and the share of the live rows' bytes at the chip's 819 GB/s.

A round is one call a layer of the pool, all in one jitted ``fori_loop`` (the
layer index varies, the donated pool is carried), timed on the host's clock
over ``--rounds`` rounds, the best of ``--repeats``. PERF.md 5's kernel-alone
tables of ``paged_decode`` were made so (PR 53, PR 65); no benchmark cell runs
this.

    python examples/kernels/paged_alone.py                    # every pool below
    python examples/kernels/paged_alone.py --tree <dir>       # another checkout's kernel
    python examples/kernels/paged_alone.py --pool win --live 1,32 --rounds 64

The pools are the three cells' (``--pool``):

- ``chat``: ``gpt2-medium.serve-chat``, (24, 16, 832, 64, 128), a round of 64
  rows of context 300, the writing form;
- ``win``: ``trinity-mini.serve-win16k``'s window class, (10, 4, 608, 64, 256),
  32 rows that hold 33 blocks and attend the last 2048 tokens, writing and
  bounded (``first``);
- ``full``: its full class, (3, 4, 2048, 64, 256), 32 rows of context 7232
  (113 blocks), writing;
- ``doc``: ``minicpm-sala.serve-doc16k``'s sparse view, (8, 1, 27136, 64, 256),
  96 (row, kv head) pairs of 64 chosen blocks each, read only.

One JSON line a (pool, live rows): the form, ``ms_round``, ``us_call``, the
bytes the live rows' blocks hold a call, ``roofline_share`` (those bytes at
819 GB/s over the time, %), the kernel's bind record (``tracing.builds()``
``kernel_attrs``: ``trip_bytes``, ``blocks_per_trip``, ``slots``,
``operand_dtype``, where the tree's kernel says them; ``--set NAME=INT`` gives
a constant of ``paged_attention`` another value for the run, to size it by)
and the largest error of two live rows
against plain float32 attention, relative to its largest value. Times mean
something on a TPU only; ``JAX_PLATFORMS=cpu DSTPU_FORCE_PAGED_KERNEL=1``
with ``--tiny`` rehearses the flow (interpreted).
"""

import argparse
import json
import os
import sys
import time

HBM_BYTES_PER_S = 819e9
#: pool (L, kvh, NB, BS, row), rows of a step, query heads a kv head, table
#: width, a live row's context, its window (0: none), does the call write
POOLS = {
    "chat": ((24, 16, 832, 64, 128), 64, 1, 16, 300, 0, True),
    "win": ((10, 4, 608, 64, 256), 32, 8, 42, 2112, 2048, True),
    "full": ((3, 4, 2048, 64, 256), 32, 8, 274, 7232, 0, True),
    "doc": ((8, 1, 27136, 64, 256), 96, 16, 64, 4096, 0, False),
}
TINY = {
    "chat": ((2, 4, 40, 16, 128), 8, 1, 4, 50, 0, True),
    "win": ((2, 2, 40, 16, 256), 8, 4, 4, 60, 40, True),
    "full": ((2, 2, 40, 16, 256), 8, 4, 4, 64, 0, True),
    "doc": ((2, 1, 80, 16, 256), 8, 4, 4, 64, 0, False),
}


def spread(rows, n):
    """``n`` row numbers spread over a step of ``rows``."""
    return sorted({round(i * (rows - 1) / max(n - 1, 1)) for i in range(n)})


def measure(name, spec, live, rounds, repeats, dtype):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.ops.transformer import paged_attention as pa
    from deepspeed_tpu.utils import tracing

    shape, rows, g, maxb, ctx, window, write = spec
    L, kvh, NB, BS, row = shape
    hd, nblk = row // 2, -(-ctx // BS)
    # rows that write share no block: no more live rows than the pool holds
    live_rows = spread(rows, min(live, (NB - 1) // nblk))
    rng = np.random.default_rng(len(live_rows))
    tables, lens = np.zeros((rows, maxb), np.int32), np.zeros(rows, np.int32)
    ids = rng.permutation(np.arange(1, NB))
    for i, b in enumerate(live_rows):
        tables[b, :nblk], lens[b] = ids[i * nblk:(i + 1) * nblk], ctx
    first = np.maximum(lens - window, 0).astype(np.int32) if window else None
    tables, lens = jnp.asarray(tables), jnp.asarray(lens)
    bound = {"first": jnp.asarray(first)} if window else {}
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    q, k, v = (jax.random.normal(key, (4, rows, kvh * n * hd), jnp.float32)
               .astype(jnp.bfloat16) for key, n in zip(keys, (g, 1, 1)))

    def call(pool, q, k, v, layer):
        if write:
            return pa.paged_decode(q, pool, layer, tables, lens,
                                   new_rows=(k, v), **bound)
        out = pa.paged_decode(q.reshape(rows, kvh * g, hd), pool, layer,
                              tables, lens, **bound)
        return out.reshape(rows, -1), pool

    def rounds_of(pool, q, k, v):
        def body(i, carry):
            pool, acc = carry
            out, pool = call(pool, q[i % 4], k[i % 4], v[i % 4], i % L)
            return pool, acc + out.astype(jnp.float32)
        return jax.lax.fori_loop(
            0, L * rounds, body,
            (pool, jnp.zeros((rows, kvh * g * hd), jnp.float32)))

    pool = jax.random.normal(keys[3], shape, dtype)
    # two live rows against plain float32 attention, before the pool is written
    err = None
    if live_rows:
        some = jnp.asarray(live_rows[:2])
        gk, gv = pa.gather_context(pool, 1, tables[some])   # (n, T, kvh, hd)
        out, pool = jax.jit(call, donate_argnums=(0,))(
            pool, q[0], k[0], v[0], jnp.int32(1))
        kpos = jnp.arange(gk.shape[1])[None]
        seen = kpos < lens[some, None]
        if window:
            seen &= kpos >= bound["first"][some, None]
        gk, gv = (x.astype(jnp.float32) for x in (gk, gv))
        if write:        # the rows' new token is theirs to see
            at = lens[some] - 1
            gk = gk.at[jnp.arange(len(some)), at].set(
                k[0][some].reshape(-1, kvh, hd).astype(jnp.float32))
            gv = gv.at[jnp.arange(len(some)), at].set(
                v[0][some].reshape(-1, kvh, hd).astype(jnp.float32))
        s = jnp.einsum("bhgd,bthd->bhgt", q[0][some].astype(jnp.float32)
                       .reshape(-1, kvh, g, hd), gk,
                       precision="highest") * hd ** -0.5
        p = jax.nn.softmax(jnp.where(seen[:, None, None], s, -1e30), axis=-1)
        ref = jnp.einsum("bhgt,bthd->bhgd", p, gv, precision="highest")
        got = out[some].astype(jnp.float32).reshape(ref.shape)
        err = float(jnp.max(jnp.abs(got - ref)) / jnp.max(jnp.abs(ref)))
        del gk, gv
    mark = tracing.clock_ns()
    run = jax.jit(rounds_of, donate_argnums=(0,))
    best = float("inf")
    for _ in range(repeats + 1):                       # the first compiles
        start = time.perf_counter()
        pool, acc = run(pool, q, k, v)
        jax.block_until_ready(acc)
        best = min(best, time.perf_counter() - start)
    attrs = {}
    for rec in tracing.builds():
        if rec.end > mark:
            attrs.update(rec.attrs.get("kernel_attrs", {}).get("paged_decode", {}))
    seen_blocks = -(-ctx // BS) - (max(ctx - window, 0) // BS if window else 0)
    call_bytes = (len(live_rows) * seen_blocks * kvh * BS * row
                  * jnp.dtype(dtype).itemsize)
    s_call = best / (rounds * L)
    device = jax.devices()[0]
    return {"pool": name, "shape": list(shape), "dtype": jnp.dtype(dtype).name,
            "form": "+".join(f for f, on in (("write", write),
                                             ("read", not write),
                                             ("bounded", window)) if on),
            "rows": rows, "live": len(live_rows), "context": ctx,
            "ms_round": 1e3 * s_call * L, "us_call": 1e6 * s_call,
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
                    help="default: all four")
    ap.add_argument("--live", default="1,2,16,all",
                    help="live rows of a step, comma separated; 'all': the "
                    "step's rows, or as many as the pool has blocks for")
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--rounds", type=int, default=32)
    ap.add_argument("--repeats", type=int, default=5)
    ap.add_argument("--set", action="append", default=[], metavar="NAME=INT",
                    help="a constant of paged_attention for this run, to size "
                    "it by: DECODE_TRIP_ROWS=2048")
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
        for text in args.live.split(","):
            live = spec[1] if text == "all" else min(int(text), spec[1])
            print(json.dumps({"tree": os.path.abspath(args.tree),
                              "set": args.set, **measure(
                name, spec, live, args.rounds, args.repeats,
                jnp.dtype(args.dtype))}), flush=True)


if __name__ == "__main__":
    main()
