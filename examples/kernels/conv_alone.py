"""The short convolution of a decode round alone: ``conv_rows``
(``ops/transformer/linear_attention.py``) at the two cells' window arrays,
the kernel ``conv_decode`` beside the XLA form.

A call is one state layer's convolution of a round: ``rows`` one-token rows on
a window array ``(layers, 1 + slots, 3, channels)`` in bfloat16, ``live`` of
them holding a slot. The call is made ``--calls`` times in one jitted
``fori_loop`` with the layer index varying, the window array donated, and a
line says what a call took beside the time of its bytes at the chip's 819
GB/s: the kernel's are the layer's whole windows in and out, ``x`` in and
``y`` out (``stream_us``); a live row's own are its ``3 x channels`` window
rows in and out (``live_us``). PERF.md 5's table of the convolution was made
so (PR 70); no benchmark cell runs this.

**What the XLA form's line leaves out**: inside this loop the compiler holds
the window array in the layout its gather and scatter want, and relays it
once before the loop and once after, not a call. A served round pays those
two copies of the whole array every dispatch (``copy bf16[5,129,3,12288]``,
47.5 MB each), which is most of what the kernel removes.

    python examples/kernels/conv_alone.py                      # both cells, live 0 | a quarter | all
    python examples/kernels/conv_alone.py --tree <dir>         # another checkout's kernel
    python examples/kernels/conv_alone.py --cell reason --live 0,31,128 --layout scattered
    python examples/kernels/conv_alone.py --set CONV_LANES=2048

Times mean something on a TPU only; ``JAX_PLATFORMS=cpu`` with ``--tiny``
rehearses the flow (interpreted).
"""

import argparse
import json
import os
import sys
import time

HBM_BYTES_PER_S = 819e9
#: cell -> (layers, slots, channels, rows of a round, bias)
CELLS = {"reason": (5, 128, 12288, 128, False),
         "crowd": (9, 64, 5120, 64, True)}
TINY = {"reason": (2, 16, 256, 16, False), "crowd": (2, 8, 128, 8, True)}
TAPS = 4


def measure(cell, shape, form, live, layout, calls, reps):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.ops.transformer import linear_attention as la
    from deepspeed_tpu.ops.transformer.attention import set_default_impl

    layers, n_slots, ch, rows, biased = shape
    rng = np.random.default_rng(live)
    slots = np.zeros(rows, np.int32)
    held = rng.permutation(n_slots)[:live] + 1
    at = np.arange(live) if layout == "prefix" else np.sort(
        rng.permutation(rows)[:live])
    slots[at] = held
    window = jnp.asarray(rng.normal(size=(layers, 1 + n_slots, TAPS - 1, ch)),
                         jnp.bfloat16)
    x = jnp.asarray(rng.normal(size=(rows, ch)), jnp.bfloat16)
    taps = jnp.asarray(rng.normal(size=(TAPS, ch)), jnp.bfloat16)
    bias = jnp.asarray(rng.normal(size=(ch,)), jnp.bfloat16) if biased else None
    fresh = jnp.zeros((rows,), bool)

    def run(window, slots, x):
        def call(i, carry):
            window, acc = carry
            y, window = conv(window, i % layers, slots, x, taps, bias, fresh)
            return window, acc + y[:, :128]
        return jax.lax.fori_loop(
            0, calls, call, (window, jnp.zeros((rows, 128), jnp.float32)))

    # the kernel by its name: ``conv_rows`` hands it no biased convolution
    conv = la.conv_decode if form == "kernel" else la.conv_rows
    set_default_impl("xla" if form == "xla" else None)
    try:
        fn = jax.jit(run, donate_argnums=(0,))
        window, acc = fn(window, jnp.asarray(slots), x)     # compiles
        acc.block_until_ready()
        best = float("inf")
        for _ in range(reps):
            start = time.perf_counter()
            window, acc = fn(window, jnp.asarray(slots), x)
            acc.block_until_ready()
            best = min(best, time.perf_counter() - start)
    finally:
        set_default_impl(None)
    plane_bytes = 2 * (TAPS - 1) * (1 + n_slots) * ch * 2
    row_bytes = rows * ch * (2 + 4)
    return {
        "cell": cell, "form": form, "live": live, "layout": layout,
        "rows": rows, "channels": ch,
        "lanes": la.conv_lanes(ch) if form == "kernel" else None,
        "us_a_call": round(best / calls * 1e6, 2),
        "stream_us": round((plane_bytes + row_bytes) / HBM_BYTES_PER_S * 1e6,
                           2),
        "live_us": round((2 * live * (TAPS - 1) * ch * 2 + row_bytes)
                         / HBM_BYTES_PER_S * 1e6, 2),
        "device": jax.devices()[0].device_kind,
    }


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tree", help="another checkout's package and kernel")
    ap.add_argument("--cell", default="reason,crowd")
    ap.add_argument("--live", help="live rows, comma separated")
    ap.add_argument("--layout", default="scattered",
                    choices=("prefix", "scattered"))
    ap.add_argument("--forms", default="xla,kernel")
    ap.add_argument("--calls", type=int, default=120)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--set", action="append", default=[],
                    metavar="NAME=VALUE",
                    help="a constant of linear_attention.py, e.g. CONV_LANES")
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.abspath(
        args.tree or os.path.join(os.path.dirname(__file__), "..", "..")))
    if args.tiny:
        os.environ.setdefault("DSTPU_FORCE_PAGED_KERNEL", "1")

    from deepspeed_tpu.ops.transformer import linear_attention as la

    for item in args.set:
        name, value = item.split("=")
        if not hasattr(la, name):
            raise SystemExit(f"linear_attention has no constant {name}")
        setattr(la, name, int(value))
    forms = [f for f in args.forms.split(",")
             if f == "xla" or hasattr(la, "conv_decode")]
    for cell in args.cell.split(","):
        shape = (TINY if args.tiny else CELLS)[cell]
        rows = shape[3]
        lives = ([int(n) for n in args.live.split(",")] if args.live
                 else [0, rows // 4, rows])
        for live in lives:
            for form in forms:
                print(json.dumps(measure(
                    cell, shape, form, min(live, rows), args.layout,
                    8 if args.tiny else args.calls,
                    1 if args.tiny else args.reps)), flush=True)


if __name__ == "__main__":
    main()
