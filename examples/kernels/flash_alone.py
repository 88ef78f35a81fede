"""The flash kernels alone: device time of ``flash_fwd`` and ``flash_bwd``.

Traces ``jax.grad`` through ``flash_attention`` at a training cell's shape
(inputs as ``(B, S, heads x head_dim)``, reshaped inside the jitted function,
as the model hands them over) with ``jax.profiler`` and sums the device time of
the events the kernels' names give, a call. PERF.md 5's kernel-alone tables
were made so (PR 47, PR 63); no benchmark cell runs this.

    python examples/kernels/flash_alone.py                 # both training shapes
    python examples/kernels/flash_alone.py --tree <dir>    # another checkout's kernels
    python examples/kernels/flash_alone.py --shape 8,1024,16,64 --calls 32

One JSON line a shape: the shape, the dtype, the calls, ``flash_fwd_ms`` and
``flash_bwd_ms`` a call (null where the trace holds no such event: the CPU's
interpreter names none), the bind records' ``pairs_computed`` / ``pairs_causal``
where the tree's kernels carry them, and the largest error of the output and
the three gradients against ``xla_attention`` relative to its largest value.
Times mean something on a TPU only; ``JAX_PLATFORMS=cpu`` with ``--shape
1,256,2,64 --calls 1`` rehearses the flow.
"""

import argparse
import glob
import json
import os
import re
import sys
import tempfile

SHAPES = ["8,1024,16,64", "4,2048,16,128"]   # train-seq1024, the four-chip cell
#: an event is named by its HLO line; under plain ``jax.grad`` the kernels'
#: instructions read ``%jvp_flash_fwd_.1`` and ``%transpose_jvp_flash_bwd__.1``
KERNEL = re.compile(r"%?\w*?(flash_(?:fwd|bwd))")


def kernel_ms(trace_dir):
    """{kernel: summed device ms} of the first device's operations."""
    from benchmark.harness.trace import reduce_xplane

    path, = glob.glob(os.path.join(trace_dir, "plugins/profile/*/*.xplane.pb"))
    total = {}
    for name, (seconds, _) in (reduce_xplane(path) or {"ops": {}})["ops"].items():
        m = KERNEL.match(name)
        if m:
            total[m.group(1)] = total.get(m.group(1), 0.0) + 1e3 * seconds
    return total


def measure(shape, dtype, calls):
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.ops.transformer.attention import xla_attention
    from deepspeed_tpu.ops.transformer.flash_attention import flash_attention
    from deepspeed_tpu.utils import tracing

    b, s, nh, hd = shape
    keys = jax.random.split(jax.random.PRNGKey(s + nh + hd), 4)
    q, k, v, w = (jax.random.normal(key, (b, s, nh * hd), jnp.float32)
                  .astype(dtype) for key in keys)

    def out_and_grads(attn):
        def loss(q, k, v):
            out = attn(*(x.reshape(shape) for x in (q, k, v)), causal=True)
            out = out.reshape(b, s, nh * hd)
            return jnp.sum(out.astype(jnp.float32) * w), out
        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2),
                                          has_aux=True))

    mark = tracing.clock_ns()
    flash = out_and_grads(flash_attention)
    (_, out), grads = jax.block_until_ready(flash(q, k, v))      # compiled
    pairs = {}
    for rec in tracing.builds():
        if rec.end > mark:
            for name, attrs in rec.attrs.get("kernel_attrs", {}).items():
                pairs[name] = [attrs["pairs_computed"], attrs["pairs_causal"]]
    (_, ref_out), ref_grads = out_and_grads(xla_attention)(q, k, v)
    err = max(float(jnp.max(jnp.abs(a.astype(jnp.float32) - r.astype(jnp.float32)))
                    / jnp.max(jnp.abs(r.astype(jnp.float32))))
              for a, r in zip((out, *grads), (ref_out, *ref_grads)))

    with tempfile.TemporaryDirectory() as trace_dir:
        jax.profiler.start_trace(trace_dir)
        try:
            for _ in range(calls):
                last = flash(q, k, v)
            jax.block_until_ready(last)
        finally:
            jax.profiler.stop_trace()
        ms = kernel_ms(trace_dir)
    device = jax.devices()[0]
    return {"shape": list(shape), "dtype": jnp.dtype(dtype).name, "calls": calls,
            "flash_fwd_ms": ms.get("flash_fwd", 0.0) / calls or None,
            "flash_bwd_ms": ms.get("flash_bwd", 0.0) / calls or None,
            "pairs": pairs, "max_rel_err": err,
            "device": [device.platform, device.device_kind]}


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tree", default=os.path.join(os.path.dirname(__file__),
                                                   "..", ".."),
                    help="the checkout whose deepspeed_tpu is measured")
    ap.add_argument("--shape", action="append",
                    help="B,S,heads,head_dim (default: both training cells')")
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--calls", type=int, default=16)
    args = ap.parse_args()
    sys.path.insert(0, os.path.abspath(args.tree))

    import jax.numpy as jnp

    from deepspeed_tpu.utils.xla_env import enable_compile_cache

    enable_compile_cache()
    for text in args.shape or SHAPES:
        shape = tuple(int(x) for x in text.split(","))
        print(json.dumps({"tree": os.path.abspath(args.tree),
                          **measure(shape, jnp.dtype(args.dtype), args.calls)}),
              flush=True)


if __name__ == "__main__":
    main()
