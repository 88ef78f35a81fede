"""The train step's attention kernels against their roofline: the least time
the chip could take (the larger of FLOPs over peak FLOP/s and bytes over peak
bytes/s, from ``kernels/flash_attention.py``) over the summed device time of
the step's custom calls (flash forward and fused backward are its only two
kinds)."""

from benchmark.harness.trace import op_kind
from benchmark.kernels import flash_attention


def read(ctx):
    t, c, peak = ctx["trace"], ctx["counters"], ctx["peak"]
    if not t or peak is None:
        return None
    secs = sum(s for name, (s, _) in t["ops"].items()
               if op_kind(name) == "kernel")
    steps = c.get("steps")
    if not secs or not steps:
        return None
    flops, nbytes = flash_attention.train_step(
        c["global_batch"] // c["chips"], c["seq_len"], c["n_heads"],
        c["head_dim"], c["n_layers"])
    least = max(flops / peak["bf16_flops_per_s"], nbytes / peak["hbm_bytes_per_s"])
    bound = "flops" if flops / peak["bf16_flops_per_s"] >= \
        nbytes / peak["hbm_bytes_per_s"] else "bytes"
    print(f"[flash_roofline] bound by {bound}: {flops:.4g} FLOPs, "
          f"{nbytes:.4g} bytes a step; kernels {1e3 * secs / steps:.3f} ms a step",
          flush=True)
    return 100.0 * least / (secs / steps)
