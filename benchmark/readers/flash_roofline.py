"""The train step's attention kernels against their roofline: the least time
the chip could take (the larger of FLOPs over peak FLOP/s and bytes over peak
bytes/s, from ``kernels/flash_attention.py``) over the summed device time of
the kernels the program names ``flash_*`` (forward and fused backward). The
step holds other kernels too (the head's ``fused_ce_*``): they are not
attention and are left out."""

from benchmark.kernels import flash_attention
from benchmark.readers import covered
from benchmark.readers.trace_kernel_ms import kernel_seconds

#: the family name the program gives its flash-attention kernels
KERNEL = "flash_"


def read(ctx):
    t, c, peak = ctx["trace"], ctx["counters"], ctx["peak"]
    if not t or peak is None:
        return None
    secs = kernel_seconds(t, KERNEL)
    steps = covered.per(ctx, "steps")
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
