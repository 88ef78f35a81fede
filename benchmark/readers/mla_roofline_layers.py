"""``mla_roofline`` for a model whose attention layers are not its
``num_layers``: the latent-attention kernels' least time over their device
time, with the required work counted over ``layers`` layers of the pool (a
LongCat-Flash double layer has two attentions, so its pool has two layers a
model layer; ``mla_roofline`` reads ``model["num_layers"]`` and would report
half the kernels' share). The counts, the kernel family and the peaks are
``mla_roofline``'s."""

from benchmark.kernels import mla_attention
from benchmark.readers.covered import inside
from benchmark.readers.mla_roofline import KERNEL
from benchmark.readers.program_spans import spans
from benchmark.readers.trace_kernel_ms import kernel_seconds


def read(ctx, layers):
    trace, peak = ctx["trace"], ctx["peak"]
    found = inside(ctx, spans("engine.dispatch"))
    if not trace or peak is None or not found:
        return None
    secs = kernel_seconds(trace, KERNEL)
    if not secs:
        return None
    model = ctx["cell"].config["model"]
    total = {k: sum(s.attrs.get(k, 0) for s in found)
             for k in ("ctx_tokens", "ctx_tokens_by_row", "rows")}
    flops, nbytes = mla_attention.dispatches(
        total["ctx_tokens"], total["ctx_tokens_by_row"], total["rows"],
        layers, model["num_heads"], model["kv_lora_rank"],
        model["qk_rope_head_dim"])
    by_flops = flops / peak["bf16_flops_per_s"]
    by_bytes = nbytes / peak["hbm_bytes_per_s"]
    print(f"[mla_roofline_layers] {layers} pool layers, bound by "
          f"{'flops' if by_flops >= by_bytes else 'bytes'}: {flops:.4g} FLOPs, "
          f"{nbytes:.4g} bytes over {len(found)} dispatches (ctx_tokens "
          f"{total['ctx_tokens']}, by row {total['ctx_tokens_by_row']}, rows "
          f"{total['rows']}); kernels {1e3 * secs:.1f} ms", flush=True)
    return 100.0 * max(by_flops, by_bytes) / secs
