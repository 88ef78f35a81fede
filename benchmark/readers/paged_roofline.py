"""The paged decode-attention kernels against their roofline: the least time
the chip could take for the window's dispatches (the larger of FLOPs over
peak FLOP/s and bytes over peak bytes/s, ``kernels/paged_attention.py``, from
the counts the engine's ``engine.dispatch`` spans carry) over the summed
device time of the kernels the program names ``paged_decode*``."""

from benchmark.kernels import paged_attention
from benchmark.readers.covered import inside
from benchmark.readers.program_spans import spans
from benchmark.readers.trace_kernel_ms import kernel_seconds


#: the family name the program gives its paged decode kernels
KERNEL = "paged_decode"


def read(ctx):
    trace, peak = ctx["trace"], ctx["peak"]
    found = inside(ctx, spans("engine.dispatch"))
    if not trace or peak is None or not found:
        return None
    secs = kernel_seconds(trace, KERNEL)
    if not secs:
        return None
    model = ctx["cell"].config["model"]
    heads = model["num_heads"]
    total = {k: sum(s.attrs.get(k, 0) for s in found)
             for k in ("ctx_tokens", "ctx_tokens_by_row", "rows")}
    flops, nbytes = paged_attention.dispatches(
        total["ctx_tokens"], total["ctx_tokens_by_row"], total["rows"],
        model["num_layers"], heads, model.get("num_kv_heads") or heads,
        model["hidden_size"] // heads)
    by_flops = flops / peak["bf16_flops_per_s"]
    by_bytes = nbytes / peak["hbm_bytes_per_s"]
    print(f"[paged_roofline] bound by {'flops' if by_flops >= by_bytes else 'bytes'}"
          f": {flops:.4g} FLOPs, {nbytes:.4g} bytes over {len(found)} "
          f"dispatches (ctx_tokens {total['ctx_tokens']}, by row "
          f"{total['ctx_tokens_by_row']}, rows {total['rows']}); kernels "
          f"{1e3 * secs:.1f} ms", flush=True)
    return 100.0 * max(by_flops, by_bytes) / secs
