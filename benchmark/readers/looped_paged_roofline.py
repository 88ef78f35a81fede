"""The paged decode-attention kernel of a looped model (``model``'s
``loop_steps``: the same layers of weights run several times a token, each
time over cache layers of its own): the kernel runs once a layer AND step for
every row of a dispatch, a prompt chunk's tokens among them (one-token rows,
each over its own context), so its required work is
``kernels/paged_attention.py``'s ``dispatches`` over ``num_layers x
loop_steps`` cache layers: a sequence's keys and values read once a dispatch
in each of them, one score and one value contraction a (row, context
position). Over the summed device time of the kernels the program names
``paged_decode*``. A model that names no ``loop_steps`` says nothing."""

from benchmark.kernels import paged_attention
from benchmark.readers.covered import inside
from benchmark.readers.program_spans import spans
from benchmark.readers.trace_kernel_ms import kernel_seconds

#: the family name the program gives its paged decode kernels
KERNEL = "paged_decode"


def read(ctx):
    trace, peak = ctx["trace"], ctx["peak"]
    found = inside(ctx, spans("engine.dispatch"))
    model = ctx["cell"].config["model"]
    if not trace or peak is None or not found or "loop_steps" not in model:
        return None
    secs = kernel_seconds(trace, KERNEL)
    if not secs:
        return None
    heads = model["num_heads"]
    layers = model["num_layers"] * model["loop_steps"]
    total = {k: sum(s.attrs.get(k, 0) for s in found)
             for k in ("ctx_tokens", "ctx_tokens_by_row", "rows")}
    flops, nbytes = paged_attention.dispatches(
        total["ctx_tokens"], total["ctx_tokens_by_row"], total["rows"],
        layers, heads, model.get("num_kv_heads") or heads,
        model.get("head_dim_override") or model["hidden_size"] // heads)
    by_flops = flops / peak["bf16_flops_per_s"]
    by_bytes = nbytes / peak["hbm_bytes_per_s"]
    print(f"[looped_paged_roofline] bound by "
          f"{'flops' if by_flops >= by_bytes else 'bytes'}: {flops:.4g} FLOPs, "
          f"{nbytes:.4g} bytes over {len(found)} dispatches and {layers} cache "
          f"layers (ctx_tokens {total['ctx_tokens']}, by row "
          f"{total['ctx_tokens_by_row']}, rows {total['rows']}); kernels "
          f"{1e3 * secs:.1f} ms", flush=True)
    return 100.0 * max(by_flops, by_bytes) / secs
