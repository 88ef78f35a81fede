"""The paged decode-attention kernel of a model whose every layer is GQA
attention over the whole context with a head width of its own (``model``'s
``head_dim_override``, not ``hidden_size // num_heads``) and whose prompt
chunks go the gather path in tiles: the kernel runs for a dispatch's
one-token rows alone (every row of a decode round, the rows beside the chunks
in a mixed step), so its required work is those rows' contexts
(``kernels/paged_attention.py``, a context read once and scored once a row),
over the summed device time of the kernels the program names
``paged_decode*``.

Where the program says what those rows attend (``decode_ctx_tokens``), every
dispatch is counted, exactly. Where it does not, only the decode rounds can
be (dispatches with no prefill token: ``ctx_tokens`` is then the live rows'
contexts summed); the mixed steps' kernel time then stays in the divisor and
the share errs low."""

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
    model = ctx["cell"].config["model"]
    if not secs or "head_dim_override" not in model:
        return None
    if all("decode_ctx_tokens" in s.attrs for s in found):
        counted, how = found, "dispatches, by row"
        tokens = sum(s.attrs["decode_ctx_tokens"] for s in found)
    else:
        counted = [s for s in found if not s.attrs.get("prefill_tokens", 0)]
        how = "decode rounds, by their totals"
        tokens = sum(s.attrs.get("ctx_tokens", 0) for s in counted)
    rows = sum(s.attrs.get("decode_rows", 0) for s in counted)
    if not rows:
        return None
    heads = model["num_heads"]
    # a one-token row scores its context once: by row is the contexts' sum
    flops, nbytes = paged_attention.dispatches(
        tokens, tokens, rows, model["num_layers"], heads,
        model.get("num_kv_heads") or heads, model["head_dim_override"])
    by_flops = flops / peak["bf16_flops_per_s"]
    by_bytes = nbytes / peak["hbm_bytes_per_s"]
    print(f"[gqa_paged_roofline] bound by "
          f"{'flops' if by_flops >= by_bytes else 'bytes'}: {flops:.4g} FLOPs, "
          f"{nbytes:.4g} bytes over {len(counted)} of {len(found)} {how} "
          f"(context tokens {tokens}, rows {rows}); kernels "
          f"{1e3 * secs:.1f} ms", flush=True)
    return 100.0 * max(by_flops, by_bytes) / secs
