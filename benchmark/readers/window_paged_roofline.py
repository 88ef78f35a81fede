"""The paged decode-attention kernel of a model with window layers beside
full ones against its roofline: a window layer's row attends the tokens
between its bound and its length, a full layer's its whole context
(``kernels/window_paged_attention.py``), so the required work is counted by
class from the ``engine.dispatch`` spans, over the summed device time of the
kernels the program names ``paged_decode*``.

The kernel runs for a dispatch's one-token rows: every row of a decode round,
and the rows beside the chunks in a mixed step (the chunks' tiles go the
gather path and are no work of this kernel). Where the program says what
those rows attend (``decode_ctx_tokens``, their contexts summed, and
``decode_window_tokens``, the same cut to the window row by row), every
dispatch is counted, exactly. Where it does not (a recorded trace of before
those attributes), only the **decode rounds** can be counted (dispatches
with no prefill token: ``ctx_tokens`` is then the live rows' contexts summed
and ``decode_rows`` the live rows), with the window cut taken from the totals
(``window_tokens``: exact where every row is at least a window long); the
mixed steps' kernel time then stays in the divisor and the share errs low."""

from benchmark.kernels import window_paged_attention
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
    types = model.get("layer_types") or []
    if not secs or "window_attn" not in types:
        return None
    window = model["sliding_window"]
    if all("decode_ctx_tokens" in s.attrs for s in found):
        counted, how = found, "dispatches, by row"
        full = sum(s.attrs["decode_ctx_tokens"] for s in found)
        cut = sum(s.attrs["decode_window_tokens"] for s in found)
    else:
        counted = [s for s in found if not s.attrs.get("prefill_tokens", 0)]
        how = "decode rounds, by their totals"
        full = sum(s.attrs.get("ctx_tokens", 0) for s in counted)
        cut = window_paged_attention.window_tokens(
            full, sum(s.attrs.get("decode_rows", 0) for s in counted), window)
    rows = sum(s.attrs.get("decode_rows", 0) for s in counted)
    if not rows:
        return None
    heads = model["num_heads"]
    flops, nbytes = window_paged_attention.work(
        full, cut, rows, types.count("full_attn"), types.count("window_attn"),
        heads, model.get("num_kv_heads") or heads,
        model.get("head_dim_override") or model["hidden_size"] // heads)
    by_flops = flops / peak["bf16_flops_per_s"]
    by_bytes = nbytes / peak["hbm_bytes_per_s"]
    print(f"[window_paged_roofline] bound by "
          f"{'flops' if by_flops >= by_bytes else 'bytes'}: {flops:.4g} FLOPs, "
          f"{nbytes:.4g} bytes over {len(counted)} of {len(found)} {how} "
          f"(full-layer tokens {full}, window-layer tokens {cut}, rows "
          f"{rows}); kernels {1e3 * secs:.1f} ms", flush=True)
    return 100.0 * max(by_flops, by_bytes) / secs
