"""The recurrence's decode kernel in a delta-rule (KDA) layer against its
roofline: the least time the chip could take for the window's one-token rows
(the larger of FLOPs over peak FLOP/s and bytes over peak bytes/s,
``kernels/delta_rule.py``, from ``decode_rows`` of the ``engine.dispatch``
spans over the model's ``delta_attn`` layers) over the summed device time of
the kernels the program names ``delta_decode*``. The family is lightning
attention's (``linear_attention.linear_decode``): one kernel body, the
channels' decays and ``beta`` two more operands, the name its own."""

from benchmark.kernels import delta_rule
from benchmark.readers.covered import inside
from benchmark.readers.program_spans import spans
from benchmark.readers.trace_kernel_ms import kernel_seconds

KERNEL = "delta_decode"


def read(ctx):
    trace, peak = ctx["trace"], ctx["peak"]
    found = inside(ctx, spans("engine.dispatch"))
    if not trace or peak is None or not found:
        return None
    secs = kernel_seconds(trace, KERNEL)
    model = ctx["cell"].config["model"]
    layers = (model.get("layer_types") or []).count("delta_attn")
    rows = sum(s.attrs.get("decode_rows", 0) for s in found)
    if not secs or not layers or not rows:
        return None
    head = model.get("head_dim_override") \
        or model["hidden_size"] // model["num_heads"]
    flops, nbytes = delta_rule.dispatches(rows, layers, model["num_heads"],
                                          head, head)
    by_flops = flops / peak["bf16_flops_per_s"]
    by_bytes = nbytes / peak["hbm_bytes_per_s"]
    print(f"[delta_roofline] bound by "
          f"{'flops' if by_flops >= by_bytes else 'bytes'}: {flops:.4g} "
          f"FLOPs, {nbytes:.4g} bytes over {len(found)} dispatches ({rows} "
          f"one-token rows, {layers} layers); kernels {1e3 * secs:.1f} ms",
          flush=True)
    return 100.0 * max(by_flops, by_bytes) / secs
