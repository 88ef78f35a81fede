"""The recurrence's decode kernel in an SSD (Mamba-2) layer against its
roofline: the least time the chip could take for the window's one-token rows
(the larger of FLOPs over peak FLOP/s and bytes over peak bytes/s,
``kernels/ssd.py``, from ``decode_rows`` of the ``engine.dispatch`` spans:
every layer of the model is such a layer) over the summed device time of the
kernels the program names ``linear_decode*``. The family is lightning
attention's: one kernel body, the decay an operand."""

from benchmark.kernels import ssd
from benchmark.readers.covered import inside
from benchmark.readers.program_spans import spans
from benchmark.readers.trace_kernel_ms import kernel_seconds

KERNEL = "linear_decode"


def read(ctx):
    trace, peak = ctx["trace"], ctx["peak"]
    found = inside(ctx, spans("engine.dispatch"))
    if not trace or peak is None or not found:
        return None
    secs = kernel_seconds(trace, KERNEL)
    model = ctx["cell"].config["model"]
    layers = (model.get("layer_types") or []).count("hybrid_ssm")
    rows = sum(s.attrs.get("decode_rows", 0) for s in found)
    if not secs or not layers or not rows:
        return None
    flops, nbytes = ssd.dispatches(
        rows, layers, model["ssm_heads"], model["ssm_groups"],
        model["ssm_state"], model["ssm_head_dim"])
    by_flops = flops / peak["bf16_flops_per_s"]
    by_bytes = nbytes / peak["hbm_bytes_per_s"]
    print(f"[ssd_roofline] bound by "
          f"{'flops' if by_flops >= by_bytes else 'bytes'}: {flops:.4g} "
          f"FLOPs, {nbytes:.4g} bytes over {len(found)} dispatches ({rows} "
          f"one-token rows, {layers} layers); kernels {1e3 * secs:.1f} ms",
          flush=True)
    return 100.0 * max(by_flops, by_bytes) / secs
