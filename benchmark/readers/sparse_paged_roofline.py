"""The paged decode-attention kernel of a block-sparse layer against its
roofline: a row attends the blocks its selector chose, not its context, so
the required work is counted from ``sel_blocks`` of the ``engine.dispatch``
spans (chosen blocks summed over the one-token rows, the kv heads and the
sparse layers), with ``kernels/paged_attention.py`` as it is: each (row, kv
head) is a sequence of its own there, of one kv head and the group's query
heads. A row's own block, the last it attends, is partly filled and is left
out: the count errs low, the share with it. Over the summed device time of
the kernels the program names ``paged_decode*``."""

from benchmark.kernels import paged_attention
from benchmark.readers.covered import inside
from benchmark.readers.program_spans import spans
from benchmark.readers.trace_kernel_ms import kernel_seconds

KERNEL = "paged_decode"


def read(ctx):
    trace, peak = ctx["trace"], ctx["peak"]
    found = inside(ctx, spans("engine.dispatch"))
    if not trace or peak is None or not found:
        return None
    secs = kernel_seconds(trace, KERNEL)
    model, engine = ctx["cell"].config["model"], ctx["cell"].traffic["engine"]
    types = model.get("layer_types") or []
    if not secs or "sparse_attn" not in types:
        return None
    layers = types.count("sparse_attn")
    heads = model["num_heads"]
    kv_heads = model.get("num_kv_heads") or heads
    chosen = sum(s.attrs.get("sel_blocks", 0) for s in found)
    cells = layers * kv_heads * sum(s.attrs.get("decode_rows", 0)
                                    for s in found)
    tokens = max(0, chosen - cells) * engine["block_size"]
    if not tokens:
        return None
    flops, nbytes = paged_attention.dispatches(
        tokens, tokens, cells, 1, heads // kv_heads, 1,
        model["hidden_size"] // heads)
    by_flops = flops / peak["bf16_flops_per_s"]
    by_bytes = nbytes / peak["hbm_bytes_per_s"]
    print(f"[sparse_paged_roofline] bound by "
          f"{'flops' if by_flops >= by_bytes else 'bytes'}: {flops:.4g} "
          f"FLOPs, {nbytes:.4g} bytes over {len(found)} dispatches "
          f"(sel_blocks {chosen}, (row, kv head, layer) cells {cells}); "
          f"kernels {1e3 * secs:.1f} ms", flush=True)
    return 100.0 * max(by_flops, by_bytes) / secs
