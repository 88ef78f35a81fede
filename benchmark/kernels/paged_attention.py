"""Operations and bytes the paged decode-attention kernel needs.

A dispatch advances some sequences; each row attends over its sequence's
context. Required work only: a perfect kernel reads each sequence's keys and
values once per dispatch however many rows the sequence has in it
(``ctx_tokens``: summed context after the step), reads each row's query and
writes its output, and computes one score and one value contraction per
(row, context position) pair (``ctx_tokens_by_row``). What today's
one-row-per-token program streams again for every row of a prompt chunk is
not required, so the roofline share stays at or below 100%.
"""


def dispatches(ctx_tokens, ctx_tokens_by_row, rows, layers, heads, kv_heads,
               head_dim, bytes_per_el=2):
    """(flops, bytes) over all layers for dispatches with these totals."""
    kv = 2 * ctx_tokens * kv_heads * head_dim * bytes_per_el     # k and v
    q_out = 2 * rows * heads * head_dim * bytes_per_el
    flops = 2 * 2 * ctx_tokens_by_row * heads * head_dim         # q.k and p.v
    return layers * flops, layers * (kv + q_out)
