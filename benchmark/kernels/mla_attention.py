"""Operations and bytes latent (MLA) attention over the paged latent pool
needs, in the absorbed form.

A dispatch advances some sequences; each row (one token) attends over its
sequence's cached latent ``[c_kv | k_rope]``, one row of ``rank + rope``
values a token a layer, shared by every head. Required work only: a perfect
kernel reads each sequence's latent once per dispatch however many rows the
sequence has in it (``ctx_tokens``: summed context after the step), reads each
row's absorbed queries (``rank + rope`` a head) and writes its weighted latent
(``rank`` a head), and computes for every head one score (``rank + rope``
multiply-adds) and one weighted sum (``rank``) per (row, context position)
pair (``ctx_tokens_by_row``). What the program streams again for every tile of
a prompt chunk, and the lanes the pool pads a row with, are not required, so
the roofline share stays at or below 100%.
"""


def dispatches(ctx_tokens, ctx_tokens_by_row, rows, layers, heads, rank, rope,
               bytes_per_el=2):
    """(flops, bytes) over all layers for dispatches with these totals."""
    latent = ctx_tokens * (rank + rope) * bytes_per_el
    q_out = rows * heads * (2 * rank + rope) * bytes_per_el
    flops = 2 * ctx_tokens_by_row * heads * ((rank + rope) + rank)
    return layers * flops, layers * (latent + q_out)
