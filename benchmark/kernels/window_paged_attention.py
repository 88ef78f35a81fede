"""Operations and bytes the paged decode-attention kernel needs in a model
whose layers are of two classes: full layers, whose rows attend their whole
context, and window layers, whose rows attend the tokens between their bound
and their length (at most the window).

Required work only, as ``kernels/paged_attention.py`` counts it: a perfect
kernel reads, a live row a layer, the keys and values between the row's bound
and its length once, reads the row's query and writes its output, and
computes one score and one value contraction per (row, attended position)
pair. Tokens are counted, not blocks: the partly attended blocks at both
ends of a window are streamed whole by today's kernel and are not required.
"""


def work(full_tokens, window_tokens, rows, full_layers, window_layers, heads,
         kv_heads, head_dim, bytes_per_el=2):
    """(flops, bytes) of the kernel's calls over ``rows`` one-token rows:
    ``full_tokens`` the rows' contexts summed (what a full layer attends),
    ``window_tokens`` the same with each row's context cut to the window
    (what a window layer attends)."""
    attended = full_layers * full_tokens + window_layers * window_tokens
    layers = full_layers + window_layers
    kv = 2 * attended * kv_heads * head_dim * bytes_per_el       # k and v
    q_out = 2 * layers * rows * heads * head_dim * bytes_per_el
    flops = 2 * 2 * attended * heads * head_dim                  # q.k and p.v
    return flops, kv + q_out


def window_tokens(ctx_tokens, rows, window):
    """The rows' contexts cut to the window and summed, from the totals
    alone: ``window`` a row where every row is at least that long (and the
    whole context where the sum is shorter); rows shorter than the window
    beside longer ones would read high."""
    return min(ctx_tokens, window * rows)
