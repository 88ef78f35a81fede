"""Operations and bytes the flash-attention kernels of one training step need.

Causal attention over S positions: the score and value contractions touch
S*(S+1)/2 query-key pairs per head, 2 multiply-adds each of ``head_dim``. The
backward pass needs the same two contractions' gradients (dq, dk, dv, dp: four
contractions) and is given the saved output and log-sum-exp, so recomputing
scores in the backward is NOT counted: required work only, which keeps the
roofline share at or below 100%.
"""


def train_step(batch, seq, heads, head_dim, layers, bytes_per_el=2):
    """(flops, bytes) of forward + backward attention for one optimizer step
    on one chip (``batch`` sequences of ``seq`` tokens there)."""
    pairs = seq * (seq + 1) / 2
    fwd = 2 * 2 * pairs * head_dim                 # q.k and p.v
    bwd = 4 * 2 * pairs * head_dim                 # dp, dq, dk, dv
    flops = batch * heads * layers * (fwd + bwd)
    qkvo = 4 * seq * head_dim * bytes_per_el       # read q, k, v; write o
    # backward reads q, k, v, o, do and writes dq, dk, dv; lse is small
    bwd_bytes = 8 * seq * head_dim * bytes_per_el
    return flops, batch * heads * layers * (qkvo + bwd_bytes)
