"""Operations and bytes the lightning-attention decode kernel needs.

A dispatch advances some sequences by one token each through every linear
layer. Required work only: a live row's state (heads x dk x dv, float32) is
read once and written once a layer; its q, k, v are read and its output
written; per head the decay, the outer product ``k^T v``, the sum and the
read-out ``q S`` are 4 x dk x dv operations. What the kernel moves for the
padding rows (the trash slot, once a head block) is not required.
"""


def dispatches(rows, layers, heads, dk, dv, state_bytes=4, act_bytes=2):
    """(flops, bytes) over all linear layers for dispatches whose one-token
    rows number ``rows`` in all."""
    state = 2 * heads * dk * dv * state_bytes              # read and written
    acts = heads * (2 * dk + 2 * dv) * act_bytes           # q, k, v in, o out
    flops = 4 * heads * dk * dv
    return layers * rows * flops, layers * rows * (state + acts)
