"""Operations and bytes the recurrence's decode kernel needs for a delta-rule
(KDA) layer: the decay a key channel's own, ``beta`` a head's.

A dispatch advances some sequences by one token each through every such
layer. Required work only: a live row's state (heads x dk x dv, float32) is
read once and written once a layer; its operands are read (q and k of every
head, the decay of every key channel, the value and ``beta`` of every head)
and its output written; a state element needs seven operations: the decay's
product, the read-out ``k S'`` before the write (a product and its sum), the
write ``k^T (beta (v - k S'))`` (a product and its sum) and the read-out ``q
S`` (a product and its sum). What the kernel moves besides (``beta`` spread
over a head's lanes, nothing for a padding row) is not required.
"""


def dispatches(rows, layers, heads, dk, dv, state_bytes=4, key_bytes=2,
               value_bytes=2, out_bytes=4):
    """(flops, bytes) over all layers for dispatches whose one-token rows
    number ``rows`` in all."""
    state = 2 * heads * dk * dv * state_bytes              # read and written
    acts = (2 * heads * dk * key_bytes                     # q and k in
            + heads * dk * 4                               # a decay a channel
            + heads * dv * value_bytes                     # v in
            + heads * 4                                    # beta a head
            + heads * dv * out_bytes)                      # o out
    flops = 7 * heads * dk * dv
    return layers * rows * flops, layers * rows * (state + acts)
