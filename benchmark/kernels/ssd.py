"""Operations and bytes the recurrence's decode kernel needs for an SSD
(Mamba-2) layer: the decay a row a head, keys and queries a group's.

A dispatch advances some sequences by one token each through every layer.
Required work only: a live row's state (heads x dk x dv, float32) is read once
and written once a layer; its operands are read (a group's B and C, the
value ``dt x`` of every head, a decay a head) and its output written; a state
element needs five operations: the decay's product, the outer product ``B^T
(dt x)`` and its sum, the read-out ``C S`` and its sum. What the kernel moves
besides (the decay spread over a head's lanes, nothing for a padding row) is
not required.
"""


def dispatches(rows, layers, heads, groups, dk, dv, state_bytes=4,
               key_bytes=2, value_bytes=4):
    """(flops, bytes) over all layers for dispatches whose one-token rows
    number ``rows`` in all."""
    state = 2 * heads * dk * dv * state_bytes              # read and written
    acts = (2 * groups * dk * key_bytes                    # B and C in
            + 2 * heads * dv * value_bytes                 # dt x in, y out
            + heads * 4)                                   # a decay a head
    flops = 5 * heads * dk * dv
    return layers * rows * flops, layers * rows * (state + acts)
