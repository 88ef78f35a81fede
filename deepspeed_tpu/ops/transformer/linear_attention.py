"""Lightning (linear) attention over per-sequence state slots.

A lightning layer keeps no growing cache: per head one matrix ``S`` (key
width x value width, float32) that every token decays and adds to::

    S_t = lambda_h * S_{t-1} + k_t^T v_t          o_t = q_t S_t

``lambda_h`` is a constant of the head (:func:`head_decay`), the same in every
layer and no parameter. The states of all lightning layers live in ONE array
``(layers, slots, heads, dk, dv)`` float32, a **slot** a sequence: slot 0 is
the trash slot that padding rows read and write (as block 0 of the KV pool
is), a live sequence holds one of the others from admission to its end. The
array rides the model's layer scan as a carry on a donated buffer and is
updated where it lies.

Nobody zeroes a slot: a row at position 0 of its sequence starts from a zero
state whatever the slot held (``fresh``), so a slot handed to the next
sequence, or to a preempted one that recomputes from its prompt, cannot leak
the last owner's state.

Two forms of the same recurrence:

- :func:`decode_rows`: rows of one token each, one sequence a row: the
  Pallas kernel :func:`linear_decode` where the paged programs take kernels
  (``paged_attention.kernels_wanted``), which reads and writes the state of
  the live rows alone, else the same arithmetic in XLA over all rows;
- :func:`chunk_tiles`: tiles of ``C`` consecutive tokens of one sequence (a
  prefill chunk's segment), the blocked form

      O   = ((Q K^T) * D) V + (Q * lambda^i) S_0
      S_n = lambda^n S_0 + sum_j lambda^(n-j) k_j^T v_j

  with ``D[i, j] = lambda^(i-j)`` for ``j <= i``. A tile may be valid only in
  its first ``n`` rows (the tail of a chunk); tiles are walked in row order,
  so two tiles of one sequence in one step see each other's state. Every
  power of ``lambda`` is computed as ``exp(-rate * distance)`` with a distance
  that is never negative: nothing overflows, and what underflows is zero.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...utils import tracing

_HI = jax.lax.Precision.HIGHEST

#: heads of one cell of :func:`linear_decode`: a state block of 8 x 128 x 128
#: float32 is 512 KiB, so the pipeline's two buffers each way take 2 MiB
DECODE_HEADS = 8


def head_decay_rates(n_heads: int) -> np.ndarray:
    """(n_heads,) float32 ``-log(lambda_h)``: the lightning-attention family's
    fixed slopes ``2^(-8 (h + 1) / n_heads)``."""
    h = np.arange(1, n_heads + 1, dtype=np.float64)
    return np.exp2(-8.0 * h / n_heads).astype(np.float32)


def init_state(layers: int, slots: int, heads: int, dk: int, dv: int):
    """The zeroed slot array ``(layers, 1 + slots, heads, dk, dv)`` float32;
    slot 0 is the trash slot."""
    return jnp.zeros((layers, 1 + slots, heads, dk, dv), jnp.float32)


def decode_rows(state, layer, slots, q, k, v, fresh):
    """One token a row. ``state``: the slot array; ``layer``: int32 scalar
    (traced or not); ``slots`` (R,) int32, 0 for a padding row; q, k (R, h,
    dk), v (R, h, dv), q already scaled; ``fresh`` (R,) bool: the row is its
    sequence's first token. Returns (o (R, h, dv) float32, new state)."""
    from .paged_attention import kernels_wanted

    if kernels_wanted() and q.shape[1] % DECODE_HEADS == 0:
        return linear_decode(state, layer, slots, q, k, v, fresh)
    rates = jnp.asarray(head_decay_rates(q.shape[1]))
    with jax.named_scope("linear_attn"):
        s = state[layer, slots]                                # (R, h, dk, dv)
        keep = jnp.where(fresh, 0.0, 1.0)[:, None] * jnp.exp(-rates)[None]
        s = s * keep[:, :, None, None] + (
            k.astype(jnp.float32)[..., :, None]
            * v.astype(jnp.float32)[..., None, :])
        o = jnp.einsum("rhk,rhkv->rhv", q.astype(jnp.float32), s,
                       precision=_HI)
        state = state.at[layer, slots].set(s)
    return o, state


def _decode_kernel(layer_ref, slots_ref, fresh_ref, q_ref, k_ref, v_ref,
                   s_ref, o_ref, s_out, *, heads, n_heads):
    """One (head block, row) cell: q_ref, k_ref (1, 1, dk, heads) hold the
    row's q and k as columns, v_ref (1, 1, heads, dv) its v as rows, s_ref /
    s_out (1, 1, heads, dk, dv) the state block of the row's slot."""
    c, r = pl.program_id(0), pl.program_id(1)
    live = slots_ref[r] > 0

    @pl.when(live)
    def _():
        started = fresh_ref[r] == 0
        for h in range(heads):
            # lambda_h as a row of lanes: exp(-2^(-8 (h + 1) / n_heads))
            head = (c * heads + h + 1).astype(jnp.float32)
            rate = jnp.exp(jnp.full((1, s_ref.shape[-1]), head, jnp.float32)
                           * (-8.0 * np.log(2.0) / n_heads))
            keep = jnp.where(started, jnp.exp(-rate), 0.0)
            new = (keep * s_ref[0, 0, h]
                   + k_ref[0, 0, :, h:h + 1] * v_ref[0, 0, h:h + 1, :])
            s_out[0, 0, h] = new
            o_ref[0, 0, h:h + 1, :] = jnp.sum(q_ref[0, 0, :, h:h + 1] * new,
                                              axis=0, keepdims=True)

    @pl.when(jnp.logical_not(live))
    def _():
        s_out[...] = s_ref[...]
        o_ref[...] = jnp.zeros_like(o_ref)


def linear_decode(state, layer, slots, q, k, v, fresh):
    """:func:`decode_rows` as a Pallas kernel, in place on the slot array
    (aliased to its result): a cell is ``DECODE_HEADS`` heads of one row, and
    the grid walks the rows inside the head blocks, so that the padding rows,
    which all name slot 0 and follow the live ones, fetch the trash slot's
    block once a head block and not once a row. A live row's state is read
    once and written once; no two live rows may name one slot."""
    from .paged_attention import _interpret

    R, H, dk = q.shape
    dv, hb = v.shape[-1], DECODE_HEADS
    cols = lambda a: a.astype(jnp.float32).reshape(  # noqa: E731
        R, H // hb, hb, -1).transpose(0, 1, 3, 2)          # (R, H/hb, d, hb)
    col = pl.BlockSpec((1, 1, dk, hb), lambda c, r, *_: (r, c, 0, 0))
    row = pl.BlockSpec((1, 1, hb, dv), lambda c, r, *_: (r, c, 0, 0))
    slot = pl.BlockSpec((1, 1, hb, dk, dv),
                        lambda c, r, layer, slots, _: (layer[0], slots[r], c,
                                                       0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,  # layer, slots, fresh
        grid=(H // hb, R),
        in_specs=[col, col, row, slot],
        out_specs=[row, slot],
    )
    with jax.named_scope("linear_attn"):
        o, state = tracing.pallas_call(
            functools.partial(_decode_kernel, heads=hb, n_heads=H),
            grid_spec=grid_spec,
            out_shape=[jax.ShapeDtypeStruct((R, H // hb, hb, dv), jnp.float32),
                       jax.ShapeDtypeStruct(state.shape, state.dtype)],
            input_output_aliases={6: 1},  # the slot array, scalars counted
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary")),
            interpret=_interpret(),
            name="linear_decode",
        )(jnp.asarray(layer, jnp.int32).reshape(1), slots.astype(jnp.int32),
          fresh.astype(jnp.int32), cols(q), cols(k),
          v.astype(jnp.float32).reshape(R, H // hb, hb, dv), state)
    return o.reshape(R, H, dv), state


def _tile_decays(n_heads: int, tile: int):
    """The constants of a tile: ``D`` (h, C, C) and the row decays
    ``lambda^i`` (h, C), ``i`` counted from 1."""
    rates = head_decay_rates(n_heads).astype(np.float64)[:, None, None]
    i = np.arange(tile)
    dist = i[:, None] - i[None, :]
    d = np.where(dist >= 0, np.exp(-rates * np.maximum(dist, 0)), 0.0)
    rows = np.exp(-rates[:, :, 0] * (i + 1)[None])
    return d.astype(np.float32), rows.astype(np.float32)


def chunk_tiles(state, layer, slots, counts, q, k, v, fresh):
    """Tiles of ``C`` consecutive tokens. ``slots`` (N,) int32 the slot of
    each tile's sequence (0: an empty tile); ``counts`` (N,) int32 the valid
    rows of each tile, a prefix of it; q, k (N, C, h, dk), v (N, C, h, dv), q
    already scaled; ``fresh`` (N,) bool: the tile starts its sequence. Returns
    (o (N, C, h, dv) float32, new state). Rows past a tile's count give
    garbage that nothing reads and add nothing to the state."""
    N, C, H, _ = q.shape
    rates = jnp.asarray(head_decay_rates(H))
    d_const, row_decay = (jnp.asarray(a) for a in _tile_decays(H, C))
    idx = jnp.arange(C)

    def tile(state, args):
        slot, n, q, k, v, fresh = args
        with jax.named_scope("linear_attn"):
            q, k, v = (a.astype(jnp.float32) for a in (q, k, v))
            s0 = jnp.where(fresh, 0.0, 1.0) * state[layer, slot]  # (h, dk, dv)
            a = jnp.einsum("ihk,jhk->hij", q, k) * d_const
            o = (jnp.einsum("hij,jhv->ihv", a, v)
                 + jnp.einsum("ihk,hkv->ihv", q, s0, precision=_HI)
                 * row_decay.T[:, :, None])
            # a row's part in the state the tile leaves: lambda^(n-1-j) for
            # the valid rows j < n, nothing for the rest
            w = jnp.where(idx[None] < n, jnp.exp(
                -rates[:, None] * jnp.maximum(n - 1 - idx, 0)[None]), 0.0)
            s = (jnp.exp(-rates * n)[:, None, None] * s0
                 + jnp.einsum("jhk,jhv->hkv", k * w.T[:, :, None], v,
                              precision=_HI))
            state = state.at[layer, slot].set(s)
        return state, o

    state, o = jax.lax.scan(tile, state, (slots, counts, q, k, v, fresh))
    return o, state
