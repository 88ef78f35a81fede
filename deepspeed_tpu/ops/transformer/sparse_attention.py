"""Block-sparse attention over the paged pool (MiniCPM4 / InfLLM-v2 style):
each query attends a few pool blocks of its context, chosen by scores against
**compressed keys**.

A compressed key is the mean of ``kernel`` consecutive keys, one every
``stride`` tokens: ``c_j = mean(k[stride*j : stride*j + kernel])``, defined
once its last token is in the pool. For a query with context ``n`` (its own
token included) and each KV head, ``s_j`` is the softmax over the defined
``j`` of ``q_h . c_j * scale`` summed over the head's query heads; a block's
score is the largest ``s_j`` of the compressed keys that overlap it; the first
``init_blocks`` blocks and the ``window_blocks`` ending at the query's own are
always taken; the ``topk`` best blocks in all are attended (ties go to the
lower block), every query head of the KV head sharing the choice. A context of
at most ``dense_len`` tokens is attended whole. The layer has no positional
term, so attention over the chosen blocks, laid side by side in ascending
order, is plain attention: :func:`compact_tables` hands the decode kernel that
exists (``paged_attention.paged_decode``) a short table and a length.

The compressed keys are cached, never recomputed over a context: one array
``(layers, 1 + slots, max_keys, kvh * hd)`` beside the lightning layers' slot
array, a sequence's keys in its slot (slot 0: trash), in the pool's dtype; a
key of all kv heads is one row of whole 128-lane tiles, so a write sets a row
and the array keeps the layout it was given (with the kv heads a dimension of
their own XLA re-laid the whole array out around each step's scatter).
They lie by sequence and not by pool block because the selector reads all of
a sequence's keys at every step: by slot that is one contiguous read a row,
by block table it would be a gather of as many pieces as the context has
blocks. A slot's stale keys are never read: only ``j`` below the row's own
count are scored.
"""

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ...utils import tracing
from . import paged_attention as pa

# a sparse layer's selector: compressed keys, scores, top-k, compacted tables
tracing.layer_scopes("sparse_select")

NEG = -1e30


class SparseSpec(NamedTuple):
    """The selector's sizes (static). ``block`` is the pool's block size."""
    block: int
    kernel: int
    stride: int
    window_blocks: int
    init_blocks: int
    topk: int
    dense_len: int

    def check(self):
        if self.kernel % self.stride or self.block % self.stride:
            raise ValueError(f"sparse attention: kernel {self.kernel} and "
                             f"block {self.block} must be multiples of the "
                             f"stride {self.stride}")
        if self.dense_len < self.topk * self.block:
            raise ValueError("sparse attention: dense_len must cover topk "
                             "blocks, so that a sparse row has more blocks "
                             "than it picks")
        if self.init_blocks + self.window_blocks > self.topk:
            raise ValueError("sparse attention: more forced blocks than topk")
        return self

    def max_keys(self, max_seq_len: int) -> int:
        """Compressed keys of a context of ``max_seq_len`` tokens, up to a
        whole number of 8-row tiles."""
        n = max(1, (max_seq_len - self.kernel) // self.stride + 1)
        return -(-n // 8) * 8

    @property
    def table_width(self) -> int:
        """Entries of a compacted table: the chosen blocks of a sparse row,
        or every block of a row still under ``dense_len``."""
        return max(self.topk, -(-self.dense_len // self.block))


def init_keys(layers, slots, kv_heads, max_keys, head_dim, dtype):
    """The zeroed compressed-key cache; slot 0 is the trash slot."""
    return jnp.zeros((layers, 1 + slots, max_keys, kv_heads * head_dim), dtype)


# ----------------------------------------------------------------------
# the compressed-key cache
# ----------------------------------------------------------------------
def write_keys(ck, pool, pool_layer, ck_layer, tables, slots, first, counts,
               spec: SparseSpec, span: int):
    """Write the compressed keys that this step's tokens complete. One entry
    a run of consecutive tokens of one sequence, already in the pool: its
    block table ``tables`` (N, MAXB), its slot ``slots`` (N,), the position
    ``first`` (N,) of its first token and its length ``counts`` (N,), at most
    ``span`` (static; 0 = nothing). A key is written by the run that holds its
    last token, from the pool's own (rounded) keys, so a context's keys are
    the same however it was cut into steps."""
    BS, K, s = spec.block, spec.kernel, spec.stride
    hd = ck.shape[-1] // pool.shape[1]
    G = (span + K - 2) // BS + 2            # pool blocks a run's windows touch
    g0 = jnp.maximum(first - (K - 1), 0) // BS                        # (N,)
    cols = jnp.clip(g0[:, None] + jnp.arange(G)[None], 0,
                    tables.shape[1] - 1)
    blocks = jnp.take_along_axis(tables, cols, axis=1)               # (N, G)
    k = pool[pool_layer, :, blocks][..., :hd].astype(jnp.float32)
    N, _, kvh = k.shape[:3]                                # (N, G, kvh, BS, hd)
    strips = k.transpose(0, 2, 1, 3, 4).reshape(N, kvh, G * BS // s, s, hd)
    strips = strips.sum(axis=3)                              # (N, kvh, n_s, hd)
    nw = (G * BS - K) // s + 1
    means = sum(strips[:, :, r:r + nw] for r in range(K // s)) / K
    j = g0[:, None] * (BS // s) + jnp.arange(nw)[None]              # (N, nw)
    last = j * s + K - 1                             # the key's last token
    done = (last >= first[:, None]) & (last < (first + counts)[:, None]) \
        & (j < ck.shape[2])
    slot = jnp.where(done, slots[:, None], 0)
    j = jnp.where(done, j, 0)
    return ck.at[ck_layer, slot, j].set(
        means.transpose(0, 2, 1, 3).reshape(N, nw, kvh * hd).astype(ck.dtype))


# ----------------------------------------------------------------------
# the selector
# ----------------------------------------------------------------------
def block_scores(q, keys, n, spec: SparseSpec, n_blocks: int, scale):
    """(R, kvh, n_blocks) float32 scores of each row's pool blocks: a forced
    block 2, a block past the row's own -1, any other the largest ``s_j`` of
    the compressed keys that overlap it (the mean over the group's heads, so
    at most 1). q (R, nh, hd); keys (R, J, kvh * hd) each row's sequence's
    compressed keys as the cache holds them, or (J, kvh * hd) where all rows
    are of one sequence; n (R,) int32 the context each row attends (its own
    token included)."""
    R, nh, hd = q.shape
    J, kvh = keys.shape[-2], keys.shape[-1] // hd
    g = nh // kvh
    BS, K, s = spec.block, spec.kernel, spec.stride
    logits = jnp.einsum(
        "rhgd,rjhd->rhgj" if keys.ndim == 3 else "rhgd,jhd->rhgj",
        q.reshape(R, kvh, g, hd), keys.reshape(keys.shape[:-1] + (kvh, hd)),
        preferred_element_type=jnp.float32) * scale
    defined = (jnp.arange(J) * s + K)[None] <= n[:, None]            # (R, J)
    logits = jnp.where(defined[:, None, None], logits, NEG)
    p = jax.nn.softmax(logits, axis=-1)
    sj = jnp.where(defined[:, None], p.sum(axis=2) / g, -1.0)     # (R, kvh, J)
    # block b is overlapped by the keys that start in it and by the
    # (kernel - 1) // stride before them
    per, back = BS // s, (K - 1) // s
    need = per * n_blocks
    sj = jnp.pad(sj, ((0, 0), (0, 0), (back, max(0, need - J))),
                 constant_values=-1.0)
    cols = per * np.arange(n_blocks)[:, None] + np.arange(per + back)[None]
    score = sj[:, :, cols].max(axis=-1)                    # (R, kvh, n_blocks)
    own = ((n - 1) // BS)[:, None, None]
    b = jnp.arange(n_blocks)[None, None]
    forced = (b < spec.init_blocks) | ((b > own - spec.window_blocks)
                                       & (b <= own))
    return jnp.where(b > own, -1.0, jnp.where(forced, 2.0, score))


def choose(q, keys, n, spec: SparseSpec, n_blocks: int, scale):
    """The blocks each (row, kv head) attends: (chosen (R, kvh, n_blocks)
    bool, count (R, kvh) int32); arguments as :func:`block_scores`. A block
    is chosen if fewer than ``topk`` blocks beat it, a block beating another
    by a higher score or, at equal scores, a lower index: the ``topk`` best,
    exactly, by comparisons alone (a sort of every row's scores cost the chip
    a third of a millisecond a tile). A row with ``n <= dense_len`` attends
    every block of its context."""
    score = block_scores(q, keys, n, spec, n_blocks, scale)
    b = jnp.arange(n_blocks)
    a, c = score[..., :, None], score[..., None, :]          # c beats a?
    beaten = jnp.sum((c > a) | ((c == a) & (b[None, :] < b[:, None])),
                     axis=-1, dtype=jnp.int32)
    blocks = (n + spec.block - 1) // spec.block
    own = b[None, None] < blocks[:, None, None]
    chosen = jnp.where((n > spec.dense_len)[:, None, None],
                       beaten < spec.topk, True) & own
    return chosen, jnp.sum(chosen, axis=-1, dtype=jnp.int32)


def compact_tables(tables, chosen, count, n, spec: SparseSpec, n_pool_blocks):
    """The short tables and lengths ``paged_decode`` attends, a (row, kv head)
    pair a row of its own against the pool viewed with its kv heads folded
    into its blocks (``pool.reshape(L, 1, kvh * NB, BS, row)``: head ``h``'s
    block ``b`` is block ``h * NB + b`` of the view): the chosen blocks'
    pool ids side by side in ascending order of their place in the context.
    tables (R, MAXB); chosen, count from :func:`choose`; n (R,). Returns
    (tables (R * kvh, W) int32, lens (R * kvh,) int32); a row with ``n`` 0 is
    dead (``lens`` 0)."""
    R, kvh, MAXB = chosen.shape
    W, BS = spec.table_width, spec.block
    # the w-th chosen block of a row is the one with w chosen before it
    place = jnp.cumsum(chosen, axis=-1, dtype=jnp.int32) - 1
    hit = chosen[:, :, None, :] & (place[:, :, None, :]
                                   == jnp.arange(W)[None, None, :, None])
    head0 = (jnp.arange(kvh, dtype=jnp.int32) * n_pool_blocks)[None, :, None]
    short = jnp.sum(jnp.where(hit, (tables[:, None] + head0)[:, :, None, :], 0),
                    axis=-1)
    # every chosen block is full but the last, which is the row's own
    lens = jnp.where(n[:, None] > 0,
                     (count - 1) * BS + ((n - 1) % BS + 1)[:, None], 0)
    return short.reshape(R * kvh, W), lens.reshape(R * kvh).astype(jnp.int32)


def decode_rows(q, pool, layer, tables, keys, n, spec: SparseSpec, scale):
    """Attention of one-token rows over their chosen blocks. q (R, nh, hd);
    pool the stacked pool; tables (R, MAXB); keys (R, J, kvh * hd); n (R,)
    the contexts (0: a dead row). Returns (o (R, nh, hd), (chosen, context)
    block counts summed over live rows and kv heads, int32 (2,))."""
    R, nh, hd = q.shape
    L, kvh, NB, BS, row = pool.shape
    g = nh // kvh
    with jax.named_scope("sparse_select"):
        chosen, count = choose(q, keys, n, spec, tables.shape[1], scale)
        short, lens = compact_tables(tables, chosen, count, n, spec, NB)
        stats = jnp.stack([
            jnp.sum(jnp.where((n > 0)[:, None], count, 0)),
            kvh * jnp.sum((n + BS - 1) // BS)]).astype(jnp.int32)
    view = pool.reshape(L, 1, kvh * NB, BS, row)
    qv = q.reshape(R * kvh, g, hd)
    with jax.named_scope("paged_attn"):
        if pa.kernels_wanted():
            o = pa.paged_decode(qv, view, layer, short, lens, scale=scale)
        else:
            gk, gv = pa.gather_context(view, layer, short)  # (R*kvh, W*BS, 1, hd)
            mask = jnp.arange(gk.shape[1])[None] < lens[:, None]
            logit = jnp.einsum("rgd,rtd->rgt", qv.astype(jnp.float32),
                               gk[:, :, 0].astype(jnp.float32)) * scale
            p = jax.nn.softmax(jnp.where(mask[:, None], logit, NEG), axis=-1)
            p = jnp.where(lens[:, None, None] > 0, p, 0.0)
            o = jnp.einsum("rgt,rtd->rgd", p,
                           gv[:, :, 0].astype(jnp.float32)).astype(q.dtype)
    return o.reshape(R, nh, hd), stats


#: context widths a tile's attention is compiled for, as shares of the
#: longest context: a tile takes the narrowest that covers its last token
#: (the paged pool's own, ``paged_attention.attend_tiles``)
TILE_WIDTHS = pa.TILE_WIDTHS


def tile_rows(q, pool, layer, tables, keys, first, spec: SparseSpec, scale):
    """Attention of tiles of consecutive tokens of one sequence (a prefill
    chunk's segment), each token by its own choice of blocks, computed as
    masked dense attention over the head of the sequence's gathered context
    that reaches the tile (one of :data:`TILE_WIDTHS`): the arithmetic of the
    unchosen blocks is not saved here, only masked. q (N, C, nh, hd); tables
    (N, MAXB) a tile's sequence's table (all zero: an empty tile); keys (N,
    J, kvh * hd); first (N,) the position of each tile's first token. Returns
    o (N, C, nh, hd)."""
    N, C, nh, hd = q.shape
    kvh, BS = pool.shape[1], pool.shape[3]
    g, MAXB = nh // kvh, tables.shape[1]
    widths = sorted({max(1, math.ceil(MAXB * w)) for w in TILE_WIDTHS})

    def attend(width, q, table, chosen, pos):
        """Over the context's first ``width`` blocks."""
        gk, gv = pa.gather_context(pool, layer, table[None, :width])
        gk, gv = gk[0], gv[0]                             # (width*BS, kvh, hd)
        kpos = jnp.arange(width * BS)
        mask = (kpos[None] <= pos[:, None])[:, None] & jnp.repeat(
            chosen[..., :width], BS, axis=-1)                  # (C, kvh, T)
        logit = jnp.einsum("chgd,thd->hgct", q.reshape(C, kvh, g, hd), gk,
                           preferred_element_type=jnp.float32) * scale
        logit = jnp.where(mask.transpose(1, 0, 2)[:, None], logit, NEG)
        p = jax.nn.softmax(logit, axis=-1).astype(gv.dtype)
        o = jnp.einsum("hgct,thd->chgd", p, gv,
                       preferred_element_type=jnp.float32)
        return o.reshape(C, nh, hd).astype(q.dtype)

    def tile(args):
        q, table, keys, first = args
        pos = first + jnp.arange(C)
        n = jnp.where(table[0] > 0, pos + 1, 0)
        with jax.named_scope("sparse_select"):
            chosen, _ = choose(q, keys, n, spec, MAXB, scale)
        with jax.named_scope("paged_attn"):
            need = (first + C + BS - 1) // BS       # blocks the tile reaches
            branch = jnp.sum(need > jnp.asarray(widths[:-1]), dtype=jnp.int32) \
                if len(widths) > 1 else 0
            return jax.lax.switch(
                branch, [functools.partial(attend, w) for w in widths],
                q, table, chosen, pos)

    return jax.lax.map(tile, (q, tables, keys, first))
