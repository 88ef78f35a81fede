"""Pallas flash attention for TPU (forward + one fused backward).

TPU-native replacement for the reference's fused attention CUDA kernels
(``csrc/transformer/softmax_kernels.cu`` training softmax,
``csrc/transformer/inference/csrc/softmax.cu`` and the blocked flash kernels in
``deepspeed/inference/v2/kernels/ragged_ops/blocked_flash``). Flash-attention-2
style: online softmax over the keys, a log-sum-exp residual, and ONE backward
kernel that produces dk/dv for its KV block and accumulates dq for the whole
sequence in a float32 VMEM scratch, written once in the model's dtype.

Layout: the kernels address q, k, v, o, ``do`` and write o, dq, dk, dv where
the model keeps them, ``(B, S, heads * head_dim)`` (a free reshape of the
``(B, S, heads, head_dim)`` the model hands ``attention()``): no transpose on
either side of a kernel. A grid cell holds a whole lane tile of heads,
``max(head_dim, 128)`` lanes wide: two heads of 64, one of 128, one of 256. A
pair of 64 is walked by the innermost grid axis, which revisits the tile: the
other head's lanes are zeroed in ONE operand of each product (the contraction
then runs over 128 lanes at the MXU cost a padded 64 has) and a product that
comes out 128 lanes wide keeps its own head's half with a select. GQA follows
the same form: the K/V tile and half are those of ``(q head) // groups``, a q
head whose K/V sits in the other half is lined up with one lane roll a cell,
and dk/dv come out a q head and are summed over the group by the caller.

Walk: a grid cell's block (up to ``BLOCK`` positions: a whole training
sequence) is taken a chunk of queries (``FWD_CHUNK``; backward: of keys,
``BWD_CHUNK``) at a time in straight code, because the chip overlaps one
chunk's vector work with the next one's products there and not across the
iterations of a loop. Under a causal mask a chunk meets only the keys up to
its own (backward: the queries from its own on), and the chunk-sized corner
on the diagonal is walked as a staircase of ``LANES`` queries a strip
(``_staircase``): a strip's products stop at the diagonal's tile, the only one
masked, so of what lies above the diagonal nothing is multiplied but the upper
halves of those tiles, and the chunk keeps its one softmax over all its
pieces. Blocks before (after) the own one, which exist when a sequence is
longer than ``BLOCK``, take a loop with no mask.
``scale`` goes onto the q (backward: k) tile once where that is exact (a power
of two: head 64 and 256) and onto the float32 scores otherwise. The backward
works on transposed scores (keys, queries), so lse and delta are lane-dense
rows, delta never leaves VMEM, and only dq contracts over the sublanes.

Gives way to the XLA path in ``attention.py`` for bias, softcap and q_offset
(cache decode) with ``UnsupportedFeature``, and refuses shapes it cannot tile
or fit with ``UnsupportedShape``, which ``attention()`` logs.
"""

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from jax.sharding import PartitionSpec as P

from ...comm.topology import MODEL_AXIS, SEQ_AXIS, ZERO_AXES
from ...utils import tracing
from ..pallas_utils import open_mesh_axes
from .attention import UnsupportedFeature, UnsupportedShape, register_impl

NEG_INF = -1e30
LANES = 128
BLOCK = 2048        # positions a grid cell: a whole training sequence
FWD_CHUNK = 512     # queries of them a straight-line step of the forward
BWD_CHUNK = 256     # keys of them a step of the backward (PERF.md 5)
VMEM = 16 * 1024 * 1024   # what the compiler gives a kernel unasked

_NT = (((1,), (1,)), ((), ()))   # a @ b^T: contract the lanes of both
_NN = (((1,), (0,)), ((), ()))   # a @ b
_TN = (((0,), (0,)), ((), ()))   # a^T @ b: contract the sublanes of both


def _interpret() -> bool:
    from ..pallas_utils import pallas_interpret

    return pallas_interpret()


def _dot(a, b, dims):
    # operands stay in their storage dtype (bf16): the MXU multiplies bf16 at
    # full rate and accumulates in float32
    return jax.lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def _exact(scale) -> bool:
    """Is multiplying by ``scale`` exact in any float dtype (a power of two)?"""
    return math.frexp(scale)[0] == 0.5


def _half(shape, hd, h):
    """The lanes of head ``h`` of a tile's ``shape[-1] // hd`` heads."""
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, len(shape) - 1)
    return (lane >= h * hd) & (lane < (h + 1) * hd)


def _other_half(x, hd):
    """``x`` with the two heads of its 128 lanes swapped (Mosaic rotates
    32-bit lanes only)."""
    return pltpu.roll(x.astype(jnp.float32), hd, 1).astype(x.dtype)


def _as_row(col):
    """A (n, 1) float32 column as a lane-dense (1, n) row."""
    return jnp.transpose(jnp.broadcast_to(col, (col.shape[0], LANES)))[:1]


def _row(rows, h):
    """Row ``h`` of the one or two (a row a head of the tile) of ``rows``."""
    return rows if rows.shape[0] == 1 else jnp.where(h == 0, rows[:1], rows[1:])


def _tile_width(heads, hd):
    """Lanes a grid cell reads: a whole lane tile of heads, or the one head
    there is."""
    return max(hd, min(LANES, heads * hd))


def _fits(block, sq, skv, width, item):
    """Does a cell of ``block`` positions fit the VMEM the compiler gives a
    kernel unasked? The kernels ask for no more: a ``vmem_limit_bytes`` of 64
    MiB crashed XLA's memory-space repacker on the four-chip step (PERF.md 6).
    Forward: the full-length K and V windows and the q and o blocks, double-
    buffered, and a chunk's scores (float32 s and p, and p in a narrower
    dtype); backward: the full-length q, o, do and dq windows and dq's float32
    accumulator, the k, v, dk, dv blocks and their accumulators, and a chunk's
    scores. The scores' factors and the 12 KiB a lane are fitted to what the
    TPU compiler takes and refuses over head sizes, dtypes, sequences and
    blocks (114 compiles at batch 4, PERF.md 7)."""
    narrow = item if item < 4 else 0
    fwd = ((4 * skv + 4 * block) * width * item
           + min(FWD_CHUNK, block) * block * (8 + narrow))
    bwd = (sq * width * (8 * item + 4) + block * width * (8 * item + 8)
           + min(BWD_CHUNK, block) * block * 13 // 2)
    return max(fwd, bwd) + 12 * 1024 * width <= VMEM


def _kv_head(t, h, per_tile, g):
    """(tile, half) of the K/V head that q head ``h`` of q tile ``t`` reads."""
    kv = (t * per_tile + h) // g
    return kv // per_tile, kv % per_tile


def _staircase(chunk):
    """A chunk's causal corner (``chunk`` queries by ``chunk`` keys from one
    position on) as strips of ``LANES`` queries: rectangles (q0, q1, k0, k1)
    of it, a strip's queries against the keys up to theirs. Tile for tile of
    ``LANES`` they hold the diagonal and what lies under it exactly once and
    nothing above it; a strip meets the diagonal in ONE tile, its last, the
    only one to mask, and a chunk of ``LANES`` is that tile alone. Cut by
    queries for both kernels: the forward's strips are then the row bands of
    its chunk, each with a softmax chain of its own, and the backward's keep
    all their keys' rows but for the first strips'. Cut by keys, both kernels
    lost on the chip (PERF.md 5, PR 63)."""
    return [(a, a + LANES, 0, a + LANES) for a in range(0, chunk, LANES)]


def _pairs(sq, skv, block, chunk, causal):
    """What a kernel's bind record says of its walk (``tracing.pallas_call``):
    the scores one head's grid cells multiply on the MXU over a whole call,
    walking chunks of ``chunk``, and those attention needs (S (S + 1) / 2
    under a causal mask)."""
    computed = needed = sq * skv
    if causal:
        stairs = sum((q1 - q0) * (k1 - k0)
                     for q0, q1, k0, k1 in _staircase(chunk))
        own = sum(stairs + chunk * c for c in range(0, block, chunk))
        nq, nk = sq // block, skv // block
        off = sum(min(i, nk) for i in range(nq))    # whole blocks off the diagonal
        short = min(sq, skv)
        computed = min(nq, nk) * own + off * block * block
        needed = short * (short + 1) // 2 + (sq - short) * skv
    return {"pairs_computed": computed, "pairs_causal": needed}


def _causal(s, q0, k0, queries):
    """Scores of the queries from ``q0`` and the keys from ``k0`` of a corner
    (each as many as ``s`` has) with the block the diagonal crosses, the
    positions both ranges hold, masked above the diagonal: a strip's one
    tile. ``queries`` is the dimension the queries run along: 0 in the
    forward, 1 on the backward's transposed scores."""
    nq, nk = s.shape[queries], s.shape[1 - queries]
    lo, hi = max(q0, k0), min(q0 + nq, k0 + nk)
    at = (lo - q0, lo - k0)                      # the block's query, key in s
    r, c = at if queries == 0 else at[::-1]
    n = hi - lo
    block = s[r:r + n, c:c + n]
    pos = functools.partial(jax.lax.broadcasted_iota, jnp.int32, block.shape)
    block = jnp.where(pos(queries) >= pos(1 - queries), block, NEG_INF)
    band = _join([s[r:r + n, :c], block, s[r:r + n, c + n:]], 1)
    return _join([s[:r], band, s[r + n:]], 0)


def _join(parts, axis):
    """The non-empty ``parts`` side by side along ``axis``."""
    parts = [x for x in parts if x.shape[axis]]
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis)


def _rows(x, at, n, fill):
    """``x`` as the rows from ``at`` of ``n``, ``fill`` in the others."""
    def const(rows):
        return jnp.full((rows, x.shape[1]), fill, x.dtype)
    return _join([const(at), x, const(n - at - x.shape[0])], 0)


def _fold(op, x):
    """The lane tiles of ``x`` folded into one by ``op``, elementwise."""
    return functools.reduce(op, [x[:, j:j + LANES]
                                 for j in range(0, x.shape[1], LANES)])


# ----------------------------------------------------------------------------
# forward
# ----------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, hd, g, block_k, chunk,
                causal, scale, inside):
    """One head's attention for this q block, a chunk of queries at a time in
    straight code, each with an online softmax of its own over the keys it
    meets."""
    t, qi, h = pl.program_id(1), pl.program_id(2), pl.program_id(3)
    block_q, width = q_ref.shape[1], q_ref.shape[2]
    per_tile = width // hd
    skv = k_ref.shape[1]
    q_start = qi * block_q

    q = q_ref[0]                                     # (BQ, width)
    if per_tile > 1:
        _, kh = _kv_head(t, h, per_tile, g)
        if g > 1:   # this head's K/V may sit in the other half: line q up
            q = jnp.where(kh != h, _other_half(q, hd), q)
        q = jnp.where(_half(q.shape, hd, kh), q, jnp.zeros_like(q))
    if _exact(scale):
        q = q * scale

    def step(qc, carry, start, size, diagonal=False):
        """Online softmax of the chunk ``qc`` over the keys [start, start +
        size); with ``diagonal`` the last ``chunk`` of them start at its
        first query and are taken strip by strip, a row band of the chunk
        each. One max, one ``exp`` and one row sum a row over all its pieces;
        what no piece holds is never multiplied."""
        m, l, acc = carry
        body = size - chunk if diagonal else size
        pieces = [(0, chunk, 0, body)] if body else []
        if diagonal:
            pieces += [(q0, q1, body + k0, body + k1)
                       for q0, q1, k0, k1 in _staircase(chunk)]
        scores, values = [], []
        for q0, q1, k0, k1 in pieces:
            keys = pl.ds(pl.multiple_of(start + k0, LANES), k1 - k0)
            s = _dot(qc[q0:q1], k_ref[0, keys, :], _NT)   # float32
            if not _exact(scale):
                s = s * scale
            if diagonal and k0 >= body:   # a strip
                s = _causal(s, q0, k0 - body, 0)
            scores.append(s)
            values.append(v_ref[0, keys, :])

        # a row's max and sum: the pieces' lane tiles folded elementwise, then
        # ONE reduction across the lanes a row of the chunk
        top = [_rows(_fold(jnp.maximum, s), q0, chunk, NEG_INF)
               for (q0, *_), s in zip(pieces, scores)]
        m_new = jnp.maximum(m, jnp.max(functools.reduce(jnp.maximum, top),
                                       axis=-1, keepdims=True))
        wide = jnp.broadcast_to(m_new, (chunk, LANES))
        alpha = jnp.exp(m - m_new)
        acc = acc * alpha
        total = []
        for (q0, q1, k0, k1), s, v in zip(pieces, scores, values):
            p = jnp.exp(s - _join([wide[q0:q1]] * ((k1 - k0) // LANES), 1))
            total.append(_rows(_fold(jnp.add, p), q0, chunk, 0.0))
            acc = acc + _rows(_dot(p.astype(v.dtype), v, _NN), q0, chunk, 0.0)
        l = alpha * l + jnp.sum(functools.reduce(jnp.add, total), axis=-1,
                                keepdims=True)
        return m_new, l, acc

    lse = []
    for c in range(0, block_q, chunk):
        rows = slice(c, c + chunk)
        carry = (jnp.full((chunk, 1), NEG_INF, jnp.float32),
                 jnp.zeros((chunk, 1), jnp.float32),
                 jnp.zeros((chunk, width), jnp.float32))
        # causal (block_q == block_k): the key blocks before this q block,
        # then its own keys up to the chunk's
        before = jnp.minimum(q_start, skv) if causal else skv
        carry = jax.lax.fori_loop(
            0, before // block_k,
            lambda j, x, rows=rows: step(q[rows], x, j * block_k, block_k),
            carry)
        if causal:
            own = functools.partial(step, q[rows], start=q_start,
                                    size=c + chunk, diagonal=True)
            # with Sq > Skv a q block may lie past the last key
            carry = own(carry) if inside else jax.lax.cond(
                q_start < skv, own, lambda x: x, carry)
        m, l, acc = carry
        l = jnp.where(l == 0.0, 1.0, l)
        out = acc / l
        if per_tile > 1:
            if g > 1:
                out = jnp.where(kh != h, _other_half(out, hd), out)
            # the other head's half: what its own visit wrote, or will overwrite
            out = jnp.where(_half(out.shape, hd, h), out,
                            o_ref[0, rows, :].astype(jnp.float32))
        o_ref[0, rows, :] = out.astype(o_ref.dtype)
        lse.append(m + jnp.log(l))
    lse = _as_row(jnp.concatenate(lse, 0))
    if per_tile > 1:   # the other head's row stays
        head = jax.lax.broadcasted_iota(jnp.int32, (per_tile, block_q), 0)
        lse = jnp.where(head == h, lse, lse_ref[0, 0])
    lse_ref[0, 0] = lse


def _fwd(q, k, v, *, causal, num_kv_groups, scale, block_q, block_k):
    """q: (B, Sq, nh, hd); k/v: (B, Skv, kvh, hd) → out like q, lse (B, nh, Sq)."""
    B, Sq, nh, hd = q.shape
    Skv, kvh = k.shape[1], k.shape[2]
    width = _tile_width(nh, hd)
    per_tile = width // hd
    g = num_kv_groups

    def kv_tile(b, t, i, h):
        return b, 0, _kv_head(t, h, per_tile, g)[0]

    chunk = math.gcd(FWD_CHUNK, block_q)
    out, lse = tracing.pallas_call(
        functools.partial(_fwd_kernel, hd=hd, g=g, block_k=block_k,
                          chunk=chunk, causal=causal, scale=scale,
                          inside=Sq <= Skv),
        grid=(B, nh // per_tile, Sq // block_q, per_tile),
        in_specs=[
            pl.BlockSpec((1, block_q, width), lambda b, t, i, h: (b, i, t)),
            pl.BlockSpec((1, Skv, width), kv_tile),
            pl.BlockSpec((1, Skv, width), kv_tile),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, width), lambda b, t, i, h: (b, i, t)),
            pl.BlockSpec((1, 1, per_tile, block_q),
                         lambda b, t, i, h: (b, t, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, Sq, nh * hd), q.dtype),
            jax.ShapeDtypeStruct((B, nh // per_tile, per_tile, Sq), jnp.float32),
        ],
        interpret=_interpret(),
        name="flash_fwd",
        attrs=_pairs(Sq, Skv, block_q, chunk, causal),
    )(q.reshape(B, Sq, nh * hd), k.reshape(B, Skv, kvh * hd),
      v.reshape(B, Skv, kvh * hd))
    return out.reshape(q.shape), lse.reshape(B, nh, Sq)


# ----------------------------------------------------------------------------
# backward
# ----------------------------------------------------------------------------

def _bwd_kernel(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref,
                dq_ref, dk_ref, dv_ref, dq_acc, delta_acc, dk_acc, dv_acc, *,
                hd, g, block_q, chunk, causal, scale, inside):
    """dk/dv of this KV block and this block's share of dq, on transposed
    scores (keys, queries): QK^T, exp and do.V^T are computed once for all
    three. dq gathers in ``dq_acc`` over the KV sweep and the heads of the tile
    (the TPU walks its grid in order) and is written with the last of them;
    delta (the row sums of do * o) is made on a tile's first visit and never
    leaves VMEM."""
    t, ki, h = pl.program_id(1), pl.program_id(2), pl.program_id(3)
    last = (ki == pl.num_programs(2) - 1) & (h == pl.num_programs(3) - 1)
    block_k, width = k_ref.shape[1], k_ref.shape[2]
    per_tile = width // hd
    sq = q_ref.shape[1]
    k_start = ki * block_k

    k, v = k_ref[0], v_ref[0]                        # (BK, width)
    if per_tile > 1:
        if g > 1:   # bring this head's K/V to the half its q, do, dq lie in
            _, kh = _kv_head(t, h, per_tile, g)
            k = jnp.where(kh != h, _other_half(k, hd), k)
            v = jnp.where(kh != h, _other_half(v, hd), v)
        mine = _half(k.shape, hd, h)
        k = jnp.where(mine, k, jnp.zeros_like(k))
        v = jnp.where(mine, v, jnp.zeros_like(v))
    k_s = k * scale if _exact(scale) else k

    @pl.when((ki == 0) & (h == 0))
    def _first_visit():
        dq_acc[...] = jnp.zeros_like(dq_acc)
        for c in range(0, sq, block_q):
            prod = (do_ref[0, c:c + block_q, :].astype(jnp.float32)
                    * o_ref[0, c:c + block_q, :].astype(jnp.float32))
            for head in range(per_tile):
                own = prod if per_tile == 1 else jnp.where(
                    _half(prod.shape, hd, head), prod, 0.0)
                delta_acc[head:head + 1, c:c + block_q] = _as_row(
                    jnp.sum(own, axis=-1, keepdims=True))

    dk_acc[...] = jnp.zeros_like(dk_acc)
    dv_acc[...] = jnp.zeros_like(dv_acc)

    def step(keys, start, size, diagonal=False):
        """The block's ``keys`` against the queries [start, start + size);
        with ``diagonal`` the first ``chunk`` of them start at the first key
        and are taken strip by strip, the last strip (it holds every key) in
        one piece with the queries after it."""
        pieces = [(0, size, 0, chunk)]
        if diagonal:
            *pieces, (q0, _, k0, k1) = _staircase(chunk)
            pieces.append((q0, size, k0, k1))
        for q0, q1, k0, k1 in pieces:
            rows = pl.ds(pl.multiple_of(start + q0, LANES), q1 - q0)
            own = slice(keys.start + k0, keys.start + k1)
            q, do = q_ref[0, rows, :], do_ref[0, rows, :]
            lse = _row(lse_ref[0, 0, :, rows], h)    # (1, queries)
            delta = _row(delta_acc[:, rows], h)
            st = _dot(k_s[own], q, _NT)              # (keys, queries) float32
            if not _exact(scale):
                st = st * scale
            if diagonal:
                st = _causal(st, q0, k0, 1)
            pt = jnp.exp(st - lse)
            dv_acc[own] += _dot(pt.astype(do.dtype), do, _NN)
            dpt = _dot(v[own], do, _NT)
            # scale: on dk, dq at the end
            dst = (pt * (dpt - delta)).astype(q.dtype)
            dk_acc[own] += _dot(dst, q, _NN)
            dq_acc[rows, :] += _dot(dst, k[own], _TN)   # this head's lanes only

    for c in range(0, block_k, chunk):
        keys = slice(c, c + chunk)
        if causal:   # block_q == block_k
            # the queries at the KV block's own positions from the chunk's on,
            # then the q blocks after it
            own = functools.partial(step, keys, k_start + c, block_k - c, True)
            if inside:
                own()
            else:   # with Skv > Sq a KV block may lie past the last query
                pl.when(k_start < sq)(own)
        jax.lax.fori_loop(
            ki + 1 if causal else 0, sq // block_q,
            lambda i, _, keys=keys: step(keys, i * block_q, block_q), None)

    dk, dv = dk_acc[...] * scale, dv_acc[...]
    if per_tile > 1:   # each product holds the pair's other head's lanes too
        dk = jnp.where(mine, dk, dk_ref[0].astype(jnp.float32))
        dv = jnp.where(mine, dv, dv_ref[0].astype(jnp.float32))
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)

    @pl.when(last)
    def _write_dq():
        dq_ref[0] = (dq_acc[...] * scale).astype(dq_ref.dtype)


def _bwd(causal, num_kv_groups, scale, block_q, block_k, res, do):
    q, k, v, out, lse = res  # (B, S, heads, hd) as the model holds them
    B, Sq, nh, hd = q.shape
    Skv, kvh = k.shape[1], k.shape[2]
    width = _tile_width(nh, hd)
    per_tile = width // hd
    tiles = nh // per_tile
    g = num_kv_groups

    def kv_block(b, t, i, h):
        return b, i, _kv_head(t, h, per_tile, g)[0]

    seq = pl.BlockSpec((1, Sq, width), lambda b, t, i, h: (b, 0, t))
    row = pl.BlockSpec((1, 1, per_tile, Sq), lambda b, t, i, h: (b, t, 0, 0))
    dkv = pl.BlockSpec((1, block_k, width), lambda b, t, i, h: (b, i, t))

    chunk = math.gcd(BWD_CHUNK, block_k)
    dq, dkh, dvh = tracing.pallas_call(
        functools.partial(_bwd_kernel, hd=hd, g=g, block_q=block_q,
                          chunk=chunk, causal=causal, scale=scale,
                          inside=Skv <= Sq),
        grid=(B, tiles, Skv // block_k, per_tile),
        in_specs=[seq, pl.BlockSpec((1, block_k, width), kv_block),
                  pl.BlockSpec((1, block_k, width), kv_block), seq, seq, row],
        out_specs=[seq, dkv, dkv],
        out_shape=[
            jax.ShapeDtypeStruct((B, Sq, nh * hd), q.dtype),
            jax.ShapeDtypeStruct((B, Skv, nh * hd), q.dtype),
            jax.ShapeDtypeStruct((B, Skv, nh * hd), q.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((Sq, width), jnp.float32),
                        pltpu.VMEM((per_tile, Sq), jnp.float32),
                        pltpu.VMEM((block_k, width), jnp.float32),
                        pltpu.VMEM((block_k, width), jnp.float32)],
        interpret=_interpret(),
        name="flash_bwd",
        attrs=_pairs(Sq, Skv, block_k, chunk, causal),
    )(q.reshape(B, Sq, nh * hd), k.reshape(B, Skv, kvh * hd),
      v.reshape(B, Skv, kvh * hd), out.reshape(B, Sq, nh * hd),
      do.reshape(B, Sq, nh * hd), lse.reshape(B, tiles, per_tile, Sq))

    def per_kv_head(d, like):
        d = d.reshape(B, Skv, kvh, g, hd)
        if g > 1:
            d = d.astype(jnp.float32).sum(axis=3)
        return d.reshape(like.shape).astype(like.dtype)

    return dq.reshape(q.shape), per_kv_head(dkh, k), per_kv_head(dvh, v)


# ----------------------------------------------------------------------------
# public entry
# ----------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, causal, num_kv_groups, scale, block_q, block_k):
    out, _ = _fwd(q, k, v, causal=causal, num_kv_groups=num_kv_groups,
                  scale=scale, block_q=block_q, block_k=block_k)
    return out


def _flash_fwd(q, k, v, causal, num_kv_groups, scale, block_q, block_k):
    from jax.ad_checkpoint import checkpoint_name

    out, lse = _fwd(q, k, v, causal=causal, num_kv_groups=num_kv_groups,
                    scale=scale, block_q=block_q, block_k=block_k)
    # name the residuals so a remat policy can elect to SAVE them — under
    # ``save_only_these_names("attn_out", "attn_lse")`` the backward pass reads
    # the stored out/lse instead of re-running the forward kernel
    out = checkpoint_name(out, "attn_out")
    lse = checkpoint_name(lse, "attn_lse")
    return out, (q, k, v, out, lse)


_flash.defvjp(_flash_fwd, _bwd)


@register_impl("pallas_flash")
def flash_attention(q, k, v, *, causal=True, q_offset=0, num_kv_groups=1,
                    softcap=0.0, bias=None, scale=None, block_q=BLOCK,
                    block_k=BLOCK):
    """Flash attention entry (same (B,S,h,d) surface as ``attention.xla_attention``).

    ``block_q`` / ``block_k`` are upper bounds: each shrinks until it divides
    its sequence and the cell fits VMEM (``_fits``); under a causal mask both
    kernels take the smaller of the two, so the diagonal crosses square
    blocks."""
    if bias is not None or (softcap and softcap > 0.0) or (
            not isinstance(q_offset, int)) or q_offset != 0:
        # a TRACED q_offset (KV-cache decode under jit/vmap) must also fall
        # back — comparing it would raise TracerBoolConversionError
        raise UnsupportedFeature("flash kernel: bias/softcap/q_offset unsupported")
    B, Sq, nh, hd = q.shape
    Skv, kvh = k.shape[1], k.shape[2]

    if hd not in (64, 128, 256):
        raise UnsupportedShape(
            f"flash kernel needs head_dim in (64, 128, 256) (got {hd})")
    mesh, axes = open_mesh_axes()

    def over(names):
        return tuple(a for a in names if a in axes) or None

    # under a mesh: every device runs the kernel on its own batch rows and
    # heads (the model's Ulysses layout: batch over the DP axes, heads over
    # seq x model); attention needs nothing from another device
    spec = P(over(ZERO_AXES), None, over((SEQ_AXIS, MODEL_AXIS)), None)
    sizes = (jax.sharding.get_abstract_mesh() if mesh is None else mesh).shape
    ways = math.prod(sizes[a] for a in spec[2] or ())
    width = _tile_width(nh // ways, hd)
    if (nh // ways * hd) % width or (kvh // ways * hd) % width:
        raise UnsupportedShape(
            f"flash kernel reads whole lane tiles of heads: heads x head_dim "
            f"must be a multiple of {width} on every device (got q "
            f"{nh // ways} x {hd}, k/v {kvh // ways} x {hd})")

    def fit(block, n):
        # largest block <= requested that divides n and fits VMEM (a power of
        # two from the second try on)
        b = min(block, n)
        while b >= 128 and (n % b or not _fits(b, Sq, Skv, width,
                                               q.dtype.itemsize)):
            b = 1 << (b - 1).bit_length() - 1
        return b

    if causal:   # one block size, so the diagonal crosses square blocks
        block_q = block_k = fit(min(block_q, block_k), math.gcd(Sq, Skv))
    else:
        block_q, block_k = fit(block_q, Sq), fit(block_k, Skv)
    if block_q < 128 or block_k < 128:
        raise UnsupportedShape(
            f"flash kernel needs sequence lengths that are multiples of 128 "
            f"(got {Sq}, {Skv}) whose full-length windows (K and V; q, o, do "
            f"and dq) fit {VMEM} bytes of VMEM beside a block of 128: long "
            f"contexts take ring attention")
    scale = scale if scale is not None else hd ** -0.5

    def local(q, k, v):
        return _flash(q, k, v, causal, num_kv_groups, scale, block_q, block_k)

    if not axes:
        return local(q, k, v)
    return jax.shard_map(local, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, axis_names=frozenset(axes),
                         check_vma=False)(q, k, v)
