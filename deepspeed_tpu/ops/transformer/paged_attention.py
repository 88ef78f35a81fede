"""The paged KV pool: its one physical layout, the programs' access to it, and
the Pallas paged-attention decode kernel (blocked KV pool + block tables).

Reference: ``deepspeed/inference/v2/kernels/ragged_ops/blocked_flash`` — flash
attention over paged KV blocks addressed through per-sequence block tables.

The pool is ONE array ``(L, kvh, NB, BS, row)``: layer, kv head, pool block,
token of the block, and a row of that token. Two row layouts live behind the
same functions, told apart by the ``(key width, value width)`` the model's
configuration gives (``TransformerConfig.kv_row``):

- ``[k_t | v_t]``, ``row = 2*hd``: a kv head's key beside its value. At head
  size 64 a row is exactly the TPU's 128 lanes, at 128 it is two tiles: no
  head size needs a re-laid-out view between the write and the read.
- ``[c_kv | k_rope | 0]``, ``kvh = 1`` (latent attention, DeepSeek-V2/V3):
  one row a token a layer, shared by every head. The "key" part is the
  normalised kv latent, the "value" part the rotated rope key, zero-padded so
  that the row is a whole number of 128-lane tiles (:func:`latent_row`: HBM
  pads the minor dimension to that anyway, and a DMA slice must cover it).
  :func:`mla_decode` attends over it in the absorbed form.

Every program touches it through the functions here: the model writes whole
rows (:func:`write_rows`, in place on a donated buffer: a scatter at computed
row numbers of the pool viewed flat, or, for a decode round, the kernel
:func:`kv_write`, which writes the live rows alone) and reads either through
the kernel
(:func:`paged_decode`, which streams ``pool[layer, heads, block]`` tiles from
HBM by the scalar-prefetched layer and block table) or through one XLA gather
with the layer among its indices (:func:`gather_context`); the engine's block
programs (COW, tier demote/promote, swap) use :func:`get_block` /
:func:`set_block`. No program slices a layer out of the pool.

WHO WRITES A DECODE ROUND'S ROWS (a step whose rows are apart, on a pool
:func:`writes_live_rows` takes): in a full-attention layer :func:`paged_decode`
itself, handed the round's new rows: it sets each live row in the block it
fetches last and writes the sub-tile back, one call a layer. The latent pools'
rounds and the sparse layers' (their attention reads a view of chosen blocks)
go through :func:`write_rows` to :func:`kv_write`, and every other step (a
mixed step's chunk rows are neighbours in one block) through
:func:`write_rows`' scatter.

The kernel is decode only (one query token per row; a prefill chunk is rows of
one token each); segments longer than one token keep the gather path. A cell
of its grid is several rows of the step (:func:`rows_per_cell`, from the row
count alone) over all their kv heads (:func:`heads_per_cell`: fewer only where
the pool's blocks are too large to buffer), and a row works as long as its
``lens`` says. A step's rows are padded to a fixed count; WHO marks a row dead
is the caller: the model gives ``lens`` 0 to a row whose table names no block
(block 0 is the trash block), and such a row fetches nothing, keeps the zeros
its cell's output starts as and costs the cell one step of a scalar loop, not
the grid a step. The kernel does not look at the table to decide it. A
cell's live rows are one stream of trips through a ring of buffers: the next
live row's first blocks arrive while the row before it computes its last.
"""

import functools
import math
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...utils import tracing

NEG_INF = -1e30

#: rows of one chunk-segment tile of the latent paged program: consecutive
#: tokens of one sequence that stream its latent once (:func:`mla_decode`)
SEGMENT_TILE = 16


def _interpret() -> bool:
    from ..pallas_utils import pallas_interpret

    return pallas_interpret()


# ----------------------------------------------------------------------
# the pool's layout
# ----------------------------------------------------------------------
def init_pool(num_layers, kv_heads, num_blocks, block_size, head_dim,
              dtype=jnp.bfloat16):
    """The zeroed pool, ``(L, kvh, NB, BS, row)``; block 0 is the reserved
    trash block that masked/padded writes land in. ``head_dim``: one width
    (``row = 2*hd``) or the (key width, value width) pair of a row."""
    row = 2 * head_dim if isinstance(head_dim, int) else sum(head_dim)
    return jnp.zeros((num_layers, kv_heads, num_blocks, block_size, row),
                     dtype)


def latent_row(rank: int, rope: int):
    """(key width, value width) of a latent pool row ``[c_kv | k_rope | 0]``:
    the rope key padded up to a whole number of 128-lane tiles."""
    return rank, -(-(rank + rope) // 128) * 128 - rank


def _k_width(pool, k_width):
    """Where a row splits: ``k_width`` or, unsaid, its middle."""
    return pool.shape[-1] // 2 if k_width is None else k_width


def write_rows(pool, layer, tables, positions, k, v, *, rows_apart=False):
    """Write the (B, S) new tokens' keys and values of layer ``layer`` into
    the pool, in place on the carried buffer: k, v (B, S, kvh, hd) land as
    whole ``[k | v]`` rows at ``pool[layer, head, block, offset]``.

    Two forms, one result on every block a sequence holds:

    - the XLA scatter: one index a (row, kv head) into the pool viewed flat
      as (rows, 2*hd), a bitcast of the row-major pool, whatever the rows
      hold. Padding rows all land in trash block 0, so the row numbers are
      not unique.
    - :func:`kv_write`, where the caller says ``rows_apart`` (static) and
      :func:`writes_live_rows` takes the pool's shape: one copy a LIVE row
      over all its kv heads, nothing for a row whose block is the trash
      block. (A full-attention layer's decode round does not come here: the
      model hands such rows to :func:`paged_decode`, which writes them the
      same way from the block it has fetched.) The kernel cannot write one
      token's row alone (Mosaic slices
      the token dimension of an HBM array only by whole tiles of 8 rows, and
      at bfloat16 two rows even share a 32-bit sublane); it reads the
      aligned sub-tile of :data:`SUB_TILE` tokens that holds the row, sets
      the row and writes the sub-tile back, which is exact only where NO TWO
      ROWS OF THE STEP LAND IN ONE POOL BLOCK. That is what ``rows_apart``
      promises, and only the builder of the step can: a decode round has one
      row a sequence and a sequence's write block is its own (shared prefix
      blocks are copied on write before the dispatch). A mixed step, whose
      chunk rows are neighbours in one block, keeps the scatter."""
    L, kvh, NB, BS, row = pool.shape
    blk = jnp.take_along_axis(tables, positions // BS, axis=1)  # (B, S)
    kv = jnp.concatenate((k, v), axis=-1).astype(pool.dtype)
    if rows_apart and writes_live_rows(pool):
        if blk.shape[1] != 1:
            raise ValueError("rows that are apart are one token each")
        return kv_write(pool, layer, blk[:, 0], positions[:, 0] % BS, kv[:, 0])
    if L * kvh * NB * BS >= 2 ** 31:
        raise ValueError(f"pool {pool.shape} has more rows than int32 "
                         "row numbers reach")
    head0 = (layer * kvh + jnp.arange(kvh, dtype=jnp.int32)) * NB
    rows = ((head0[None, None, :] + blk[:, :, None]) * BS
            + (positions % BS)[:, :, None])  # (B, S, kvh)
    return pool.reshape(-1, row).at[rows].set(kv).reshape(pool.shape)


def gather_context(pool, layer, tables, k_width=None):
    """Layer ``layer``'s logical cache of each row's table, gathered by XLA:
    (k, v) of shape (B, MAXB*BS, kvh, width), the row split at ``k_width``
    (its middle if unsaid). One gather with the layer among its indices; the
    whole-context copy it makes is what the kernels avoid."""
    row, hd = pool.shape[-1], _k_width(pool, k_width)
    B = tables.shape[0]
    ctx = pool[layer, :, tables]  # (B, MAXB, kvh, BS, row)
    ctx = jnp.swapaxes(ctx, 2, 3).reshape(B, -1, pool.shape[1], row)
    return ctx[..., :hd], ctx[..., hd:]


def get_block(pool, block, k_width=None):
    """Pool block ``block`` of every layer as the host-side payload the
    tiers, swaps, CRCs and cross-engine hand-off keep whatever the pool's
    layout is: (2, L, kvh, BS, hd), K stacked on V, for a ``[k | v]`` row of
    two equal parts; the whole rows (1, L, kvh, BS, row) where the parts
    differ in width (the latent ``[c_kv | k_rope]``)."""
    hd = _k_width(pool, k_width)
    blk = pool[:, :, block]  # (L, kvh, BS, row)
    if 2 * hd != pool.shape[-1]:
        return blk[None]
    return jnp.stack((blk[..., :hd], blk[..., hd:]))


def set_block(pool, block, payload):
    """Write a :func:`get_block` payload into pool block ``block``."""
    rows = payload[0] if payload.shape[0] == 1 else jnp.concatenate(
        (payload[0], payload[1]), axis=-1)
    return pool.at[:, :, block].set(rows.astype(pool.dtype))


def payload_shape(pool, k_width=None):
    """Shape of one block's :func:`get_block` payload."""
    L, kvh, _, BS, row = pool.shape
    if 2 * _k_width(pool, k_width) != row:
        return (1, L, kvh, BS, row)
    return (2, L, kvh, BS, row // 2)


# ----------------------------------------------------------------------
# the kernels
# ----------------------------------------------------------------------
#: bytes of VMEM two pool blocks of a cell's heads may take: what decides how
#: many kv heads a cell covers. (The kernel's ring is :data:`DECODE_SLOTS`
#: trips, 1.5 MB for the cells' pools, 3 MB at most, where a block is 1 MB.)
DECODE_BUFFER_BYTES = 2 * 1024 * 1024


def heads_per_cell(pool) -> int:
    """kv heads one cell of :func:`paged_decode` covers, from the pool's
    shape and dtype alone: all ``kvh`` where two blocks of them,
    ``(2, kvh, BS, row)``, fit :data:`DECODE_BUFFER_BYTES`, else the largest
    divisor of ``kvh`` whose blocks do (at least one)."""
    _, kvh, _, BS, row = pool.shape
    fit = DECODE_BUFFER_BYTES // (2 * BS * row * pool.dtype.itemsize)
    return max(d for d in range(1, kvh + 1) if kvh % d == 0 and d <= max(fit, 1))


#: ``[k | v]`` rows (a token of one kv head) one trip of the decode kernel's
#: loop fetches, at least, where a row has that many blocks. A trip is a chain
#: (the fetch's wait, the scores' product, the running softmax, the values'
#: product, each waiting for the one before) of ~0.55 us on the chip however
#: little it holds: 256 rows (128 KB) or 1024 took the same time, PERF.md 6.
#: What a trip does a row is the same at a head of 64 and of 128 (a push, a
#: score, an exp), so the trip is sized in rows, not bytes: 1024 are 256 KB of
#: heads of 64 (gpt2-medium's block of 16 heads, whose products take as long
#: as its fetch) and 512 KB of heads of 128 (four blocks of trinity-mini's
#: four heads, sixteen of the sparse view's one)
DECODE_TRIP_ROWS = 1024
#: pool blocks a trip may fetch at most
DECODE_TRIP_BLOCKS = 16
#: buffers of a trip each that the kernel's fetches go round: the fetches run
#: two trips ahead of the products, so the DMA engine has the next trip when it
#: is done with one (on the chip three beat two by 14-31% at a full cell and
#: four gave nothing over three, PERF.md 6)
DECODE_SLOTS = 3


def blocks_per_trip(pool) -> int:
    """Pool blocks one trip of :func:`paged_decode`'s loop fetches and
    attends together, from the pool's shape alone (as
    :func:`heads_per_cell`): as many as hold :data:`DECODE_TRIP_ROWS` rows of
    a cell's heads, at most :data:`DECODE_TRIP_BLOCKS`. One wherever a cell's
    block is that large already (16 kv heads over 64 tokens); four for
    trinity-mini's four heads, sixteen for a pool read one kv head a cell
    (sparse attention's view, 32 KB a block)."""
    _, _, _, BS, _ = pool.shape
    return max(1, min(DECODE_TRIP_BLOCKS,
                      DECODE_TRIP_ROWS // (heads_per_cell(pool) * BS)))


#: rows of a step one cell of the decode kernel covers at most (on the chip,
#: ms a round of 24 layers at 4 | 8 | 16 | 32 rows a cell, PERF.md 5: one live
#: row of 64 0.174 | 0.167 | 0.162 | 0.160, 256 live rows 21.2 | 20.8 | 20.5 |
#: 20.4, the parent's row a cell 0.277 and 24.0)
DECODE_CELL_ROWS = 32


def rows_per_cell(rows: int) -> int:
    """Rows of the step one cell of :func:`paged_decode` covers, from the
    step's row count alone: its largest divisor that is at most
    :data:`DECODE_CELL_ROWS` (32 of a round of 64, of a mixed step's 256 and
    of the sparse view's 96; a count with no such divisor but one keeps a
    row a cell)."""
    return max(d for d in range(1, DECODE_CELL_ROWS + 1) if rows % d == 0)


def kernels_wanted() -> bool:
    """Do the paged programs take the Pallas kernels here? On a TPU, or
    forced (``DSTPU_FORCE_PAGED_KERNEL=1``: tests, interpreted on the CPU),
    unless the attention implementation is pinned to XLA. Read as a program
    is traced."""
    from .attention import get_default_impl

    return get_default_impl() != "xla" and (
        jax.default_backend() == "tpu"
        or os.environ.get("DSTPU_FORCE_PAGED_KERNEL") == "1")


#: bytes of VMEM :func:`kv_write` may take for the sub-tiles in flight
WRITE_BUFFER_BYTES = 2 * 1024 * 1024
#: tokens of the aligned piece of a pool block that :func:`kv_write` reads
#: and writes back around one row: the rows of one HBM tile, (8, 128)
#: whatever the dtype (bfloat16 packs two rows to a 32-bit sublane inside
#: it). On the chip 8 beat 16: 0.32 against 0.45 ms a round of 64 live rows
SUB_TILE = 8


def writes_live_rows(pool) -> bool:
    """Are the rows of a step whose rows are apart written by the kernels
    (:func:`kv_write` from :func:`write_rows`, :func:`paged_decode` handed
    the new rows), the live rows alone? Where the kernels are wanted, the
    pool's blocks are whole sub-tiles of whole 128-lane rows, and a cell's
    heads are whole lane tiles of the model's ``(rows, heads * hd)`` arrays
    (not so only where one kv head of 64 is all a cell can buffer)."""
    _, kvh, _, BS, row = pool.shape
    hpc = heads_per_cell(pool)
    return (kernels_wanted() and BS % SUB_TILE == 0 and row % 128 == 0
            and (hpc == kvh or hpc * (row // 2) % 128 == 0))


def _write_slots(pool) -> int:
    """Sub-tiles :func:`kv_write` keeps in flight, from the pool's shape and
    dtype alone (as :func:`heads_per_cell`): what fits
    :data:`WRITE_BUFFER_BYTES`, between 2 and 16."""
    _, kvh, _, _, row = pool.shape
    slab = kvh * SUB_TILE * row * pool.dtype.itemsize
    return max(2, min(16, WRITE_BUFFER_BYTES // slab))


def _kv_write_kernel(layer_ref, blk_ref, off_ref, kv_ref, pool_in, pool_ref,
                     buf, rsem, wsem, *, rows, sub, slots):
    """One cell. Row ``b`` is live where ``blk[b] > 0``; for a live row the
    aligned sub-tile ``pool[layer, :, blk[b], off[b] // sub * sub : +sub]``
    (``kvh`` pieces of (sub, row), each contiguous) is fetched into a slot of
    ``buf``, the row ``off[b] % sub`` of every head is set from ``kv[b]``,
    and the slot is written back where it came from. A software pipeline
    over the rows: row ``i``'s fetch starts ``slots // 2`` trips before it is
    needed, and a slot is fetched into again once the write from it, started
    ``slots`` rows ago, is done. ``blk`` and ``off`` are padded by ``slots``
    dead rows, which drain the pipeline. A dead row starts nothing."""
    del pool_in  # the same buffer as ``pool_ref``: input_output_aliases
    layer = layer_ref[0]
    ahead = slots // 2

    def piece(b):
        start = pl.multiple_of(off_ref[b] // sub * sub, sub)
        return pool_ref.at[layer, :, blk_ref[b], pl.ds(start, sub)]

    def slot_of(b):
        return jax.lax.rem(b, slots)

    def fetch(b):
        return pltpu.make_async_copy(piece(b), buf.at[slot_of(b)],
                                     rsem.at[slot_of(b)])

    def store(b):
        return pltpu.make_async_copy(buf.at[slot_of(b)], piece(b),
                                     wsem.at[slot_of(b)])

    def trip(i, _):
        freed = jnp.maximum(i - slots, 0)

        @pl.when((i >= slots) & (blk_ref[freed] > 0))
        def _slot_is_free():
            store(freed).wait()

        @pl.when(blk_ref[i] > 0)
        def _fetch():
            fetch(i).start()

        b = jnp.maximum(i - ahead, 0)

        @pl.when((i >= ahead) & (blk_ref[b] > 0))
        def _set_row():
            fetch(b).wait()
            slot = slot_of(b)
            # through float32, which every bfloat16 survives unchanged: the
            # select is on whole 32-bit sublanes
            tile = buf[slot].astype(jnp.float32)           # (kvh, sub, row)
            new = kv_ref[b].astype(jnp.float32)            # (kvh, 1, row)
            at = jax.lax.broadcasted_iota(jnp.int32, tile.shape, 1)
            buf[slot] = jnp.where(at == jax.lax.rem(off_ref[b], sub), new,
                                  tile).astype(buf.dtype)
            store(b).start()

        return 0

    jax.lax.fori_loop(0, rows + slots, trip, 0)


def kv_write(pool, layer, blk, off, kv):
    """Set ``pool[layer, :, blk[b], off[b]] = kv[b]`` for every row ``b``
    with ``blk[b] > 0``, in place: the pool is left in HBM whole and aliased
    to the result, so a donated, carried buffer is written where it lies,
    and a row whose block is trash block 0 writes nothing.

    Its callers are the rounds of the latent pools and of the sparse
    layers; a full-attention layer's round is written by :func:`paged_decode`.

    pool (L, kvh, NB, BS, row); layer: int32 scalar (traced or not); blk,
    off (B,) int32: each row's pool block and its token's offset in it; kv
    (B, kvh, row): the rows. NO TWO LIVE ROWS MAY NAME THE SAME BLOCK: a
    row's write is a read-modify-write of its :data:`SUB_TILE` neighbours
    (see :func:`write_rows`), and two in one sub-tile would race."""
    B, kvh, row = kv.shape
    slots = _write_slots(pool)
    pad = jnp.zeros((slots,), jnp.int32)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,  # layer, blk, off
        grid=(1,),
        in_specs=[pl.BlockSpec((B, kvh, 1, row), lambda *_: (0, 0, 0, 0)),
                  pl.BlockSpec(memory_space=pl.ANY)],  # the pool stays in HBM
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[
            pltpu.VMEM((slots, kvh, SUB_TILE, row), pool.dtype),
            pltpu.SemaphoreType.DMA((slots,)),
            pltpu.SemaphoreType.DMA((slots,)),
        ],
    )
    return tracing.pallas_call(
        functools.partial(_kv_write_kernel, rows=B, sub=SUB_TILE, slots=slots),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        input_output_aliases={4: 0},  # the pool, counting the scalar operands
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=_interpret(),
        name="kv_write",
    )(jnp.asarray(layer, jnp.int32).reshape(1),
      jnp.concatenate((blk.astype(jnp.int32), pad)),
      jnp.concatenate((off.astype(jnp.int32), pad)),
      kv.astype(pool.dtype)[:, :, None, :], pool)


def _decode_kernel(layer_ref, tables_ref, lens_ref, *refs, block_size, scale,
                   bpt, hd, write, bound=False):
    """Grid (B / rpc, kvh / hpc): ONE cell per ``rpc`` rows of the step
    (:func:`rows_per_cell`) and group of ``hpc`` kv heads (all of them
    wherever the buffer fits: :func:`heads_per_cell`). The cell first sorts
    its rows by what ``lens`` says in a loop of scalar steps (the live rows'
    numbers go to ``live_ref`` in order), starts its first fetches, and zeroes
    its output block while they are on their way: a row with ``lens[b] == 0``
    is dead, keeps those zeros and costs its compare and nothing else. Then
    the cell walks its live rows. A row streams its ACTIVE pool blocks from
    HBM, ``bpt`` blocks a trip (:func:`blocks_per_trip`), and computes every
    head of the group from each: a row's work follows its real length, and
    what it computes does not depend on its neighbours.

    WHAT A TRIP COMPUTES WITH is what it fetched: both products take the
    buffer in the pool's dtype and accumulate in float32, ``q`` cast to that
    dtype once a live row and ``p`` once a trip, as :func:`mla_decode`, the
    flash kernels and the XLA forms (:func:`attend_rows`,
    :func:`attend_tiles`) do. The scores lose nothing by it: a product of two
    bfloat16 numbers is exact in float32, and the scale multiplies their
    float32 sum. The running softmax (``m``, ``l``, ``alpha``, ``acc``) is
    float32. On the chip a trip of heads of 128 is bound by its fetch either
    way (alone the products take 56% of it; at float32 the ring ran the
    same), and gpt2-medium's 16 heads of 64, whose products pass their
    fetch, run 9% faster for it at a full cell (PERF.md 6, PR 65).

    The cell's trips are ONE STREAM, row behind row, through a ring of
    ``slots`` buffers (:data:`DECODE_SLOTS`): the fetches run ``slots - 1``
    trips ahead of the products, over the row's end into the next live row's
    first trips, so the DMA engine is handed the next trip before it is done
    with one and only the cell's first fetch has nothing to hide it. The
    fetches' cursor ``(k, j)``, trip ``j`` of the ``k``-th live row, is
    carried with the count of the cell's trips, which names the slot.

    The pool is the whole stacked pool as it lies in HBM (see
    :func:`init_pool`): one DMA fetches ``pool[layer, h0:h0+hpc, tables[b, j]]``,
    ``hpc`` tiles of (BS, 2*hd), each contiguous, whose rows are
    ``[k_t | v_t]``; K and V are the lane halves. The row is a multiple of
    128 lanes for every supported head size, which is what Mosaic asks of an
    HBM DMA slice. A trip's blocks lie one behind the other along the
    buffer's token axis; where the row's blocks end inside a trip, its last
    block is fetched again in their place (finite values, masked like the
    tokens past ``lens`` in any last block).

    ``write`` (static: the caller handed the step's new rows) is the decode
    round's form. q, the new k and v and the result are blocks of the
    model's ``(rows, heads * hd)`` arrays, staged through float32 scratch
    once a cell with a live row (Mosaic reads no single row out of a packed
    bfloat16 block) and cut into heads a live row at a time. The pool is the
    call's aliased OUTPUT, read and written where it lies: a live row's last
    trip holds the block of its new token (``lens`` counts it), so once that
    trip has arrived the row ``[k | v]`` of every head of the group is set
    in the buffer at token ``(lens - 1) % BS`` of that block, before the
    trip's products, and the aligned :data:`SUB_TILE` tokens around it go
    back to ``pool[layer, heads, block]`` by one async copy out of ``wbuf``,
    which is waited for before the next live row fills ``wbuf`` again and at
    the end of the cell. The copies of the last block that fill the trip
    behind it have arrived before the store starts and are masked; no other
    row of the step names that block (the caller's promise), and a dead row
    starts nothing."""
    if bound:
        first_ref, *refs = refs
    if write:
        (q_ref, k_ref, v_ref, _, o_ref, pool_ref, buf, sem, live_ref,
         q32, k32, v32, o32, wbuf, wsem) = refs
        rpc, hpc = q_ref.shape[0], k_ref.shape[1] // hd
        g = q_ref.shape[1] // (hpc * hd)
    else:
        q_ref, pool_ref, o_ref, buf, sem, live_ref = refs
        rpc, hpc, g, _ = q_ref.shape
    row0 = pl.program_id(0) * rpc
    layer = layer_ref[0]
    heads = pl.ds(pl.program_id(1) * hpc, hpc)
    slots = buf.shape[0]
    span = bpt * block_size            # tokens a trip covers

    # ``lax.div`` / ``lax.rem``: the counts are not negative, and ``//`` and
    # ``%`` trace (and run) a sign's worth of scalar steps more each
    div, rem = jax.lax.div, jax.lax.rem

    def blocks(b):
        return div(lens_ref[b] + (block_size - 1), block_size)

    def trip0(r):
        """The first trip of the cell's row ``r``: the one that holds its
        bound."""
        return div(first_ref[row0 + r], span) if bound else 0

    def trips(r):
        """Where the trips of the cell's row ``r`` end: behind the one that
        holds its last token."""
        return div(blocks(row0 + r) + (bpt - 1), bpt)

    def sort_row(r, n_live):
        # a dead row's number is overwritten by the next live row's
        live_ref[n_live] = r
        return n_live + (lens_ref[row0 + r] > 0).astype(jnp.int32)

    n_live = jax.lax.fori_loop(0, rpc, sort_row, 0)

    def live_row(k):
        """The cell's ``k``-th live row (its first row behind the last)."""
        return jax.lax.select(k < n_live, live_ref[jax.lax.min(k, rpc - 1)], 0)

    def copies(r, j, slot):
        """Trip ``j`` of the cell's row ``r`` into buffer ``slot``."""
        b = row0 + r
        last = blocks(b) - 1
        return [pltpu.make_async_copy(
            pool_ref.at[layer, heads,
                        tables_ref[b, jax.lax.min(j * bpt + i, last)
                                   if bpt > 1 else j]],
            buf.at[slot, :, pl.ds(i * block_size, block_size)],
            sem.at[slot, i]) for i in range(bpt)]

    def fetch(t, cursor):
        """Start the fetch the cursor stands at, ``(k, j)``: trip ``j`` of the
        ``k``-th live row, the ``t``-th trip of the cell, into the slot whose
        turn it is; nothing behind the last live row. Returns the cursor
        moved on."""
        k, j = cursor
        r = live_row(k)

        @pl.when(k < n_live)
        def _start():
            for c in copies(r, j, rem(t, slots)):
                c.start()

        row_ends = j + 1 >= trips(r)
        return (jax.lax.select(row_ends, k + 1, k),
                jax.lax.select(row_ends, trip0(live_row(k + 1)), j + 1))

    def store(b):
        """``wbuf`` to the sub-tile of row ``b``'s new token."""
        start = div(rem(lens_ref[b] - 1, block_size), SUB_TILE) * SUB_TILE
        return pltpu.make_async_copy(
            wbuf, pool_ref.at[layer, heads, tables_ref[b, blocks(b) - 1],
                              pl.ds(pl.multiple_of(start, SUB_TILE), SUB_TILE)],
            wsem.at[0])

    def heads_of(ref, r, n):
        """Row ``r`` of a staged ``(rpc, n * hd)`` block, a head a piece."""
        flat = ref[pl.ds(r, 1), :]
        return [flat[:, i * hd:(i + 1) * hd] for i in range(n)]

    def set_row(k, r, j, slot):
        """The new ``[k | v]`` row of the cell's ``k``-th live row ``r`` into
        its last block, which trip ``j`` has brought into buffer ``slot``,
        and the sub-tile around it on its way back to the pool."""
        b = row0 + r
        tok = ((blocks(b) - 1 - j * bpt) * block_size
               + rem(lens_ref[b] - 1, block_size))
        at = pl.multiple_of(div(tok, SUB_TILE) * SUB_TILE, SUB_TILE)
        new = jnp.stack([jnp.concatenate(kv, axis=-1) for kv in zip(
            heads_of(k32, r, hpc), heads_of(v32, r, hpc))])  # (hpc, 1, 2*hd)
        # through float32, which every bfloat16 survives unchanged: the
        # select is on whole 32-bit sublanes
        tile = buf[slot, :, pl.ds(at, SUB_TILE), :].astype(jnp.float32)
        token = jax.lax.broadcasted_iota(jnp.int32, tile.shape, 1)
        tile = jnp.where(token == tok - at, new, tile).astype(buf.dtype)
        buf[slot, :, pl.ds(at, SUB_TILE), :] = tile

        @pl.when(k > 0)
        def _wbuf_is_free():
            store(b).wait()   # the row's before: same bytes, same semaphore

        wbuf[...] = tile
        store(b).start()

    # the cell's trips are one stream, row behind row, and the fetches run
    # ``slots - 1`` trips ahead of the products
    cursor = jax.lax.fori_loop(
        0, slots - 1, fetch, (jnp.int32(0), jnp.int32(trip0(live_row(0)))))

    # every row's output starts as zeros, which is what a dead row keeps
    # (stored while the first fetches are on their way)
    o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)

    def attend(k, stream):
        """The cell's ``k``-th live row; ``stream`` is the number of the
        cell's trips so far and the fetches' cursor."""
        r = live_ref[k]
        seq_len = lens_ref[row0 + r]
        j0, ntrip = trip0(r), trips(r)
        if write:
            qh = heads_of(q32, r, hpc * g)
            q = jnp.stack([jnp.concatenate(qh[i * g:(i + 1) * g], axis=0)
                           for i in range(hpc)]).astype(buf.dtype)
        else:
            q = q_ref[r].astype(buf.dtype)            # (hpc, g, hd)

        def body(j, carry):
            m, l, acc, t, cursor = carry
            slot = rem(t, slots)
            cursor = fetch(t + slots - 1, cursor)   # into the slot left last
            for c in copies(r, j, slot):
                c.wait()
            if write:
                @pl.when(j + 1 == ntrip)
                def _new_token():
                    set_row(k, r, j, slot)

            kv = buf[slot]                    # (hpc, bpt*BS, 2*hd), as it came
            s = jax.lax.dot_general(          # every head of the group: (hpc, g, T)
                q, kv[..., :hd], (((2,), (2,)), ((0,), (0,))),
                preferred_element_type=jnp.float32) * scale
            kpos = j * span + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
            seen = kpos < seq_len
            if bound:
                seen &= kpos >= first_ref[row0 + r]
            s = jnp.where(seen, s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m - m_new)
            l_new = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
            acc_new = acc * alpha + jax.lax.dot_general(
                p.astype(kv.dtype), kv[..., hd:], (((2,), (1,)), ((0,), (0,))),
                preferred_element_type=jnp.float32)
            return m_new, l_new, acc_new, t + 1, cursor

        m0 = jnp.full((hpc, g, 1), NEG_INF, jnp.float32)
        l0 = jnp.zeros((hpc, g, 1), jnp.float32)
        acc0 = jnp.zeros((hpc, g, hd), jnp.float32)
        _, l, acc, t, cursor = jax.lax.fori_loop(
            j0, ntrip, body, (m0, l0, acc0, *stream))
        out = acc / l                              # a live row sees a key
        if write:
            o32[pl.ds(r, 1), :] = jnp.concatenate(
                [out[i, n:n + 1] for i in range(hpc) for n in range(g)],
                axis=-1)
        else:
            o_ref[r] = out.astype(o_ref.dtype)
        return t, cursor

    stream = (jnp.int32(0), cursor)
    if not write:
        jax.lax.fori_loop(0, n_live, attend, stream)
        return

    @pl.when(n_live > 0)
    def _live_cell():
        for staged, ref in ((q32, q_ref), (k32, k_ref), (v32, v_ref)):
            staged[...] = ref[...].astype(jnp.float32)
        o32[...] = jnp.zeros(o32.shape, o32.dtype)
        jax.lax.fori_loop(0, n_live, attend, stream)
        o_ref[...] = o32[...].astype(o_ref.dtype)
        store(row0 + live_ref[n_live - 1]).wait()


def paged_decode(q, pool, layer, tables, lens, *, scale=None, new_rows=None,
                 first=None):
    """One-token decode attention of layer ``layer`` against the stacked pool,
    read where it lies; with ``new_rows``, the decode round's whole attention
    sublayer in one call: the rows' new keys and values written into the pool
    on the way.

    q: (B, nh, hd); pool: (L, kvh, NB, BS, 2*hd) as :func:`init_pool` lays it
    out, left in HBM whole; layer: int32 scalar (traced or not), a
    scalar-prefetch operand the kernel's DMAs index the pool by, so no layer
    is ever sliced out; tables: (B, MAXB) int32 pool block ids (0-padded);
    lens: (B,) int32 valid token counts (position + 1). Returns (B, nh, hd)
    in q's dtype.

    ``first``: (B,) int32, each row's lower bound (a window layer's): the row
    attends over the tokens ``first[b] <= t < lens[b]`` of its table, the
    trips start at the block that holds ``first[b]`` and that block is
    masked below it. A live row has ``first < lens``. Without it the call is
    the one it was, bit for bit: the bound is a fourth scalar operand that
    only a bounded call has.

    ``new_rows``: (k, v), each (B, kvh * hd), the step's new token of every
    row as the projections leave it; ``lens`` counts it. q is then
    (B, nh * hd) too, the result comes back in that layout with the pool,
    ``(out, pool)``, and the pool is aliased from input to output (donated
    and carried, it is written where it lies). A live row's ``[k | v]`` lands
    at ``pool[layer, :, block, (lens - 1) % BS]`` of its last block, in the
    block the kernel has fetched to attend over, and the :data:`SUB_TILE`
    tokens around it are written back: what :func:`kv_write` followed by the
    read-only call gives, bit for bit, pool and result. As for
    :func:`kv_write`, NO TWO LIVE ROWS MAY NAME THE SAME LAST BLOCK
    (``rows_apart``, the step's builder's promise) and the pool must be one
    that :func:`writes_live_rows` takes; a dead row writes nothing.

    The grid is ``(B / rpc, kvh / hpc)``: a cell takes ``rpc`` rows
    (:func:`rows_per_cell`, from ``B`` alone) over ``hpc`` kv heads.

    A row with ``lens`` 0 is dead: it fetches nothing, its output is zeros,
    and it costs its cell one step of a scalar loop, not the grid a step. The
    CALLER decides
    which rows are dead (the model: a row whose table names no block, since
    block 0 is the trash block no sequence holds); the kernel never reads
    deadness out of the table, and a row with ``lens`` 1 and an all-zero
    table attends to the trash block's first token. A live row's output is
    bit for bit what the row gives alone, whatever shares its cell."""
    write, bound = new_rows is not None, first is not None
    _, kvh, _, BS, row = pool.shape
    B, hd = q.shape[0], row // 2 if write else q.shape[2]
    nh = q.shape[1] // hd if write else q.shape[1]
    g, hpc, bpt = nh // kvh, heads_per_cell(pool), blocks_per_trip(pool)
    rpc = rows_per_cell(B)
    in_hbm = pl.BlockSpec(memory_space=pl.ANY)     # the pool stays in HBM
    scratch = [
        pltpu.VMEM((DECODE_SLOTS, hpc, bpt * BS, row), pool.dtype),
        pltpu.SemaphoreType.DMA((DECODE_SLOTS, bpt)),    # the ring's
        pltpu.SMEM((rpc,), jnp.int32),         # the cell's live rows
    ]
    if write:
        def lanes(n):
            return pl.BlockSpec((rpc, n * hd), lambda b, c, *_: (b, c))

        operands = (q, *(a.astype(pool.dtype) for a in new_rows), pool)
        in_specs = [lanes(hpc * g), lanes(hpc), lanes(hpc), in_hbm]
        out_specs = [lanes(hpc * g), in_hbm]
        out_shape = [jax.ShapeDtypeStruct(q.shape, q.dtype),
                     jax.ShapeDtypeStruct(pool.shape, pool.dtype)]
        scratch += [pltpu.VMEM((rpc, hpc * n * hd), jnp.float32)
                    for n in (g, 1, 1, g)]       # q, k, v and the result
        scratch += [pltpu.VMEM((hpc, SUB_TILE, row), pool.dtype),
                    pltpu.SemaphoreType.DMA((1,))]
    else:
        block = pl.BlockSpec((rpc, hpc, g, hd), lambda b, c, *_: (b, c, 0, 0))
        operands = (q.reshape(B, kvh, g, hd), pool)
        in_specs, out_specs = [block, in_hbm], block
        out_shape = jax.ShapeDtypeStruct((B, kvh, g, hd), q.dtype)
    out = tracing.pallas_call(
        functools.partial(_decode_kernel, block_size=BS, bpt=bpt, hd=hd,
                          write=write, **({"bound": True} if bound else {}),
                          scale=scale if scale is not None else hd ** -0.5),
        attrs={"trip_bytes": hpc * bpt * BS * row * pool.dtype.itemsize,
               "blocks_per_trip": bpt, "slots": DECODE_SLOTS,
               "operand_dtype": pool.dtype.name},
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3 + bound,  # layer, tables, lens[, first]
            grid=(B // rpc, kvh // hpc),
            in_specs=in_specs, out_specs=out_specs, scratch_shapes=scratch),
        out_shape=out_shape,
        # the pool, counting the scalar operands
        input_output_aliases={6 + bound: 1} if write else {},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=_interpret(),
        name="paged_decode",
    )(jnp.asarray(layer, jnp.int32).reshape(1), tables, lens,
      *((first.astype(jnp.int32),) if bound else ()), *operands)
    return tuple(out) if write else out.reshape(B, nh, hd)


def attend_rows(q, pool, layer, tables, lens, *, first=None, scale=None):
    """One-token rows over their tables, each the tokens ``first[b] <= t <
    lens[b]`` (from 0 without ``first``): :func:`paged_decode` where the
    kernels are wanted, one XLA gather of the rows' contexts off the chip.
    q (B, nh, hd); a row with ``lens`` 0 is dead and gives zeros. Positions
    are the table's own: a bounded class's table starts at the first block
    its sequence still holds, and its caller counts from there."""
    if kernels_wanted():
        return paged_decode(q, pool, layer, tables, lens, scale=scale,
                            first=first)
    B, nh, hd = q.shape
    kvh = pool.shape[1]
    gk, gv = gather_context(pool, layer, tables)         # (B, T, kvh, hd)
    kpos = jnp.arange(gk.shape[1])[None]
    seen = kpos < lens[:, None]
    if first is not None:
        seen &= kpos >= first[:, None]
    logit = jnp.einsum("bhgd,bthd->bhgt", q.reshape(B, kvh, nh // kvh, hd),
                       gk, preferred_element_type=jnp.float32) \
        * (scale if scale is not None else hd ** -0.5)
    p = jax.nn.softmax(jnp.where(seen[:, None, None], logit, NEG_INF), axis=-1)
    p = jnp.where((lens > 0)[:, None, None, None], p, 0.0).astype(gv.dtype)
    o = jnp.einsum("bhgt,bthd->bhgd", p, gv,
                   preferred_element_type=jnp.float32)
    return o.reshape(B, nh, hd).astype(q.dtype)


#: context widths a tile's attention is compiled for, as shares of the
#: table: a tile takes the narrowest that covers its last token. One width
#: for a table of at most :data:`TILE_WIDTH_BLOCKS` blocks (a window class's)
TILE_WIDTHS = (0.25, 0.5, 0.75, 1.0)
TILE_WIDTH_BLOCKS = 64


def attend_tiles(q, pool, layer, tables, first, *, window=0, scale=None):
    """Attention of tiles of consecutive tokens of one sequence (a prefill
    chunk's segment) over the sequence's gathered context: masked dense
    attention, the gather path (there is no segment kernel for a pool of
    heads). q (N, C, nh, hd); tables (N, MB) each tile's sequence's table
    (all zero: an empty tile); first (N,) the position of each tile's first
    token, counted as the table counts. A query at ``i`` sees the keys ``j <=
    i``, and with ``window`` only those with ``i - j < window``; a window
    class's table holds nothing older, so its gather is the segment the
    tile needs and no more. Returns (N, C, nh, hd)."""
    N, C, nh, hd = q.shape
    kvh, BS = pool.shape[1], pool.shape[3]
    g, MB = nh // kvh, tables.shape[1]
    scale = scale if scale is not None else hd ** -0.5
    widths = sorted({max(1, math.ceil(MB * w)) for w in TILE_WIDTHS}) \
        if MB > TILE_WIDTH_BLOCKS else [MB]

    def attend(width, q, table, pos):
        """Over the table's first ``width`` blocks."""
        gk, gv = gather_context(pool, layer, table[None, :width])
        gk, gv = gk[0], gv[0]                             # (width*BS, kvh, hd)
        kpos = jnp.arange(width * BS)[None]
        seen = kpos <= pos[:, None]                       # (C, T)
        if window:
            seen &= kpos > pos[:, None] - window
        logit = jnp.einsum("chgd,thd->hgct", q.reshape(C, kvh, g, hd), gk,
                           preferred_element_type=jnp.float32) * scale
        p = jax.nn.softmax(jnp.where(seen[None, None], logit, NEG_INF),
                           axis=-1).astype(gv.dtype)
        o = jnp.einsum("hgct,thd->chgd", p, gv,
                       preferred_element_type=jnp.float32)
        return o.reshape(C, nh, hd).astype(q.dtype)

    def tile(args):
        q, table, first = args
        pos = first + jnp.arange(C)
        need = (first + C + BS - 1) // BS           # blocks the tile reaches
        branch = jnp.sum(need > jnp.asarray(widths[:-1]), dtype=jnp.int32) \
            if len(widths) > 1 else 0
        return jax.lax.switch(
            branch, [functools.partial(attend, w) for w in widths],
            q, table, pos)

    return jax.lax.map(tile, (q, tables, first))


def paged_decode_attention(q, k_pool, v_pool, tables, lens, *, scale=None):
    """One-token decode attention against ONE layer's blocked K and V pools:
    the kernel's numerical reference entry, not the model's (the model calls
    :func:`paged_decode` on its stacked pool). K and V are laid side by side
    as a one-layer stacked pool (a copy, which is why the model does not come
    this way) and go through the same kernel.

    q: (B, nh, hd) — this step's query per sequence.
    k_pool/v_pool: (kvh, NB, BS, hd); tables: (B, MAXB) int32 pool block ids
    (0-padded); lens: (B,) int32 valid token counts (position + 1).
    Returns (B, nh, hd) in q's dtype."""
    pool = jnp.concatenate((k_pool, v_pool), axis=-1)[None]
    return paged_decode(q, pool, 0, tables, lens, scale=scale)


# ----------------------------------------------------------------------
# latent attention (MLA) over the ``[c_kv | k_rope]`` pool, absorbed form
# ----------------------------------------------------------------------
#: pool blocks a trip of the latent kernel's loop fetches at most
MLA_TRIP_BLOCKS = 16
#: bytes the float32 scores of one trip (query rows x the trip's tokens) may
#: take: past it a trip is bound by its products, not by its fixed cost
MLA_SCORE_BYTES = 2 * 1024 * 1024


def mla_blocks_per_trip(q_rows: int, pool) -> int:
    """Pool blocks one trip of :func:`mla_decode`'s loop fetches at most, from
    the query rows of a cell (``q_tile`` x heads) and the pool's shape alone
    (as :func:`blocks_per_trip` for :func:`paged_decode`): as many as keep a
    trip's scores within :data:`MLA_SCORE_BYTES`, at most
    :data:`MLA_TRIP_BLOCKS`. Over blocks of 64 tokens: sixteen for a
    one-token row of 64 heads (a trip's fixed cost is as long as four such
    blocks take to arrive, and the scores of 64 query rows are small), eight
    for a segment tile of 16 such rows, whose trip is bound by its products
    (measured on the chip, PERF.md 5: four, eight, twelve and sixteen)."""
    BS = pool.shape[3]
    return max(1, min(MLA_TRIP_BLOCKS, MLA_SCORE_BYTES // (q_rows * BS * 4)))


def _mla_trip(q_lat, q_rope, kv, first, lim, m_ref, l_ref, acc_ref, *, rank,
              scale):
    """One trip of the latent kernels' running softmax (``m``, ``l``, ``acc``
    in float32 scratch): the cell's query rows against the fetched latent
    rows ``kv``, tokens ``first`` on of the sequence, a query row seeing the
    tokens below its ``lim`` (a scalar, or a column a row). Scores
    ``q_lat . c_kv + q_rope . k_rope`` in float32 from the operands as they
    lie, ``p`` cast once, the weighted latent accumulated."""
    c_kv, k_rope = kv[:, :rank], kv[:, rank:rank + q_rope.shape[-1]]
    dims = (((1,), (1,)), ((), ()))
    s = (jax.lax.dot_general(q_lat, c_kv, dims,
                             preferred_element_type=jnp.float32)
         + jax.lax.dot_general(q_rope, k_rope, dims,
                               preferred_element_type=jnp.float32)) * scale
    kpos = first + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(kpos < lim, s, NEG_INF)                 # (Q, width)
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p.astype(kv.dtype), c_kv, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[...] = m_new


def _mla_kernel(layer_ref, tables_ref, nblk_ref, ql_ref, qr_ref, lim_ref,
                pool_ref, o_ref, buf, sem, m_ref, l_ref, acc_ref, slot_ref, *,
                block_size, rank, scale, kv_blocks):
    """The segment form (``q_tile`` > 1; one-token rows take
    :func:`_mla_rows_kernel`). Grid (tiles,): ONE cell per query tile. The
    tile's ``Q`` rows (its tokens times all heads) are the absorbed queries
    ``[q_nope W_uk | q_rope]``
    of tokens of ONE sequence; the kernel streams that sequence's pool blocks
    ``pool[layer, 0, tables[t, j]]`` from HBM, up to ``kv_blocks`` a trip and
    double-buffered, and contracts every head with each latent tile once:
    scores ``q_lat . c_kv + q_rope . k_rope``, masked per row by the tokens it
    may see, online softmax, and the weighted LATENT as the result (the
    caller up-projects it through ``W_uv``).

    What ``nblk`` says is what the cell does. ``nblk[t] == 0`` (every row of
    the tile has ``limits`` 0) starts no DMA of its own, runs no trip of the
    loop and writes zeros. A live cell fetches its ``nblk[t]`` blocks and no
    other: a trip takes what the row has left, at most ``kv_blocks``, and is
    computed at half the buffer's width where that holds it (the buffer
    behind the fetched blocks is masked; it is zeroed once a call, so what
    lies there is zeros or older pool blocks, finite either way).

    The double buffer is handed from cell to cell (the grid runs in order and
    the scratch outlives a cell): before a cell computes its last trip, and a
    dead cell at once, it starts the first trip of row ``t + 1`` into the
    slot that is free, so only the first cell of a call waits for a fetch
    with nothing to hide it. ``slot_ref`` carries the slot of the cell's
    first trip."""
    t = pl.program_id(0)
    layer = layer_ref[0]
    nblk = nblk_ref[t]
    n_trips = (nblk + kv_blocks - 1) // kv_blocks
    nblk_next = nblk_ref[t + 1]          # 0 behind the last cell
    half = kv_blocks // 2

    def each_copy(row, blocks, j, slot, act):
        """``act`` on the copies of trip ``j`` of ``row``, which has
        ``blocks`` blocks in all: one a block the row has left."""
        def one(i, _):
            at = pl.multiple_of(i * block_size, block_size)
            act(pltpu.make_async_copy(
                pool_ref.at[layer, 0, tables_ref[row, j * kv_blocks + i]],
                buf.at[slot, pl.ds(at, block_size)], sem.at[slot, i]))
            return 0

        jax.lax.fori_loop(
            0, jnp.minimum(blocks - j * kv_blocks, kv_blocks), one, 0)

    def start(row, blocks, j, slot):
        each_copy(row, blocks, j, slot, lambda c: c.start())

    @pl.when(t == 0)
    def _first_cell():
        buf[...] = jnp.zeros_like(buf)
        slot_ref[0] = 0

        @pl.when(nblk > 0)
        def _cold():
            start(t, nblk, 0, 0)

    slot0 = slot_ref[0]

    def attend(j, slot, w):
        """Trip ``j``'s first ``w`` blocks of buffer ``slot`` into the
        running softmax."""
        _mla_trip(ql_ref[0], qr_ref[0], buf[slot, :w * block_size],
                  j * (kv_blocks * block_size), lim_ref[0], m_ref, l_ref,
                  acc_ref, rank=rank, scale=scale)

    def body(j, _):
        slot = jax.lax.rem(slot0 + j, 2)

        @pl.when(j + 1 < n_trips)
        def _prefetch():
            start(t, nblk, j + 1, 1 - slot)

        @pl.when((j + 1 == n_trips) & (nblk_next > 0))
        def _hand_over():
            start(t + 1, nblk_next, 0, 1 - slot)

        each_copy(t, nblk, j, slot, lambda c: c.wait())
        if not half:
            attend(j, slot, kv_blocks)
            return 0
        fits_half = nblk - j * kv_blocks <= half

        @pl.when(fits_half)
        def _narrow():
            attend(j, slot, half)

        @pl.when(jnp.logical_not(fits_half))
        def _wide():
            attend(j, slot, kv_blocks)

        return 0

    @pl.when(n_trips == 0)
    def _dead():
        @pl.when(nblk_next > 0)
        def _hand_over():
            start(t + 1, nblk_next, 0, slot0)

        o_ref[0] = jnp.zeros(o_ref.shape[1:], o_ref.dtype)

    @pl.when(n_trips > 0)
    def _live():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)
        jax.lax.fori_loop(0, n_trips, body, 0)
        # a live tile's row may see nothing (a padding row behind the
        # chunk's last token): its l is the count of masked keys, not 0
        o_ref[0] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)
        slot_ref[0] = jax.lax.rem(slot0 + n_trips, 2)


#: buffers of a trip each that the one-token latent kernel's fetches go round,
#: three trips ahead of the products. One more than :data:`DECODE_SLOTS`: a
#: trip's fetch is started from inside the block of its products (behind them
#: in the program's order, so that the compiler schedules the copies' scalar
#: work among the products), which is a trip later than in front of them. On
#: the chip, us a call of 96 full | 39 of 96 | 6 of 32 live rows: two buffers
#: 373.6 | 148.9 | 74.3, three 247.1 | 100.2 | 54.4, four 230.5 | 94.1 | 53.6,
#: and the fetches alone, with no products, 231.1 | 93.0 | 51.2 (PERF.md 5)
MLA_SLOTS = 4


def _mla_rows_kernel(layer_ref, tables_ref, lim_ref, ql_hbm, qr_hbm, pool_ref,
                     o_hbm, buf, sem, live_ref, m_ref, l_ref, acc_ref, ql, qr,
                     qsem, obuf, osem, zeros, zsem, *, block_size, rank, scale,
                     bpt):
    """The one-token form of :func:`mla_decode`: ONE cell walks all the
    step's rows. Each row's 64 x 576 queries and 64 x 512 result are 138 KB,
    so they stay in HBM like the pool, and the cell moves what a LIVE row
    needs by its own copies: blocks of rows through Pallas' pipeline would
    move every row's, dead or alive (13 MB a call of 96 rows beside the live
    rows' ~58 MB of latent).

    The cell sorts the rows by what ``limits`` says in a loop of scalar steps
    (the live rows' numbers go to ``live_ref`` in order) and starts its first
    fetches. A row with ``limits[b] == 0`` is dead: it costs that compare and
    one copy of 64 KB of zeros from a zeroed buffer to its row of the result,
    and a call with no live row starts no fetch. Then the cell walks its live
    rows: a row streams the ``ceil(limits / BS)`` blocks it has,
    ``pool[layer, 0, tables[b, i]]``, ``bpt`` a trip
    (:func:`mla_blocks_per_trip`), and contracts every head with each latent
    tile once: scores ``q_lat . c_kv + q_rope . k_rope`` in float32 from the
    operands as they lie, masked by ``limits``, the running softmax in
    float32, ``p`` cast once a trip, and the weighted LATENT as the result.
    A trip takes what the row has left and is computed at half the buffer's
    width where that holds it (what lies behind the fetched blocks is masked;
    the ring is zeroed once a call, so it is zeros or older pool blocks,
    finite either way). What a live row computes does not depend on its
    neighbours.

    The live rows' trips are ONE STREAM, row behind row, through a ring of
    ``slots`` buffers (:data:`MLA_SLOTS`): the fetches' cursor ``(k, j)``,
    trip ``j`` of the ``k``-th live row, runs ``slots - 1`` trips ahead of
    the products, over a row's end into the next live row's first trip, so
    only the call's first fetch has nothing to hide it. A row's ``q_lat``
    (64 KB, a ring of its own) rides with its first trip; ``q_rope`` comes
    whole, once a call (a DMA cannot slice rows of 64 lanes); a row's result
    leaves through ``obuf`` by a copy that the next live row waits for.

    The copies of a trip are straight code, each under its own condition, and
    the fetch stands inside the block of the products: the compiler turns
    them into predicated instructions and schedules their scalar work among
    the products (in a loop of their own, sixteen copies cost a trip a third
    of its products' time with nothing beside them). Mosaic's bounds checks
    are off for the same reason: twenty scalar bundles a copy."""
    rows = live_ref.shape[0]
    layer = layer_ref[0]
    slots = buf.shape[0]
    half = bpt // 2
    span = bpt * block_size
    div, rem = jax.lax.div, jax.lax.rem

    def blocks(b):
        return div(lim_ref[b] + (block_size - 1), block_size)

    def trips(b):
        return div(blocks(b) + (bpt - 1), bpt)

    def sort_row(b, n_live):
        # a dead row's number is overwritten by the next live row's
        live_ref[n_live] = b
        return n_live + (lim_ref[b] > 0).astype(jnp.int32)

    n_live = jax.lax.fori_loop(0, rows, sort_row, 0)

    def live_row(k):
        """The ``k``-th live row (row 0 behind the last)."""
        return jax.lax.select(k < n_live, live_ref[jax.lax.min(k, rows - 1)], 0)

    def each_copy(b, j, slot, act, on=True):
        """``act`` on the copies of trip ``j`` of row ``b`` into buffer
        ``slot``: one a block the row has left, ``bpt`` at most, none unless
        ``on``. ``lax`` on the scalars: a ``jnp`` operator is a traced call
        each."""
        left, first = blocks(b) - j * bpt, j * bpt
        if on is not True:
            left = jax.lax.select(on, left, 0)
        for i in range(bpt):
            @pl.when(jax.lax.gt(left, jnp.int32(i)))
            def _one():
                act(pltpu.make_async_copy(
                    pool_ref.at[layer, 0,
                                tables_ref[b, jax.lax.add(first, jnp.int32(i))]],
                    buf.at[slot, pl.ds(i * block_size, block_size)],
                    sem.at[slot, i]))

    def queries(b, k):
        """Row ``b``'s latent queries into the slot of the ``k``-th live
        row."""
        s = rem(k, slots)
        return pltpu.make_async_copy(ql_hbm.at[b], ql.at[s], qsem.at[s])

    def rope_queries():
        return pltpu.make_async_copy(qr_hbm, qr, qsem.at[slots])

    def fetch(t, cursor):
        """Start the fetch the cursor stands at, ``(k, j)``: trip ``j`` of the
        ``k``-th live row, the ``t``-th trip of the call, into the slot whose
        turn it is (a row's queries with its first trip); nothing behind the
        last live row."""
        k, j = cursor
        b = live_row(k)

        @pl.when((k < n_live) & (j == 0))
        def _queries():
            queries(b, k).start()

        each_copy(b, j, rem(t, slots), lambda c: c.start(), k < n_live)

    def moved_on(cursor):
        k, j = cursor
        row_ends = j + 1 >= trips(live_row(k))
        return (jax.lax.select(row_ends, k + 1, k),
                jax.lax.select(row_ends, 0, j + 1))

    @pl.when(n_live > 0)
    def _rope():
        rope_queries().start()

    def prime(t, cursor):
        # what lies behind a short trip's blocks is masked, and has to be
        # finite: zeros, or older pool blocks
        buf[t] = jnp.zeros(buf.shape[1:], buf.dtype)
        fetch(t, cursor)
        return moved_on(cursor)

    some = (n_live > 0).astype(jnp.int32)
    cursor = jax.lax.fori_loop(0, some * (slots - 1), prime,
                               (jnp.int32(0), jnp.int32(0)))

    @pl.when(n_live > 0)
    def _last_slot():
        buf[slots - 1] = jnp.zeros(buf.shape[1:], buf.dtype)

    def zero_store(b):
        return pltpu.make_async_copy(zeros, o_hbm.at[b], zsem.at[0])

    def result_store(b):
        return pltpu.make_async_copy(obuf, o_hbm.at[b], osem.at[0])

    # every dead row's result is zeros (on their way while the first fetches
    # arrive)
    zeros[...] = jnp.zeros(zeros.shape, zeros.dtype)

    def zero_row(b, _):
        @pl.when(lim_ref[b] == 0)
        def _dead():
            zero_store(b).start()

        return 0

    jax.lax.fori_loop(0, rows, zero_row, 0)

    @pl.when(n_live > 0)
    def _rope_is_here():
        rope_queries().wait()

    def row(k, stream):
        """The ``k``-th live row; ``stream`` is the number of the call's
        trips so far and the fetches' cursor."""
        b = live_ref[k]
        lim, nblk = lim_ref[b], blocks(b)
        queries(b, k).wait()
        qs = rem(k, slots)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

        def body(j, carry):
            t, cursor = carry
            slot = rem(t, slots)
            each_copy(b, j, slot, lambda c: c.wait())

            def trip(w):
                # the products, and in their block the fetch ``slots - 1``
                # trips ahead, into the slot left last
                _mla_trip(ql[qs], qr[b], buf[slot, :w * block_size], j * span,
                          lim, m_ref, l_ref, acc_ref, rank=rank, scale=scale)
                fetch(t + slots - 1, cursor)

            if half:
                fits_half = nblk - j * bpt <= half
                pl.when(fits_half)(lambda: trip(half))
                pl.when(jnp.logical_not(fits_half))(lambda: trip(bpt))
            else:
                trip(bpt)
            return t + 1, moved_on(cursor)

        stream = jax.lax.fori_loop(0, trips(b), body, stream)

        @pl.when(k > 0)
        def _obuf_is_free():
            result_store(b).wait()   # the row's before: same bytes

        # a live row sees a key
        obuf[...] = (acc_ref[...] / l_ref[...]).astype(obuf.dtype)
        result_store(b).start()
        return stream

    jax.lax.fori_loop(0, n_live, row, (jnp.int32(0), cursor))

    @pl.when(n_live > 0)
    def _last_result():
        result_store(0).wait()

    def zero_done(_, carry):
        zero_store(0).wait()
        return carry

    jax.lax.fori_loop(0, rows - n_live, zero_done, 0)


@functools.partial(jax.jit, inline=True, static_argnames=("scale", "interpret"))
def _mla_rows(q_lat, q_rope, pool, layer, tables, limits, *, scale, interpret):
    """:func:`mla_decode` at ``q_tile`` 1: :func:`_mla_rows_kernel` over the
    step's rows, queries and result left in HBM. Jitted for its cache alone,
    its equations inlined into the program that holds it (as
    ``linear_attention._decode_call``): a serving process binds the kernel
    once a pool sublayer of each program, and the body's Python, with its
    copies unrolled half a second a bind on a busy host, runs for the first
    of them (``kernel.setup_trace_s``)."""
    N, nh, rank = q_lat.shape
    rope = q_rope.shape[-1]
    _, _, _, BS, row = pool.shape
    bpt = mla_blocks_per_trip(nh, pool)
    pad = -tables.shape[1] % bpt
    if pad:    # a whole number of trips: a copy's condition is its own, and
        # the table is read before it is asked; the padding is never fetched
        tables = jnp.pad(tables, ((0, 0), (0, pad)))
    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    return tracing.pallas_call(
        functools.partial(_mla_rows_kernel, block_size=BS, rank=rank,
                          scale=scale, bpt=bpt),
        attrs={"rows_per_cell": N, "slots": MLA_SLOTS, "blocks_per_trip": bpt,
               "trip_bytes": bpt * BS * row * pool.dtype.itemsize,
               "operand_dtype": pool.dtype.name},
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,  # layer, tables, limits
            grid=(1,),
            in_specs=[in_hbm, in_hbm, in_hbm], out_specs=in_hbm,
            scratch_shapes=[
                pltpu.VMEM((MLA_SLOTS, bpt * BS, row), pool.dtype),  # the ring
                pltpu.SemaphoreType.DMA((MLA_SLOTS, bpt)),
                pltpu.SMEM((N,), jnp.int32),          # the live rows
                pltpu.VMEM((nh, 1), jnp.float32),     # m
                pltpu.VMEM((nh, 1), jnp.float32),     # l
                pltpu.VMEM((nh, rank), jnp.float32),  # acc
                pltpu.VMEM((MLA_SLOTS, nh, rank), q_lat.dtype),  # q_lat's ring
                pltpu.VMEM((N, nh, rope), q_rope.dtype),
                pltpu.SemaphoreType.DMA((MLA_SLOTS + 1,)),
                pltpu.VMEM((nh, rank), q_lat.dtype),  # a live row's result
                pltpu.SemaphoreType.DMA((1,)),
                pltpu.VMEM((nh, rank), q_lat.dtype),  # a dead row's
                pltpu.SemaphoreType.DMA((1,)),
            ]),
        out_shape=jax.ShapeDtypeStruct(q_lat.shape, q_lat.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=64 * 1024 * 1024,
            disable_bounds_checks=True),
        interpret=interpret,
        name="mla_decode",
    )(jnp.asarray(layer, jnp.int32).reshape(1), tables,
      limits.astype(jnp.int32), q_lat, q_rope, pool)


def mla_decode(q_lat, q_rope, pool, layer, tables, limits, *, scale,
               q_tile: int = 1):
    """Absorbed latent attention of layer ``layer`` against the stacked
    latent pool, read where it lies.

    q_lat (N, nh, rank): each row's queries through ``W_uk``; q_rope
    (N, nh, rope): their rotated rope parts; pool (L, 1, NB, BS, rank + rope)
    (padded: :func:`latent_row`) left in HBM whole; ``layer`` a scalar-prefetch operand the DMAs index the
    pool by. The N rows come in tiles of ``q_tile``: a tile's rows are tokens
    of ONE sequence, whose table is ``tables[t]`` (N / q_tile, MAXB); row
    ``n`` may see the first ``limits[n]`` tokens of it (its position + 1; the
    tile's own tokens are in the pool already). ``q_tile`` 1 is decode (one
    row a sequence); a larger tile is a prefill chunk's segment, whose latent
    is streamed once a tile, not once a token. Returns the weighted latent
    (N, nh, rank) in q_lat's dtype.

    A row with ``limits`` 0 is dead. At ``q_tile`` 1 (:func:`_mla_rows_kernel`)
    one cell walks all the step's rows: a dead row costs a step of a scalar
    loop and a copy of zeros to its row of the result, a call with no live row
    starts no fetch, and the live rows' trips are one stream through a ring of
    :data:`MLA_SLOTS` buffers, the fetches ``MLA_SLOTS - 1`` trips ahead of
    the products, out of a row into the next live one; ``q_lat``, ``q_rope``
    and the result stay in HBM and the kernel moves a live row's by its own
    copies. A larger tile (:func:`_mla_kernel`) is a cell of its own: a tile
    all of whose rows are dead fetches nothing and its output is zeros, and
    a dead row inside a live tile sees nothing and gets a finite value nobody
    reads. As for :func:`paged_decode` the CALLER decides which rows are dead
    (the model: a row whose table names no block); the kernel never reads
    deadness out of the table, and a row with ``limits`` 1 and an all-zero
    table attends to the trash block's first token. A live row or tile
    fetches the blocks its largest ``limits`` reaches,
    :func:`mla_blocks_per_trip` a trip, and never the table's padding; what
    it computes does not depend on what else the call holds."""
    N, nh, rank = q_lat.shape
    rope = q_rope.shape[-1]
    _, _, _, BS, row = pool.shape
    if row != sum(latent_row(rank, rope)):
        raise ValueError(f"pool row {row} is not rank {rank} + rope {rope}, "
                         "padded to 128 lanes")
    if q_tile == 1:
        return _mla_rows(q_lat, q_rope, pool, layer, tables, limits,
                         scale=float(scale), interpret=_interpret())
    tiles, Q = N // q_tile, q_tile * nh
    kv_blocks = mla_blocks_per_trip(Q, pool)
    pad = -tables.shape[1] % kv_blocks
    if pad:           # a whole number of trips; the padding is never fetched
        tables = jnp.pad(tables, ((0, 0), (0, pad)))
    lim = limits.astype(jnp.int32)
    nblk = -(-jnp.max(lim.reshape(tiles, q_tile), axis=1) // BS)
    lim_rows = jnp.repeat(lim, nh).reshape(tiles, Q, 1)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,  # layer, tables, each tile's blocks
        grid=(tiles,),
        in_specs=[
            pl.BlockSpec((1, Q, rank), lambda t, *_: (t, 0, 0)),
            pl.BlockSpec((1, Q, rope), lambda t, *_: (t, 0, 0)),
            pl.BlockSpec((1, Q, 1), lambda t, *_: (t, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),  # the pool stays in HBM
        ],
        out_specs=pl.BlockSpec((1, Q, rank), lambda t, *_: (t, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, kv_blocks * BS, row), pool.dtype),  # double buffer
            pltpu.SemaphoreType.DMA((2, kv_blocks)),
            pltpu.VMEM((Q, 1), jnp.float32),      # m
            pltpu.VMEM((Q, 1), jnp.float32),      # l
            pltpu.VMEM((Q, rank), jnp.float32),   # acc
            pltpu.SMEM((1,), jnp.int32),          # the next trip's slot
        ],
    )
    out = tracing.pallas_call(
        functools.partial(_mla_kernel, block_size=BS, rank=rank, scale=scale,
                          kv_blocks=kv_blocks),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((tiles, Q, rank), q_lat.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=_interpret(),
        name="mla_decode" if q_tile == 1 else "mla_decode_segment",
    )(jnp.asarray(layer, jnp.int32).reshape(1), tables,
      jnp.pad(nblk, (0, 1)),       # the cell behind the last has no blocks
      q_lat.reshape(tiles, Q, rank), q_rope.reshape(tiles, Q, rope), lim_rows,
      pool)
    return out.reshape(N, nh, rank)


def mla_attend_xla(q_lat, q_rope, pool, layer, tables, limits, *, scale,
                   q_tile: int = 1):
    """:func:`mla_decode` by XLA: each tile's latent context gathered once
    (:func:`gather_context`) and attended to plainly, float32 softmax. The
    path off the TPU, and the kernel's comparison."""
    N, nh, rank = q_lat.shape
    tiles = N // q_tile
    c_kv, k_rope = gather_context(pool, layer, tables, rank)   # (tiles, T, 1, .)
    k_rope = k_rope[..., :q_rope.shape[-1]]
    ql = q_lat.reshape(tiles, q_tile, nh, rank)
    qr = q_rope.reshape(tiles, q_tile, nh, -1)
    s = (jnp.einsum("nqhr,ntr->nqht", ql, c_kv[:, :, 0],
                    preferred_element_type=jnp.float32)
         + jnp.einsum("nqhr,ntr->nqht", qr, k_rope[:, :, 0],
                      preferred_element_type=jnp.float32)) * scale
    seen = (jnp.arange(c_kv.shape[1])[None, None, :]
            < limits.reshape(tiles, q_tile)[:, :, None])
    p = jax.nn.softmax(jnp.where(seen[:, :, None, :], s, NEG_INF), axis=-1)
    out = jnp.einsum("nqht,ntr->nqhr", p.astype(q_lat.dtype), c_kv[:, :, 0],
                     preferred_element_type=jnp.float32)
    return out.reshape(N, nh, rank).astype(q_lat.dtype)
