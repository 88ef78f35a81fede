"""``mla_decode`` follows the live context of the step it is given.

The kernel (interpret mode) against ``gather_context`` and a plain softmax:
one-token rows and 16-row segment tiles over contexts of one block, exactly
one trip, one trip plus a token, several trips, and the widest trip plus a
remainder. A row with ``limits`` 0 is dead: with the trash block filled with
``inf``, a dead one-token row or a tile all of whose rows are dead reads
exact zeros and touches nothing, a dead row inside a live tile is finite, and
a live row reads the same whatever stands around it: behind a dead row,
behind a long row, or alone.

The one-token form walks all the step's rows in one cell, its live rows'
trips one stream through a ring of ``MLA_SLOTS`` buffers: live rows first,
last, alternating, none and all; rows whose trips run over each other's ends
(one, two, ``slots`` and ``slots + 2`` trips, a one-block row before a long
one, contexts on a trip's edge and a block past it); a row between
neighbours of every kind, bit for bit; row counts with no divisor but one;
and the bind record. The segment form hands its double buffer from cell to
cell.

The model's side (which rows it marks dead) is ``test_mla_padding_rows.py``.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.transformer import paged_attention as pa

NH, RANK, ROPE, BS = 4, 32, 8, 16
SCALE = 0.3
TRIP = BS * pa.mla_blocks_per_trip(
    NH, jax.ShapeDtypeStruct((2, 1, 1, BS, 128), jnp.float32))
#: contexts (tokens): one block, exactly one trip, one trip plus a token,
#: several trips, the widest trip plus a remainder
CONTEXTS = {"one_block": BS, "one_trip": TRIP, "trip_plus_one": TRIP + 1,
            "several_trips": 3 * TRIP, "trip_and_remainder": TRIP + 2 * BS + 5}
MAX_BLOCKS = 3 * TRIP // BS + 1      # a table width that is no whole trip


def case(contexts, q_tile, seed=0, trash=np.inf, width=MAX_BLOCKS):
    """Queries, a pool of 2 layers whose trash block holds ``trash``, tables
    (``width`` blocks wide) and limits for one cell a context: a context of 0
    is a dead cell (the zero table, ``limits`` 0); a live tile's rows are its
    context's last ``q_tile`` tokens."""
    rng = np.random.default_rng(seed)
    row = sum(pa.latent_row(RANK, ROPE))
    tiles = len(contexts)
    pool = rng.normal(0, 1, (2, 1, 1 + tiles * width, BS, row))
    pool[:, :, 0] = trash
    tables = np.zeros((tiles, width), np.int32)
    limits = np.zeros((tiles, q_tile), np.int32)
    for t, c in enumerate(contexts):
        if c:
            n = -(-c // BS)
            tables[t, :n] = 1 + t * width + np.arange(n)
            limits[t] = np.maximum(c - q_tile + 1 + np.arange(q_tile), 0)
    q_lat = rng.normal(0, 1, (tiles * q_tile, NH, RANK))
    q_rope = rng.normal(0, 1, (tiles * q_tile, NH, ROPE))
    return (jnp.asarray(q_lat, jnp.float32), jnp.asarray(q_rope, jnp.float32),
            jnp.asarray(pool, jnp.float32), jnp.asarray(tables),
            jnp.asarray(limits.reshape(-1)))


def plain(q_lat, q_rope, pool, tables, limits, q_tile):
    """``gather_context`` and a plain softmax, a row at a time (numpy, the
    trash block's ``inf`` never multiplied: keys past ``limits`` are cut)."""
    c_kv, k_rope = pa.gather_context(pool, jnp.int32(1), tables, RANK)
    c_kv = np.asarray(c_kv[:, :, 0], np.float64)
    k_rope = np.asarray(k_rope[:, :, 0, :ROPE], np.float64)
    out = np.zeros(q_lat.shape, np.float64)
    for n in range(q_lat.shape[0]):
        t, lim = n // q_tile, int(limits[n])
        if not lim:
            continue
        s = (np.asarray(q_lat[n], np.float64) @ c_kv[t, :lim].T
             + np.asarray(q_rope[n], np.float64) @ k_rope[t, :lim].T) * SCALE
        p = np.exp(s - s.max(-1, keepdims=True))
        out[n] = (p / p.sum(-1, keepdims=True)) @ c_kv[t, :lim]
    return out


def decode(args, q_tile):
    q_lat, q_rope, pool, tables, limits = args
    return np.asarray(pa.mla_decode(q_lat, q_rope, pool, jnp.int32(1), tables,
                                    limits, scale=SCALE, q_tile=q_tile))


@pytest.mark.parametrize("context", sorted(CONTEXTS))
@pytest.mark.parametrize("q_tile", [1, pa.SEGMENT_TILE])
def test_kernel_matches_gather_plus_plain_softmax(q_tile, context):
    """A live cell of each context between dead cells and in front of one:
    the trash block (all ``inf``) is never read, a dead cell is exact
    zeros."""
    c = max(CONTEXTS[context], q_tile)
    contexts = (0, c, 0, 0, BS + 3 if BS + 3 >= q_tile else q_tile, 0)
    args = case(contexts, q_tile)
    got = decode(args, q_tile)
    assert np.isfinite(got).all()
    want = plain(*args, q_tile)
    np.testing.assert_allclose(got, want, atol=2e-5)
    dead = np.repeat(np.asarray(contexts) == 0, q_tile)
    assert not got[dead].any()
    assert np.abs(got[~dead]).max() > 0.01


@pytest.mark.parametrize("q_tile", [1, pa.SEGMENT_TILE])
def test_xla_form_is_finite_on_dead_rows_and_equal_on_live_ones(q_tile):
    """Off the TPU the same limits go to ``mla_attend_xla``: a dead row is a
    softmax of equal scores over a finite pool, a live row the plain
    softmax's."""
    contexts = (0, max(TRIP + 1, q_tile), 0)
    args = case(contexts, q_tile, trash=0.0)
    q_lat, q_rope, pool, tables, limits = args
    got = np.asarray(pa.mla_attend_xla(q_lat, q_rope, pool, jnp.int32(1),
                                       tables, limits, scale=SCALE,
                                       q_tile=q_tile))
    assert np.isfinite(got).all()
    live = np.asarray(limits) > 0
    np.testing.assert_allclose(got[live], plain(*args, q_tile)[live],
                               atol=2e-5)


def test_dead_row_inside_a_live_tile_is_finite():
    """A chunk that ends inside a tile: the rows behind its last token carry
    ``limits`` 0. The tile is live, its dead rows finite, its live rows the
    plain softmax's."""
    q_tile = pa.SEGMENT_TILE
    args = list(case((TRIP + BS, 0), q_tile))
    limits = np.asarray(args[4]).copy()
    limits[5:q_tile] = 0                     # five live rows, eleven dead
    args[4] = jnp.asarray(limits)
    got = decode(args, q_tile)
    assert np.isfinite(got).all()
    want = plain(*args, q_tile)
    np.testing.assert_allclose(got[:5], want[:5], atol=2e-5)
    assert not got[q_tile:].any()            # the dead tile behind it


@functools.lru_cache(maxsize=None)
def lone_row(c, q_tile):
    """A row of context ``c`` alone in its call: the case and what the
    kernel gives for it."""
    alone = case((c,), q_tile, seed=5)
    return alone, decode(alone, q_tile)


@pytest.mark.parametrize("q_tile", [1, pa.SEGMENT_TILE])
@pytest.mark.parametrize("before", ["dead", "long", "short", "first"])
def test_live_row_reads_the_same_whatever_stands_before_it(q_tile, before):
    """What the fetches ahead of a row leave behind (the ring's hand-over
    from row to row, the segment form's double buffer from cell to cell): the
    same row alone, behind a dead row, behind a row of many trips (an odd and
    an even number of them, so it starts in either slot) and behind a
    one-trip row, bit for bit."""
    c = max(TRIP + BS + 1, q_tile)
    alone, want = lone_row(c, q_tile)
    fronts = {"dead": [(0,), (0, 0)], "long": [(3 * TRIP,), (2 * TRIP,)],
              "short": [(max(BS, q_tile),), (max(BS, q_tile), 0)],
              "first": [()]}[before]
    for front in fronts:
        contexts = front + (c, 0, max(2 * TRIP, q_tile))
        args = list(case(contexts, q_tile, seed=9))
        at = len(front)
        # the row under test: the lone case's queries and blocks
        q_lat, q_rope, pool, tables, limits = args
        rows = slice(at * q_tile, (at + 1) * q_tile)
        q_lat = q_lat.at[rows].set(alone[0])
        q_rope = q_rope.at[rows].set(alone[1])
        n = -(-c // BS)
        pool = pool.at[:, :, tables[at, :n]].set(alone[2][:, :, alone[3][0, :n]])
        got = decode((q_lat, q_rope, pool, tables, limits), q_tile)
        np.testing.assert_array_equal(got[rows], want)
        np.testing.assert_allclose(
            got, plain(q_lat, q_rope, pool, tables, limits, q_tile), atol=2e-5)


#: which rows of a cell of six one-token rows are live
LIVE_ROWS = {"first": (1, 1, 1, 0, 0, 0), "last": (0, 0, 0, 1, 1, 1),
             "alternating": (1, 0, 1, 0, 1, 0), "none": (0,) * 6,
             "all": (1,) * 6}


def check_rows(contexts, **kw):
    """One-token rows of ``contexts`` in one call against the plain softmax:
    live rows to the file's tolerance, dead rows exact zeros, nothing read
    out of the trash block."""
    args = case(contexts, 1, **kw)
    got = decode(args, 1)
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, plain(*args, 1), atol=2e-5)
    dead = np.asarray(contexts) == 0
    assert not got[dead].any()
    if not dead.all():
        assert np.abs(got[~dead]).max() > 0.01


@pytest.mark.parametrize("live", sorted(LIVE_ROWS))
def test_live_rows_anywhere_in_a_cell_of_several_rows(live):
    """The one-token form's cell holds the step's rows: its live rows, of one
    block to several trips, are walked wherever they stand, and a dead row
    keeps zeros."""
    lengths = (BS + 3, TRIP + 1, 2 * TRIP + BS, 5, TRIP, 3 * TRIP)
    check_rows(tuple(c * on for c, on in zip(lengths, LIVE_ROWS[live])))


SLOTS = pa.MLA_SLOTS
#: rows of one cell whose trips run over each other's ends in the ring
STREAMS = {
    "trips_1_2_slots_and_two_more": (
        TRIP - 5, 2 * TRIP - 5, SLOTS * TRIP, (SLOTS + 2) * TRIP - 7),
    "two_more_trips_than_slots_first": ((SLOTS + 2) * TRIP, 1, TRIP + 1),
    "one_block_before_a_long_row": (3, 4 * TRIP + 1),
    "one_block_rows": (1, BS, 2, BS - 1, 7),
    "on_a_trips_edge": (TRIP, 2 * TRIP, TRIP),
    "a_block_past_the_edge": (TRIP + BS, 2 * TRIP + BS, TRIP + 1),
    "half_a_trip_and_a_block_more": (TRIP // 2, TRIP // 2 + BS,
                                     TRIP + TRIP // 2, TRIP + TRIP // 2 + 1),
}


@pytest.mark.parametrize("stream", sorted(STREAMS))
def test_the_stream_runs_over_row_ends(stream):
    """A cell's trips are one stream: the fetches run ``slots - 1`` trips
    ahead of the products, out of a row into the next live one. Rows of one,
    two, ``slots`` and ``slots + 2`` trips, a one-block row before a long
    one, contexts that end on a trip's edge, a block and half a trip past
    it."""
    check_rows(STREAMS[stream], width=(SLOTS + 2) * TRIP // BS, seed=3)


@pytest.mark.parametrize("rows", [1, 7, 13])
def test_a_row_count_with_no_divisor_but_one(rows):
    """The cell takes the step's rows whatever their count: a prime number
    of them, dead and alive, is still right."""
    lengths = (TRIP + 2, 0, BS, 0, 0, 2 * TRIP + 1, 9)
    check_rows(tuple(lengths[i % len(lengths)] for i in range(rows)), seed=rows)


@pytest.mark.parametrize("after", ["nothing", "dead", "short", "long"])
@pytest.mark.parametrize("before", ["dead", "short", "long"])
def test_live_row_reads_the_same_whatever_stands_around_it_in_its_cell(
        before, after):
    """A one-token row between neighbours of its cell: what the fetches
    ahead of it and behind it bring (a dead row, a one-block row, a row of
    more trips than the ring has slots) does not reach its result, bit for
    bit."""
    c = TRIP + BS + 1
    alone, want = lone_row(c, 1)
    rows = {"nothing": (), "dead": (0, 0), "short": (BS,),
            "long": ((SLOTS + 1) * TRIP,)}
    contexts = rows[before] + (c,) + rows[after]
    width = (SLOTS + 1) * TRIP // BS
    q_lat, q_rope, pool, tables, limits = case(contexts, 1, seed=9,
                                               width=width)
    at = len(rows[before])
    q_lat = q_lat.at[at].set(alone[0][0])
    q_rope = q_rope.at[at].set(alone[1][0])
    n = -(-c // BS)
    pool = pool.at[:, :, tables[at, :n]].set(alone[2][:, :, alone[3][0, :n]])
    got = decode((q_lat, q_rope, pool, tables, limits), 1)
    np.testing.assert_array_equal(got[at], want[0])
    np.testing.assert_allclose(
        got, plain(q_lat, q_rope, pool, tables, limits, 1), atol=2e-5)


def test_the_bind_record_says_how_the_round_is_walked():
    """``tracing.builds()``: the one-token kernel's bind says how many rows a
    cell takes, the ring's slots, the trip's blocks and bytes and what it
    multiplies in; the segment kernel keeps a tile a cell."""
    from deepspeed_tpu.utils import tracing

    contexts = (TRIP + 1, 0, BS, 0, 5)
    # a table width of its own: the call is traced once a shape in a process
    q_lat, q_rope, pool, tables, limits = case(contexts, 1, trash=0.0,
                                               width=MAX_BLOCKS + 5)
    mark = tracing.clock_ns()
    jax.jit(lambda *a: pa.mla_decode(*a, scale=SCALE)).lower(
        q_lat, q_rope, pool, jnp.int32(1), tables, limits).compile()
    attrs, = [rec.attrs["kernel_attrs"] for rec in tracing.builds()
              if rec.end > mark and rec.attrs.get("kernel_attrs")]
    row = sum(pa.latent_row(RANK, ROPE))
    assert attrs == {"mla_decode": {
        "rows_per_cell": len(contexts), "slots": pa.MLA_SLOTS,
        "blocks_per_trip": TRIP // BS, "trip_bytes": TRIP * row * 4,
        "operand_dtype": "float32"}}


def test_trip_width_follows_the_cell_not_a_name():
    """From the cell's query rows and the pool's block alone: sixteen blocks
    a trip for a one-token row of 64 heads over the cells' blocks of 64
    tokens, eight for a segment tile of sixteen such rows, and never more
    than the score tile allows."""
    pool = jax.ShapeDtypeStruct((8, 1, 2560, 64, 640), jnp.bfloat16)
    assert pa.mla_blocks_per_trip(64, pool) == pa.MLA_TRIP_BLOCKS == 16
    assert pa.mla_blocks_per_trip(pa.SEGMENT_TILE * 64, pool) == 8
    assert pa.mla_blocks_per_trip(1 << 20, pool) == 1
