"""Linear recurrences over per-sequence state slots: lightning attention,
the state-space duality (SSD, Mamba-2) form and the gated delta rule (Kimi
Delta Attention, KDA), one family.

Such a layer keeps no growing cache: per head one matrix ``S`` (key width x
value width, float32) that every token decays and writes to. Three
recurrences, told apart by what the caller passes::

    lightning   S_t = lambda_h S_{t-1} + k_t^T v_t            o_t = q_t S_t
    SSD         S_t = a_t,h S_{t-1} + k_t^T v_t               o_t = q_t S_t
    delta       S'  = Diag(alpha_t,h) S_{t-1}
                S_t = S' + beta_t,h k_t^T (v_t - k_t S')      o_t = q_t S_t

**The decay is an operand.** Lightning attention's ``a`` is a constant of the
head, ``lambda_h`` (:func:`head_decay_rates`), the same in every layer and no
parameter: a caller that passes no ``log_decay`` gets it. An SSD layer's is the
token's own, ``exp(dt_t,h A_h)``: the caller passes ``log_decay`` ``(rows,
heads)`` float32, never positive, so every power taken here is ``exp(<= 0)``.
The delta rule's is **a key channel's own**: ``log_decay`` ``(rows, heads,
dk)``, and with it ``beta`` ``(rows, heads)`` in (0, 1), which is what selects
the form (a static choice: the lightning and SSD programs hold nothing of it):
before a token writes ``k^T v`` it erases what the decayed state already
answers to its key (``k S'``, a read-out before the write).
Keys and queries may be a group's (lightning, SSD): ``q, k (rows, groups,
dk)`` beside ``v (rows, heads, dv)``, ``groups`` a divisor of ``heads`` and
``dk`` any width beside ``dv``. What else an SSD layer has (``dt`` on the
value, the skip ``D x``, its gate and norm) is the caller's, as are the delta
rule's L2 norms of q and k. The states of all layers of a group live in ONE
array ``(layers, slots, heads, dk, dv)`` float32, a **slot** a sequence: slot
0 is the trash slot that padding rows read and write (as block 0 of the KV
pool is), a live sequence holds one of the others from admission to its end.
The array rides the model's layer scan as a carry on a donated buffer and is
updated where it lies.

**The window array.** A causal convolution's window, the last ``taps - 1``
rows a sequence in the model's dtype, is a second slot array of the same
slots, ``(layers, 1 + slots, taps - 1, channels)`` (:func:`init_conv`), on
the same carry. Nobody but :func:`conv_rows` and :func:`conv_tiles` touches
it. On the chip such an array lies with its SLOTS as the second-minor
dimension (``bf16[5,129,3,12288]{3,1,2,0:T(8,128)(2,1)}``: the least
padding), whatever a program does with it: it IS the planes ``(layers, taps -
1, 1 + slots, channels)``, a slot one row of each, and a program that reads
``window[layer, slot]`` slabs relays the whole array into its layer scan and
back, every dispatch. So where the paged programs take kernels a
convolution without a bias works on the planes (:func:`_on_planes`): a
round's one-token rows through the kernel :func:`conv_decode`, which streams
a layer's planes through VMEM a block of channels at a time, the array pinned
in HBM and aliased to the result, and changes the live rows' slots; a mixed
step's tiles through :func:`conv_tiles`' scan, which reads and writes a slot
with the eight rows of its HBM tile. Elsewhere (the CPU, a biased
convolution, channels that are no lane tiles) both keep the XLA form on
``window[layer, slot]``.

Nobody zeroes a slot: a row at position 0 of its sequence starts from a zero
state whatever the slot held (``fresh``), so a slot handed to the next
sequence, or to a preempted one that recomputes from its prompt, cannot leak
the last owner's state.

Two forms of each recurrence:

- :func:`decode_rows`: rows of one token each, one sequence a row: the
  Pallas kernel :func:`linear_decode` where the paged programs take kernels
  (``paged_attention.kernels_wanted``), else the same arithmetic in XLA over
  all rows. The kernel's work follows the rows that hold a slot: a cell
  lists its live rows with a loop of scalar steps and walks them, each
  one's state block fetched, updated and written back by the kernel's own
  overlapped copies from the slot array left in HBM; a padding row costs
  its compare and touches no slot, the trash slot included. It takes q, k, v
  and gives o as the model's ``(rows, heads * d)`` rows, a head a lane tile:
  on a chip a model whose values are not 128 wide, or whose keys are no
  multiple of 128, keeps the XLA form;
- :func:`chunk_tiles`: tiles of ``C`` consecutive tokens of one sequence (a
  prefill chunk's segment), the blocked form, with ``c_i`` the running sum of
  the tile's log-decays up to row ``i`` (lightning: ``-rate * (i + 1)``)

      O   = ((Q K^T) * L) V + (Q * exp(c_i)) S_0
      S_n = exp(c_n) S_0 + sum_j exp(c_n - c_j) k_j^T v_j

  with ``L[i, j] = exp(c_i - c_j)`` for ``j <= i``. A tile may be valid only in
  its first ``n`` rows (the tail of a chunk); tiles are walked in row order,
  so two tiles of one sequence in one step see each other's state.

**Which invariant holds for which.** Lightning and SSD: every power is
``exp`` of a difference that is never positive: nothing overflows, and what
underflows is zero (``L`` is made from the differences, a (heads, C, C)
term). The delta rule's ``L`` would be a (C, C, dk) term a head, so its
blocked form (:func:`_delta_tiles`) factors ``exp(c_i - c_j)`` into ``(k *
exp(c)) (k * exp(-c))^T``, and ``exp(-c)`` is a power of a POSITIVE number: it
is bounded by working in sub-tiles of :data:`DELTA_SUB` rows, ``c`` counted
from the sub-tile's first row, under the caller's promise that a channel
loses at most :data:`DELTA_LOG_FLOOR` a token (KDA's ``kda_safe_gate`` lower
bound, -5: ``exp(16 * 5)`` fits float32). Sub-tiles join through the state,
whose decays are differences that are never positive.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...utils import tracing

# a lightning layer's recurrence: decay, update, read-out
tracing.layer_scopes("linear_attn")

_HI = jax.lax.Precision.HIGHEST

#: heads of one cell of :func:`linear_decode`: a state block of 8 x 128 x 128
#: float32 is 512 KiB, and the kernel's three buffers take 1.5 MiB (on the
#: chip, us a call over 48 rows of 32 heads at 0 | 11 | 48 live rows, PERF.md
#: 5: 4 heads 1.7 | 86.0 | 373.4, 8 heads 1.4 | 72.9 | 311.0, 16 heads 1.4 |
#: 71.7 | 307.3, 32 heads 1.0 | 70.7 | 307.3, the parent's row a cell 41.3 |
#: 104.4 | 309.2: from 8 heads on a live row takes what its 4.19 MB take at
#: 650 GB/s, and 3 to 6 buffers read alike)
DECODE_HEADS = 8
#: rows of a step one cell of :func:`linear_decode` covers at most (us a call
#: at 11 live rows of 48, a prefix | scattered: 16 rows a cell 73.7 | 75.4,
#: 24 rows 73.3 | 74.1, all 48 in one cell 72.9 | 72.9)
DECODE_CELL_ROWS = 64
#: rows of a sub-tile of the delta rule's blocked form, and the most a key
#: channel may lose a token there (``log_decay >= -DELTA_LOG_FLOOR``): inside a
#: sub-tile ``exp(-c)`` is at most ``exp(DELTA_SUB * DELTA_LOG_FLOOR)`` =
#: ``exp(80)`` = 5.5e34, which float32 holds
DELTA_SUB = 16
DELTA_LOG_FLOOR = 5.0


def head_decay_rates(n_heads: int) -> np.ndarray:
    """(n_heads,) float32 ``-log(lambda_h)``: the lightning-attention family's
    fixed slopes ``2^(-8 (h + 1) / n_heads)``."""
    h = np.arange(1, n_heads + 1, dtype=np.float64)
    return np.exp2(-8.0 * h / n_heads).astype(np.float32)


def init_state(layers: int, slots: int, heads: int, dk: int, dv: int):
    """The zeroed slot array ``(layers, 1 + slots, heads, dk, dv)`` float32;
    slot 0 is the trash slot."""
    return jnp.zeros((layers, 1 + slots, heads, dk, dv), jnp.float32)


def _to_heads(x, heads: int):
    """``x`` (..., groups, d) with a group's row repeated for each of its
    heads, (..., heads, d); itself where a head has its own."""
    groups = x.shape[-2]
    return x if groups == heads else jnp.repeat(x, heads // groups, axis=-2)


def decode_rows(state, layer, slots, q, k, v, fresh, log_decay=None,
                scope="linear_attn", beta=None):
    """One token a row. ``state``: the slot array; ``layer``: int32 scalar
    (traced or not); ``slots`` (R,) int32, 0 for a padding row; q, k (R, g,
    dk), ``g`` the heads or a divisor of them (a group's keys and queries), v
    (R, h, dv), q already scaled; ``fresh`` (R,) bool: the row is its
    sequence's first token; ``log_decay`` (R, h) float32, never positive: the
    row's own decay, or None for lightning's constant of the head. ``beta``
    (R, h) float32 selects the delta rule: ``log_decay`` is then (R, h, dk), a
    key channel's own, and ``g`` the heads. ``scope``: the
    ``jax.named_scope`` the recurrence's operations are traced under.
    Returns (o (R, h, dv) float32, new state)."""
    from .paged_attention import _interpret, kernels_wanted

    H, G = v.shape[1], q.shape[1]
    # Mosaic takes rows whose heads are whole lane tiles; the interpreter any
    lane_tiles = q.shape[2] % 128 == 0 and v.shape[2] == 128
    if (kernels_wanted() and H % DECODE_HEADS == 0
            and (G == H or (H // G) % DECODE_HEADS == 0)
            and (lane_tiles or _interpret())):
        return linear_decode(state, layer, slots, q, k, v, fresh, log_decay,
                             scope, beta)
    with jax.named_scope(scope):
        if beta is None:
            decay = jnp.exp(-jnp.asarray(head_decay_rates(H)))[None] \
                if log_decay is None else jnp.exp(log_decay)
            s = state[layer, slots]                            # (R, h, dk, dv)
            keep = jnp.where(fresh, 0.0, 1.0)[:, None] * decay
            s = s * keep[:, :, None, None] + (
                _to_heads(k, H).astype(jnp.float32)[..., :, None]
                * v.astype(jnp.float32)[..., None, :])
        else:
            kf, vf = k.astype(jnp.float32), v.astype(jnp.float32)
            keep = jnp.where(fresh, 0.0, 1.0)[:, None, None] \
                * jnp.exp(log_decay)
            s = state[layer, slots] * keep[..., None]
            # what the decayed state answers to the key, erased before the
            # write
            erase = jnp.einsum("rhk,rhkv->rhv", kf, s, precision=_HI)
            s = s + kf[..., :, None] * (
                beta[..., None] * (vf - erase))[..., None, :]
        o = jnp.einsum("rhk,rhkv->rhv", _to_heads(q, H).astype(jnp.float32),
                       s, precision=_HI)
        state = state.at[layer, slots].set(s)
    return o, state


def rows_per_cell(rows: int) -> int:
    """Rows of the step one cell of :func:`linear_decode` covers, from the
    step's row count alone (``paged_attention.rows_per_cell``'s rule): its
    largest divisor that is at most :data:`DECODE_CELL_ROWS` (all 48 of the
    ``serve-doc16k`` round and of its mixed step's one-token rows; a count
    with no such divisor but one keeps a row a cell)."""
    return max(d for d in range(1, DECODE_CELL_ROWS + 1) if rows % d == 0)


#: rows of a state block's key axis one piece of a live row's update holds
KEY_PIECE = 128


def _decode_kernel(layer_ref, slots_ref, fresh_ref, q_ref, k_ref, v_ref,
                   *rest, n_heads, operand, grouped, delta=False):
    """Grid (R / rpc, heads / hb): ONE cell per ``rpc`` rows of the step
    (:func:`rows_per_cell`) and block of ``hb`` heads (:data:`DECODE_HEADS`).
    v_ref, o_ref (rpc, hb * dv) are blocks of the model's lane-dense rows;
    q_ref, k_ref (rpc, hb * dk) likewise, or (``grouped``) the (rpc, dk) row
    of the one group the block's heads share; ``s_ref`` is the whole slot
    array where it lies in HBM, the call's aliased OUTPUT, read and written
    by the kernel's own copies through ``buf`` (3, hb, dk, dv). With
    ``operand`` a block ``d_ref`` (rpc, hb * dv) float32 comes behind v: each
    row's own decay of each head, a head a lane tile; without, the decay is
    lightning's constant of the head, made here. With ``delta`` two blocks
    come behind v: ``a_ref`` (rpc, hb * dk) float32, each row's decay of each
    key channel (laid out as k is), and ``b_ref`` (rpc, hb * dv) float32, each
    row's ``beta`` of each head over the head's lanes; a live row's block is
    decayed a key row at a time, read out against the key (``k S'``) and
    written with ``beta k^T (v - k S')``, all where it lies in its buffer: the
    block is still fetched once and stored once.

    The first head block of a row cell lists the cell's live rows
    (``slots > 0``) in ``live_ref`` with a loop of scalar steps and leaves
    their count behind them; every head block of the cell walks that list. A
    dead row costs its compare and nothing else: no copy names its slot (not
    the trash slot either) and its ``o`` row keeps the zeros the cell starts
    from.

    The walk is one pipeline over the row cell's (head block, live row)
    pairs, ``g`` counting them across its head blocks, pair ``g`` in buffer
    ``g % 3``: while pair ``g`` is computed where it lies in its buffer, the
    block of pair ``g + 1`` (the next live row, or the first one of the next
    head block: the hand-over) is on its way in and the write-back of pair
    ``g - 1`` on its way out; the buffer of pair ``g + 1`` is free once the
    write-back of pair ``g - 2`` is done. Only the first pair of a row cell
    waits for a fetch with nothing to hide it, and only its last head block
    waits for the write-backs to drain."""
    d_ref = rest[0] if operand else None
    a_ref, b_ref = rest[:2] if delta else (None, None)
    (_, o_ref, s_ref, buf, rsem, wsem, live_ref, q32, k32,
     v32) = rest[2 if delta else 1 if operand else 0:]
    del _  # the same buffer as ``s_ref``: input_output_aliases
    rpc, (nbuf, hb, dk, dv) = v_ref.shape[0], buf.shape
    kp = min(dk, KEY_PIECE)      # a state block's key axis goes in pieces
    pieces = dk // kp
    c, n_blocks = pl.program_id(1), pl.num_programs(1)
    row0 = pl.program_id(0) * rpc
    layer = layer_ref[0]

    @pl.when(c == 0)
    def _sort():
        def sort_row(r, n_live):
            # a dead row's number is overwritten by the next live row's
            live_ref[n_live] = r
            return n_live + (slots_ref[row0 + r] > 0).astype(jnp.int32)

        live_ref[rpc] = jax.lax.fori_loop(0, rpc, sort_row, 0)

    n_live = live_ref[rpc]

    def block(c, k):
        """The state of head block ``c`` of the cell's ``k``-th live row."""
        return s_ref.at[layer, slots_ref[row0 + live_ref[k]],
                        pl.ds(c * hb, hb)]

    def fetch(c, k, slot):
        return pltpu.make_async_copy(block(c, k), buf.at[slot], rsem.at[slot])

    def store(c, k, slot):
        return pltpu.make_async_copy(buf.at[slot], block(c, k), wsem.at[slot])

    @pl.when((c == 0) & (n_live > 0))
    def _cold():
        fetch(0, 0, 0).start()

    # every row's output starts as zeros, which is what a dead row keeps
    # (stored while the first fetch is on its way)
    o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)

    # the kernel's Python runs once a program in every process, compile
    # cache or none (``kernel.setup_trace_s``): a row's and a head's pieces
    # are cut and spread by ``lax`` primitives, which bind an equation each,
    # where ``jnp``'s indexing and operators trace a jitted function each
    def piece(x, axis, h, n=1):
        """``x[h * n:(h + 1) * n]`` along ``axis`` of a 2-D value."""
        lo, hi = [0, 0], list(x.shape)
        lo[axis], hi[axis] = h * n, (h + 1) * n
        return jax.lax.slice(x, lo, hi)

    def spread(x):
        return jax.lax.broadcast_in_dim(x, (kp, dv), (0, 1))

    def heads_of(staged, r, d):
        """Row ``r`` of a staged ``(rpc, hb * d)`` block as (hb, d)."""
        flat = staged[pl.ds(r, 1), :]
        return jax.lax.concatenate([piece(flat, 1, h, d) for h in range(hb)],
                                   0)

    def columns(staged, r):
        """Row ``r`` of staged q or k a head a column: [(kp, hb)] a piece of
        the key axis; of a group's row [(kp, dv)], the one column its heads
        share, spread once."""
        if not grouped:
            rows = heads_of(staged, r, dk)
            if pieces == 1:
                return [rows.T]
            return [piece(rows, 1, p, kp).T for p in range(pieces)]
        # a piece of the row eight times over is the tile Mosaic transposes
        # (a (1, 128) value it does not broadcast over sublanes)
        flat = staged[pl.ds(r, 1), :]
        return [spread(piece(jax.lax.concatenate(
            [piece(flat, 1, p, kp)] * 8, 0).T, 1, 0)) for p in range(pieces)]

    def column(cols, p, h):
        return cols[p] if grouped else spread(piece(cols[p], 1, h))

    def state_piece(slot, h, p):
        return (slot, h) if pieces == 1 else (slot, h, pl.ds(p * kp, kp))

    def update(k, decay):
        """The cell's ``k``-th live row, its block in flight or arrived;
        ``decay`` (hb, dv): lambda_h a row of lanes, a head a row (the
        constant; with ``operand`` the row's own is read here)."""
        g = c * n_live + k
        slot, free = jax.lax.rem(g, nbuf), jax.lax.rem(g + 1, nbuf)
        last = k + 1 == n_live

        @pl.when(g + 1 >= nbuf)
        def _buffer_is_free():
            # pair g + 1 - nbuf's: same bytes, same semaphore
            store(c, k, free).wait()

        @pl.when(jnp.logical_not(last) | (c + 1 < n_blocks))
        def _prefetch():
            fetch(jnp.where(last, c + 1, c), jnp.where(last, 0, k + 1),
                  free).start()

        r = live_ref[k]
        started = fresh_ref[row0 + r] == 0
        # q and k a head a column; v a head a row
        q_cols, k_cols = columns(q32, r), columns(k32, r)
        v_row = heads_of(v32, r, dv)
        if delta:
            a_cols = [jnp.where(started, a, 0.0) for a in columns(a_ref, r)]
            b_row = heads_of(b_ref, r, dv)
        else:
            keep = jnp.where(started, heads_of(d_ref, r, dv) if operand
                             else decay, 0.0)
        fetch(c, k, slot).wait()
        out = []
        for h in range(hb):
            read = None
            if delta:
                # S' = Diag(alpha) S, a piece at a time, and k S' over all
                # the pieces before any of them is written
                kept, erase = [], None
                for p in range(pieces):
                    kept.append(jax.lax.mul(column(a_cols, p, h),
                                            buf[state_piece(slot, h, p)]))
                    part = jax.lax.mul(column(k_cols, p, h), kept[p])
                    erase = part if erase is None else jax.lax.add(erase,
                                                                   part)
                write = spread(jax.lax.mul(piece(b_row, 0, h), jax.lax.sub(
                    piece(v_row, 0, h), jnp.sum(erase, axis=0,
                                                keepdims=True))))
            for p in range(pieces):
                at = state_piece(slot, h, p)
                if delta:
                    new = jax.lax.add(kept[p], jax.lax.mul(
                        column(k_cols, p, h), write))
                else:
                    new = jax.lax.add(
                        jax.lax.mul(spread(piece(keep, 0, h)), buf[at]),
                        jax.lax.mul(column(k_cols, p, h),
                                    spread(piece(v_row, 0, h))))
                buf[at] = new
                part = jax.lax.mul(column(q_cols, p, h), new)
                # the pieces folded before the one reduction over the rows
                read = part if read is None else jax.lax.add(read, part)
            out.append(jnp.sum(read, axis=0, keepdims=True))
        o_ref[pl.ds(r, 1), :] = jax.lax.concatenate(out, 1)
        store(c, k, slot).start()
        return decay

    @pl.when(n_live > 0)
    def _live_cell():
        # Mosaic reads no single row out of a packed bfloat16 block
        for staged, ref in ((q32, q_ref), (k32, k_ref), (v32, v_ref)):
            staged[...] = ref[...].astype(jnp.float32)
        # exp(-2^(-8 (h + 1) / n_heads))
        head = c * hb + 1 + jax.lax.broadcasted_iota(jnp.int32, (hb, dv), 0)
        jax.lax.fori_loop(0, n_live, update, jnp.exp(-jnp.exp(
            head.astype(jnp.float32) * (-8.0 * np.log(2.0) / n_heads))))

        @pl.when(c + 1 == n_blocks)
        def _drain():
            pairs = n_blocks * n_live

            def wait(g, _):
                store(c, 0, jax.lax.rem(g, nbuf)).wait()
                return 0

            jax.lax.fori_loop(jnp.maximum(pairs - nbuf + 1, 0), pairs, wait, 0)


def linear_decode(state, layer, slots, q, k, v, fresh, log_decay=None,
                  scope="linear_attn", beta=None):
    """:func:`decode_rows` as a Pallas kernel, in place on the slot array
    (left in HBM whole and aliased to the result). q, k, v go in and o comes
    out as the model holds them, ``(R, heads * d)`` lane-dense rows in their
    own dtype (o float32; q and k ``(R, groups * dk)`` where a group shares
    them): the column forms of q and k that the outer product and the
    read-out need are made in the kernel, a live row at a time. A row's own
    decay (``log_decay``) goes in as one more such block, ``exp`` taken and a
    head's spread over its ``dv`` lanes here (rows x heads x dv x 4 bytes: a
    five-hundredth of what the row's state moves). The delta rule
    (``beta`` (R, h), ``log_decay`` (R, h, dk)) hands the channels' decays in
    laid out as k is and ``beta`` as a head's scalar decay is, and runs as
    the kernel ``delta_decode``. The
    grid is ``(R / rpc, heads / DECODE_HEADS)``; a cell lists its live rows
    (``slots > 0``) and walks them, moving each one's state block in, updating
    it where it lies in VMEM and moving it back with its own overlapped
    copies (:func:`_decode_kernel`). A live row's state is read once and
    written once; a dead row reads and writes nothing of the slot array, the
    trash slot included, and costs a scalar step, not a grid step. No two
    live rows may name one slot."""
    from .paged_attention import _interpret

    R, H, dv = v.shape
    G, dk = q.shape[1:]
    def lanes(x):
        """A head's scalar (R, h) float32 over the head's ``dv`` lanes."""
        return jnp.broadcast_to(x.astype(jnp.float32)[:, :, None],
                                (R, H, dv)).reshape(R, H * dv)

    if beta is not None:
        decay = jnp.exp(log_decay.astype(jnp.float32)).reshape(R, H * dk)
        beta = lanes(beta)
    else:
        decay = None if log_decay is None else lanes(jnp.exp(
            log_decay.astype(jnp.float32)))
    o, state = _decode_call(
        jnp.asarray(layer, jnp.int32).reshape(1), slots.astype(jnp.int32),
        fresh.astype(jnp.int32), q.reshape(R, G * dk), k.reshape(R, G * dk),
        v.reshape(R, H * dv), decay, state, beta, heads=H, groups=G,
        hb=DECODE_HEADS, rpc=rows_per_cell(R), interpret=_interpret(),
        scope=scope)
    return o.reshape(R, H, dv), state


@functools.partial(jax.jit, inline=True, static_argnames=(
    "heads", "groups", "hb", "rpc", "interpret", "scope"))
def _decode_call(layer, slots, fresh, q, k, v, decay, state, beta=None, *,
                 heads, groups, hb, rpc, interpret, scope):
    """The call of :func:`linear_decode`, its equations inlined into the
    program that holds it. Jitted for its cache alone: a process binds the
    kernel once a program (four in a serving process: the decode round and
    the mixed step of the check's engine and of the cell's), and the body's
    Python, a third of a second to a second a bind on a busy host, runs for
    the first of them (``kernel.setup_trace_s``)."""
    R, dk, dv = q.shape[0], q.shape[1] // groups, v.shape[1] // heads
    delta = beta is not None
    grouped, operand = groups != heads, decay is not None and not delta
    # behind v: a head's decay (SSD), or the channels' decays and beta (delta)
    extra = [decay, beta] if delta else [decay] if operand else []
    per_group = heads // groups

    def lanes(d):
        return pl.BlockSpec((rpc, hb * d), lambda i, c, *_: (i, c))

    # a group's keys and queries: the one row its head blocks share
    keys = pl.BlockSpec((rpc, dk), lambda i, c, *_: (
        i, jax.lax.div(c * hb, per_group))) if grouped else lanes(dk)
    in_hbm = pl.BlockSpec(memory_space=pl.ANY)     # the slot array stays there
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,  # layer, slots, fresh
        grid=(R // rpc, heads // hb),
        in_specs=[keys, keys, lanes(dv), *(
            [lanes(dk), lanes(dv)] if delta else [lanes(dv)] if operand
            else []), in_hbm],
        out_specs=[lanes(dv), in_hbm],
        scratch_shapes=[
            pltpu.VMEM((3, hb, dk, dv), state.dtype),
            pltpu.SemaphoreType.DMA((3,)),         # the fetches'
            pltpu.SemaphoreType.DMA((3,)),         # the write-backs'
            pltpu.SMEM((rpc + 1,), jnp.int32),     # the live rows, their count
            *(pltpu.VMEM((rpc, (1 if grouped else hb) * dk), jnp.float32)
              for _ in "qk"),
            pltpu.VMEM((rpc, hb * dv), jnp.float32),
        ],
    )
    block_bytes = hb * dk * dv * state.dtype.itemsize
    with jax.named_scope(scope):
        return tracing.pallas_call(
            functools.partial(_decode_kernel, n_heads=heads, operand=operand,
                              grouped=grouped, delta=delta),
            grid_spec=grid_spec,
            out_shape=[jax.ShapeDtypeStruct((R, heads * dv), jnp.float32),
                       jax.ShapeDtypeStruct(state.shape, state.dtype)],
            # the slot array, scalars counted
            input_output_aliases={6 + len(extra): 1},
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary")),
            interpret=interpret,
            name="delta_decode" if delta else "linear_decode",
            attrs=dict(state_block_bytes=block_bytes, dk=dk, dv=dv,
                       rows_per_cell=rpc, groups=groups,
                       decay="channel" if delta else
                       "operand" if operand else "head"),
        )(layer, slots, fresh, q, k, v, *extra, state)


def _tile_decays(n_heads: int, tile: int):
    """The constants of a lightning tile: ``D`` (h, C, C) and the row decays
    ``lambda^i`` (h, C), ``i`` counted from 1."""
    rates = head_decay_rates(n_heads).astype(np.float64)[:, None, None]
    i = np.arange(tile)
    dist = i[:, None] - i[None, :]
    d = np.where(dist >= 0, np.exp(-rates * np.maximum(dist, 0)), 0.0)
    rows = np.exp(-rates[:, :, 0] * (i + 1)[None])
    return d.astype(np.float32), rows.astype(np.float32)


def chunk_tiles(state, layer, slots, counts, q, k, v, fresh, log_decay=None,
                scope="linear_attn", beta=None):
    """Tiles of ``C`` consecutive tokens. ``slots`` (N,) int32 the slot of
    each tile's sequence (0: an empty tile); ``counts`` (N,) int32 the valid
    rows of each tile, a prefix of it; q, k (N, C, g, dk), ``g`` the heads or
    a divisor of them, v (N, C, h, dv), q already scaled; ``fresh`` (N,)
    bool: the tile starts its sequence; ``log_decay`` (N, C, h) float32,
    never positive, or None for lightning's constant of the head. ``beta``
    (N, C, h) float32 selects the delta rule (:func:`_delta_tiles`):
    ``log_decay`` is then (N, C, h, dk), a key channel's own, not below
    ``-DELTA_LOG_FLOOR``. Returns (o
    (N, C, h, dv) float32, new state). Rows past a tile's count give garbage
    that nothing reads and add nothing to the state."""
    if beta is not None:
        return _delta_tiles(state, layer, slots, counts, q, k, v, fresh,
                            log_decay, beta, scope)
    N, C, H, _ = v.shape
    idx = jnp.arange(C)
    if log_decay is None:
        rates = jnp.asarray(head_decay_rates(H))
        d_const, row_decay = (jnp.asarray(a) for a in _tile_decays(H, C))

    def decays(n, ld):
        """What a tile of ``n`` valid rows needs of its decays: ``L`` (h, C,
        C), the rows' ``exp(c_i)`` (h, C), each valid row's part in the state
        the tile leaves ``exp(c_n - c_j)`` (h, C), nothing for the rest, and
        ``exp(c_n)`` (h,)."""
        valid = idx < n
        if ld is None:
            # lambda^(n-1-j) for the valid rows j < n
            w = jnp.where(valid[None], jnp.exp(
                -rates[:, None] * jnp.maximum(n - 1 - idx, 0)[None]), 0.0)
            return d_const, row_decay, w, jnp.exp(-rates * n)
        c = jnp.cumsum(jnp.where(valid[:, None], ld, 0.0), axis=0).T  # (h, C)
        lower = idx[:, None] >= idx[None, :]
        d = jnp.where(lower[None], jnp.exp(jnp.minimum(
            c[:, :, None] - c[:, None, :], 0.0)), 0.0)
        total = c[:, -1]            # the valid rows' sum: the rest add zero
        w = jnp.where(valid[None], jnp.exp(
            jnp.minimum(total[:, None] - c, 0.0)), 0.0)
        return d, jnp.exp(c), w, jnp.exp(total)

    def tile(state, args):
        slot, n, q, k, v, fresh, ld = args
        with jax.named_scope(scope):
            q, k = (_to_heads(a, H).astype(jnp.float32) for a in (q, k))
            v = v.astype(jnp.float32)
            d, rows, w, total = decays(n, ld)
            s0 = jnp.where(fresh, 0.0, 1.0) * state[layer, slot]  # (h, dk, dv)
            a = jnp.einsum("ihk,jhk->hij", q, k) * d
            o = (jnp.einsum("hij,jhv->ihv", a, v)
                 + jnp.einsum("ihk,hkv->ihv", q, s0, precision=_HI)
                 * rows.T[:, :, None])
            s = (total[:, None, None] * s0
                 + jnp.einsum("jhk,jhv->hkv", k * w.T[:, :, None], v,
                              precision=_HI))
            state = state.at[layer, slot].set(s)
        return state, o

    state, o = jax.lax.scan(
        tile, state, (slots, counts, q, k, v, fresh, log_decay))
    return o, state


def _unit_lower_inverse(low):
    """``(I + low)^-1`` of strictly lower triangular ``low`` (..., C, C), by
    forward substitution a row: row ``i`` of the inverse is ``e_i - low[i, :i]
    @ rows[:i]``."""
    C = low.shape[-1]
    eye = jnp.eye(C, dtype=low.dtype)
    rows = [jnp.broadcast_to(eye[0], low.shape[:-2] + (C,))]
    for i in range(1, C):
        rows.append(eye[i] - jnp.einsum(
            "...j,...jc->...c", low[..., i, :i], jnp.stack(rows, axis=-2),
            precision=_HI))
    return jnp.stack(rows, axis=-2)


def _delta_tiles(state, layer, slots, counts, q, k, v, fresh, log_decay, beta,
                 scope):
    """:func:`chunk_tiles` of the delta rule: q, k (N, C, h, dk), v (N, C, h,
    dv), ``log_decay`` (N, C, h, dk) in ``[-DELTA_LOG_FLOOR, 0]``, ``beta``
    (N, C, h). A tile goes in sub-tiles of :data:`DELTA_SUB` rows (all of it
    where it has fewer). With ``c_i`` the running sum of a sub-tile's
    log-decays from its first row, ``Kd = K * exp(c)``, ``Ki = K * exp(-c)``
    (the one power of a positive number: at most ``exp(DELTA_SUB *
    DELTA_LOG_FLOOR)``) and ``A = tril(Kd Ki^T, -1)``, the writes ``U`` of a
    sub-tile solve ``(I + diag(beta) A) U = diag(beta) (V - Kd S_0)``: with
    ``T`` the inverse (forward substitution), ``U = T beta V - (T beta Kd)
    S_0``, the two products made for all sub-tiles at once and only what
    holds ``S_0`` walked in row order::

        O   = (Q * exp(c)) S_0 + tril((Q * exp(c)) Ki^T) U
        S_n = exp(c_n) S_0 + (K * exp(c_n - c))^T U

    A row past its tile's count decays nothing and writes nothing (``beta``
    0), so ``c_n`` is the valid rows' sum."""
    N, C, H, dk = q.shape
    if C > DELTA_SUB and C % DELTA_SUB:
        raise ValueError(f"a tile of {C} rows is no multiple of the delta "
                         f"rule's sub-tile of {DELTA_SUB}")
    sub = min(C, DELTA_SUB)
    per = C // sub
    f32 = jnp.float32
    with jax.named_scope(scope):
        valid = (jnp.arange(C)[None] < counts[:, None])[..., None]  # (N, C, 1)
        beta = jnp.where(valid, beta.astype(f32), 0.0)
        log_decay = jnp.where(valid[..., None], log_decay.astype(f32), 0.0)

        def subs(x):
            return x.astype(f32).reshape(N * per, sub, *x.shape[2:])

        q, k, v, beta, log_decay = map(subs, (q, k, v, beta, log_decay))
        c = jnp.cumsum(log_decay, axis=1)                    # (M, sub, h, dk)
        total = c[:, -1]                                     # (M, h, dk)
        qd, kd, ki = q * jnp.exp(c), k * jnp.exp(c), k * jnp.exp(-c)
        k_end = k * jnp.exp(total[:, None] - c)
        idx = jnp.arange(sub)
        below = (idx[:, None] > idx[None, :])[None, None]    # j < i
        upto = (idx[:, None] >= idx[None, :])[None, None]    # j <= i
        a = jnp.einsum("mihk,mjhk->mhij", kd, ki, precision=_HI)
        t = _unit_lower_inverse(jnp.where(
            below, a * beta.transpose(0, 2, 1)[..., None], 0.0))
        t = t * beta.transpose(0, 2, 1)[:, :, None, :]       # T diag(beta)
        w_v = jnp.einsum("mhij,mjhv->mihv", t, v, precision=_HI)
        w_k = jnp.einsum("mhij,mjhk->mihk", t, kd, precision=_HI)
        b = jnp.where(upto, jnp.einsum("mihk,mjhk->mhij", qd, ki,
                                       precision=_HI), 0.0)
        # a sub-tile's slot is its tile's; only a tile's first can be fresh
        slot_of = jnp.repeat(slots, per)
        fresh_of = jnp.repeat(fresh, per) & (jnp.arange(N * per) % per == 0)

    def step(state, args):
        slot, fresh, qd, w_v, w_k, b, k_end, total = args
        with jax.named_scope(scope):
            s0 = jnp.where(fresh, 0.0, 1.0) * state[layer, slot]  # (h, dk, dv)
            u = w_v - jnp.einsum("ihk,hkv->ihv", w_k, s0, precision=_HI)
            o = (jnp.einsum("ihk,hkv->ihv", qd, s0, precision=_HI)
                 + jnp.einsum("hij,jhv->ihv", b, u, precision=_HI))
            s = (jnp.exp(total)[..., None] * s0
                 + jnp.einsum("jhk,jhv->hkv", k_end, u, precision=_HI))
            state = state.at[layer, slot].set(s)
        return state, o

    state, o = jax.lax.scan(
        step, state, (slot_of, fresh_of, qd, w_v, w_k, b, k_end, total))
    return o.reshape(N, C, H, -1), state


# -- the causal convolution before an SSD layer's recurrence ----------------

def init_conv(layers: int, slots: int, taps: int, channels: int, dtype):
    """The zeroed window array ``(layers, 1 + slots, taps - 1, channels)``: a
    sequence's last ``taps - 1`` rows of the convolution's input, the oldest
    first; slot 0 is the trash slot."""
    return jnp.zeros((layers, 1 + slots, taps - 1, channels), dtype)


def _on_planes(taps, bias, channels: int) -> bool:
    """Does a step's convolution run on the window array as the planes it is
    on the chip (:func:`conv_decode`; :func:`conv_tiles` beside it, so that a
    program holds the array in one layout)? Where the paged programs take
    kernels and the channels are whole lane tiles. A convolution with a bias
    (the SSD mixer's) keeps the XLA form whatever the kernel takes: the
    benchmark's own compile test of its cell
    (``tests/benchmark/test_chip_compile_falcon.py``) pins the custom calls
    of a hybrid layer's body at two, and is the benchmark's to change."""
    from .paged_attention import kernels_wanted

    return (kernels_wanted() and bias is None and taps.shape[0] > 1
            and channels % 128 == 0)


def conv_rows(window, layer, slots, x, taps, bias, fresh):
    """The depthwise causal convolution of one token a row: ``y = sum_i
    taps[i] * x_{t-K+1+i} + bias`` over the slot's ``K - 1`` rows and the
    row's own. ``window``: :func:`init_conv`'s array; ``slots`` (R,) int32;
    ``x`` (R, ch); ``taps`` (K, ch), the oldest first; ``bias`` (ch,) or None;
    ``fresh`` (R,) bool: the row starts its sequence, on zeros whatever the
    slot held. Returns (y (R, ch) float32, the window with each row's slot
    moved on by its row). The kernel :func:`conv_decode` where
    :func:`_on_planes` says so, else the same arithmetic in XLA over all rows
    (what the tests compare the kernel with)."""
    if _on_planes(taps, bias, x.shape[1]):
        return conv_decode(window, layer, slots, x, taps, bias, fresh)
    past = jnp.where(fresh[:, None, None], 0, window[layer, slots])
    full = jnp.concatenate([past, x[:, None].astype(window.dtype)], axis=1)
    y = jnp.sum(full.astype(jnp.float32) * taps.astype(jnp.float32)[None],
                axis=1)
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    return y, window.at[layer, slots].set(full[:, 1:])


#: channels of one cell of :func:`conv_decode` at most: a cell holds the
#: layer's windows of that many channels twice over, coming and going, and
#: three float32 stages of them, 6.7 MiB of VMEM at 129 slots of three rows
#: and 128 rows (on the chip, us a call there at 31 | 128 live rows, PERF.md
#: 5: 512 channels 54.1 | 66.3, 1024 49.5 | 56.5, 2048 49.4 | 52.6; 3072 do
#: not fit)
CONV_LANES = 1024


#: slots of the piece of the window array that :func:`conv_tiles` reads and
#: writes back around a tile's slot: the rows of one HBM tile (8, 128)
CONV_SLOT_ROWS = 8


def conv_lanes(channels: int) -> int:
    """Channels of one cell of :func:`conv_decode`: the most whole lane tiles
    that divide ``channels`` and are at most :data:`CONV_LANES`."""
    return max(d for d in range(128, CONV_LANES + 1, 128) if channels % d == 0)


def _conv_kernel(layer_ref, slots_ref, fresh_ref, x_ref, t_ref, *rest, bias):
    """Grid (channels / cb,): ONE cell per ``cb`` channels
    (:func:`conv_lanes`) of all the step's rows. ``w_ref``, ``o_ref`` (K - 1,
    1 + slots, cb): the layer's windows of those channels, a plane a tap and
    a slot a row, as the array lies in HBM (:func:`conv_decode`), the result
    aliased to the operand; ``x_ref`` (R, cb), ``y_ref`` (R, cb) float32,
    ``t_ref`` (K, cb) float32, with ``bias`` a (1, cb) float32 block behind
    it.

    The first cell lists the step's live rows (``slots > 0``) in
    ``live_ref`` with a loop of scalar steps, their count behind them, and
    says of every slot in ``mode_ref`` (1 + slots, 128) what its row is: 0
    none, 1 a row that goes on, 2 a fresh one. Every cell then makes what the
    windows give each SLOT (``sum_i taps[i] * plane_i``, zeros for a fresh
    slot's planes) over all slots at once, walks the live rows, each one's
    sum carried to its row of ``y_ref`` and its ``x`` row to its slot's row
    of ``xs``, adds the row's own tap (and the bias) over all rows at once,
    and writes the planes back moved on by a row where the slot is live and
    as they were where it is not: a dead row costs a scalar step, gets the
    ``y`` of a zero window, and no slot changes for it, the trash slot
    included."""
    b_ref = rest[0] if bias else None
    w_ref, y_ref, o_ref, live_ref, mode_ref, x32, ys, xs = rest[bias:]
    del layer_ref                # the blocks' index: the specs read it
    R, planes = x_ref.shape[0], w_ref.shape[0]
    f32 = jnp.float32

    @pl.when(pl.program_id(0) == 0)
    def _sort():
        mode_ref[...] = jnp.zeros(mode_ref.shape, jnp.int32)

        def sort_row(r, n_live):
            slot = slots_ref[r]
            live = (slot > 0).astype(jnp.int32)
            # a dead row's number is overwritten by the next live row's, and
            # it says of the trash slot what is true of it: no row
            live_ref[n_live] = r
            mode_ref[pl.ds(slot, 1), :] = jnp.full(
                (1, mode_ref.shape[1]), live * (1 + fresh_ref[r]), jnp.int32)
            return n_live + live

        live_ref[R] = jax.lax.fori_loop(0, R, sort_row, 0)

    mode = mode_ref[:, :1]
    live, fresh = mode > 0, mode > 1

    def tap(i):
        return t_ref[pl.ds(i, 1), :]

    def past(i):
        """Plane ``i`` as a row's convolution reads it."""
        return jnp.where(fresh, 0.0, w_ref[i].astype(f32))

    acc = tap(0) * past(0)
    for i in range(1, planes):
        acc = acc + tap(i) * past(i)
    ys[...] = acc
    x32[...] = x_ref[...].astype(f32)
    y_ref[...] = jnp.zeros(y_ref.shape, f32)

    def row(k, _):
        r = live_ref[k]
        slot = slots_ref[r]
        xs[pl.ds(slot, 1), :] = x32[pl.ds(r, 1), :]
        y_ref[pl.ds(r, 1), :] = ys[pl.ds(slot, 1), :]
        return 0

    jax.lax.fori_loop(0, live_ref[R], row, 0)
    y = y_ref[...] + tap(planes) * x32[...]
    y_ref[...] = y + b_ref[...] if bias else y
    for i in range(planes):
        moved = past(i + 1) if i + 1 < planes else xs[...]
        o_ref[i] = jnp.where(live, moved, w_ref[i].astype(f32)).astype(
            o_ref.dtype)


def conv_decode(window, layer, slots, x, taps, bias, fresh):
    """:func:`conv_rows` as a Pallas kernel, in place on the window array
    (aliased to the result). The array's second-minor dimension on the chip
    is its SLOTS, not a slot's ``K - 1`` rows (a device array ``(layers, 1 +
    slots, 3, channels)`` in bfloat16 lies as ``{3,1,2,0:T(8,128)(2,1)}``:
    the least padding), so the call takes it as the planes ``(layers, K - 1,
    1 + slots, channels)`` that it is there, which costs nothing (a transpose
    that is a bitcast), where a call on ``window[layer, slot]`` slabs has the
    whole array relaid into the layer scan and back, every dispatch. A slot
    is then one row of each plane, half of a packed pair of rows that no copy
    moves alone: a cell takes ALL the layer's slots of its channels
    (:func:`_conv_kernel`; ``K - 1`` rows a slot, where a slot of the float32
    state beside them is ``dk x dv`` rows a head) through the pipeline of its
    block specs, and the live rows decide what changes, not what moves. No
    two live rows may name one slot."""
    from .paged_attention import _interpret

    y, planes = _conv_call(
        jnp.asarray(layer, jnp.int32).reshape(1), slots.astype(jnp.int32),
        fresh.astype(jnp.int32), x.astype(window.dtype),
        taps.astype(jnp.float32),
        None if bias is None else bias.astype(jnp.float32)[None],
        jnp.swapaxes(window, 1, 2), lanes=conv_lanes(x.shape[1]),
        interpret=_interpret())
    return y, jnp.swapaxes(planes, 1, 2)


@functools.partial(jax.jit, inline=True,
                   static_argnames=("lanes", "interpret"))
def _conv_call(layer, slots, fresh, x, taps, bias, planes, *, lanes,
               interpret):
    """The call of :func:`conv_decode`, behind a jit for its cache alone, as
    :func:`_decode_call` is."""
    (R, ch), K = x.shape, taps.shape[0]
    S = planes.shape[2]
    extra = [] if bias is None else [bias]

    def rows(n):
        return pl.BlockSpec((n, lanes), lambda c, *_: (0, c))

    layers_planes = pl.BlockSpec((None, K - 1, S, lanes),
                                 lambda c, layer, *_: (layer[0], 0, 0, c))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,  # layer, slots, fresh
        grid=(ch // lanes,),
        in_specs=[rows(R), rows(K), *(rows(1) for _ in extra), layers_planes],
        out_specs=[rows(R), layers_planes],
        scratch_shapes=[
            pltpu.SMEM((R + 1,), jnp.int32),       # the live rows, their count
            pltpu.VMEM((S, 128), jnp.int32),       # what each slot's row is
            pltpu.VMEM((R, lanes), jnp.float32),   # x, a row a sublane
            pltpu.VMEM((S, lanes), jnp.float32),   # the windows' sums, a slot
            pltpu.VMEM((S, lanes), jnp.float32),   # the live rows' x, a slot
        ],
    )
    return tracing.pallas_call(
        functools.partial(_conv_kernel, bias=len(extra)),
        grid_spec=grid_spec,
        # the window array is the compiler's to place no more than the state
        # beside it: left where it can, a layer's call waits for a copy of
        # the whole array into VMEM and another one back
        out_shape=[jax.ShapeDtypeStruct((R, ch), jnp.float32),
                   pltpu.HBM(planes.shape, planes.dtype)],
        # the window array, scalars counted
        input_output_aliases={5 + len(extra): 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="conv_decode",
        attrs=dict(window_slot_bytes=(K - 1) * ch * planes.dtype.itemsize,
                   channels=ch, taps=K, lanes=lanes, slots=S),
    )(layer, slots, fresh, x, taps, *extra, planes)


def conv_tiles(window, layer, slots, counts, x, taps, bias, fresh):
    """:func:`conv_rows` over tiles of ``C`` consecutive tokens: a tile reads
    the ``K - 1`` rows before its first from its slot (zeros where ``fresh``)
    and leaves its last ``K - 1`` valid ones there. ``slots``, ``counts``,
    ``fresh`` (N,) as :func:`chunk_tiles` takes them, ``x`` (N, C, ch); tiles
    are walked in order, so a tile sees what the tile before it left. Returns
    (y (N, C, ch) float32, new window). Where the one-token rows beside them
    go through :func:`conv_decode` (:func:`_on_planes`) the tiles take the
    array as the planes it is too, and read and write a slot with the
    :data:`CONV_SLOT_ROWS` rows of its HBM tile: a lone row of each plane the
    compiler reads from a copy of the whole array in a layout of its own,
    every tile."""
    K, C = taps.shape[0], x.shape[1]
    w = taps.astype(jnp.float32)
    b = 0.0 if bias is None else bias.astype(jnp.float32)
    planes = _on_planes(taps, bias, x.shape[2])
    if planes:
        window = jnp.swapaxes(window, 1, 2)
        S = window.shape[2]
        rows = min(S, CONV_SLOT_ROWS)

    def tile(window, args):
        slot, n, x, fresh = args
        if planes:
            first = jnp.minimum(slot // rows * rows, S - rows)
            at = (layer, 0, first, 0)
            group = jax.lax.dynamic_slice(window, at,
                                          (1, K - 1, rows, x.shape[1]))
            mine = (jnp.arange(rows) == slot - first)[None, None, :, None]
            past = jnp.sum(jnp.where(mine & ~fresh, group, 0), axis=(0, 2))
        else:
            past = jnp.where(fresh, 0, window[layer, slot])   # (K - 1, ch)
        full = jnp.concatenate([past, x.astype(window.dtype)])
        f32 = full.astype(jnp.float32)
        y = sum(w[i] * f32[i:i + C] for i in range(K)) + b
        # the tokens n - K + 1 .. n - 1, counted from the tile's first
        last = jax.lax.dynamic_slice_in_dim(full, n, K - 1)
        if planes:
            return jax.lax.dynamic_update_slice(
                window, jnp.where(mine, last[None, :, None], group), at), y
        return window.at[layer, slot].set(last), y

    window, y = jax.lax.scan(tile, window, (slots, counts, x, fresh))
    return y, jnp.swapaxes(window, 1, 2) if planes else window
