"""The vocabulary head and its loss as one unit with its own backward.

``head_nll(x, w, labels)`` is ``logsumexp(x @ w^T) - (x @ w^T)[label]`` a
token (TPU-native counterpart of the reference's fused logits/loss path, the
CUDA softmax of ``csrc/transformer/softmax_kernels.cu`` and the
``logits_gather`` of ``deepspeed/inference/v2/kernels/ragged_ops``). Under
its ``jax.custom_vjp`` the only ``[tokens, vocab]`` array is the logits in the
activations' dtype, the residual the step saves anyway: the label's logit is
gathered from them (no float32 copy is written for a gather), and the
backward forms ``softmax - onehot`` from them, cast to the activations' dtype
before the two products as autodiff's own backward does.

Two paths, chosen from the shapes and the mesh alone (``_plan``):

* plain: the expressions above as XLA compiles them, for every shape, a head
  bias and a head sharded over the vocabulary;
* fused: two Pallas kernels over a grid of (vocabulary tile, row tile), the
  vocabulary outermost, so the weight is streamed once a direction.
  ``fused_ce_fwd`` writes each logits tile once and keeps a running maximum
  and a rescaled running sum of exponentials a row, both taken over the
  *rounded* tile, the values that are saved; ``fused_ce_bwd`` forms
  ``softmax - onehot`` once a tile and feeds both products with it: the
  weight's gradient tile stays in VMEM across the row axis, the activations'
  gradient is one float32 accumulator in VMEM for the whole call.

The weight is read where it lies: ``(V, H)`` (the tied embedding table,
``vocab_major``) or ``(H, V)`` (an untied head), never through a transposed
copy.

``head_logits(x, w)`` is the product alone for the few rows of a served step
(``stream_block`` says when): the same walk of the vocabulary, the table
streamed from HBM once under the products and each logits tile written once,
with no row statistics. Left to XLA, a served program's table of at most
VMEM's size is copied HBM -> VMEM whole by a cross-program prefetch that the
program's first op, the embedding gather, waits for, and then multiplied out
of VMEM (PERF.md 5, ``serve-chat``); a kernel's operand is no candidate for
that prefetch.

Status (my chip runs, PR 56, TPU v5e, ``.bench_scratch/kbench.py``: the unit's
``value_and_grad`` behind the final norm, eight calls in one jitted loop,
device time a call from the profiler; PERF.md 5 has every form tried). At
``(8192, 1024) x (50304, 1024)`` tied: autodiff of the float32 loss 16.64 ms;
the plain path here 16.10 (no float32 logits); fused 14.18, ``fused_ce_fwd``
4.53 for XLA's 4.43 + 1.33 (product, then a pass for the sum of
exponentials) and ``fused_ce_bwd`` 8.85 for XLA's 5.25 + 4.74. A grid step
that is one product behind ``pl.when`` branches lost to XLA (5.6-6.1 and
10.0-10.9): what won is the step as straight code over row chunks. At
``(8192, 2048) x (2048, 50304)`` untied: 32.48, 31.47 and, forward kernel
alone, 30.02 (8.85 for 8.88 + 1.33). The kernels of before PR 56 (rows
outermost, the weight's gradient as a partial sum a row block) do not fit
VMEM at the first shape and were never in a step.
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
from ..pallas_utils import one_device, open_mesh_axes, pallas_interpret

LANES = 128
#: VMEM the kernels ask for, of the v5e's 128 MiB; a call that would need more
#: stays XLA's (the backward at a width of 2048 and 8192 tokens a chip)
VMEM_LIMIT = 100 * 1024 * 1024
#: rows a product in a grid step's straight code: the next chunk's product
#: runs under this chunk's exponentials (PERF.md 5: 128 | 256 | 512 | whole)
CHUNK = 256
#: rows up to which :func:`head_logits` takes a head: below the chip's ridge
#: (197 TFLOP/s over 819 GB/s: 240 rows of bf16) the product is bound by the
#: table's bytes; a prefill's or an evaluation's rows are bound by the
#: products, where XLA is at its rate
STREAM_ROWS = 256
#: the v5e's VMEM: a table that fits is the one XLA's cross-program prefetch
#: would copy there before a served program's first op
VMEM_BYTES = 128 * 1024 * 1024
#: VMEM :func:`head_logits` may take (two table tiles, the rows, two logits
#: tiles), inside the 16 MiB a call gets without asking
STREAM_VMEM = 12 * 1024 * 1024
LSE, G, LABEL = 0, 1, 2   # lanes of the backward's one (rows, 128) row table
_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("arbitrary", "arbitrary"), vmem_limit_bytes=VMEM_LIMIT)


def _product(a, b, contract):
    return jax.lax.dot_general(a, b, ((contract[:1], contract[1:]), ((), ())),
                               preferred_element_type=jnp.float32)


# ----------------------------------------------------------------------------
# forward: logits tile, running maximum and sum of exponentials
# ----------------------------------------------------------------------------

def _fwd_kernel(x_ref, w_ref, lg_ref, stat_ref, *, vocab_major, chunk):
    j, i = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _first():
        lane = jax.lax.broadcasted_iota(jnp.int32, stat_ref.shape[1:], 1)
        stat_ref[i] = jnp.where(lane == 0, -jnp.inf, 0.0)

    # straight code, a chunk of rows at a time: the next chunk's product runs
    # under this chunk's exponentials, which a grid step's would not
    w = w_ref[...]
    for c in range(0, x_ref.shape[1], chunk):
        rows = slice(c, c + chunk)
        s = _product(x_ref[i, rows], w, (1, 1 if vocab_major else 0))
        rounded = s.astype(lg_ref.dtype)
        lg_ref[rows] = rounded
        s = rounded.astype(jnp.float32)        # the values that are saved
        m = stat_ref[i, rows, 0:1]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        stat_ref[i, rows, 1:2] = (
            stat_ref[i, rows, 1:2] * jnp.exp(m - m_new)
            + jnp.sum(jnp.exp(s - m_new), axis=-1, keepdims=True))
        stat_ref[i, rows, 0:1] = m_new


def _whole(shape):
    """A block that is the whole array, fetched once and kept: one buffer."""
    return pl.BlockSpec(shape, lambda j, i: (0,) * len(shape),
                        pipeline_mode=pl.Buffered(1))


def _w_spec(H, block_v, vocab_major):
    if vocab_major:
        return pl.BlockSpec((block_v, H), lambda j, i: (j, 0))
    return pl.BlockSpec((H, block_v), lambda j, i: (0, j))


def _fwd_call(x, w, vocab_major, block_r, block_v):
    """(logits (N, V) in x's dtype, lse (N,) float32)."""
    N, H = x.shape
    V = w.shape[0 if vocab_major else 1]
    nr = N // block_r
    lg, stat = tracing.pallas_call(
        functools.partial(_fwd_kernel, vocab_major=vocab_major,
                          chunk=min(CHUNK, block_r)),
        grid=(V // block_v, nr),
        in_specs=[_whole((nr, block_r, H)), _w_spec(H, block_v, vocab_major)],
        out_specs=[pl.BlockSpec((block_r, block_v), lambda j, i: (i, j)),
                   _whole((nr, block_r, LANES))],
        out_shape=[jax.ShapeDtypeStruct((N, V), x.dtype),
                   jax.ShapeDtypeStruct((nr, block_r, LANES), jnp.float32)],
        compiler_params=_PARAMS,
        interpret=pallas_interpret(),
        name="fused_ce_fwd",
    )(x.reshape(nr, block_r, H), w)
    stat = stat.reshape(N, LANES)
    return lg, stat[:, 0] + jnp.log(stat[:, 1])


# ----------------------------------------------------------------------------
# the served head: the logits alone, the table streamed once
# ----------------------------------------------------------------------------

def _logits_kernel(x_ref, w_ref, lg_ref, *, vocab_major):
    # a body of its own and not ``_fwd_kernel`` with its statistics thrown
    # away: those are a maximum, an exponential and two lane reductions a
    # tile that nobody reads, and the training bind stays what it was
    lg_ref[...] = _product(x_ref[0], w_ref[...],
                           (1, 1 if vocab_major else 0)).astype(lg_ref.dtype)


def stream_block(x, w, vocab_major):
    """The vocabulary tile with which :func:`head_logits` takes the head of
    ``x`` (..., H) over ``w``, or None where the product stays XLA's. From
    what the head is handed alone: a vocabulary-major table of at most
    VMEM's size (the tied table, which a served program's first op gathers
    from, so its copy to VMEM is waited for in full; an untied ``(H, V)``
    head's prefetch is waited for by the head, the program's last product,
    and hides under the layers: PERF.md 7 has the read of every serving
    cell's programs), vocabulary and width multiples of 128, one dtype, at
    most ``STREAM_ROWS`` rows, and no mesh of several devices in sight (XLA
    partitions its own product over one, a Mosaic kernel it cannot)."""
    H = x.shape[-1]
    N = math.prod(x.shape[:-1])
    if (not vocab_major or x.dtype != w.dtype or H % LANES
            or not 0 < N <= STREAM_ROWS or not one_device()):
        return None
    V, item = w.shape[0], x.dtype.itemsize
    if V * H * item > VMEM_BYTES:
        return None
    rows = _padded(N, item)
    return next((v for v in (512, 384, 256, 128) if V % v == 0 and
                 (2 * v * H + rows * H + 2 * rows * v) * item <= STREAM_VMEM),
                None)


def _padded(rows, itemsize):
    """``rows`` up to the sublane tile: 8 rows of 32 bits, 16 of bfloat16."""
    tile = 8 * max(1, 4 // itemsize)
    return -(-rows // tile) * tile


def head_logits(x, w, *, vocab_major, block_v):
    """``x @ w^T`` in ``x``'s dtype from float32 sums, as XLA's product of
    the same operands gives it: ``x`` (..., H), ``w`` (V, H) if
    ``vocab_major`` else (H, V), ``block_v`` from :func:`stream_block`. One
    call whose grid walks the vocabulary: the rows stay in VMEM whole, a
    table tile is fetched under the tile before's product, a logits tile is
    written once, and the table is read from HBM once. Differentiable (an
    evaluation's logits under somebody's own loss): the backward is XLA's
    two products."""
    *lead, H = x.shape
    lg = _stream(x.reshape(-1, H), w, vocab_major, block_v)
    return lg.reshape(*lead, lg.shape[1])


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _stream(x, w, vocab_major, block_v):
    N = x.shape[0]
    rows = _padded(N, x.dtype.itemsize)
    # padded for one row or a verify step's odd count, not for a round's 64
    lg = _logits_call(jnp.pad(x, ((0, rows - N), (0, 0))) if rows != N else x,
                      w, vocab_major=vocab_major, block_v=block_v,
                      interpret=pallas_interpret())
    return lg[:N] if rows != N else lg


def _stream_fwd(x, w, vocab_major, block_v):
    return _stream(x, w, vocab_major, block_v), (x, w)


def _stream_bwd(vocab_major, block_v, res, dlg):
    x, w = res
    return (dlg @ (w if vocab_major else w.T),
            dlg.T @ x if vocab_major else x.T @ dlg)


_stream.defvjp(_stream_fwd, _stream_bwd)


@functools.partial(jax.jit, inline=True,
                   static_argnames=("vocab_major", "block_v", "interpret"))
def _logits_call(x, w, *, vocab_major, block_v, interpret):
    """The call of :func:`head_logits`, its equations inlined into the
    program that holds it; jitted for its cache alone, so that a process
    traces the body once a shape and not once a program (a serving process
    holds four: the round and the mixed step, greedy and not)."""
    N, H = x.shape
    V = w.shape[0 if vocab_major else 1]
    return tracing.pallas_call(
        functools.partial(_logits_kernel, vocab_major=vocab_major),
        dict(rows=N, block_v=block_v, table_bytes=w.size * w.dtype.itemsize,
             vocab_major=vocab_major, operand_dtype=x.dtype.name),
        grid=(V // block_v, 1),
        in_specs=[_whole((1, N, H)), _w_spec(H, block_v, vocab_major)],
        out_specs=pl.BlockSpec((N, block_v), lambda j, i: (0, j)),
        out_shape=jax.ShapeDtypeStruct((N, V), x.dtype),
        interpret=interpret,
        name="head_logits",
    )(x.reshape(1, N, H), w)


# ----------------------------------------------------------------------------
# backward: softmax - onehot once a tile, into both products
# ----------------------------------------------------------------------------

def _bwd_kernel(lg_ref, row_ref, xt_ref, w_ref, dx_ref, dw_ref, acc_ref, *,
                vocab_major, block_v, chunk):
    j, i = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _dx_first():
        dx_ref[i] = jnp.zeros(dx_ref.shape[1:], dx_ref.dtype)

    @pl.when(i == 0)
    def _dw_first():
        acc_ref[...] = jnp.zeros(acc_ref.shape, acc_ref.dtype)

    w = w_ref[...]
    dwt = None
    for c in range(0, lg_ref.shape[0], chunk):
        rows = slice(c, c + chunk)
        lg = lg_ref[rows].astype(jnp.float32)                  # (chunk, Vb)
        row = row_ref[i, rows]                                 # (chunk, 128)
        col = row[:, LABEL:LABEL + 1].astype(jnp.int32) - j * block_v
        p = jnp.exp(lg - row[:, LSE:LSE + 1])
        hit = jax.lax.broadcasted_iota(jnp.int32, lg.shape, 1) == col
        dlg = (jnp.where(hit, p - 1.0, p) * row[:, G:G + 1]).astype(
            xt_ref.dtype)
        dx_ref[i, rows] += _product(dlg, w, (1, 0 if vocab_major else 1))
        part = _product(xt_ref[i, :, rows], dlg, (1, 0))       # (H, Vb)
        dwt = part if dwt is None else dwt + part
    acc_ref[...] += dwt

    @pl.when(i == pl.num_programs(1) - 1)
    def _dw_out():
        acc = acc_ref[...]
        dw_ref[...] = (acc.T if vocab_major else acc).astype(dw_ref.dtype)


def _bwd_call(lg, lse, labels, g, x, w, vocab_major, block_r, block_v):
    """(dx (N, H), dw as w) from the saved logits."""
    N, H = x.shape
    V = lg.shape[1]
    nr = N // block_r
    rows = jnp.pad(jnp.stack([lse, g, labels.astype(jnp.float32)], axis=1),
                   ((0, 0), (0, LANES - 3)))
    xt = x.reshape(nr, block_r, H).swapaxes(1, 2)          # (nr, H, R)
    dx, dw = tracing.pallas_call(
        functools.partial(_bwd_kernel, vocab_major=vocab_major,
                          block_v=block_v, chunk=min(CHUNK, block_r)),
        grid=(V // block_v, nr),
        in_specs=[pl.BlockSpec((block_r, block_v), lambda j, i: (i, j)),
                  _whole((nr, block_r, LANES)),
                  _whole((nr, H, block_r)),
                  _w_spec(H, block_v, vocab_major)],
        out_specs=[_whole((nr, block_r, H)),
                   _w_spec(H, block_v, vocab_major)],
        out_shape=[jax.ShapeDtypeStruct((nr, block_r, H), jnp.float32),
                   jax.ShapeDtypeStruct(w.shape, w.dtype)],
        scratch_shapes=[pltpu.VMEM((H, block_v), jnp.float32)],
        compiler_params=_PARAMS,
        interpret=pallas_interpret(),
        name="fused_ce_bwd",
    )(lg, rows.reshape(nr, block_r, LANES), xt, w)
    return dx.reshape(N, H).astype(x.dtype), dw


# ----------------------------------------------------------------------------
# the plain path
# ----------------------------------------------------------------------------

def _plain_logits(x, w, bias, vocab_major):
    lg = x @ (w.T if vocab_major else w).astype(x.dtype)
    return lg if bias is None else lg + bias.astype(x.dtype)


def _plain_bwd(lg, lse, labels, g, x, w, bias, vocab_major):
    p = jnp.exp(lg.astype(jnp.float32) - lse[:, None])
    hit = jax.lax.broadcasted_iota(jnp.int32, lg.shape, 1) == labels[:, None]
    dlg = (jnp.where(hit, p - 1.0, p) * g[:, None]).astype(x.dtype)
    wx = w.astype(x.dtype)
    dx = dlg @ (wx if vocab_major else wx.T)
    dw = dlg.T @ x if vocab_major else x.T @ dlg
    db = None if bias is None else jnp.sum(dlg, axis=0).astype(bias.dtype)
    return dx, dw.astype(w.dtype), db


# ----------------------------------------------------------------------------
# the unit
# ----------------------------------------------------------------------------

def _plan(N, H, V, x_dtype, w_dtype, bias):
    """``(blocks of the forward kernel, blocks of the backward kernel)``,
    each ``(block_r, block_v)`` or None where XLA takes that direction. The
    kernels take vocabulary and width multiples of 128, tokens a multiple of
    the row tile, one dtype, no head bias and what fits ``VMEM_LIMIT``: the
    backward where its float32 accumulator of the activations' gradient fits
    beside the rest."""
    block_r = next((r for r in (4096, 2048, 1024, 512, 256, 128)
                    if N % r == 0), None)
    block_v = next((v for v in (512, 384, 256, 128) if V % v == 0), None)
    if (bias is not None or block_r is None or block_v is None or H % LANES
            or x_dtype != w_dtype or V > 1 << 24):
        return None, None
    item = jnp.dtype(x_dtype).itemsize
    tiles = (4 * block_v * H * item          # w in, dw out, two buffers each
             + 2 * block_r * block_v * item  # a logits tile, two buffers
             + 4 * min(CHUNK, block_r) * (block_v + H) * 4  # a chunk's values
             + 2 * block_v * H * 4)          # dw's sums
    rows = N * LANES * 4
    fits = VMEM_LIMIT * 7 // 8
    if N * H * item + rows + tiles > fits:
        return None, None
    blocks = (block_r, block_v)
    return blocks, blocks if N * H * (item + 4) + rows + tiles <= fits else None


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _head_nll(x, w, bias, labels, vocab_major, fwd_blocks, bwd_blocks):
    return _head_nll_fwd(x, w, bias, labels, vocab_major, fwd_blocks,
                         bwd_blocks)[0]


def _head_nll_fwd(x, w, bias, labels, vocab_major, fwd_blocks, bwd_blocks):
    if fwd_blocks is None:
        lg = _plain_logits(x, w, bias, vocab_major)
        lse = jax.scipy.special.logsumexp(lg.astype(jnp.float32), axis=-1)
    else:
        lg, lse = _fwd_call(x, w, vocab_major, *fwd_blocks)
    gold = jnp.take_along_axis(lg, labels[:, None], axis=-1)[:, 0]
    return lse - gold.astype(jnp.float32), (lg, lse, labels, x, w, bias)


def _head_nll_bwd(vocab_major, fwd_blocks, bwd_blocks, res, g):
    lg, lse, labels, x, w, bias = res
    if bwd_blocks is None:
        return (*_plain_bwd(lg, lse, labels, g, x, w, bias, vocab_major), None)
    dx, dw = _bwd_call(lg, lse, labels, g, x, w, vocab_major, *bwd_blocks)
    return dx, dw, None, None


_head_nll.defvjp(_head_nll_fwd, _head_nll_bwd)


def head_nll(x, w, labels, bias=None, *, vocab_major):
    """``logsumexp(x @ w^T + bias) - (x @ w^T + bias)[label]`` a token, float32.

    ``x``: (B, S, H) activations as the head reads them (normed, scaled);
    ``w``: (V, H) if ``vocab_major`` (the tied embedding table) else (H, V);
    ``labels``: (B, S) valid indices (mask outside); ``bias``: (V,) or None.
    Under a ``kernel_mesh`` of several devices the fused path maps itself
    over the mesh: a device's own tokens against the whole weight, whose
    gradient is then the devices' partial sums, reduced as any gradient is.
    A mesh with a ``model`` axis shards the head over the vocabulary, which
    XLA partitions and the kernels do not: plain."""
    B, S, H = x.shape
    V = w.shape[0 if vocab_major else 1]
    mesh, axes = open_mesh_axes()
    sizes = (jax.sharding.get_abstract_mesh() if mesh is None else mesh).shape

    # a device's own tokens: the batch over the ZeRO axes, a sequence over seq
    over = [tuple(a for a in names if a in axes)
            for names in (ZERO_AXES, (SEQ_AXIS,))]
    ways = [math.prod(sizes[a] for a in names) for names in over]
    spec = P(*(names or None for names in over))
    fwd_blocks = bwd_blocks = None
    if sizes.get(MODEL_AXIS, 1) == 1 and not (B % ways[0] or S % ways[1]):
        fwd_blocks, bwd_blocks = _plan(B * S // (ways[0] * ways[1]), H, V,
                                       x.dtype, w.dtype, bias)
    tracing.set_program_attr(head_loss=(
        "plain" if fwd_blocks is None else
        "fused_fwd" if bwd_blocks is None else "fused"))

    def local(x, w, labels):
        nll = _head_nll(x.reshape(-1, H), w, bias, labels.reshape(-1),
                        vocab_major, fwd_blocks, bwd_blocks)
        return nll.reshape(labels.shape)

    if fwd_blocks is None or not axes:
        return local(x, w, labels)
    return jax.shard_map(
        local, mesh=mesh, in_specs=(P(*spec, None), P(), spec),
        out_specs=spec, axis_names=frozenset(axes), check_vma=False,
    )(x, w, labels)
