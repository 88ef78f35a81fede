"""Pallas paged-attention decode kernel (blocked KV pool + block tables).

Reference: ``deepspeed/inference/v2/kernels/ragged_ops/blocked_flash`` — flash
attention over paged KV blocks addressed through per-sequence block tables.

TPU design: the XLA fallback in ``TransformerLM.forward_paged`` materializes
the table-gathered logical cache (read pool + write copy) every decode step;
this kernel instead streams ONE pool block per grid step straight from HBM,
with the block id resolved in the BlockSpec index map from the
scalar-prefetched table — the canonical TPU paged-attention pattern. Online
softmax state lives in VMEM scratch across the (sequential) block-step axis
of the grid.

Decode only (one query token per sequence); prefill keeps the XLA path.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _interpret() -> bool:
    from ..pallas_utils import pallas_interpret

    return pallas_interpret()


def _decode_kernel(tables_ref, lens_ref, q_ref, k_ref, v_ref, o_ref,
                   m_ref, l_ref, acc_ref, *, block_size, scale, max_blocks):
    b, j = pl.program_id(0), pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    seq_len = lens_ref[b]
    # tokens this block holds: positions [j*BS, j*BS + BS) ∩ [0, seq_len)
    @pl.when(j * block_size < seq_len)
    def _step():
        q = q_ref[0, 0, :, :].astype(jnp.float32) * scale  # (g, hd)
        k = k_ref[0, 0, :, :].astype(jnp.float32)          # (BS, hd)
        v = v_ref[0, 0, :, :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # (g, BS)
        kpos = j * block_size + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        s = jnp.where(kpos < seq_len, s, NEG_INF)
        m_prev = m_ref[:, 0]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_ref[:, 0] = alpha * l_ref[:, 0] + jnp.sum(p, axis=-1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_ref[:, 0] = m_new

    @pl.when(j == max_blocks - 1)
    def _finish():
        l = l_ref[:, 0]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0, :, :] = (acc_ref[...] / l_safe[:, None]).astype(o_ref.dtype)


def _decode_kernel_stream(tables_ref, lens_ref, q_ref, kpool_ref, vpool_ref,
                          o_ref, kbuf, vbuf, ksem, vsem, *, block_size, scale,
                          pack):
    """Grid (B, kvh): ONE cell per (sequence, kv head); the kernel itself
    streams this sequence's ACTIVE pool blocks from HBM with double-buffered
    DMA (prefetch j+1 while computing j). Versus the grid-per-block variant
    this cuts grid cells by MAXB× and does work proportional to each
    sequence's real length — the serving regime has mostly-short sequences
    against a long max-context table.

    ``pack``: Mosaic requires HBM DMA slices 128-lane-aligned; for hd=64 the
    pool arrives viewed as (kvh, NB, BS/2, 128) — each buffer row holds two
    interleaved tokens ([t_{2i} | t_{2i+1}]), and the kernel processes the
    even/odd half-lanes as two sub-tiles of the same block."""
    b = pl.program_id(0)
    h = pl.program_id(1)
    seq_len = lens_ref[b]
    nblk = (seq_len + block_size - 1) // block_size
    g = q_ref.shape[2]
    hd = q_ref.shape[3]
    q = q_ref[0, 0, :, :].astype(jnp.float32) * scale  # (g, hd)

    def start(j, slot):
        blk = tables_ref[b, j]
        pltpu.make_async_copy(kpool_ref.at[h, blk], kbuf.at[slot],
                              ksem.at[slot]).start()
        pltpu.make_async_copy(vpool_ref.at[h, blk], vbuf.at[slot],
                              vsem.at[slot]).start()

    @pl.when(nblk > 0)
    def _prologue():
        start(0, 0)

    def body(j, carry):
        m, l, acc = carry
        slot = jax.lax.rem(j, 2)

        @pl.when(j + 1 < nblk)
        def _prefetch():
            start(j + 1, 1 - slot)

        blk = tables_ref[b, j]
        pltpu.make_async_copy(kpool_ref.at[h, blk], kbuf.at[slot],
                              ksem.at[slot]).wait()
        pltpu.make_async_copy(vpool_ref.at[h, blk], vbuf.at[slot],
                              vsem.at[slot]).wait()
        kb = kbuf[slot].astype(jnp.float32)  # (BS, hd) or packed (BS/2, 2hd)
        vb = vbuf[slot].astype(jnp.float32)
        iota1 = jax.lax.broadcasted_iota

        def online_update(carry, k, v, kpos):
            m, l, acc = carry
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            s = jnp.where(kpos < seq_len, s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[:, None])
            alpha = jnp.exp(m - m_new)
            l_new = alpha * l + jnp.sum(p, axis=-1)
            acc_new = acc * alpha[:, None] + jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            return m_new, l_new, acc_new

        base = j * block_size
        if pack:
            # two interleaved sub-tiles of the block = two online updates
            # (online softmax is associative over any partition of the keys)
            half = iota1(jnp.int32, (q.shape[0], kb.shape[0]), 1)
            carry = online_update((m, l, acc), kb[:, :hd], vb[:, :hd],
                                  base + 2 * half)
            return online_update(carry, kb[:, hd:], vb[:, hd:],
                                 base + 2 * half + 1)
        kpos = base + iota1(jnp.int32, (q.shape[0], kb.shape[0]), 1)
        return online_update((m, l, acc), kb, vb, kpos)

    m0 = jnp.full((g,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((g,), jnp.float32)
    acc0 = jnp.zeros((g, hd), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, nblk, body, (m0, l0, acc0))
    l_safe = jnp.where(l == 0.0, 1.0, l)
    o_ref[0, 0, :, :] = (acc / l_safe[:, None]).astype(o_ref.dtype)


def _paged_decode_stream(q, k_pool, v_pool, tables, lens, *, scale):
    B, nh, hd = q.shape
    kvh, NB, BS, _ = k_pool.shape
    g = nh // kvh
    qg = q.reshape(B, kvh, g, hd)
    pack = hd < 128
    if pack:
        if BS % 2:
            raise NotImplementedError("packed stream kernel needs even block_size")
        # free view: two consecutive tokens side by side → 128-lane DMA slices
        k_pool = k_pool.reshape(kvh, NB, BS // 2, 2 * hd)
        v_pool = v_pool.reshape(kvh, NB, BS // 2, 2 * hd)
    buf_shape = (2,) + k_pool.shape[2:]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # tables, lens
        grid=(B, kvh),
        in_specs=[
            pl.BlockSpec((1, 1, g, hd), lambda b, h, tables, lens: (b, h, 0, 0)),
            pl.BlockSpec(memory_space=pltpu.ANY),  # k pool stays in HBM
            pl.BlockSpec(memory_space=pltpu.ANY),  # v pool stays in HBM
        ],
        out_specs=pl.BlockSpec((1, 1, g, hd),
                               lambda b, h, tables, lens: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM(buf_shape, k_pool.dtype),   # k double buffer
            pltpu.VMEM(buf_shape, v_pool.dtype),   # v double buffer
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_decode_kernel_stream, block_size=BS, scale=scale,
                          pack=pack),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, kvh, g, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=_interpret(),
        name="paged_decode",
    )(tables, lens, qg, k_pool, v_pool)
    return out.reshape(B, nh, hd)


def paged_decode_attention(q, k_pool, v_pool, tables, lens, *, scale=None,
                           stream: bool = True):
    """One-token decode attention against a blocked KV pool.

    q: (B, nh, hd) — this step's query per sequence.
    k_pool/v_pool: (kvh, NB, BS, hd) — kv-head-major so a pool block is a
    Mosaic-tileable (BS, hd) tile; tables: (B, MAXB) int32 pool block ids
    (0-padded); lens: (B,) int32 valid token counts (position + 1).
    Returns (B, nh, hd) in q's dtype.

    ``stream=True`` (default) uses the (B, kvh)-grid kernel with an in-kernel
    double-buffered DMA loop over only the ACTIVE blocks; ``stream=False``
    keeps the (B, kvh, MAXB)-grid variant whose block fetch rides the
    BlockSpec index map (one grid cell per table slot — simpler, but cell
    count scales with max context rather than actual lengths).
    """
    if stream:
        B, nh, hd = q.shape
        scale_v = scale if scale is not None else hd ** -0.5
        return _paged_decode_stream(q, k_pool, v_pool, tables, lens,
                                    scale=scale_v)
    B, nh, hd = q.shape
    kvh, NB, BS, _ = k_pool.shape
    MAXB = tables.shape[1]
    g = nh // kvh
    qg = q.reshape(B, kvh, g, hd)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # tables, lens
        grid=(B, kvh, MAXB),
        in_specs=[
            pl.BlockSpec((1, 1, g, hd), lambda b, h, j, tables, lens: (b, h, 0, 0)),
            # THE paged trick: each grid step fetches pool block tables[b, j]
            pl.BlockSpec((1, 1, BS, hd),
                         lambda b, h, j, tables, lens: (h, tables[b, j], 0, 0)),
            pl.BlockSpec((1, 1, BS, hd),
                         lambda b, h, j, tables, lens: (h, tables[b, j], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, hd),
                               lambda b, h, j, tables, lens: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g, 1), jnp.float32),   # m
            pltpu.VMEM((g, 1), jnp.float32),   # l
            pltpu.VMEM((g, hd), jnp.float32),  # acc
        ],
    )
    scale = scale if scale is not None else hd ** -0.5
    out = pl.pallas_call(
        functools.partial(_decode_kernel, block_size=BS, scale=scale,
                          max_blocks=MAXB),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, kvh, g, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=_interpret(),
        name="paged_decode_grid",
    )(tables, lens, qg, k_pool, v_pool)
    return out.reshape(B, nh, hd)
