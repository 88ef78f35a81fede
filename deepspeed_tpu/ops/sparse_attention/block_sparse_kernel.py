"""Block-sparse (splash-style) Pallas attention: masked KV blocks are SKIPPED.

Reference: the Triton block-sparse SDD/DSD matmuls + masked softmax in
``deepspeed/ops/sparse_attention/{matmul.py,softmax.py}`` (+ ``csrc/
sparse_attention/utils.cpp``). The mask-based path in
``sparse_self_attention.py`` is the numerics oracle; this kernel achieves the
actual compute saving by iterating, per query block, only the ACTIVE KV blocks
of the layout (and per KV block only the active query blocks in the backward),
with the block lists scalar-prefetched into SMEM.

Layout granularity must equal the kernel block (>=128 — MXU starves below);
finer layouts fall back to the masked XLA path in ``SparseSelfAttention``.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...utils import tracing

NEG_INF = -1e30


def _interpret() -> bool:
    from ..pallas_utils import pallas_interpret

    return pallas_interpret()


def layout_to_lists(layout: np.ndarray, causal: bool):
    """(H, nQ, nK) bool → compacted per-row / per-col block index lists.

    Returns (kcnt (H,nQ), kidx (H,nQ,MAXK), qcnt (H,nK), qidx (H,nK,MAXQ))
    int32, zero-padded. Under ``causal`` the layout is intersected with the
    block-level lower triangle first.
    """
    H, nQ, nK = layout.shape
    lay = layout.copy()
    if causal:
        tri = np.tril(np.ones((nQ, nK), bool))
        lay &= tri[None]
    kcnt = lay.sum(axis=2).astype(np.int32)
    qcnt = lay.sum(axis=1).astype(np.int32)
    maxk = max(1, int(kcnt.max()))
    maxq = max(1, int(qcnt.max()))
    kidx = np.zeros((H, nQ, maxk), np.int32)
    qidx = np.zeros((H, nK, maxq), np.int32)
    for h in range(H):
        for i in range(nQ):
            nz = np.nonzero(lay[h, i])[0]
            kidx[h, i, : len(nz)] = nz
        for j in range(nK):
            nz = np.nonzero(lay[h, :, j])[0]
            qidx[h, j, : len(nz)] = nz
    return kcnt, kidx, qcnt, qidx


# ----------------------------------------------------------------------------
# kernels (scalar-prefetched block lists; otherwise mirror flash_attention.py)
# ----------------------------------------------------------------------------

def _fwd_kernel(kcnt_ref, kidx_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                *, block, causal, scale):
    h, qi = pl.program_id(1), pl.program_id(2)
    q = q_ref[0, 0, :, :].astype(jnp.float32) * scale  # (B, hd)
    hd = q.shape[-1]
    q_start = qi * block

    def body(j, carry):
        m, l, acc = carry
        kb = kidx_ref[h, qi, j]
        k = k_ref[0, 0, pl.ds(kb * block, block), :].astype(jnp.float32)
        v = v_ref[0, 0, pl.ds(kb * block, block), :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if causal:
            qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (block, block), 0)
            kpos = kb * block + jax.lax.broadcasted_iota(jnp.int32, (block, block), 1)
            s = jnp.where(qpos >= kpos, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m - m_new)
        l_new = alpha * l + jnp.sum(p, axis=-1)
        acc_new = acc * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    m0 = jnp.full((block,), NEG_INF, jnp.float32)
    m, l, acc = jax.lax.fori_loop(
        0, kcnt_ref[h, qi], body,
        (m0, jnp.zeros((block,), jnp.float32), jnp.zeros((block, hd), jnp.float32)))
    l_safe = jnp.where(l == 0.0, 1.0, l)
    o_ref[0, 0, :, :] = (acc / l_safe[:, None]).astype(o_ref.dtype)
    lse_ref[0, 0, :, 0] = m + jnp.log(l_safe)


def _bwd_dq_kernel(kcnt_ref, kidx_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                   delta_ref, dq_ref, *, block, causal, scale):
    h, qi = pl.program_id(1), pl.program_id(2)
    q = q_ref[0, 0, :, :].astype(jnp.float32) * scale
    do = do_ref[0, 0, :, :].astype(jnp.float32)
    lse = lse_ref[0, 0, :, 0]
    delta = delta_ref[0, 0, :, 0]
    hd = q.shape[-1]
    q_start = qi * block

    def body(j, dq):
        kb = kidx_ref[h, qi, j]
        k = k_ref[0, 0, pl.ds(kb * block, block), :].astype(jnp.float32)
        v = v_ref[0, 0, pl.ds(kb * block, block), :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if causal:
            qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (block, block), 0)
            kpos = kb * block + jax.lax.broadcasted_iota(jnp.int32, (block, block), 1)
            s = jnp.where(qpos >= kpos, s, NEG_INF)
        p = jnp.exp(s - lse[:, None])
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None]) * scale
        return dq + jax.lax.dot_general(ds, k, (((1,), (0,)), ((), ())),
                                        preferred_element_type=jnp.float32)

    dq = jax.lax.fori_loop(0, kcnt_ref[h, qi], body,
                           jnp.zeros((block, hd), jnp.float32))
    dq_ref[0, 0, :, :] = dq.astype(dq_ref.dtype)


def _bwd_dkv_kernel(qcnt_ref, qidx_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                    delta_ref, dk_ref, dv_ref, *, block, causal, scale):
    h, ki = pl.program_id(1), pl.program_id(2)
    k = k_ref[0, 0, :, :].astype(jnp.float32)
    v = v_ref[0, 0, :, :].astype(jnp.float32)
    hd = k.shape[-1]
    k_start = ki * block

    def body(jj, carry):
        dk, dv = carry
        qb = qidx_ref[h, ki, jj]
        q = q_ref[0, 0, pl.ds(qb * block, block), :].astype(jnp.float32) * scale
        do = do_ref[0, 0, pl.ds(qb * block, block), :].astype(jnp.float32)
        lse = lse_ref[0, 0, pl.ds(qb * block, block), 0]
        delta = delta_ref[0, 0, pl.ds(qb * block, block), 0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if causal:
            qpos = qb * block + jax.lax.broadcasted_iota(jnp.int32, (block, block), 0)
            kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (block, block), 1)
            s = jnp.where(qpos >= kpos, s, NEG_INF)
        p = jnp.exp(s - lse[:, None])
        dv_new = dv + jax.lax.dot_general(p, do, (((0,), (0,)), ((), ())),
                                          preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None])  # q pre-scaled: ds·q carries the scale
        dk_new = dk + jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())),
                                          preferred_element_type=jnp.float32)
        return dk_new, dv_new

    init = (jnp.zeros((block, hd), jnp.float32), jnp.zeros((block, hd), jnp.float32))
    dk, dv = jax.lax.fori_loop(0, qcnt_ref[h, ki], body, init)
    dk_ref[0, 0, :, :] = dk.astype(dk_ref.dtype)
    dv_ref[0, 0, :, :] = dv.astype(dv_ref.dtype)


# ----------------------------------------------------------------------------
# host wrappers
# ----------------------------------------------------------------------------

def _grid_spec(n_scalar, grid, in_specs, out_specs):
    return pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=n_scalar, grid=grid,
        in_specs=in_specs, out_specs=out_specs)


def _sparse_fwd(q, k, v, kcnt, kidx, *, causal, g, scale, block):
    B, nh, Sq, hd = q.shape
    Skv = k.shape[2]
    out, lse = tracing.pallas_call(
        functools.partial(_fwd_kernel, block=block, causal=causal, scale=scale),
        grid_spec=_grid_spec(
            2, (B, nh, Sq // block),
            [
                pl.BlockSpec((1, 1, block, hd), lambda b, h, i, *_: (b, h, i, 0)),
                pl.BlockSpec((1, 1, Skv, hd), lambda b, h, i, *_: (b, h // g, 0, 0)),
                pl.BlockSpec((1, 1, Skv, hd), lambda b, h, i, *_: (b, h // g, 0, 0)),
            ],
            [
                pl.BlockSpec((1, 1, block, hd), lambda b, h, i, *_: (b, h, i, 0)),
                pl.BlockSpec((1, 1, block, 1), lambda b, h, i, *_: (b, h, i, 0)),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((B, nh, Sq, 1), jnp.float32),
        ],
        interpret=_interpret(),
        name="block_sparse_fwd",
    )(kcnt, kidx, q, k, v)
    return out, lse


def _sparse_bwd(kcnt, kidx, qcnt, qidx, causal, g, scale, block, res, do):
    q, k, v, out, lse = res
    B, nh, Sq, hd = q.shape
    kvh, Skv = k.shape[1], k.shape[2]
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)[..., None]

    dq = tracing.pallas_call(
        functools.partial(_bwd_dq_kernel, block=block, causal=causal, scale=scale),
        grid_spec=_grid_spec(
            2, (B, nh, Sq // block),
            [
                pl.BlockSpec((1, 1, block, hd), lambda b, h, i, *_: (b, h, i, 0)),
                pl.BlockSpec((1, 1, Skv, hd), lambda b, h, i, *_: (b, h // g, 0, 0)),
                pl.BlockSpec((1, 1, Skv, hd), lambda b, h, i, *_: (b, h // g, 0, 0)),
                pl.BlockSpec((1, 1, block, hd), lambda b, h, i, *_: (b, h, i, 0)),
                pl.BlockSpec((1, 1, block, 1), lambda b, h, i, *_: (b, h, i, 0)),
                pl.BlockSpec((1, 1, block, 1), lambda b, h, i, *_: (b, h, i, 0)),
            ],
            pl.BlockSpec((1, 1, block, hd), lambda b, h, i, *_: (b, h, i, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=_interpret(),
        name="block_sparse_dq",
    )(kcnt, kidx, q, k, v, do, lse, delta)

    dkh, dvh = tracing.pallas_call(
        functools.partial(_bwd_dkv_kernel, block=block, causal=causal, scale=scale),
        grid_spec=_grid_spec(
            2, (B, nh, Skv // block),
            [
                pl.BlockSpec((1, 1, Sq, hd), lambda b, h, i, *_: (b, h, 0, 0)),
                pl.BlockSpec((1, 1, block, hd), lambda b, h, i, *_: (b, h // g, i, 0)),
                pl.BlockSpec((1, 1, block, hd), lambda b, h, i, *_: (b, h // g, i, 0)),
                pl.BlockSpec((1, 1, Sq, hd), lambda b, h, i, *_: (b, h, 0, 0)),
                pl.BlockSpec((1, 1, Sq, 1), lambda b, h, i, *_: (b, h, 0, 0)),
                pl.BlockSpec((1, 1, Sq, 1), lambda b, h, i, *_: (b, h, 0, 0)),
            ],
            [
                pl.BlockSpec((1, 1, block, hd), lambda b, h, i, *_: (b, h, i, 0)),
                pl.BlockSpec((1, 1, block, hd), lambda b, h, i, *_: (b, h, i, 0)),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((B, nh, Skv, hd), q.dtype),
            jax.ShapeDtypeStruct((B, nh, Skv, hd), q.dtype),
        ],
        interpret=_interpret(),
        name="block_sparse_dkv",
    )(qcnt, qidx, q, k, v, do, lse, delta)

    if g > 1:
        dk = dkh.reshape(B, kvh, g, Skv, hd).astype(jnp.float32).sum(axis=2).astype(k.dtype)
        dv = dvh.reshape(B, kvh, g, Skv, hd).astype(jnp.float32).sum(axis=2).astype(v.dtype)
    else:
        dk, dv = dkh.astype(k.dtype), dvh.astype(v.dtype)
    return dq, dk, dv


_FN_CACHE = {}
_FN_CACHE_MAX = 16


def _make_sparse_fn(kcnt, kidx, qcnt, qidx, causal, g, scale, block):
    kcnt_j, kidx_j = jnp.asarray(kcnt), jnp.asarray(kidx)
    qcnt_j, qidx_j = jnp.asarray(qcnt), jnp.asarray(qidx)

    @jax.custom_vjp
    def f(q, k, v):
        return _sparse_fwd(q, k, v, kcnt_j, kidx_j, causal=causal, g=g,
                           scale=scale, block=block)[0]

    def fwd(q, k, v):
        out, lse = _sparse_fwd(q, k, v, kcnt_j, kidx_j, causal=causal, g=g,
                               scale=scale, block=block)
        return out, (q, k, v, out, lse)

    def bwd(res, do):
        return _sparse_bwd(kcnt_j, kidx_j, qcnt_j, qidx_j, causal, g, scale,
                           block, res, do)

    f.defvjp(fwd, bwd)
    return f


def block_sparse_attention(q, k, v, layout: np.ndarray, block: int, *,
                           causal: bool = False, num_kv_groups: int = 1,
                           scale=None):
    """Splash-style attention over a (H, nQ, nK) block layout.

    q/k/v: (B, S, h, d) like ``attention.xla_attention``. Only active layout
    blocks are visited — compute scales with layout density, not S².
    """
    B, Sq, nh, hd = q.shape
    Skv = k.shape[1]
    if block < 128 or Sq % block or Skv % block:
        raise NotImplementedError("block_sparse kernel: block must be >=128 "
                                  "and divide both sequence lengths")
    if layout.shape != (nh, Sq // block, Skv // block):
        raise ValueError(f"layout shape {layout.shape} != "
                         f"{(nh, Sq // block, Skv // block)}")
    # K/V (and Q/dO in the backward) are staged whole per grid cell, like the
    # dense flash kernel — guard the VMEM window; per-active-block DMA is the
    # future long-context path
    if 2 * Skv * hd * k.dtype.itemsize > 12 * 1024 * 1024:
        raise NotImplementedError("block_sparse kernel: KV window exceeds VMEM budget")
    scale = scale if scale is not None else hd ** -0.5
    key = (layout.tobytes(), bool(causal), num_kv_groups, float(scale), block)
    fn = _FN_CACHE.get(key)
    if fn is None:
        if len(_FN_CACHE) >= _FN_CACHE_MAX:  # bound device-array pinning
            _FN_CACHE.pop(next(iter(_FN_CACHE)))
        lists = layout_to_lists(np.asarray(layout, bool), causal)
        fn = _FN_CACHE[key] = _make_sparse_fn(
            *lists, causal, num_kv_groups, scale, block)
    qt = jnp.transpose(q, (0, 2, 1, 3))
    kt = jnp.transpose(k, (0, 2, 1, 3))
    vt = jnp.transpose(v, (0, 2, 1, 3))
    return jnp.transpose(fn(qt, kt, vt), (0, 2, 1, 3))
