"""Pallas flash attention for TPU (forward + backward).

TPU-native replacement for the reference's fused attention CUDA kernels
(``csrc/transformer/softmax_kernels.cu`` training softmax,
``csrc/transformer/inference/csrc/softmax.cu`` and the blocked flash kernels in
``deepspeed/inference/v2/kernels/ragged_ops/blocked_flash``). Flash-attention-2
style: online softmax over KV blocks, logsumexp residuals, separate dq and dk/dv
backward kernels. Designed for the MXU: all matmuls are (128×hd)·(hd×128)-shaped
with fp32 accumulation; causal blocks beyond the diagonal are skipped by bounding
the KV loop with the query block's position (dynamic fori_loop trip count).

Layout: kernels run on (B, heads, S, hd) so the trailing two block dims are the
MXU-aligned (seq_block, head_dim); the public entry transposes from the model's
(B, S, heads, hd). GQA is handled in the BlockSpec index maps (kv head =
q head // groups) for forward/dq; dk/dv are produced per-q-head and group-summed
by the caller.

Gives way to the XLA path in ``attention.py`` for bias, softcap and q_offset
(cache decode) with ``UnsupportedFeature``, and refuses shapes it cannot tile
or fit with ``UnsupportedShape``, which ``attention()`` logs.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.sharding import PartitionSpec as P

from ...comm.topology import MODEL_AXIS, SEQ_AXIS, ZERO_AXES
from ..pallas_utils import open_mesh_axes
from .attention import UnsupportedFeature, UnsupportedShape, register_impl

NEG_INF = -1e30


def _interpret() -> bool:
    from ..pallas_utils import pallas_interpret

    return pallas_interpret()


# ----------------------------------------------------------------------------
# forward
# ----------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, block_q, block_k, causal, scale):
    qi = pl.program_id(2)
    # keep matmul inputs in their storage dtype (bf16): the MXU multiplies
    # bf16 at full rate with fp32 accumulation; casting to fp32 first would
    # run the MXU at a fraction of peak
    q = q_ref[0, 0, :, :]  # (BQ, hd)
    skv = k_ref.shape[2]
    hd = q.shape[-1]

    m0 = jnp.full((block_q,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q,), jnp.float32)
    acc0 = jnp.zeros((block_q, hd), jnp.float32)

    q_start = qi * block_q
    if causal:
        # only KV blocks whose start is <= the last query row
        num_kv = jnp.minimum((q_start + block_q + block_k - 1) // block_k,
                             skv // block_k)
    else:
        num_kv = skv // block_k

    def body(j, carry):
        m, l, acc = carry
        k = k_ref[0, 0, pl.ds(j * block_k, block_k), :]
        v = v_ref[0, 0, pl.ds(j * block_k, block_k), :]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # (BQ, BK) fp32
        if causal:
            qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            kpos = j * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
            s = jnp.where(qpos >= kpos, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m - m_new)
        l_new = alpha * l + jnp.sum(p, axis=-1)
        acc_new = acc * alpha[:, None] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return m_new, l_new, acc_new

    m, l, acc = jax.lax.fori_loop(0, num_kv, body, (m0, l0, acc0))
    l_safe = jnp.where(l == 0.0, 1.0, l)
    o_ref[0, 0, :, :] = (acc / l_safe[:, None]).astype(o_ref.dtype)
    lse_ref[0, 0, :, 0] = m + jnp.log(l_safe)


def _fwd(q, k, v, *, causal, num_kv_groups, scale, block_q, block_k):
    """q: (B, nh, Sq, hd); k/v: (B, kvh, Skv, hd) → out (B, nh, Sq, hd), lse (B, nh, Sq)."""
    B, nh, Sq, hd = q.shape
    Skv = k.shape[2]
    grid = (B, nh, Sq // block_q)
    g = num_kv_groups

    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, block_q=block_q, block_k=block_k,
                          causal=causal, scale=scale),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, hd), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, Skv, hd), lambda b, h, i: (b, h // g, 0, 0)),
            pl.BlockSpec((1, 1, Skv, hd), lambda b, h, i: (b, h // g, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, hd), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_q, 1), lambda b, h, i: (b, h, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((B, nh, Sq, 1), jnp.float32),
        ],
        interpret=_interpret(),
        name="flash_fwd",
    )(q, k, v)
    return out, lse


# ----------------------------------------------------------------------------
# backward
# ----------------------------------------------------------------------------

def _bwd_fused_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      dq_ref, dk_ref, dv_ref, *, block_q, block_k, causal, scale):
    """One pass producing dk/dv for this KV block AND accumulating this
    block's dq contributions. The QK^T, exp and do·v^T work is computed once
    instead of once per backward kernel; dq is a REVISITED fp32 output (same
    block for every ki — TPU grids run sequentially, so the accumulator
    stays resident in VMEM across the kv sweep)."""
    ki = pl.program_id(2)
    k = k_ref[0, 0, :, :]  # (BK, hd) bf16: MXU inputs stay in storage dtype
    v = v_ref[0, 0, :, :]
    sq = q_ref.shape[2]
    hd = k.shape[-1]
    k_start = ki * block_k

    @pl.when(ki == 0)
    def _zero_dq():
        dq_ref[0, 0, :, :] = jnp.zeros((sq, hd), jnp.float32)

    # first q block that can see this kv block
    start_q = (k_start // block_q) if causal else 0
    num_q = sq // block_q

    def body(i, carry):
        dk, dv = carry
        q = q_ref[0, 0, pl.ds(i * block_q, block_q), :]
        do = do_ref[0, 0, pl.ds(i * block_q, block_q), :]
        lse = lse_ref[0, 0, pl.ds(i * block_q, block_q), 0]
        delta = delta_ref[0, 0, pl.ds(i * block_q, block_q), 0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale  # (BQ, BK)
        if causal:
            qpos = i * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
            s = jnp.where(qpos >= kpos, s, NEG_INF)
        p = jnp.exp(s - lse[:, None])
        dv_new = dv + jax.lax.dot_general(p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
                                          preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta[:, None]) * scale).astype(q.dtype)
        dk_new = dk + jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())),
                                          preferred_element_type=jnp.float32)
        dq_blk = jax.lax.dot_general(ds, k, (((1,), (0,)), ((), ())),
                                     preferred_element_type=jnp.float32)
        sl = pl.ds(i * block_q, block_q)
        dq_ref[0, 0, sl, :] = dq_ref[0, 0, sl, :] + dq_blk
        return dk_new, dv_new

    init = (jnp.zeros((block_k, hd), jnp.float32), jnp.zeros((block_k, hd), jnp.float32))
    dk, dv = jax.lax.fori_loop(start_q, num_q, body, init)
    dk_ref[0, 0, :, :] = dk.astype(dk_ref.dtype)
    dv_ref[0, 0, :, :] = dv.astype(dv_ref.dtype)


def _bwd(causal, num_kv_groups, scale, block_q, block_k, res, do):
    q, k, v, out, lse = res  # (B, nh, Sq, hd) layout
    B, nh, Sq, hd = q.shape
    kvh, Skv = k.shape[1], k.shape[2]
    g = num_kv_groups

    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)[..., None]  # (B,nh,Sq,1)

    # ONE fused kernel: dk/dv per kv block + dq accumulated into a revisited
    # fp32 output across the kv sweep (sequential TPU grid) — halves the
    # QK^T/exp/do·v^T recompute of the former split dq / dkv kernels
    dq, dkh, dvh = pl.pallas_call(
        functools.partial(_bwd_fused_kernel, block_q=block_q, block_k=block_k,
                          causal=causal, scale=scale),
        grid=(B, nh, Skv // block_k),
        in_specs=[
            pl.BlockSpec((1, 1, Sq, hd), lambda b, h, i: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, block_k, hd), lambda b, h, i: (b, h // g, i, 0)),
            pl.BlockSpec((1, 1, block_k, hd), lambda b, h, i: (b, h // g, i, 0)),
            pl.BlockSpec((1, 1, Sq, hd), lambda b, h, i: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, Sq, 1), lambda b, h, i: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, Sq, 1), lambda b, h, i: (b, h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, Sq, hd), lambda b, h, i: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, block_k, hd), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_k, hd), lambda b, h, i: (b, h, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, nh, Sq, hd), jnp.float32),
            jax.ShapeDtypeStruct((B, nh, Skv, hd), q.dtype),
            jax.ShapeDtypeStruct((B, nh, Skv, hd), q.dtype),
        ],
        interpret=_interpret(),
        name="flash_bwd",
    )(q, k, v, do, lse, delta)
    dq = dq.astype(q.dtype)

    if g > 1:
        dk = dkh.reshape(B, kvh, g, Skv, hd).astype(jnp.float32).sum(axis=2).astype(k.dtype)
        dv = dvh.reshape(B, kvh, g, Skv, hd).astype(jnp.float32).sum(axis=2).astype(v.dtype)
    else:
        dk, dv = dkh.astype(k.dtype), dvh.astype(v.dtype)
    return dq, dk, dv


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


def _flash_bwd(causal, num_kv_groups, scale, block_q, block_k, res, do):
    return _bwd(causal, num_kv_groups, scale, block_q, block_k, res, do)


_flash.defvjp(_flash_fwd, _flash_bwd)


@register_impl("pallas_flash")
def flash_attention(q, k, v, *, causal=True, q_offset=0, num_kv_groups=1,
                    softcap=0.0, bias=None, scale=None, block_q=512, block_k=512):
    """Flash attention entry (same (B,S,h,d) surface as ``attention.xla_attention``).

    Default 512-blocks: measured 1.5× faster than 128-blocks on v5e (the MXU
    starves below ~512×hd work per grid cell)."""
    if bias is not None or (softcap and softcap > 0.0) or (
            not isinstance(q_offset, int)) or q_offset != 0:
        # a TRACED q_offset (KV-cache decode under jit/vmap) must also fall
        # back — comparing it would raise TracerBoolConversionError
        raise UnsupportedFeature("flash kernel: bias/softcap/q_offset unsupported")
    B, Sq, nh, hd = q.shape
    Skv = k.shape[1]

    def fit(block, n):
        # largest power-of-two block <= requested that divides n (>= 128)
        b = min(block, n)
        while b >= 128 and n % b:
            b //= 2
        return b

    block_q = fit(block_q, Sq)
    block_k = fit(block_k, Skv)
    if block_q < 128 or block_k < 128 or hd not in (64, 128, 256):
        raise UnsupportedShape(
            f"flash kernel needs sequence lengths that are multiples of 128 "
            f"(got {Sq}, {Skv}) and head_dim in (64, 128, 256) (got {hd})")
    # VMEM budget guard (long-context should use ring attention): the forward
    # stages a full-length K/V window per grid cell; the fused backward
    # additionally holds full-length q/do windows PLUS the revisited fp32 dq
    # accumulator (Sq*hd*(2+2+4) bytes)
    fwd_bytes = 2 * Skv * hd * k.dtype.itemsize
    bwd_bytes = Sq * hd * 8 + 2 * 512 * hd * k.dtype.itemsize
    if max(fwd_bytes, bwd_bytes) > 12 * 1024 * 1024:
        raise UnsupportedShape(
            f"flash kernel: a {max(fwd_bytes, bwd_bytes)}-byte K/V or q/dq "
            "window exceeds the 12 MiB VMEM budget")
    scale = scale if scale is not None else hd ** -0.5

    def local(q, k, v):
        qt = jnp.transpose(q, (0, 2, 1, 3))
        kt = jnp.transpose(k, (0, 2, 1, 3))
        vt = jnp.transpose(v, (0, 2, 1, 3))
        out = _flash(qt, kt, vt, causal, num_kv_groups, scale, block_q, block_k)
        return jnp.transpose(out, (0, 2, 1, 3))

    mesh, axes = open_mesh_axes()
    if not axes:
        return local(q, k, v)

    # under a mesh: every device runs the kernel on its own batch rows and
    # heads (the model's Ulysses layout: batch over the DP axes, heads over
    # seq x model); attention needs nothing from another device
    def over(names):
        return tuple(a for a in names if a in axes) or None

    spec = P(over(ZERO_AXES), None, over((SEQ_AXIS, MODEL_AXIS)), None)
    return jax.shard_map(local, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, axis_names=frozenset(axes),
                         check_vma=False)(q, k, v)
