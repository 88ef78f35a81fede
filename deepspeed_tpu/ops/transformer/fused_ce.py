"""Fused linear-cross-entropy (vocab head + softmax CE) Pallas kernels.

TPU-native replacement for the reference's fused logits/loss path (the CUDA
softmax in ``csrc/transformer/softmax_kernels.cu`` and the fused
``logits_gather`` of ``deepspeed/inference/v2/kernels/ragged_ops``): computes
``nll = logsumexp(x @ W^T) - (x @ W^T)[label]`` without ever re-reading the
(N, V) logits from HBM for the reductions, and a backward that forms
``dlogits = softmax - onehot`` tile-by-tile in VMEM, feeding the dX / dW
matmuls directly — the (N, V) fp32 dlogits tensor of the naive path is never
materialized.

Layout: W is (V, H) — the embedding-table layout — so the tied-embedding head
needs no transpose in either direction and dW comes out ready to accumulate
with the embedding gradient.

Forward grid: (N/R rows outer, V/Vb inner); the running max / sum-exp / gold
accumulators live in revisited output blocks whose index map ignores the vocab
axis (consecutive revisits stay VMEM-resident on the sequential TPU grid).
The logits tile is written once (bf16) as the backward's residual — the same
bytes the engine's "dots" remat policy would have saved.

Backward grid: (N/R outer, V/Vb inner): dX accumulates in a revisited block;
dW is produced as N/R partial sums (one per row block) and reduced by XLA —
O(N/R · V · H) extra bytes but no non-consecutive output revisiting, which
Pallas TPU does not guarantee.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _interpret() -> bool:
    from ..pallas_utils import pallas_interpret

    return pallas_interpret()


# ----------------------------------------------------------------------------
# forward
# ----------------------------------------------------------------------------

def _fwd_kernel(x_ref, w_ref, lab_ref, *out_refs, block_v, write_lg):
    if write_lg:
        lg_ref, m_ref, l_ref, gold_ref = out_refs
    else:
        m_ref, l_ref, gold_ref = out_refs
    j = pl.program_id(1)
    x = x_ref[0, :, :]              # (R, H) bf16
    w = w_ref[0, :, :]              # (Vb, H) bf16
    s = jax.lax.dot_general(x, w, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (R, Vb)
    if write_lg:
        lg_ref[0, :, :] = s.astype(lg_ref.dtype)

    tile_max = jnp.max(s, axis=-1)                     # (R,)
    lab = lab_ref[0, :, 0]                             # (R,) int32
    col = lab - j * block_v
    cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    hit = cols == col[:, None]
    tile_gold = jnp.sum(jnp.where(hit, s, 0.0), axis=-1)

    @pl.when(j == 0)
    def _init():
        m_ref[0, :, 0] = tile_max
        l_ref[0, :, 0] = jnp.sum(jnp.exp(s - tile_max[:, None]), axis=-1)
        gold_ref[0, :, 0] = tile_gold

    @pl.when(j > 0)
    def _update():
        m = m_ref[0, :, 0]
        m_new = jnp.maximum(m, tile_max)
        alpha = jnp.exp(m - m_new)
        l_ref[0, :, 0] = (l_ref[0, :, 0] * alpha
                          + jnp.sum(jnp.exp(s - m_new[:, None]), axis=-1))
        m_ref[0, :, 0] = m_new
        gold_ref[0, :, 0] = gold_ref[0, :, 0] + tile_gold


def _ce_fwd_impl(x, w, labels, block_r, block_v, write_lg=True):
    N, H = x.shape
    V = w.shape[0]
    grid = (N // block_r, V // block_v)
    small = pl.BlockSpec((1, block_r, 1), lambda i, j: (0, i, 0))
    out_specs = [small, small, small]
    out_shape = [jax.ShapeDtypeStruct((1, N, 1), jnp.float32)] * 3
    if write_lg:
        out_specs = [pl.BlockSpec((1, block_r, block_v),
                                  lambda i, j: (0, i, j))] + out_specs
        out_shape = [jax.ShapeDtypeStruct((1, N, V), x.dtype)] + out_shape
    outs = pl.pallas_call(
        functools.partial(_fwd_kernel, block_v=block_v, write_lg=write_lg),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_r, H), lambda i, j: (0, i, 0)),
            pl.BlockSpec((1, block_v, H), lambda i, j: (0, j, 0)),
            pl.BlockSpec((1, block_r, 1), lambda i, j: (0, i, 0)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=_interpret(),
        name="fused_ce_fwd",
    )(x[None], w[None], labels[None, :, None])
    lg, (m, l, gold) = (outs[0][0], outs[1:]) if write_lg else (None, outs)
    lse = m[0, :, 0] + jnp.log(l[0, :, 0])
    return lg, lse, gold[0, :, 0]


# ----------------------------------------------------------------------------
# backward
# ----------------------------------------------------------------------------

def _bwd_kernel(lg_ref, lse_ref, lab_ref, g_ref, x_ref, w_ref,
                dx_ref, dwp_ref, *, block_v):
    j = pl.program_id(1)
    lg = lg_ref[0, :, :].astype(jnp.float32)           # (R, Vb)
    lse = lse_ref[0, :, 0]                             # (R,)
    g = g_ref[0, :, 0]                                 # (R,) upstream d(nll)
    lab = lab_ref[0, :, 0]
    p = jnp.exp(lg - lse[:, None])
    col = lab - j * block_v
    cols = jax.lax.broadcasted_iota(jnp.int32, lg.shape, 1)
    onehot = (cols == col[:, None]).astype(jnp.float32)
    dlg = ((p - onehot) * g[:, None]).astype(x_ref.dtype)   # (R, Vb) bf16

    x = x_ref[0, :, :]                                 # (R, H)
    w = w_ref[0, :, :]                                 # (Vb, H)
    dwp_ref[0, :, :] = jax.lax.dot_general(
        dlg, x, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(dwp_ref.dtype)  # (Vb, H)
    dx_blk = jax.lax.dot_general(
        dlg, w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)            # (R, H)

    @pl.when(j == 0)
    def _init():
        dx_ref[0, :, :] = dx_blk

    @pl.when(j > 0)
    def _acc():
        dx_ref[0, :, :] = dx_ref[0, :, :] + dx_blk


def _ce_bwd_impl(lg, lse, labels, g, x, w, block_r, block_v):
    N, H = x.shape
    V = w.shape[0]
    ni = N // block_r
    grid = (ni, V // block_v)
    dx, dwp = pl.pallas_call(
        functools.partial(_bwd_kernel, block_v=block_v),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_r, block_v), lambda i, j: (0, i, j)),
            pl.BlockSpec((1, block_r, 1), lambda i, j: (0, i, 0)),
            pl.BlockSpec((1, block_r, 1), lambda i, j: (0, i, 0)),
            pl.BlockSpec((1, block_r, 1), lambda i, j: (0, i, 0)),
            pl.BlockSpec((1, block_r, H), lambda i, j: (0, i, 0)),
            pl.BlockSpec((1, block_v, H), lambda i, j: (0, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_r, H), lambda i, j: (0, i, 0)),
            pl.BlockSpec((1, block_v, H), lambda i, j: (i, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, N, H), jnp.float32),
            jax.ShapeDtypeStruct((ni, V, H), x.dtype),
        ],
        interpret=_interpret(),
        name="fused_ce_bwd",
    )(lg[None], lse[None, :, None], labels[None, :, None],
      g[None, :, None], x[None], w[None])
    dw = dwp.astype(jnp.float32).sum(axis=0) if ni > 1 else dwp[0].astype(jnp.float32)
    return dx[0].astype(x.dtype), dw.astype(w.dtype)


# ----------------------------------------------------------------------------
# public entry (custom VJP)
# ----------------------------------------------------------------------------

def _pick_blocks(N, V, H):
    # VMEM guard: the backward holds an (R, H) fp32 dx accumulator + (R, H)
    # bf16 x tile + (R, Vb) tiles; keep the dominant R*H buffers under ~8 MB
    r_cap = max(128, (8 * 1024 * 1024) // (6 * H))
    block_r = next((r for r in (2048, 1024, 512, 256, 128)
                    if r <= r_cap and N % r == 0), None)
    block_v = next((v for v in (512, 384, 256, 128) if V % v == 0), None)
    return block_r, block_v


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _fused_ce(x, w, labels, block_r, block_v):
    # no-grad primal: skip the (N, V) logits residual entirely — it is only
    # needed by the backward, and the pallas_call is opaque to XLA DCE
    _, lse, gold = _ce_fwd_impl(x, w, labels, block_r, block_v, write_lg=False)
    return lse - gold


def _fused_ce_fwd(x, w, labels, block_r, block_v):
    lg, lse, gold = _ce_fwd_impl(x, w, labels, block_r, block_v)
    return lse - gold, (lg, lse, labels, x, w)


def _fused_ce_bwd(block_r, block_v, res, g):
    lg, lse, labels, x, w = res
    dx, dw = _ce_bwd_impl(lg, lse, labels, g, x, w, block_r, block_v)
    return dx, dw, None


_fused_ce.defvjp(_fused_ce_fwd, _fused_ce_bwd)


def fused_ce_loss(x, w, labels):
    """Per-row ``logsumexp(x @ w^T) - (x @ w^T)[label]`` (f32), fused.

    ``x``: (N, H) activations; ``w``: (V, H) vocab table (embedding layout);
    ``labels``: (N,) int32 — must be valid indices (mask outside; rows whose
    label is out of range still produce a finite lse-based value).
    Returns (N,) f32. Raises ``NotImplementedError`` for shapes the kernel
    does not cover — catch it and use the unfused logsumexp/gather path.

    Status: opt-in op, not wired into ``TransformerLM.apply`` — measured
    XLA-competitive (not faster) at GPT-2 shapes on v5e, where XLA already
    fuses the reduction passes; it exists for fusion-hostile shapes and as
    the ragged-logits building block (reference
    ``inference/v2/kernels/ragged_ops/logits_gather``).
    """
    N, H = x.shape
    V, H2 = w.shape
    if H != H2:
        raise ValueError(f"x H={H} vs w H={H2}")
    block_r, block_v = _pick_blocks(N, V, H)
    if block_r is None or block_v is None or H % 128 or H > 8192:
        raise NotImplementedError(f"fused_ce: unsupported shape N={N} V={V} H={H}")
    return _fused_ce(x, w, labels.astype(jnp.int32), block_r, block_v)
