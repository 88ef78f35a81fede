"""Pallas weight-only-quantized matmul — dequant fused into operand reads.

Reference: ``deepspeed/inference/v2/kernels/core_ops/cuda_linear`` (TC-FPx /
FP6 weight-only GEMM: 6-bit weights dequantized in the tensor-core operand
pipeline, ~2.1× over fp16 GEMM at near-fp16 quality,
blogs/deepspeed-fp6/03-05-2024/README.md:67).

TPU design: decode GEMMs are HBM-bandwidth-bound, so the win is the byte
count of the weight stream the kernel pulls per output tile — int6 streams
0.75 B/param (37.5% of bf16, 75% of int8). The kernel walks the contraction
dimension group-by-group (sequential grid axis): each step reads one packed
(codes, scale) tile from HBM into VMEM, unpacks the 6-bit (or 4/8-bit) codes
with vector shifts, applies the per-group scale, and feeds the MXU — the
dequantized weights never round-trip through HBM (the "dequant in operand
reads" property of the reference kernel). Accumulation lives in VMEM scratch
across the group axis.

Non-TPU backends run the same kernel under the Pallas interpreter (tests);
``woq_matmul`` is the public entry and matches ``dequant_params`` +
``jnp.dot`` bit-for-bit in fp32.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...utils import tracing
from .woq import unpack6


def _interpret() -> bool:
    from ..pallas_utils import pallas_interpret

    return pallas_interpret()


def _kernel(x_ref, codes_ref, scale_ref, o_ref, acc_ref, *, num_bits, group):
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    codes = codes_ref[0]                      # (packed_rows, BO) int8
    if num_bits == 6:
        q = codes.reshape(group // 4, 3, -1).astype(jnp.int32) & 0xFF
        cs = unpack6(q[:, 0, :], q[:, 1, :], q[:, 2, :])
        w = jnp.stack(cs, axis=1).reshape(group, -1).astype(jnp.float32)
    elif num_bits == 4:
        lo = ((codes.astype(jnp.int8) << 4) >> 4).astype(jnp.float32)
        hi = (codes.astype(jnp.int8) >> 4).astype(jnp.float32)
        w = jnp.stack([lo, hi], axis=1).reshape(group, -1)
    else:
        w = codes.astype(jnp.float32)
    w = w * scale_ref[0]                      # (group, BO) × (1, BO)
    x = x_ref[...].astype(jnp.float32)        # (B, group)
    acc_ref[...] += jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(k == pl.num_programs(1) - 1)
    def _done():
        o_ref[...] = acc_ref[...]


def woq_matmul(x, codes, scale, num_bits: int, *, block_out: int = 512):
    """``x @ dequant(codes, scale)`` with dequant fused into the weight reads.

    - ``x``: (B, In) activations (any float dtype; accumulated in fp32)
    - ``codes``: (ng, packed, Out) int8 from ``quantize_leaf``
    - ``scale``: (ng, 1, Out) fp32
    Returns (B, Out) fp32.
    """
    B, In = x.shape
    ng, packed, Out = codes.shape
    group = {8: packed, 6: (packed // 3) * 4, 4: packed * 2}[num_bits]
    if ng * group != In:
        raise ValueError(f"codes {codes.shape} (group {group}) != In {In}")
    bo = min(block_out, Out)
    while Out % bo:
        bo -= 1
    grid = (Out // bo, ng)
    return tracing.pallas_call(
        functools.partial(_kernel, num_bits=num_bits, group=group),
        grid=grid,
        in_specs=[
            pl.BlockSpec((B, group), lambda o, k: (0, k)),
            pl.BlockSpec((1, packed, bo), lambda o, k: (k, 0, o)),
            pl.BlockSpec((1, 1, bo), lambda o, k: (k, 0, o)),
        ],
        out_specs=pl.BlockSpec((B, bo), lambda o, k: (0, o)),
        out_shape=jax.ShapeDtypeStruct((B, Out), jnp.float32),
        scratch_shapes=[pltpu.VMEM((B, bo), jnp.float32)],
        interpret=_interpret(),
        name="woq_gemm",
    )(x, codes, scale)
