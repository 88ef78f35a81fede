"""Weight-only quantization (WOQ) for inference.

Reference: ``deepspeed/inference/quantization/`` (post-training 4/8-bit
weight-only quantization with dequant matmul, ``quantization.py:111``,
``layers.py:114``) and the FP6 weight-only GEMM
(``inference/v2/kernels/core_ops/cuda_linear``).

TPU-native design: decode is HBM-bandwidth-bound, so the win is shrinking the
weight bytes the matmul streams — int8 halves, packed int6 (FP6-class, 4
codes per 3 bytes) takes 37.5%, and packed int4 quarters them relative to
bf16. Weights are stored as per-group symmetric codes + scales in the
parameter pytree (``<name>::q8``/``::q6``/``::q4`` + ``<name>::scale``); the
model dequantizes per layer inside the scan body, so XLA fuses the dequant
into the matmul read and only one layer's weights ever materialize in bf16.

Grouping is along the contraction (input) dim — scale shape
``(..., groups, 1, out)`` — matching the reference's per-group granularity.
Packed int4 stores two codes per int8 byte (lo/hi nibble, sign-extended on
unpack with arithmetic shifts).
"""

import re
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

# block-weight leaves that are matmul operands (quantization targets);
# norms/biases/router stay full precision like the reference skip list
DEFAULT_TARGETS = frozenset(
    {"wq", "wk", "wv", "wo", "w_up", "w_gate", "w_down", "wi"})


def _group_size(in_dim: int, requested: int, num_bits: int) -> int:
    """Largest divisor of ``in_dim`` that is <= requested (and compatible with
    the packing unit: 2 codes/byte for int4, 4 codes/3 bytes for int6)."""
    step = {4: 2, 6: 4, 8: 1}[num_bits]
    if in_dim % step:
        raise ValueError(
            f"int{num_bits} packing needs a contraction dim divisible by "
            f"{step}, got {in_dim}")
    g = min(requested, in_dim)
    while in_dim % g or g % step:
        g -= 1
    return g


def quantize_leaf(w, num_bits: int = 8, group_size: int = 128
                  ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Quantize (..., in, out) → (codes int8, scale f32 (..., ng, 1, out))."""
    *lead, in_dim, out = w.shape
    g = _group_size(in_dim, group_size, num_bits)
    ng = in_dim // g
    x = np.asarray(w, np.float32).reshape(*lead, ng, g, out)
    qmax = 2.0 ** (num_bits - 1) - 1
    scale = np.max(np.abs(x), axis=-2, keepdims=True) / qmax
    scale = np.where(scale == 0, 1.0, scale)
    codes = np.clip(np.round(x / scale), -qmax - 1, qmax).astype(np.int8)
    if num_bits == 4:
        pairs = codes.reshape(*lead, ng, g // 2, 2, out)
        lo, hi = pairs[..., 0, :], pairs[..., 1, :]
        codes = ((lo & 0x0F) | (hi << 4)).astype(np.int8)
    elif num_bits == 6:
        # FP6-class density (reference inference/v2 cuda_linear TC-FPx): four
        # 6-bit codes pack into three bytes — 0.75 B/code, 62% of int8's
        # weight stream and 37.5% of bf16's
        quads = codes.reshape(*lead, ng, g // 4, 4, out).astype(np.uint8)
        c0, c1, c2, c3 = (quads[..., j, :] for j in range(4))
        b0 = (c0 & 0x3F) | ((c1 & 0x03) << 6)
        b1 = ((c1 >> 2) & 0x0F) | ((c2 & 0x0F) << 4)
        b2 = ((c2 >> 4) & 0x03) | ((c3 & 0x3F) << 2)
        codes = np.stack([b0, b1, b2], axis=-2)  # (..., ng, g//4, 3, out)
        codes = codes.reshape(*lead, ng, (g // 4) * 3, out).astype(np.int8)
    return jnp.asarray(codes), jnp.asarray(scale.astype(np.float32))


def unpack6(u0, u1, u2):
    """Unpack three byte planes (int32, 0..255) into four signed 6-bit codes."""
    c0 = u0 & 0x3F
    c1 = ((u0 >> 6) & 0x03) | ((u1 & 0x0F) << 2)
    c2 = ((u1 >> 4) & 0x0F) | ((u2 & 0x03) << 4)
    c3 = (u2 >> 2) & 0x3F
    return tuple((c ^ 32) - 32 for c in (c0, c1, c2, c3))  # sign-extend


def _dequant_leaf(codes, scale, num_bits: int, dtype):
    *lead, ng, gc, out = codes.shape
    if num_bits == 4:
        lo = ((codes.astype(jnp.int8) << 4) >> 4).astype(jnp.float32)
        hi = (codes.astype(jnp.int8) >> 4).astype(jnp.float32)
        x = jnp.stack([lo, hi], axis=-2).reshape(*lead, ng, gc * 2, out)
    elif num_bits == 6:
        q = codes.reshape(*lead, ng, gc // 3, 3, out).astype(jnp.int32) & 0xFF
        cs = unpack6(q[..., 0, :], q[..., 1, :], q[..., 2, :])
        x = jnp.stack(cs, axis=-2).astype(jnp.float32)
        x = x.reshape(*lead, ng, (gc // 3) * 4, out)
    else:
        x = codes.astype(jnp.float32)
    w = (x * scale).reshape(*lead, ng * x.shape[-2], out)
    return w.astype(dtype)


def dequant_params(d: Dict, dtype) -> Dict:
    """Expand ``<name>::q{4,8}`` / ``<name>::scale`` pairs in a param dict back
    to full weights (called per scan slice — one layer materializes at a time)."""
    if not any("::q" in k for k in d):
        return d
    out = {}
    for k, v in d.items():
        if k.endswith("::scale"):
            continue
        if k.endswith(("::q8", "::q6", "::q4")):
            base, suffix = k.rsplit("::", 1)
            bits = int(suffix[1:])
            out[base] = _dequant_leaf(v, d[base + "::scale"], bits, dtype)
        else:
            out[k] = v
    return out


def quantize_param_tree(params: Dict, num_bits: int = 8, group_size: int = 128,
                        targets=DEFAULT_TARGETS) -> Dict:
    """Quantize the matmul weights in a TransformerLM param tree.

    Only leaves of ``blocks`` (of a ``layer_types`` model: of every
    ``blocks_<i>`` group) named in ``targets`` (>=2-D, floating) are
    converted; everything else passes through unchanged.
    """
    if num_bits not in (4, 6, 8):
        raise ValueError(f"num_bits must be 4, 6 or 8, got {num_bits}")
    out = dict(params)
    groups = [g for g in params
              if g == "blocks" or re.fullmatch(r"blocks_\d+", g)]
    if not groups:
        raise ValueError("expected a TransformerLM param tree with 'blocks'")
    for group in groups:
        new_blocks = {}
        for k, v in params[group].items():
            if k in targets and hasattr(v, "ndim") and v.ndim >= 2 \
                    and jnp.issubdtype(jnp.asarray(v).dtype, jnp.floating):
                codes, scale = quantize_leaf(v, num_bits, group_size)
                new_blocks[f"{k}::q{num_bits}"] = codes
                new_blocks[f"{k}::scale"] = scale
            else:
                new_blocks[k] = v
        out[group] = new_blocks
    return out


def quantized_tp_specs(tp_specs: Dict, qparams: Dict) -> Dict:
    """Map a model's tp_specs onto a quantized param tree: codes keep the
    weight's spec with an extra unsharded sub-group dim; scales likewise."""
    out = dict(tp_specs)
    blocks = dict(tp_specs.get("blocks", {}))
    new_blocks = {}
    for k in qparams["blocks"]:
        if k.endswith("::scale"):
            continue
        if "::q" in k:
            base = k.rsplit("::", 1)[0]
            spec = blocks.get(base)
            entries = list(spec) if spec is not None else []
            # (..., in, out) → (..., ng, g, out): 'in' entry rides the major
            # (ng) factor; the intra-group dim is never sharded
            if len(entries) >= 2:
                qspec = P(*entries[:-1], None, entries[-1])
            else:
                qspec = P()
            new_blocks[k] = qspec
            new_blocks[base + "::scale"] = qspec
        else:
            new_blocks[k] = blocks.get(k, P())
    out["blocks"] = new_blocks
    return out
