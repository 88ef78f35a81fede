"""Attention ops — XLA reference implementation + Pallas kernel dispatch.

Reference analogues: the fused CUDA attention kernels (training
``csrc/transformer/softmax_kernels.cu`` and inference
``csrc/transformer/inference/csrc/softmax.cu`` + KV-cache attention in
``pt_binding.cpp softmax_context``). On TPU the hot path is a Pallas flash
attention kernel (``ops/transformer/flash_attention.py``); the XLA einsum path
below is the always-available fallback and the numerics oracle for kernel tests
(mirroring the reference's kernel-vs-torch test strategy, SURVEY.md §4).

Dispatch: ``attention()`` picks the registered implementation — the op-builder
registry seam (reference ``op_builder/builder.py`` +
``accelerator.create_op_builder``). On a TPU backend that is the flash kernel;
it gives way to the XLA path only for the features it documents as unsupported
(bias, softcap, q_offset) and for shapes it refuses with ``UnsupportedShape``,
and the latter is logged when the program is traced. A kernel that fails to
import, lower or fit raises.
"""

from typing import Optional

import jax
import jax.numpy as jnp

from ...utils.logging import logger

_IMPLS = {}
_DEFAULT_IMPL = None


def register_impl(name):
    def deco(fn):
        _IMPLS[name] = fn
        return fn
    return deco


def set_default_impl(name: Optional[str]):
    """Force an implementation (None = auto)."""
    global _DEFAULT_IMPL
    _DEFAULT_IMPL = name


def get_default_impl() -> Optional[str]:
    return _DEFAULT_IMPL


def _auto_impl() -> str:
    if _DEFAULT_IMPL is not None:
        return _DEFAULT_IMPL
    return "pallas_flash" if jax.default_backend() == "tpu" else "xla"


class UnsupportedFeature(NotImplementedError):
    """A kernel asked for a feature it documents as unsupported (bias, softcap,
    q_offset): ``attention()`` takes the XLA path. Its own class, so that a
    ``NotImplementedError`` out of lowering or compiling is never swallowed."""


class UnsupportedShape(ValueError):
    """A kernel refusing a shape (block divisibility, head size, VMEM window).

    ``attention()`` lets it give way to the XLA path only when it chose the
    kernel itself, and logs the reason as the program is traced; a caller that
    named the kernel gets the error."""


@register_impl("xla")
def xla_attention(q, k, v, *, causal=True, q_offset=0, num_kv_groups=1,
                  softcap=0.0, bias=None, scale=None):
    """Plain einsum attention on (B, Sq, h, d) q and (B, Skv, hkv, d) k/v.

    fp32 softmax; GQA handled by reshaping q into (hkv, groups); ``q_offset``
    shifts the causal diagonal for KV-cache decode (query i attends to keys
    ≤ i + q_offset).
    """
    B, Sq, nh, hd = q.shape
    Skv, kvh = k.shape[1], k.shape[2]
    groups = num_kv_groups
    scale = scale if scale is not None else hd ** -0.5

    qg = q.reshape(B, Sq, kvh, groups, hd)
    logits = jnp.einsum("bqhgd,bkhd->bhgqk", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    if softcap and softcap > 0.0:
        logits = softcap * jnp.tanh(logits / softcap)
    if bias is not None:
        logits = logits + bias.astype(jnp.float32)
    if causal:
        qpos = jnp.arange(Sq)[:, None] + q_offset
        kpos = jnp.arange(Skv)[None, :]
        mask = qpos >= kpos  # (Sq, Skv)
        logits = jnp.where(mask[None, None, None], logits, jnp.float32(-1e30))
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs, v.astype(jnp.float32))
    return out.reshape(B, Sq, nh, v.shape[-1]).astype(q.dtype)


def attention(q, k, v, *, causal=True, q_offset=0, num_kv_groups=1,
              softcap=0.0, bias=None, scale=None, impl: Optional[str] = None):
    """Multi-head attention with optional GQA / causal offset / softcap.

    q: (B, Sq, num_heads, head_dim); k/v: (B, Skv, kv_heads, head_dim).
    Returns (B, Sq, num_heads, head_dim) in q.dtype.
    """
    kw = dict(causal=causal, q_offset=q_offset, num_kv_groups=num_kv_groups,
              softcap=softcap, bias=bias, scale=scale)
    try:
        return _IMPLS[impl or _auto_impl()](q, k, v, **kw)
    except UnsupportedFeature:
        pass
    except UnsupportedShape as e:
        if impl is not None or _DEFAULT_IMPL is not None:
            raise
        # trace time: once per compiled program, never per step
        logger.warning(f"attention: q{tuple(q.shape)} k{tuple(k.shape)} takes "
                       f"the XLA einsum path: {e}")
    return xla_attention(q, k, v, **kw)


from . import flash_attention  # noqa: E402,F401  (registers "pallas_flash")
