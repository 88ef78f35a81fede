"""Sparse self-attention op.

Reference: ``deepspeed/ops/sparse_attention/{sparse_self_attention.py,
matmul.py,softmax.py}`` — Triton block-sparse SDD/DSD matmuls + masked softmax.

TPU mapping: the block layout becomes an additive bias over the attention
logits consumed by the standard attention dispatch. XLA folds the mask into
the fused softmax; a Pallas kernel that *skips* masked KV blocks entirely
(splash-attention style) is the optimization path — the layout abstraction
here is what it would consume.
"""

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ...utils.logging import logger
from ..transformer.attention import attention
from .sparsity_config import SparsityConfig


def layout_to_bias(layout: np.ndarray, block: int) -> jnp.ndarray:
    """(H, nb, nb) block layout → (H, S, S) additive bias (0 / -inf)."""
    dense = np.repeat(np.repeat(layout, block, axis=1), block, axis=2)
    return jnp.where(jnp.asarray(dense), 0.0, -1e30)


class SparseSelfAttention:
    """reference ``SparseSelfAttention``: attention restricted to a block layout."""

    def __init__(self, sparsity_config: SparsityConfig, key_padding_mask_mode="add",
                 attn_mask_mode="mul", max_seq_length: int = 2048):
        self.config = sparsity_config
        self._bias_cache = {}
        self._layout_cache = {}

    def _layout(self, seq_len: int):
        if seq_len not in self._layout_cache:
            self._layout_cache[seq_len] = self.config.make_layout(seq_len)
        return self._layout_cache[seq_len]

    def _bias(self, seq_len: int):
        if seq_len not in self._bias_cache:
            self._bias_cache[seq_len] = layout_to_bias(
                self._layout(seq_len), self.config.block)
        return self._bias_cache[seq_len]

    def __call__(self, q, k, v, *, causal: Optional[bool] = None,
                 use_kernel: str = "auto"):
        """q/k/v: (B, S, h, d). Causality defaults to the layout's attention mode.

        ``use_kernel``: "auto" picks the block-skipping Pallas kernel
        (``block_sparse_kernel.py``) when the layout block is >=128 and the
        shapes fit; "never" forces the masked-XLA path (the numerics oracle);
        "always" raises if the kernel cannot run.
        """
        S = q.shape[1]
        if causal is None:
            causal = getattr(self.config, "attention", "bidirectional") == "unidirectional"
        # interpreted Pallas off the TPU would be a silent massive slowdown
        # against the fused XLA mask path, so "auto" means the kernel on TPU
        if use_kernel == "always" or (
                use_kernel == "auto" and jax.default_backend() == "tpu"):
            from .block_sparse_kernel import block_sparse_attention

            try:
                return block_sparse_attention(
                    q, k, v, self._layout(S), self.config.block, causal=causal)
            except NotImplementedError as e:
                if use_kernel == "always":
                    raise
                logger.warning(f"sparse attention takes the masked XLA path: {e}")
        bias = self._bias(S)  # (H, S, S)
        # bias broadcast: attention expects (B?, h, groups, Sq, Sk)-compatible
        return attention(q, k, v, causal=causal,
                         bias=bias[None, :, None, :, :], impl="xla")
