"""Sequence parallelism: Ulysses all-to-all attention + ring attention.

Reference: ``deepspeed/sequence/layer.py`` — ``single_all_to_all:15``,
``_SeqAllToAll:44``, ``DistributedAttention:60``. The reference's long-context
mechanism is Ulysses only (SURVEY.md §5): an all-to-all re-shards activations
from sequence-sharded to head-sharded around any local attention, giving O(N/P)
activation memory in the sequence dimension.

TPU-native design adds two modes:

1. **Ulysses** (``DistributedAttention``): ``lax.all_to_all`` over the ``seq``
   mesh axis inside ``shard_map`` — identical math to the reference, with the
   all-to-all riding ICI. Also usable implicitly through GSPMD: the model's
   sharding constraints (``models/transformer.py _heads_spec``) express the same
   reshard declaratively.

2. **Ring attention** (``ring_attention``): blockwise flash-style attention where
   K/V chunks rotate around the seq axis via ``ppermute`` (the reference has no
   equivalent; this surpasses Ulysses for P > num_heads and overlaps comm with
   compute). Causal masking is resolved per (query-chunk, source-chunk) pair;
   autodiff goes through ``lax.scan``'s transpose (reverse-direction ppermutes).
"""

from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ..comm.topology import SEQ_AXIS, ZERO_AXES, get_topology

NEG_INF = -1e30


def single_all_to_all(x, scatter_idx: int, gather_idx: int, axis_name: str = SEQ_AXIS):
    """All-to-all re-shard inside shard_map (reference ``layer.py:15``): splits
    dim ``scatter_idx`` across the axis, gathers dim ``gather_idx``."""
    return lax.all_to_all(x, axis_name, split_axis=scatter_idx,
                          concat_axis=gather_idx, tiled=True)


class DistributedAttention:
    """Ulysses attention wrapper (reference ``DistributedAttention``, ``layer.py:60``).

    ``local_attention(q, k, v, *args, **kwargs)`` operates on (B, S, h, d); this
    wrapper is called with sequence-sharded (B, S/P, H, d) inputs *inside*
    shard_map (or via ``__call__`` which builds the shard_map over the global
    mesh). scatter_idx=2 (heads), gather_idx=1 (sequence) as in the reference.
    """

    def __init__(self, local_attention: Callable, sequence_process_group: str = SEQ_AXIS,
                 scatter_idx: int = 2, gather_idx: int = 1):
        self.local_attn = local_attention
        self.axis = sequence_process_group
        self.scatter_idx = scatter_idx
        self.gather_idx = gather_idx

    def attend_sharded(self, query, key, value, *args, **kwargs):
        """Body to call when already inside shard_map over the seq axis."""
        q = single_all_to_all(query, self.scatter_idx, self.gather_idx, self.axis)
        k = single_all_to_all(key, self.scatter_idx, self.gather_idx, self.axis)
        v = single_all_to_all(value, self.scatter_idx, self.gather_idx, self.axis)
        ctx = self.local_attn(q, k, v, *args, **kwargs)
        # inverse reshard: scatter sequence, gather heads
        return single_all_to_all(ctx, self.gather_idx, self.scatter_idx, self.axis)

    def __call__(self, query, key, value, *args, **kwargs):
        topo = get_topology()
        if topo.get_dim(self.axis) == 1:
            return self.local_attn(query, key, value, *args, **kwargs)
        spec = P(None, self.axis, None, None)

        def body(q, k, v):
            return self.attend_sharded(q, k, v, *args, **kwargs)

        return jax.shard_map(
            body, mesh=topo.mesh, in_specs=(spec, spec, spec), out_specs=spec,
        )(query, key, value)


# ----------------------------------------------------------------------------
# ring attention
# ----------------------------------------------------------------------------

def _ring_attention_sharded(q, k, v, *, axis_name: str, causal: bool,
                            num_kv_groups: int = 1, scale: Optional[float] = None):
    """Blockwise attention over a rotating K/V ring (call inside shard_map).

    q: (B, Sl, nh, hd); k/v: (B, Sl, kvh, hd) — the local sequence chunk.
    Online-softmax accumulation identical to flash attention, one step per ring
    position; K/V travel around the ring via ppermute while the accumulator
    stays put.
    """
    B, Sl, nh, hd = q.shape
    kvh = k.shape[2]
    g = num_kv_groups
    scale = scale if scale is not None else hd ** -0.5
    p_size = lax.axis_size(axis_name)
    my = lax.axis_index(axis_name)

    qf = q.astype(jnp.float32) * scale
    qg = qf.reshape(B, Sl, kvh, g, hd)

    # derive the init carry from q so it carries q's varying-axes type under
    # shard_map (a plain jnp.zeros is "unvarying" and trips scan's type check)
    zvar = jnp.sum(qf) * 0.0
    m0 = jnp.full((B, kvh, g, Sl), NEG_INF, jnp.float32) + zvar
    l0 = jnp.zeros((B, kvh, g, Sl), jnp.float32) + zvar
    acc0 = jnp.zeros((B, Sl, kvh, g, hd), jnp.float32) + zvar
    perm = [(i, (i + 1) % p_size) for i in range(p_size)]

    def step(carry, t):
        m, l, acc, kc, vc = carry
        src = (my - t) % p_size  # which chunk we currently hold
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, kc.astype(jnp.float32))
        if causal:
            qpos = my * Sl + lax.broadcasted_iota(jnp.int32, (Sl, Sl), 0)
            kpos = src * Sl + lax.broadcasted_iota(jnp.int32, (Sl, Sl), 1)
            s = jnp.where((qpos >= kpos)[None, None, None], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l_new = alpha * l + jnp.sum(p, axis=-1)
        acc_new = acc * jnp.moveaxis(alpha, -1, 1)[..., None] + jnp.einsum(
            "bhgqk,bkhd->bqhgd", p, vc.astype(jnp.float32)
        )
        # rotate K/V to the next rank (last rotation returns them home; XLA
        # dead-code-eliminates it when the result is unused)
        kc = lax.ppermute(kc, axis_name, perm)
        vc = lax.ppermute(vc, axis_name, perm)
        return (m_new, l_new, acc_new, kc, vc), None

    (m, l, acc, _, _), _ = lax.scan(step, (m0, l0, acc0, k, v), jnp.arange(p_size))
    l = jnp.where(l == 0.0, 1.0, l)
    out = acc / jnp.moveaxis(l, -1, 1)[..., None]
    return out.reshape(B, Sl, nh, hd).astype(q.dtype)


def ring_attention(q, k, v, *, causal: bool = True, num_kv_groups: int = 1,
                   scale: Optional[float] = None, axis_name: str = SEQ_AXIS,
                   batch_axes: Any = ZERO_AXES):
    """Ring attention over the global mesh: q/k/v are global (B, S, h, d) arrays
    (sequence axis sharded over ``axis_name``)."""
    topo = get_topology()
    if topo.get_dim(axis_name) == 1:
        from ..ops.transformer.attention import attention

        return attention(q, k, v, causal=causal, num_kv_groups=num_kv_groups, scale=scale)
    spec = P(batch_axes, axis_name, None, None)

    def body(q, k, v):
        return _ring_attention_sharded(
            q, k, v, axis_name=axis_name, causal=causal,
            num_kv_groups=num_kv_groups, scale=scale,
        )

    return jax.shard_map(
        body, mesh=topo.mesh, in_specs=(spec, spec, spec), out_specs=spec,
    )(q, k, v)


UlyssesAttention = DistributedAttention
