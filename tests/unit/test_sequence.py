"""Sequence-parallel tests (reference: Ulysses usage in Megatron-DeepSpeed; here
the oracle is single-device XLA attention)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.comm import topology as topo_mod
from deepspeed_tpu.ops.transformer.attention import xla_attention
from deepspeed_tpu.sequence import DistributedAttention, ring_attention


@pytest.fixture
def seq_mesh():
    topo_mod.reset_topology()
    topo = topo_mod.initialize_topology(data=2, seq=4)
    yield topo
    topo_mod.reset_topology()


def _qkv(B=2, S=64, nh=8, kvh=8, hd=16, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (B, S, nh, hd), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, kvh, hd), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, kvh, hd), jnp.float32)
    return q, k, v


class TestUlysses:
    def test_matches_local_attention(self, seq_mesh):
        q, k, v = _qkv()
        ref = xla_attention(q, k, v, causal=True)
        dist_attn = DistributedAttention(
            lambda q, k, v: xla_attention(q, k, v, causal=True)
        )
        out = dist_attn(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-4, atol=1e-4)

    def test_grads_flow(self, seq_mesh):
        q, k, v = _qkv()
        dist_attn = DistributedAttention(lambda q, k, v: xla_attention(q, k, v, causal=True))

        def loss_d(q, k, v):
            return jnp.sum(dist_attn(q, k, v) ** 2)

        def loss_r(q, k, v):
            return jnp.sum(xla_attention(q, k, v, causal=True) ** 2)

        gd = jax.jit(jax.grad(loss_d, argnums=(0, 1, 2)))(q, k, v)
        gr = jax.jit(jax.grad(loss_r, argnums=(0, 1, 2)))(q, k, v)
        for a, b in zip(gd, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-3, atol=1e-3)


class TestRingAttention:
    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_xla(self, seq_mesh, causal):
        q, k, v = _qkv()
        ref = xla_attention(q, k, v, causal=causal)
        out = ring_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-4, atol=1e-4)

    def test_gqa(self, seq_mesh):
        q, k, v = _qkv(nh=8, kvh=2)
        ref = xla_attention(q, k, v, causal=True, num_kv_groups=4)
        out = ring_attention(q, k, v, causal=True, num_kv_groups=4)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-4, atol=1e-4)

    def test_backward_matches(self, seq_mesh):
        q, k, v = _qkv()
        gr = jax.jit(jax.grad(
            lambda *a: jnp.sum(xla_attention(*a, causal=True) ** 2),
            argnums=(0, 1, 2)))(q, k, v)
        gf = jax.jit(jax.grad(
            lambda *a: jnp.sum(ring_attention(*a, causal=True) ** 2),
            argnums=(0, 1, 2)))(q, k, v)
        for a, b in zip(gr, gf):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-3, atol=1e-3)

    def test_under_jit(self, seq_mesh):
        q, k, v = _qkv()
        f = jax.jit(lambda q, k, v: ring_attention(q, k, v, causal=True))
        ref = xla_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(f(q, k, v)), np.asarray(ref),
                                   rtol=1e-4, atol=1e-4)
