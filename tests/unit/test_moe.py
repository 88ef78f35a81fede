"""MoE tests (reference ``tests/unit/moe/``: gating semantics, EP dispatch,
MoE model training)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.comm import topology as topo_mod
from deepspeed_tpu.models import TransformerLM, gpt2_config
from deepspeed_tpu.moe.layer import MoE
from deepspeed_tpu.moe.sharded_moe import compute_capacity, topk_gating


class TestGating:
    @pytest.mark.parametrize("k", [1, 2])
    def test_dispatch_respects_capacity(self, k):
        T, E = 64, 4
        logits = jax.random.normal(jax.random.PRNGKey(0), (T, E))
        combine, dispatch, l_aux, meta = topk_gating(logits, k=k, capacity_factor=1.0)
        C = meta["capacity"]
        assert C == compute_capacity(T, E, 1.0, k=k)
        d = np.asarray(dispatch)
        # each (expert, slot) pair serves at most one token
        assert d.sum(axis=0).max() <= 1
        # each token sent to at most k experts
        assert d.reshape(T, -1).sum(axis=1).max() <= k

    def test_combine_weights_sum_to_one_when_not_dropped(self):
        T, E = 32, 8
        logits = jax.random.normal(jax.random.PRNGKey(1), (T, E))
        combine, dispatch, _, _ = topk_gating(logits, k=2, capacity_factor=8.0)
        sums = np.asarray(combine).reshape(T, -1).sum(axis=1)
        np.testing.assert_allclose(sums, 1.0, atol=1e-5)

    def test_aux_loss_uniform_router_is_one(self):
        # uniform gates + uniform dispatch → l_aux == 1 (reference normalization)
        T, E = 1024, 4
        logits = jnp.zeros((T, E))
        _, _, l_aux, _ = topk_gating(logits, k=1, capacity_factor=4.0)
        assert 0.9 < float(l_aux) < 1.1


class TestMoELayer:
    def test_forward_and_grads(self):
        layer = MoE(hidden_size=32, num_experts=4, expert_intermediate_size=64, k=2)
        p = layer.init_params(jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 32))
        y, aux = layer.apply(p, x)
        assert y.shape == x.shape and jnp.isfinite(aux)

        def loss(p):
            y, aux = layer.apply(p, x)
            return jnp.sum(y ** 2) + 0.01 * aux

        g = jax.jit(jax.grad(loss))(p)
        assert all(bool(jnp.all(jnp.isfinite(v))) for v in jax.tree.leaves(g))
        # router must receive gradient through the combine weights
        assert float(jnp.max(jnp.abs(g["wg"]))) > 0

    def test_expert_parallel_matches_single_device(self):
        layer = MoE(hidden_size=32, num_experts=4, expert_intermediate_size=64, k=1)
        p = layer.init_params(jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, 32))
        topo_mod.reset_topology()
        y_ref, aux_ref = jax.jit(layer.apply)(p, x)
        topo_mod.initialize_topology(data=2, expert=4)
        y_ep, aux_ep = jax.jit(layer.apply)(p, x)
        topo_mod.reset_topology()
        np.testing.assert_allclose(np.asarray(y_ep), np.asarray(y_ref), rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(float(aux_ep), float(aux_ref), rtol=1e-5)


class TestMoEModel:
    def test_moe_transformer_trains(self):
        topo_mod.reset_topology()
        cfg = gpt2_config("125m", vocab_size=128, hidden_size=64, num_layers=2,
                          num_heads=4, max_seq_len=32, num_experts=4, moe_top_k=2)
        m = TransformerLM(cfg)
        config = {
            "train_batch_size": 8,
            "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
            "zero_optimization": {"stage": 2},
            "mesh": {"data": 2, "expert": 4},
        }
        engine, _, _, _ = deepspeed_tpu.initialize(model=m, config=config)
        rng = np.random.default_rng(0)
        ids = jnp.asarray(rng.integers(0, 128, (8, 32), dtype=np.int32))
        losses = []
        for _ in range(8):
            loss = engine({"input_ids": ids})
            engine.backward(loss)
            engine.step()
            losses.append(float(loss))
        assert losses[-1] < losses[0]
        assert np.isfinite(losses).all()


class TestGatingEdges:
    """Capacity/drop-policy edges (reference sharded_moe top1/top2 gating)."""

    def test_capacity_formula(self):
        from deepspeed_tpu.moe.sharded_moe import compute_capacity

        assert compute_capacity(64, 8, 1.0) == 8
        assert compute_capacity(64, 8, 1.25) == 10
        assert compute_capacity(64, 8, 1.0, k=2) == 16
        assert compute_capacity(4, 8, 1.0, min_capacity=4) == 4  # floor

    def test_overloaded_expert_drops_exactly_overflow(self):
        from deepspeed_tpu.moe.sharded_moe import topk_gating

        # all 16 tokens prefer expert 0; capacity 4 → 12 dropped
        logits = jnp.tile(jnp.asarray([[10.0, 0.0]]), (16, 1))
        combine, dispatch, l_aux, meta = topk_gating(
            logits, k=1, capacity_factor=1.0, min_capacity=4)
        assert meta["capacity"] == 8  # ceil(16/2 * 1.0)
        kept = int(dispatch.sum())
        assert kept == 8  # expert 0 filled to capacity, rest dropped
        assert float(meta["dropped_fraction"]) == pytest.approx(0.5)

    def test_no_drop_mode_keeps_everything(self):
        from deepspeed_tpu.moe.sharded_moe import topk_gating

        logits = jnp.tile(jnp.asarray([[10.0, 0.0]]), (16, 1))
        _, dispatch, _, meta = topk_gating(logits, k=1, drop_tokens=False)
        assert int(dispatch.sum()) == 16
        assert float(meta["dropped_fraction"]) == 0.0

    def test_first_choice_priority_over_second(self):
        from deepspeed_tpu.moe.sharded_moe import topk_gating

        # expert 0 is everyone's first choice; with k=2 the second choices
        # (expert 1) must not displace first-choice slots of expert 0
        T = 8
        logits = jnp.tile(jnp.asarray([[5.0, 4.0, -5.0]]), (T, 1))
        combine, dispatch, _, meta = topk_gating(
            logits, k=2, capacity_factor=1.0, min_capacity=2)
        C = meta["capacity"]
        # expert 0 gets exactly C tokens — all first choices
        assert int(dispatch[:, 0, :].sum()) == min(T, C)
        # combine weights normalized over the kept top-k pair
        row = np.asarray(combine[0].sum(-1))
        assert row[0] + row[1] == pytest.approx(1.0, abs=1e-5)

    def test_combine_zero_for_dropped_tokens(self):
        from deepspeed_tpu.moe.sharded_moe import topk_gating

        logits = jnp.tile(jnp.asarray([[10.0, 0.0]]), (16, 1))
        combine, dispatch, _, meta = topk_gating(
            logits, k=1, capacity_factor=0.5, min_capacity=2)
        # a dropped token's combine row is exactly zero (no phantom output)
        per_token = np.asarray(combine.sum((1, 2)))
        dropped = per_token == 0.0
        assert dropped.sum() == 16 - int(dispatch.sum())

    def test_balanced_router_fills_all_experts(self):
        from deepspeed_tpu.moe.sharded_moe import topk_gating

        rngs = np.random.default_rng(0)
        logits = jnp.asarray(rngs.standard_normal((64, 8)), jnp.float32)
        _, dispatch, l_aux, meta = topk_gating(logits, k=2,
                                               capacity_factor=2.0)
        assert (np.asarray(meta["tokens_per_expert"]) > 0).all()
        assert float(l_aux) > 0
