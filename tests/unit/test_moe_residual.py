"""Residual/PR-MoE tests (reference ``moe/layer.py:29,47,80-84,125-132``
``use_residual=True`` per arXiv:2201.05596): a dense MLP runs alongside the
routed experts and a learned ``softmax(Linear(H, 2))`` coefficient blends the
two outputs per token."""

import jax
import jax.numpy as jnp
import numpy as np

import deepspeed_tpu
from deepspeed_tpu.comm import topology as topo_mod
from deepspeed_tpu.models import TransformerLM, gpt2_config
from deepspeed_tpu.moe.layer import MoE, residual_mix


def _tiny_moe(use_residual, activation="gelu"):
    return MoE(hidden_size=16, num_experts=4, expert_intermediate_size=32,
               k=2, use_residual=use_residual, activation=activation)


class TestResidualMoELayer:
    def test_matches_manual_blend(self):
        """Residual output == coef0·moe_out + coef1·dense_mlp(x), with the
        plain-MoE branch bit-identical to use_residual=False on shared
        params (the reference formula, moe/layer.py:125-132)."""
        res = _tiny_moe(True)
        plain = _tiny_moe(False)
        p = res.init_params(jax.random.PRNGKey(0))
        p_plain = {k: p[k] for k in plain.init_params(jax.random.PRNGKey(0))}
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 16))

        y_res, aux_res = res.apply(p, x)
        y_moe, aux_moe = plain.apply(p_plain, x)
        np.testing.assert_allclose(float(aux_res), float(aux_moe), rtol=1e-6)

        h = jax.nn.gelu(x @ p["mlp_wi"], approximate=True)
        mlp_out = h @ p["mlp_wo"]
        coef = jax.nn.softmax(
            x.astype(jnp.float32) @ p["coef_w"] + p["coef_b"], axis=-1)
        expect = y_moe * coef[..., 0:1] + mlp_out * coef[..., 1:2]
        np.testing.assert_allclose(np.asarray(y_res), np.asarray(expect),
                                   rtol=2e-5, atol=2e-6)

    def test_zero_coef_bias_starts_balanced(self):
        """coef_b initializes to zero, so with a near-zero coef_w the blend
        starts ~50/50 — the PR-MoE warm-start the reference's Linear init
        gives in expectation."""
        res = _tiny_moe(True)
        p = res.init_params(jax.random.PRNGKey(0))
        p = dict(p, coef_w=jnp.zeros_like(p["coef_w"]))
        x = jax.random.normal(jax.random.PRNGKey(1), (1, 4, 16))
        y, _ = res.apply(p, x)
        plain = _tiny_moe(False)
        y_moe, _ = plain.apply({k: p[k] for k in ("wg", "wi", "wo")}, x)
        h = jax.nn.gelu(x @ p["mlp_wi"], approximate=True)
        mlp_out = h @ p["mlp_wo"]
        np.testing.assert_allclose(
            np.asarray(y), np.asarray(0.5 * y_moe + 0.5 * mlp_out),
            rtol=2e-5, atol=2e-6)

    def test_grads_flow_to_residual_branch(self):
        res = _tiny_moe(True)
        p = res.init_params(jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 16))

        def loss(p):
            y, aux = res.apply(p, x)
            return jnp.sum(y ** 2) + 0.01 * aux

        g = jax.jit(jax.grad(loss))(p)
        for k in ("mlp_wi", "mlp_wo", "coef_w", "coef_b", "wg", "wi", "wo"):
            assert float(jnp.max(jnp.abs(g[k]))) > 0, f"no grad into {k}"

    def test_swiglu_residual_branch(self):
        res = _tiny_moe(True, activation="swiglu")
        p = res.init_params(jax.random.PRNGKey(0))
        assert "mlp_wgate" in p and "wgate" in p
        x = jax.random.normal(jax.random.PRNGKey(1), (1, 8, 16))
        y, aux = res.apply(p, x)
        assert y.shape == x.shape and bool(jnp.isfinite(aux))

    def test_tp_specs_cover_params(self):
        res = _tiny_moe(True, activation="swiglu")
        p = res.init_params(jax.random.PRNGKey(0))
        assert set(res.tp_specs) == set(p)


class TestResidualMoEModel:
    def _cfg(self):
        return gpt2_config(
            "125m", hidden_size=32, num_layers=2, num_heads=2, vocab_size=128,
            max_seq_len=32, num_experts=4, moe_top_k=1, moe_use_residual=True)

    def test_param_surface_and_count(self):
        cfg = self._cfg()
        model = TransformerLM(cfg)
        params = model.init_params(jax.random.PRNGKey(0))
        blocks = params["blocks"]
        for k in ("res_wi", "res_wo", "res_coef_w", "res_coef_b"):
            assert k in blocks, k
        # the residual branch adds exactly L·(dense MLP + Linear(H,2)) params,
        # in both the actual tree and the num_parameters accounting
        cfg0 = gpt2_config(
            "125m", hidden_size=32, num_layers=2, num_heads=2, vocab_size=128,
            max_seq_len=32, num_experts=4, moe_top_k=1)
        params0 = TransformerLM(cfg0).init_params(jax.random.PRNGKey(0))
        count = lambda p: sum(int(np.prod(a.shape))  # noqa: E731
                              for a in jax.tree.leaves(p))
        H, I, L = cfg.hidden_size, cfg.mlp_dim, cfg.num_layers
        expected_delta = L * (2 * H * I + 2 * H + 2)
        assert count(params) - count(params0) == expected_delta
        assert cfg.num_parameters - cfg0.num_parameters == expected_delta

    def test_trains_and_beats_no_train(self):
        topo_mod.reset_topology()
        cfg = self._cfg()
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=TransformerLM(cfg), config={
                "train_micro_batch_size_per_gpu": 4,
                "gradient_accumulation_steps": 1,
                "optimizer": {"type": "adamw", "params": {"lr": 1e-2}},
                "zero_optimization": {"stage": 0},
                "steps_per_print": 0,
            })
        rng = np.random.default_rng(0)
        ids = jnp.asarray(rng.integers(0, 128, (4, 32), dtype=np.int32))
        losses = []
        for _ in range(8):
            loss = engine({"input_ids": ids})
            engine.backward(loss)
            engine.step()
            losses.append(float(loss))
        assert all(np.isfinite(losses))
        assert losses[-1] < losses[0], losses

    def test_expert_parallel_matches_single_device(self):
        """EP-sharded residual model reproduces the unsharded logits — the
        residual branch is replicated math, sharded over model axis only."""
        cfg = self._cfg()
        model = TransformerLM(cfg)
        params = model.init_params(jax.random.PRNGKey(0))
        rng = np.random.default_rng(1)
        ids = jnp.asarray(rng.integers(0, 128, (4, 32), dtype=np.int32))

        topo_mod.reset_topology()
        ref = np.asarray(model.apply(params, {"input_ids": ids}, train=False))

        topo_mod.reset_topology()
        topo = topo_mod.initialize_topology(data=2, model=1, seq=1, pipe=1,
                                            expert=4)
        sharded_params = jax.device_put(
            params, jax.tree.map(
                lambda s: jax.sharding.NamedSharding(topo.mesh, s),
                model.tp_specs, is_leaf=lambda x: isinstance(
                    x, jax.sharding.PartitionSpec)))
        got = np.asarray(model.apply(sharded_params, {"input_ids": ids},
                                     train=False))
        np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-5)
        topo_mod.reset_topology()
