"""Tests: 1-bit optimizers, HF converters, sparse attention, random-LTD
(reference tests/unit/{runtime/half_precision/onebit, inference, ops/sparse_attention})."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.comm import topology as topo_mod
from deepspeed_tpu.models import TransformerLM, gpt2_config


def tiny_model(**kw):
    base = dict(vocab_size=128, hidden_size=64, num_layers=2, num_heads=4, max_seq_len=32)
    base.update(kw)
    return TransformerLM(gpt2_config("125m", **base))


class TestOnebit:
    def test_compressed_allreduce_error_feedback(self):
        topo_mod.reset_topology()
        topo = topo_mod.initialize_topology(data=8)
        from deepspeed_tpu.runtime.comm.compressed import compressed_allreduce
        from jax.sharding import PartitionSpec as P

        # distinct per-device grads; EF must preserve the mean over repeats
        g = jax.random.normal(jax.random.PRNGKey(0), (8, 4, 64))
        true_mean = jnp.mean(g, axis=0)

        def body(g, e):
            r, ne = compressed_allreduce(g[0], e[0], ("data",))
            return r[None], ne[None]

        import functools

        f = jax.jit(jax.shard_map(
            body, mesh=topo.mesh, in_specs=(P("data"), P("data")),
            out_specs=(P("data"), P("data")), axis_names={"data"}))
        err = jnp.zeros_like(g)
        acc = jnp.zeros_like(true_mean)
        rels = {}
        for i in range(1, 201):
            red, err = f(g, err)
            acc = acc + red[0]
            if i in (10, 200):
                rels[i] = float(jnp.max(jnp.abs(acc / i - true_mean)) /
                                jnp.max(jnp.abs(true_mean)))
        # EF guarantee: the time-average converges toward the true mean (the
        # residual is bounded, so the bias decays; exact rate depends on the
        # sign-quantizer limit cycle)
        assert rels[200] < 0.6 * rels[10]
        # single uncorrected step is much worse than the EF average
        one_shot, _ = f(g, jnp.zeros_like(g))
        rel1 = float(jnp.max(jnp.abs(one_shot[0] - true_mean)) /
                     jnp.max(jnp.abs(true_mean)))
        assert rels[200] < rel1
        topo_mod.reset_topology()

    def test_packed_wire_is_8x_smaller_than_int8(self):
        """The compiled HLO's all-gather operands prove the wire format:
        uint8 bitmaps move n/8 bytes vs n for int8 signs (32x vs fp32)."""
        import re

        topo_mod.reset_topology()
        topo = topo_mod.initialize_topology(data=8)
        from jax.sharding import PartitionSpec as P

        from deepspeed_tpu.runtime.comm.compressed import compressed_allreduce

        n = 4096

        def make(wire):
            def body(g, e):
                r, ne = compressed_allreduce(g[0], e[0], ("data",), wire=wire)
                return r[None], ne[None]

            return jax.jit(jax.shard_map(
                body, mesh=topo.mesh, in_specs=(P("data"), P("data")),
                out_specs=(P("data"), P("data")), axis_names={"data"}))

        g = jax.random.normal(jax.random.PRNGKey(0), (8, n))
        e = jnp.zeros_like(g)

        def gather_bytes(fn):
            hlo = fn.lower(g, e).compile().as_text()
            sizes = {"u8": 1, "s8": 1, "f32": 4, "bf16": 2, "pred": 1}
            total = 0
            # anchor on the all-gather DEF (`= u8[...]{...} all-gather(`):
            # a later fusion-call line merely REFERENCING %all-gather would
            # otherwise count its own (f32) result bytes for both wires
            for m in re.finditer(
                    r"=\s*(\w+)\[([\d,]*)\](?:\{[^}]*\})?\s+all-gather\(", hlo):
                dt, dims = m.group(1), m.group(2)
                count = 1
                for d in dims.split(","):
                    if d:
                        count *= int(d)
                total += count * sizes.get(dt, 4)
            return total

        b1, b8 = gather_bytes(make("1bit")), gather_bytes(make("int8"))
        assert 0 < b1 <= b8 / 7  # ~8x smaller (scales add a few bytes)
        # numerics: both wires EF-converge to the same mean
        f1, f8 = make("1bit"), make("int8")
        e1 = e8 = e
        a1 = a8 = jnp.zeros((n,))
        for _ in range(50):
            r1, e1 = f1(g, e1)
            r8, e8 = f8(g, e8)
            a1, a8 = a1 + r1[0], a8 + r8[0]
        true = jnp.mean(g, axis=0)
        rel = lambda a: float(jnp.max(jnp.abs(a / 50 - true)))  # noqa: E731
        assert abs(rel(a1) - rel(a8)) < 0.05
        topo_mod.reset_topology()

    def test_fp16_overflow_interaction(self):
        """fp16 + 1-bit: an overflow step must be skipped (scale drops), the
        EF residual must stay finite (the sanitizer), and training must
        recover afterwards."""
        topo_mod.reset_topology()
        engine, _, _, _ = deepspeed_tpu.initialize(model=tiny_model(), config={
            "train_batch_size": 8,
            "optimizer": {"type": "onebitadam", "params": {
                "lr": 1e-3, "freeze_step": 4}},
            "zero_optimization": {"stage": 1},
            # absurd initial scale: the first scaled fp16 grads overflow
            "fp16": {"enabled": True, "initial_scale_power": 18,
                     "loss_scale_window": 2},
            "mesh": {"data": 8},
            "steps_per_print": 0,
        })
        b = {"input_ids": jnp.asarray(np.random.default_rng(0).integers(
            0, 128, (8, 32), dtype=np.int32))}
        losses = []
        for _ in range(16):
            loss = engine(b)
            engine.backward(loss)
            engine.step()
            losses.append(float(loss))
        assert engine.skipped_steps >= 1  # the overflow was detected+skipped
        assert float(engine.scaler_state.cur_scale) < 2.0 ** 18  # backed off
        if engine._ef_errors is not None:  # compressed phase engaged
            for e in jax.tree.leaves(engine._ef_errors):
                assert bool(jnp.isfinite(e).all())  # sanitizer held
        assert np.isfinite(losses).all()
        assert losses[-1] < losses[0]  # recovered and trains

    def test_onebit_adam_trains_through_freeze(self):
        topo_mod.reset_topology()
        engine, _, _, _ = deepspeed_tpu.initialize(model=tiny_model(), config={
            "train_batch_size": 8,
            "optimizer": {"type": "OnebitAdam",
                          "params": {"lr": 1e-3, "freeze_step": 3}},
            "zero_optimization": {"stage": 1}, "mesh": {"data": 8}})
        b = {"input_ids": jnp.asarray(
            np.random.default_rng(0).integers(0, 128, (8, 32), dtype=np.int32))}
        losses = []
        for _ in range(8):
            loss = engine(b)
            engine.backward(loss)
            engine.step()
            losses.append(float(loss))
        assert np.isfinite(losses).all() and losses[-1] < losses[0]
        assert engine._ef_errors is not None  # compressed phase engaged


class TestHFConverters:
    def test_gpt2_logits_match(self):
        topo_mod.reset_topology()
        import torch
        from transformers import GPT2Config, GPT2LMHeadModel

        from deepspeed_tpu.models.hf_converters import from_hf

        torch.manual_seed(0)
        hf = GPT2LMHeadModel(GPT2Config(vocab_size=100, n_positions=32, n_embd=64,
                                        n_layer=2, n_head=4)).eval()
        model, params = from_hf(hf)
        ids = np.random.default_rng(0).integers(0, 100, (2, 16))
        with torch.no_grad():
            ref = hf(torch.tensor(ids)).logits.numpy()
        ours = np.asarray(model.logits(params, jnp.asarray(ids, jnp.int32)))[:, :, :100]
        np.testing.assert_allclose(ours, ref, atol=2e-3)

    def test_llama_gqa_logits_match(self):
        topo_mod.reset_topology()
        import torch
        from transformers import LlamaConfig, LlamaForCausalLM

        from deepspeed_tpu.models.hf_converters import from_hf

        torch.manual_seed(1)
        hf = LlamaForCausalLM(LlamaConfig(
            vocab_size=100, hidden_size=64, intermediate_size=128,
            num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
            max_position_embeddings=64)).eval()
        model, params = from_hf(hf)
        ids = np.random.default_rng(1).integers(0, 100, (2, 16))
        with torch.no_grad():
            ref = hf(torch.tensor(ids)).logits.numpy()
        ours = np.asarray(model.logits(params, jnp.asarray(ids, jnp.int32)))
        np.testing.assert_allclose(ours, ref, atol=2e-3)

    def test_converted_model_serves_through_inference_engine(self):
        topo_mod.reset_topology()
        import torch
        from transformers import GPT2Config, GPT2LMHeadModel

        from deepspeed_tpu.models.hf_converters import from_hf

        hf = GPT2LMHeadModel(GPT2Config(vocab_size=100, n_positions=64, n_embd=64,
                                        n_layer=2, n_head=4)).eval()
        model, params = from_hf(hf)
        eng = deepspeed_tpu.init_inference(model, dtype="fp32")
        eng.params = jax.device_put(params)
        ids = jnp.asarray(np.random.default_rng(0).integers(0, 100, (1, 8)), jnp.int32)
        out = eng.generate(ids, max_new_tokens=4, temperature=0.0)
        assert out.shape == (1, 4)


class TestSparseAttention:
    def test_dense_layout_equals_full(self):
        from deepspeed_tpu.ops.sparse_attention import (DenseSparsityConfig,
                                                        SparseSelfAttention)
        from deepspeed_tpu.ops.transformer.attention import xla_attention

        q = jax.random.normal(jax.random.PRNGKey(0), (2, 64, 4, 16))
        sa = SparseSelfAttention(DenseSparsityConfig(num_heads=4, block=16))
        np.testing.assert_allclose(np.asarray(sa(q, q, q, causal=False)),
                                   np.asarray(xla_attention(q, q, q, causal=False)),
                                   atol=1e-5)

    @pytest.mark.parametrize("which", ["fixed", "bigbird", "longformer", "variable"])
    def test_layouts_generate(self, which):
        from deepspeed_tpu.ops import sparse_attention as sp

        cfg = {
            "fixed": sp.FixedSparsityConfig(num_heads=4, block=16, num_local_blocks=2),
            "bigbird": sp.BigBirdSparsityConfig(num_heads=4, block=16),
            "longformer": sp.BSLongformerSparsityConfig(num_heads=4, block=16),
            "variable": sp.VariableSparsityConfig(num_heads=4, block=16),
        }[which]
        layout = cfg.make_layout(128)
        assert layout.shape == (4, 8, 8)
        assert layout.any()
        out = sp.SparseSelfAttention(cfg)(
            jax.random.normal(jax.random.PRNGKey(0), (1, 128, 4, 16)),
            jax.random.normal(jax.random.PRNGKey(1), (1, 128, 4, 16)),
            jax.random.normal(jax.random.PRNGKey(2), (1, 128, 4, 16)),
            causal=False)
        assert np.isfinite(np.asarray(out)).all()


class TestRandomLTD:
    def test_token_drop_passthrough(self):
        from deepspeed_tpu.runtime.data_pipeline.data_routing import random_ltd_apply

        x = jax.random.normal(jax.random.PRNGKey(0), (2, 16, 8))
        out = random_ltd_apply(lambda t: t * 2.0, x, keep=8, rng=jax.random.PRNGKey(1))
        doubled = np.isclose(np.asarray(out), 2 * np.asarray(x)).all(axis=-1)
        kept = np.isclose(np.asarray(out), np.asarray(x)).all(axis=-1)
        assert (doubled.sum(axis=1) == 8).all()  # exactly `keep` tokens processed
        assert (kept.sum(axis=1) == 8).all()  # the rest untouched

    def test_scheduler_anneals(self):
        from deepspeed_tpu.runtime.data_pipeline.data_routing import RandomLTDScheduler

        s = RandomLTDScheduler(total_layers=12, start_length=128, seq_length=1024,
                               schedule_steps=1000, increment=64)
        assert s.get_reserved_length(0) == 128
        assert s.get_reserved_length(1000) == 1024
        assert 128 < s.get_reserved_length(500) < 1024
        assert not s.applies_to_layer(0) and s.applies_to_layer(5)

    def test_trunk_ltd_model_loss_and_grads(self):
        from deepspeed_tpu.models import TransformerLM, gpt2_config

        m = TransformerLM(gpt2_config(
            "125m", vocab_size=128, hidden_size=64, num_layers=4, num_heads=4,
            max_seq_len=32, random_ltd=True))
        p = m.init_params(jax.random.PRNGKey(0))
        ids = jnp.asarray(np.random.default_rng(0).integers(0, 128, (2, 32)),
                          jnp.int32)
        batch = {"input_ids": ids, "ltd_keep": 16}
        loss = m.apply(p, batch, train=True, rng=jax.random.PRNGKey(1))
        assert jnp.isfinite(loss)
        g = jax.jit(jax.grad(lambda pp: m.apply(
            pp, batch, train=True, rng=jax.random.PRNGKey(1))))(p)
        assert all(bool(jnp.all(jnp.isfinite(x))) for x in jax.tree.leaves(g))
        # full-keep is exactly the plain trunk
        full = m.apply(p, {"input_ids": ids, "ltd_keep": 32}, train=True, rng=None)
        ref = m.apply(p, {"input_ids": ids}, train=True, rng=None)
        np.testing.assert_allclose(float(full), float(ref), rtol=1e-6)

    def test_engine_random_ltd_trains_and_anneals(self):
        from deepspeed_tpu.models import TransformerLM, gpt2_config

        topo_mod.reset_topology()
        m = TransformerLM(gpt2_config(
            "125m", vocab_size=128, hidden_size=64, num_layers=4, num_heads=4,
            max_seq_len=32, random_ltd=True))
        engine, _, _, _ = deepspeed_tpu.initialize(model=m, config={
            "train_batch_size": 8,
            "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
            "mesh": {"data": 8},
            "data_efficiency": {"data_routing": {"enabled": True, "random_ltd": {
                "enabled": True,
                "random_ltd_schedule": {
                    "min_value": 8, "max_value": 32,
                    "schedule_config": {"require_steps": 4, "seq_per_step": 8},
                }}}}})
        assert engine._ltd_keep_now() == 8
        b = {"input_ids": jnp.asarray(
            np.random.default_rng(0).integers(0, 128, (8, 32), dtype=np.int32))}
        losses = []
        for _ in range(6):
            loss = engine(b)
            engine.backward(loss)
            engine.step()
            losses.append(float(loss))
        assert np.isfinite(losses).all() and losses[-1] < losses[0]
        # schedule reached full length → LTD off (no subset variant)
        assert engine._ltd_keep_now() is None

    def test_engine_random_ltd_requires_model_flag(self):
        topo_mod.reset_topology()
        with pytest.raises(ValueError, match="random_ltd"):
            deepspeed_tpu.initialize(model=tiny_model(), config={
                "train_batch_size": 8,
                "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
                "mesh": {"data": 8},
                "data_efficiency": {"data_routing": {
                    "enabled": True, "random_ltd": {"enabled": True}}}})
