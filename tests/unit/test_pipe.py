"""Pipeline parallelism tests (reference ``tests/unit/runtime/pipe/``: schedule
correctness + LinearStackPipe training; here the oracle is the unpipelined model)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.comm import topology as topo_mod
from deepspeed_tpu.models import TransformerLM, gpt2_config
from deepspeed_tpu.runtime.pipe import LayerSpec, PipelinedLM, PipelineModule


@pytest.fixture
def pipe_mesh():
    topo_mod.reset_topology()
    topo = topo_mod.initialize_topology(data=2, pipe=4)
    yield topo
    topo_mod.reset_topology()


def tiny_cfg(**kw):
    base = dict(vocab_size=128, hidden_size=64, num_layers=4, num_heads=4, max_seq_len=32)
    base.update(kw)
    return gpt2_config("125m", **base)


class Linear:
    """Homogeneous layer for PipelineModule (reference LinearStackPipe fixture)."""

    def __init__(self, dim):
        self.dim = dim

    def init_params(self, rng):
        return {"w": jax.random.normal(rng, (self.dim, self.dim)) * 0.1 + jnp.eye(self.dim)}

    def apply(self, p, x):
        return jax.nn.relu(x @ p["w"])


class TestSpmdPipeline:
    def test_matches_dense_loss_and_grads(self, pipe_mesh):
        cfg = tiny_cfg()
        base = TransformerLM(cfg)
        p_dense = base.init_params(jax.random.PRNGKey(0))
        ids = jnp.asarray(np.random.default_rng(0).integers(0, 128, (8, 32), dtype=np.int32))
        plm = PipelinedLM(base, topology=pipe_mesh)
        plm.num_micro = 4
        pp = plm.init_params(jax.random.PRNGKey(0))
        ld = float(base.apply(p_dense, {"input_ids": ids}))
        # jitted: eagerly, every operation of the pipelined program is
        # compiled and dispatched over the eight devices by itself
        lp = float(jax.jit(plm.apply)(pp, {"input_ids": ids}))
        assert abs(ld - lp) < 1e-4
        gd = jax.grad(lambda p: base.apply(p, {"input_ids": ids}))(p_dense)
        gp = jax.jit(jax.grad(lambda p: plm.apply(p, {"input_ids": ids})))(pp)
        a, b = np.asarray(gd["wte"]), np.asarray(gp["wte"])
        assert np.max(np.abs(a - b)) / (np.max(np.abs(a)) + 1e-9) < 1e-4

    def test_microbatch_count_indifference(self, pipe_mesh):
        cfg = tiny_cfg()
        base = TransformerLM(cfg)
        ids = jnp.asarray(np.random.default_rng(1).integers(0, 128, (8, 32), dtype=np.int32))
        losses = []
        for M in (2, 8):
            plm = PipelinedLM(base, topology=pipe_mesh)
            plm.num_micro = M
            pp = plm.init_params(jax.random.PRNGKey(0))
            losses.append(float(jax.jit(plm.apply)(pp, {"input_ids": ids})))
        assert abs(losses[0] - losses[1]) < 1e-4


class TestPipelineModule:
    def test_linear_stack(self, pipe_mesh):
        dim = 16
        layers = [LayerSpec(Linear, dim) for _ in range(8)]
        pm = PipelineModule(layers, num_stages=4, topology=pipe_mesh,
                            loss_fn=lambda out, y: jnp.mean((out - y) ** 2))
        pm.num_micro = 2
        p = pm.init_params(jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (4, dim))
        y = jax.random.normal(jax.random.PRNGKey(2), (4, dim))
        loss = pm.apply(p, (x, y))
        assert jnp.isfinite(loss)
        # oracle: run the 8 layers sequentially
        built = [s.build() for s in [LayerSpec(Linear, dim)] * 8]
        stacked = jax.tree.map(lambda a: a.reshape((8,) + a.shape[2:]), p["stages"])
        h = x
        for i in range(8):
            h = built[i].apply(jax.tree.map(lambda a: a[i], stacked), h)
        ref = jnp.mean((h - y) ** 2)
        assert abs(float(loss) - float(ref)) < 1e-5


class Embed:
    """Token embedding (shape-changing ingest layer)."""

    def __init__(self, vocab, dim):
        self.vocab, self.dim = vocab, dim

    def init_params(self, rng):
        return {"w": jax.random.normal(rng, (self.vocab, self.dim)) * 0.05}

    def apply(self, p, ids):
        return jnp.take(p["w"], ids, axis=0)


class TiedHead:
    """LM head reusing the embedding weights (TiedLayerSpec partner)."""

    def __init__(self, vocab, dim):
        self.vocab, self.dim = vocab, dim

    def init_params(self, rng):
        return {"w": jax.random.normal(rng, (self.vocab, self.dim)) * 0.05}

    def apply(self, p, x):
        return x @ p["w"].T


class TestHeterogeneousPipeline:
    def test_tied_embedding_unequal_stages_match_dense(self):
        """Reference TiedLayerSpec (pipe/module.py:77) + arbitrary layer lists
        (_partition_layers:370): an embedding-tied LM head with an UNEQUAL
        middle (3 layers over 2 stages) must match the dense composition's
        loss and grads — including the tied weight's summed cotangent (the
        ReduceTiedGrads analogue)."""
        from deepspeed_tpu.runtime.pipe import TiedLayerSpec

        topo_mod.reset_topology()
        topo = topo_mod.initialize_topology(data=4, pipe=2)
        V, D = 64, 32
        specs = [
            TiedLayerSpec("embed", Embed, V, D),
            LayerSpec(Linear, D),
            LayerSpec(Linear, D),
            LayerSpec(Linear, D),
            TiedLayerSpec("embed", TiedHead, V, D),
        ]

        def ce(logits, labels):
            lg = logits.astype(jnp.float32)
            logz = jax.scipy.special.logsumexp(lg, axis=-1)
            gold = jnp.take_along_axis(lg, labels[..., None], axis=-1)[..., 0]
            return jnp.mean(logz - gold)

        mod = PipelineModule(specs, loss_fn=ce, topology=topo)
        assert mod._heterogeneous
        mod.num_micro = 2
        params = mod.init_params(jax.random.PRNGKey(0))
        assert set(params["tied"]) == {"embed"}
        assert len(params["layers"]) == 3

        rng = np.random.default_rng(3)
        ids = jnp.asarray(rng.integers(0, V, (4, 16), dtype=np.int32))
        labels = jnp.asarray(rng.integers(0, V, (4, 16), dtype=np.int32))

        # dense oracle: same layers applied sequentially, shared tied weights
        built = mod._built

        def dense(params):
            h = built[0].apply(params["tied"]["embed"], ids)
            for i in (1, 2, 3):
                h = built[i].apply(params["layers"][f"l{i}"], h)
            return ce(built[4].apply(params["tied"]["embed"], h), labels)

        ld = float(dense(params))
        lp = float(mod.apply(params, (ids, labels)))
        assert abs(ld - lp) < 1e-5
        gd = jax.grad(dense)(params)
        gp = jax.jit(jax.grad(lambda p: mod.apply(p, (ids, labels))))(params)
        for a, b in zip(jax.tree.leaves(gd), jax.tree.leaves(gp)):
            scale = np.abs(np.asarray(a)).max() + 1e-9
            np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                       atol=1e-5 * scale, rtol=1e-4)
        topo_mod.reset_topology()

    def test_parameters_partition_balances(self):
        """partition_method='parameters' splits a lopsided stack by weight
        count, not layer count — and never yields empty or inverted stages."""
        topo_mod.reset_topology()
        topo = topo_mod.initialize_topology(data=4, pipe=2)

        class Wide(Linear):
            """Bottleneck layer with 32x the weight of a Linear(64)."""

            def init_params(self, rng):
                k1, k2 = jax.random.split(rng)
                return {"w1": jax.random.normal(k1, (self.dim, self.dim * 16)) * 0.05,
                        "w2": jax.random.normal(k2, (self.dim * 16, self.dim)) * 0.05}

            def apply(self, p, x):
                return jax.nn.relu(x @ p["w1"] @ p["w2"]) + x

        specs = [LayerSpec(Wide, 64)] + [LayerSpec(Linear, 64)] * 5
        mod = PipelineModule(specs, topology=topo,
                             partition_method="parameters")
        assert mod._heterogeneous
        params = mod.init_params(jax.random.PRNGKey(0))
        mb = jax.eval_shape(lambda: jnp.zeros((2, 64)))
        _, _, ranges = mod._analyze(params, mb)
        assert len(ranges) == 2
        assert sum(hi - lo for lo, hi in ranges) == 6
        for lo, hi in ranges:
            assert hi > lo  # no empty/inverted stages
        # the Wide layer dominates the weight count: stage 0 takes ONLY it
        assert ranges[0] == (0, 1)
        x = jnp.asarray(np.random.default_rng(0).normal(size=(4, 64)),
                        jnp.float32)
        y = x * 0.5
        mod.num_micro = 2
        loss = mod.apply(params, (x, y))
        assert np.isfinite(float(loss))
        topo_mod.reset_topology()


class TestPipelineEngine:
    def test_train_batch_loss_decreases(self, pipe_mesh):
        cfg = tiny_cfg(num_layers=4)
        model = PipelinedLM(TransformerLM(cfg), topology=pipe_mesh)
        config = {
            "train_micro_batch_size_per_gpu": 2,
            "gradient_accumulation_steps": 4,
            "optimizer": {"type": "adamw", "params": {"lr": 2e-3}},
            "zero_optimization": {"stage": 1},
            "bf16": {"enabled": True},
            "mesh": {"data": 2, "pipe": 4},
        }
        engine, _, _, _ = deepspeed_tpu.initialize(model=model, config=config)
        rng = np.random.default_rng(0)
        fixed = rng.integers(0, 128, (4, 32), dtype=np.int32)

        def it():
            while True:  # fixed data → loss must fall by memorization
                yield {"input_ids": fixed}

        data = it()
        losses = [float(engine.train_batch(data)) for _ in range(8)]
        assert losses[-1] < losses[0]
        assert engine.global_steps == 8

    def test_forward_outside_train_batch_raises(self, pipe_mesh):
        cfg = tiny_cfg(num_layers=4)
        model = PipelinedLM(TransformerLM(cfg), topology=pipe_mesh)
        config = {
            "train_batch_size": 8,
            "optimizer": {"type": "sgd", "params": {"lr": 1e-3}},
            "mesh": {"data": 2, "pipe": 4},
        }
        engine, _, _, _ = deepspeed_tpu.initialize(model=model, config=config)
        with pytest.raises(RuntimeError):
            engine({"input_ids": jnp.zeros((8, 32), jnp.int32)})


class Test3DParallelism:
    def test_pp_dp_tp_hybrid_trains(self):
        """Full 3D: pipeline x data x tensor parallel in one mesh (reference
        PipeModelDataParallelTopology, runtime/pipe/topology.py:244)."""
        topo = topo_mod.initialize_topology(data=2, pipe=2, model=2)
        cfg = tiny_cfg(num_layers=4, vocab_size=256, hidden_size=128)
        model = PipelinedLM(TransformerLM(cfg), topology=topo)
        engine, _, _, _ = deepspeed_tpu.initialize(model=model, config={
            "train_micro_batch_size_per_gpu": 2,
            "gradient_accumulation_steps": 2,
            "optimizer": {"type": "adamw", "params": {"lr": 2e-3}},
            "zero_optimization": {"stage": 1},
            "bf16": {"enabled": True},
            "mesh": {"data": 2, "pipe": 2, "model": 2},
        })
        rng = np.random.default_rng(0)
        fixed = rng.integers(0, 256, (4, 32), dtype=np.int32)

        def it():
            while True:
                yield {"input_ids": fixed}

        losses = [float(engine.train_batch(it())) for _ in range(5)]
        assert np.isfinite(losses).all() and losses[-1] < losses[0]


class TestStageShardedHeterogeneous:
    def test_stage_sharded_bytes_and_grads(self):
        """With an example_input at construction, untied middle layers are
        flat-packed per stage and SHARDED over pipe: per-stage bytes ≈ the
        stage's own share (not the full model), and grads still match the
        dense composition — including the tied weight's psum'd cotangent."""
        from deepspeed_tpu.runtime.pipe import TiedLayerSpec

        topo_mod.reset_topology()
        topo = topo_mod.initialize_topology(data=4, pipe=2)
        V, D = 64, 32
        specs = [
            TiedLayerSpec("embed", Embed, V, D),
            LayerSpec(Linear, D),
            LayerSpec(Linear, D),
            LayerSpec(Linear, D),
            LayerSpec(Linear, D),
            TiedLayerSpec("embed", TiedHead, V, D),
        ]

        def ce(logits, labels):
            lg = logits.astype(jnp.float32)
            logz = jax.scipy.special.logsumexp(lg, axis=-1)
            gold = jnp.take_along_axis(lg, labels[..., None], axis=-1)[..., 0]
            return jnp.mean(logz - gold)

        rng = np.random.default_rng(3)
        ids = jnp.asarray(rng.integers(0, V, (4, 16), dtype=np.int32))
        labels = jnp.asarray(rng.integers(0, V, (4, 16), dtype=np.int32))

        mod = PipelineModule(specs, loss_fn=ce, topology=topo,
                             example_input=jax.ShapeDtypeStruct((2, 16), jnp.int32))
        assert mod._heterogeneous and mod._plan is not None
        mod.num_micro = 2
        params = mod.init_params(jax.random.PRNGKey(0))

        # memory accounting: the packed rows hold exactly the middle layers,
        # each stage row ≈ its share — NOT the full middle replicated per stage
        middle_elems = 4 * (D * D)  # 4 x Linear (weight-only fixture)
        packed = params["stages"]
        total_packed = sum(int(np.prod(a.shape)) for a in packed.values())
        P_, per_stage = 2, middle_elems // 2
        assert total_packed == P_ * per_stage  # = middle once, split in half
        assert "layers" in params and len(params["layers"]) == 0  # all packed

        # dense oracle with the SAME values: unpack each stage row
        def unpacked(params):
            out = {}
            for i in range(1, 5):
                row = {dt: params["stages"][dt][mod._plan["stage_of"][i]]
                       for dt in params["stages"]}
                out[i] = mod._unpack_layer(row, i)
            return out

        def dense(params):
            lp = unpacked(params)
            h = mod._built[0].apply(params["tied"]["embed"], ids)
            for i in range(1, 5):
                h = mod._built[i].apply(lp[i], h)
            return ce(mod._built[5].apply(params["tied"]["embed"], h), labels)

        ld = float(dense(params))
        lp_ = float(mod.apply(params, (ids, labels)))
        assert abs(ld - lp_) < 1e-5
        gd = jax.grad(dense)(params)
        gp = jax.jit(jax.grad(lambda p: mod.apply(p, (ids, labels))))(params)
        for a, b in zip(jax.tree.leaves(gd), jax.tree.leaves(gp)):
            scale = np.abs(np.asarray(a)).max() + 1e-9
            np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                       atol=1e-5 * scale, rtol=1e-4)
        topo_mod.reset_topology()
