"""Model family + attention kernel tests (reference test strategy: kernel-vs-torch
numerics in ``tests/unit/ops/transformer``, model fixtures in ``tests/unit/simple_model.py``)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models import TransformerLM, build_model, gpt2_config, llama_config
from deepspeed_tpu.ops.transformer.attention import attention, xla_attention
from deepspeed_tpu.ops.transformer.flash_attention import flash_attention


def tiny_gpt(**kw):
    base = dict(vocab_size=128, hidden_size=64, num_layers=2, num_heads=4, max_seq_len=32)
    base.update(kw)
    return TransformerLM(gpt2_config("125m", **base))


def tiny_llama(**kw):
    return build_model("llama-tiny", vocab_size=128, hidden_size=64, num_layers=2,
                       num_heads=4, num_kv_heads=2, intermediate_size=128,
                       max_seq_len=32, **kw)


def batch_of(model, B=4, seed=0):
    S = model.config.max_seq_len
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, model.config.vocab_size, (B, S), dtype=np.int32)
    return {"input_ids": jnp.asarray(ids)}


class TestTransformerLM:
    @pytest.mark.parametrize("family", ["gpt", "llama"])
    def test_forward_and_grad_finite(self, family):
        m = tiny_gpt() if family == "gpt" else tiny_llama()
        p = m.init_params(jax.random.PRNGKey(0))
        loss = m.apply(p, batch_of(m))
        assert jnp.isfinite(loss)
        g = jax.grad(lambda pp: m.apply(pp, batch_of(m)))(p)
        assert all(bool(jnp.all(jnp.isfinite(x))) for x in jax.tree.leaves(g))

    def test_remat_matches(self):
        m1 = tiny_gpt()
        m2 = TransformerLM(gpt2_config("125m", vocab_size=128, hidden_size=64,
                                       num_layers=2, num_heads=4, max_seq_len=32, remat=True))
        p = m1.init_params(jax.random.PRNGKey(0))
        b = batch_of(m1)
        assert np.allclose(m1.apply(p, b), m2.apply(p, b), atol=1e-5)
        g1 = jax.grad(lambda pp: m1.apply(pp, b))(p)
        g2 = jax.grad(lambda pp: m2.apply(pp, b))(p)
        chex_close = lambda a, c: np.allclose(np.asarray(a), np.asarray(c), atol=1e-5)
        assert all(chex_close(a, c) for a, c in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)))

    def test_tp_specs_match_param_tree(self):
        for m in (tiny_gpt(), tiny_llama()):
            p = m.init_params(jax.random.PRNGKey(0))
            specs = m.tp_specs
            pt, st = jax.tree.structure(p), jax.tree.structure(
                specs, is_leaf=lambda s: not isinstance(s, dict))
            assert pt == st
            for leaf, spec in zip(jax.tree.leaves(p),
                                  jax.tree.leaves(specs, is_leaf=lambda s: not isinstance(s, dict))):
                assert len(spec) <= leaf.ndim

    def test_loss_decreases_under_engine(self):
        m = tiny_gpt()
        config = {
            "train_batch_size": 8,
            "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
            "zero_optimization": {"stage": 2},
        }
        engine, _, _, _ = deepspeed_tpu.initialize(model=m, config=config)
        b = batch_of(m, B=8)
        losses = []
        for _ in range(10):
            loss = engine(b)
            engine.backward(loss)
            engine.step()
            losses.append(float(loss))
        assert losses[-1] < losses[0]

    def test_kv_cache_decode_matches_full_forward(self):
        m = tiny_llama()
        p = m.init_params(jax.random.PRNGKey(0))
        ids = batch_of(m, B=2)["input_ids"]
        full = m.logits(p, ids)  # (B,S,V)
        S = ids.shape[1]
        cache = m.init_kv_cache(2, S, dtype=jnp.float32)
        # prefill on the first S-4 tokens, then decode token-by-token
        split = S - 4
        lg, cache = m.forward_with_cache(p, ids[:, :split], cache, 0)
        np.testing.assert_allclose(np.asarray(lg), np.asarray(full[:, split - 1]),
                                   rtol=2e-3, atol=2e-3)
        for t in range(split, S):
            lg, cache = m.forward_with_cache(p, ids[:, t:t + 1], cache, t)
            np.testing.assert_allclose(np.asarray(lg), np.asarray(full[:, t]),
                                       rtol=2e-3, atol=2e-3)

    def test_param_count(self):
        cfg = gpt2_config("125m")
        n = cfg.num_parameters
        assert 115e6 < n < 180e6  # 125m class (padded vocab inflates it)


class TestFlashAttention:
    @pytest.mark.parametrize("kvh,hd", [(4, 64), (2, 64), (1, 128)])
    def test_matches_xla(self, kvh, hd):
        B, S, nh = 2, 256, 4
        k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
        q = jax.random.normal(k1, (B, S, nh, hd), jnp.float32)
        k = jax.random.normal(k2, (B, S, kvh, hd), jnp.float32)
        v = jax.random.normal(k3, (B, S, kvh, hd), jnp.float32)
        g = nh // kvh
        ref = xla_attention(q, k, v, causal=True, num_kv_groups=g)
        out = flash_attention(q, k, v, causal=True, num_kv_groups=g)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-2, atol=2e-2)

    def test_backward_matches_xla(self):
        B, S, nh, kvh, hd = 1, 256, 4, 2, 64
        k1, k2, k3 = jax.random.split(jax.random.PRNGKey(1), 3)
        q = jax.random.normal(k1, (B, S, nh, hd), jnp.float32)
        k = jax.random.normal(k2, (B, S, kvh, hd), jnp.float32)
        v = jax.random.normal(k3, (B, S, kvh, hd), jnp.float32)
        g = nh // kvh
        gr = jax.grad(lambda *a: jnp.sum(xla_attention(*a, causal=True, num_kv_groups=g) ** 2),
                      argnums=(0, 1, 2))(q, k, v)
        gf = jax.grad(lambda *a: jnp.sum(flash_attention(*a, causal=True, num_kv_groups=g) ** 2),
                      argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gr, gf):
            scale = float(jnp.max(jnp.abs(a))) + 1e-9
            assert float(jnp.max(jnp.abs(a - b))) / scale < 3e-2

    def test_fallback_on_unsupported(self):
        # odd seq length → dispatch falls back to the XLA path without error
        B, S, nh, hd = 1, 100, 2, 64
        k1 = jax.random.PRNGKey(0)
        q = jax.random.normal(k1, (B, S, nh, hd), jnp.float32)
        out = attention(q, q, q, causal=True)
        assert out.shape == q.shape


def head_model(tied=True, vocab=256, seq=64, **kw):
    """Shapes the head's kernels take: width and vocabulary multiples of 128,
    2 x 64 tokens a row tile."""
    return TransformerLM(gpt2_config(
        "125m", vocab_size=vocab, hidden_size=128, num_layers=1, num_heads=2,
        max_seq_len=seq, tie_embeddings=tied, **kw))


def plain_loss(model, params, batch, labels=None):
    """The loss written out: ``logsumexp`` and ``take_along_axis`` on float32
    logits, ignored rows (-100) out of the mean."""
    ids = batch["input_ids"]
    if labels is None:
        labels = jnp.concatenate(
            [ids[:, 1:], jnp.full_like(ids[:, :1], -100)], axis=1)
    lg = model.logits(params, ids, train=True).astype(jnp.float32)
    mask = labels != -100
    safe = jnp.where(mask, labels, 0)
    nll = (jax.scipy.special.logsumexp(lg, axis=-1)
           - jnp.take_along_axis(lg, safe[..., None], axis=-1)[..., 0]) * mask
    return jnp.sum(nll) / jnp.maximum(jnp.sum(mask), 1)


def head_labels(kind, batch, vocab):
    ids = np.asarray(batch["input_ids"])
    if kind == "absent":
        return None
    if kind == "all_ignored":
        return jnp.full(ids.shape, -100, jnp.int32)
    labels = np.random.default_rng(1).integers(0, vocab, ids.shape,
                                               dtype=np.int32)
    labels[1], labels[0, ::3] = -100, -100
    return jnp.asarray(labels)


HEAD_CASES = [
    (dict(tied=tied), labels, "fused")
    for tied in (True, False)
    for labels in ("absent", "some_ignored", "all_ignored")
] + [
    (dict(vocab=200), "absent", "plain"),           # no multiple of 128
    (dict(tied=False, lm_head_bias=True), "some_ignored", "plain"),
    (dict(dim_model_base=32), "absent", "fused"),   # muP: the head reads x / 4
    (dict(tied=False, dim_model_base=32), "some_ignored", "fused"),
]


@pytest.mark.parametrize(
    "kw, labels, path", HEAD_CASES,
    ids=["-".join([*map(str, kw.values()), labels]) for kw, labels, _ in HEAD_CASES])
def test_head_loss_matches_plain_expression(kw, labels, path):
    """``apply``'s loss and every gradient, of the parameters below the head
    and of the head's weight, against the plain expression; the head's unit
    says which of its paths the shape took."""
    from deepspeed_tpu.utils import tracing

    model = head_model(**kw)
    params = model.init_params(jax.random.PRNGKey(0))
    if "lm_head_bias" in params:
        params["lm_head_bias"] = 0.1 * jax.random.normal(
            jax.random.PRNGKey(3), params["lm_head_bias"].shape)
    batch = batch_of(model, B=2)
    labels = head_labels(labels, batch, model.config.vocab_size)
    if labels is not None:
        batch["labels"] = labels
    took = {}
    with tracing.program_attrs(took):
        got, dgot = jax.jit(jax.value_and_grad(
            lambda p: model.apply(p, batch, train=True)))(params)
    want, dwant = jax.jit(jax.value_and_grad(
        lambda p: plain_loss(model, p, batch, labels)))(params)
    assert took == {"head_loss": path}
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-6)
    for (name, a), b in zip(jax.tree.leaves_with_path(dgot),
                            jax.tree.leaves(dwant)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4,
                                   atol=1e-5, err_msg=jax.tree_util.keystr(name))


def test_head_loss_under_zero3_data4_matches_stage0():
    """The kernels map themselves over the engine's mesh (``shard_map`` over
    ``data=4`` x ``hpz=2``, a sequence a device, the weight whole on every
    device and its gradient the devices' partial sums): ZeRO stage 3 gives
    the losses and the parameters of the plain expression at stage 0."""
    from deepspeed_tpu.comm.topology import reset_topology

    model = head_model(seq=128)
    batches = [batch_of(model, B=8, seed=s) for s in range(3)]

    def run(stage, apply):
        reset_topology()
        engine, *_ = deepspeed_tpu.initialize(
            model=(model.init_params(jax.random.PRNGKey(0)), apply),
            config={"train_micro_batch_size_per_gpu": 1,
                    "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
                    "zero_optimization": {
                        "stage": stage,
                        "zero_hpz_partition_size": 2 if stage == 3 else 1},
                    "mesh": {"data": 4, "hpz": 2} if stage == 3
                    else {"data": 8}})
        data = iter(batches)
        losses = [float(engine.train_batch(data)) for _ in batches]
        return (losses, jax.tree.map(np.asarray, engine.params),
                engine._program_attrs)

    one, p_one, took = run(0, lambda p, b, **kw: plain_loss(model, p, b))
    assert took == {"head": "xla"}      # ``logits``: XLA's product (a mesh)
    four, p_four, took = run(3, model.apply)
    assert took == {"head_loss": "fused"}
    reset_topology()
    np.testing.assert_allclose(four, one, rtol=1e-5)
    for a, b in zip(jax.tree.leaves(p_four), jax.tree.leaves(p_one)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)


def test_dots_ln_remat_policy_matches_dots():
    """dots_ln (saves LN outputs) must not change the gradients vs dots."""
    from deepspeed_tpu.models import TransformerLM, gpt2_config

    grads = {}
    ids = jnp.asarray(np.random.default_rng(0).integers(0, 256, (2, 32), dtype=np.int32))
    for pol in ("dots", "dots_ln"):
        cfg = gpt2_config("125m", max_seq_len=32, remat=True, remat_policy=pol)
        cfg = cfg.__class__(**{**cfg.__dict__, "vocab_size": 256, "hidden_size": 64,
                               "num_layers": 2, "num_heads": 2, "intermediate_size": 128,
                               "remat": True, "remat_policy": pol, "max_seq_len": 32,
                               "pos_embedding": "learned", "norm": "layernorm",
                               "activation": "gelu", "tie_embeddings": True})
        model = TransformerLM(cfg)
        params = model.init_params(jax.random.PRNGKey(0))
        grads[pol] = jax.grad(lambda p: model.apply(p, {"input_ids": ids}))(params)
    for a, b in zip(jax.tree.leaves(grads["dots"]), jax.tree.leaves(grads["dots_ln"])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6)
