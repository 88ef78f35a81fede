"""``TransformerConfig.loop_steps``: a run of layers visited several times a
token (the ``full`` kind: rotary, SwiGLU, RMSNorm, the two post-sublayer
norms), each visit over cache layers of its own.

Every function that walks the layers runs the loop or raises naming
``loop_steps``: the full forward and the dense cache against each other, the
fused decode and the verification against the one-token program, the
counts, the specs, the tracer's scope, the spans, the converter's name map."""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models import TransformerLM
from deepspeed_tpu.models.transformer import TransformerConfig
from deepspeed_tpu.utils import tracing

TINY = dict(vocab_size=256, hidden_size=64, num_layers=2, num_heads=2,
            intermediate_size=96, max_seq_len=64, pos_embedding="rope",
            rope_theta=1e6, norm="rmsnorm", norm_eps=1e-6,
            activation="swiglu", tie_embeddings=False, post_norms=True,
            loop_steps=3)


def tiny(**over):
    model = TransformerLM(TransformerConfig(**{**TINY, **over}))
    params = model.init_params(jax.random.PRNGKey(0))
    # off their initial ones and zeros, so that every leaf matters
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(1), len(leaves))
    return model, jax.tree.unflatten(tree, [
        a + 0.05 * jax.random.normal(k, a.shape) for a, k in zip(leaves, keys)])


def test_the_counts_tell_cache_from_weights():
    cfg = TransformerConfig(**TINY)
    assert cfg.looped and cfg.num_layers == 2 and cfg.pool_layers == 6
    assert cfg.class_layers == {"full": 6}
    assert cfg.cache_kinds == {"attn": (("kv_blocks", 3 * 2 * 2 * 32 * 2),)}
    model, params = tiny()
    assert cfg.num_parameters == sum(a.size for a in jax.tree.leaves(params))
    assert sorted(params["loop"]) == ["exit_b", "exit_w", "norm_scale"]
    assert "lnf_scale" not in params
    assert "post_attn_scale" in params["blocks"]
    # a layer counts once as a parameter and three times as work
    one = TransformerConfig(**{**TINY, "loop_steps": 1})
    assert cfg.num_parameters == one.num_parameters + 64 + 1
    layer = 4 * 64 * 64 + 3 * 64 * 96
    assert cfg.flops_per_token(32) - one.flops_per_token(32) \
        == 6 * (64 + 1) + 2 * 2 * (6 * layer + 12 * 2 * 32 * 32)
    assert model.init_kv_pool(5, 8).shape[0] == 6
    assert model.init_kv_cache(1, 16)[0].shape[0] == 6
    specs = model.tp_specs
    assert jax.tree.structure(jax.tree.map(lambda a: 0, params)) \
        == jax.tree.structure(jax.tree.map(
            lambda s: 0, specs,
            is_leaf=lambda s: isinstance(s, jax.sharding.PartitionSpec)))


@pytest.mark.parametrize("over,match", [
    (dict(layer_types=("full_attn", "full_attn")), "loop_steps"),
    (dict(attention="mla"), "loop_steps"),
    (dict(num_experts=2), "loop_steps"),
    (dict(loop_steps=0), "loop_steps"),
    (dict(norm="layernorm"), "post_norms"),
])
def test_what_the_loop_is_not_wired_for_raises(over, match):
    with pytest.raises((NotImplementedError, ValueError), match=match):
        TransformerConfig(**{**TINY, **over})


def test_the_walkers_that_cannot_loop_say_so():
    """Random-LTD walks the layers once and dropout draws a key a layer:
    neither runs one step of a looped model silently."""
    model, params = tiny(dropout=0.1)
    ids = jnp.ones((1, 8), jnp.int32)
    with pytest.raises(NotImplementedError, match="loop_steps"):
        model.logits(params, ids, train=True, rng=jax.random.PRNGKey(0))
    model, params = tiny(random_ltd=True)
    with pytest.raises(NotImplementedError, match="loop_steps"):
        model.apply(params, {"input_ids": ids, "ltd_keep": 4}, train=True,
                    rng=jax.random.PRNGKey(0))


def test_the_engines_that_walk_the_layers_themselves_refuse():
    """The pipeline's stages and the parameter-streaming engine scan
    ``_block`` over the stack once: both refuse a looped model by name."""
    from deepspeed_tpu.runtime.pipe.module import PipelinedLM
    from deepspeed_tpu.runtime.swap_tensor.streamed import StreamedZeroEngine

    model = TransformerLM(TransformerConfig(**TINY))
    with pytest.raises(NotImplementedError, match="loop_steps"):
        PipelinedLM(model, num_stages=2)
    with pytest.raises(NotImplementedError, match="loop_steps"):
        StreamedZeroEngine(model, types.SimpleNamespace(fp16_enabled=False))


@pytest.mark.parametrize("threshold", [1.0, 0.5])
def test_the_dense_cache_walks_every_step(threshold):
    """``forward_with_cache`` (prefill of 10, then four tokens one by one
    through a cache of 3 x 2 layers) gives the full forward's logits."""
    model, params = tiny(early_exit_threshold=threshold)
    ids = jax.random.randint(jax.random.PRNGKey(2), (2, 14), 0, 256)
    want = model.logits(params, ids)
    cache = model.init_kv_cache(2, 16, dtype=jnp.float32)
    lg, cache = model.forward_with_cache(params, ids[:, :10], cache, 0)
    np.testing.assert_allclose(lg, want[:, 9], atol=2e-5)
    for i in range(10, 14):
        lg, cache = model.forward_with_cache(params, ids[:, i:i + 1], cache, i)
        np.testing.assert_allclose(lg, want[:, i], atol=2e-5)


def test_the_last_step_is_not_an_earlier_one():
    """At a threshold of 0.5 tokens leave at different steps; at 1.0 every
    token reads the last step, and the two differ."""
    model, params = tiny()
    early, _ = tiny(early_exit_threshold=0.5)
    ids = jax.random.randint(jax.random.PRNGKey(3), (2, 12), 0, 256)
    a, b = model.logits(params, ids), early.logits(params, ids)
    assert float(jnp.max(jnp.abs(a - b))) > 1e-3


def test_fused_decode_and_verification_are_the_one_token_program():
    """``decode_paged_multi`` (a scan of rounds) and ``verify_paged_multi``
    (a segment's tokens as rows of one dispatch) run ``forward_paged``, so
    every step's cache layers: their tokens are the sequential rollout's,
    and ``draft_greedy`` the full forward's."""
    model, params = tiny()
    pool = model.init_kv_pool(9, 8, dtype=jnp.float32)
    prompt = jax.random.randint(jax.random.PRNGKey(4), (12,), 0, 256)
    tables = jnp.asarray([[1, 2, 3, 0]], jnp.int32)
    # prefill the prompt as twelve one-token rows of one sequence
    lg, pool = model.forward_paged(
        params, prompt[:, None], pool, jnp.repeat(tables, 12, axis=0),
        jnp.arange(12, dtype=jnp.int32))
    full = model.logits(params, prompt[None])[0]
    np.testing.assert_allclose(lg, full, atol=2e-5)
    first = jnp.argmax(lg[-1:], axis=-1).astype(jnp.int32)
    # four rounds one by one
    toks, t, p = [], first, pool
    for i in range(4):
        lg1, p = model.forward_paged(params, t[:, None], p, tables,
                                     jnp.asarray([12 + i], jnp.int32))
        t = jnp.argmax(lg1, axis=-1).astype(jnp.int32)
        toks.append(int(t[0]))
    fused, _ = model.decode_paged_multi(params, pool, first, tables,
                                        jnp.asarray([12], jnp.int32), 4)
    assert fused[0].tolist() == toks
    seg = jnp.asarray([[int(first[0])] + toks[:3]], jnp.int32)
    seen, _ = model.verify_paged_multi(params, pool, seg, tables,
                                       jnp.asarray([12], jnp.int32))
    assert seen[0].tolist() == toks
    window = jnp.zeros((20,), jnp.int32).at[:12].set(prompt)
    drafted = model.draft_greedy(params, window, jnp.int32(12), 1)
    assert int(drafted[0]) == int(first[0])


def test_the_closing_norm_has_a_scope_of_its_own():
    assert tracing.classify(
        "jit(step)/kv_carry/loop_close/reduce_sum") == "loop_close"
    model, params = tiny()
    text = jax.jit(model.logits).lower(
        params, jnp.ones((1, 8), jnp.int32)).as_text(debug_info=True)
    assert "loop_close" in text
    plain, pparams = tiny(loop_steps=1)
    assert "loop_close" not in jax.jit(plain.logits).lower(
        pparams, jnp.ones((1, 8), jnp.int32)).as_text(debug_info=True)


@pytest.mark.parametrize("steps", [1, 3])
def test_a_dispatch_says_the_pools_blocks_and_the_steps(steps, tmp_path):
    """``engine.dispatch`` of every model carries ``loop_steps`` and the
    pool's blocks held and free (``pool_blocks`` + ``pool_free`` = the usable
    blocks), which ``cache.pool_fill.qa`` reads."""
    from deepspeed_tpu.inference.v2 import InferenceEngineV2

    model, params = tiny(loop_steps=steps)
    eng = InferenceEngineV2(model, params, dtype=jnp.float32, max_seqs=2,
                            max_seq_len=64, block_size=8, token_budget=18,
                            prefill_chunk=16, num_blocks=12)
    tracing.clear()
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.put([1], [list(range(1, 21))])
        eng.decode_step({1: 7})
    finally:
        jax.profiler.stop_trace()
    found = [r for r in tracing.snapshot() if r.name == "engine.dispatch"]
    assert found
    for r in found:
        assert r.attrs["loop_steps"] == steps
        assert r.attrs["pool_blocks"] + r.attrs["pool_free"] == 11
    assert found[-1].attrs["pool_blocks"] == 3


def test_the_converter_maps_the_published_names():
    """``from_hf_ouro`` on a state dict the test makes (the names recalled
    from the public ``modeling_ouro.py``): every leaf lands where the program
    reads it, transposed where a ``Linear`` stores (out, in), and the
    converted model computes what the same tree computes."""
    from deepspeed_tpu.models.hf_converters import from_hf, from_hf_ouro

    H, L, I, V = 64, 2, 96, 256
    rng = np.random.default_rng(0)
    sd = {"model.embed_tokens.weight": rng.normal(size=(V, H)),
          "model.norm.weight": 1 + 0.1 * rng.normal(size=(H,)),
          "model.early_exit_gate.weight": rng.normal(size=(1, H)),
          "model.early_exit_gate.bias": rng.normal(size=(1,)),
          "lm_head.weight": rng.normal(size=(V, H))}
    for i in range(L):
        pre = f"model.layers.{i}."
        for name in ("input_layernorm", "input_layernorm_2",
                     "post_attention_layernorm", "post_attention_layernorm_2"):
            sd[pre + name + ".weight"] = 1 + 0.1 * rng.normal(size=(H,))
        for name in ("q_proj", "k_proj", "v_proj", "o_proj"):
            sd[pre + f"self_attn.{name}.weight"] = 0.1 * rng.normal(size=(H, H))
        sd[pre + "mlp.gate_proj.weight"] = 0.1 * rng.normal(size=(I, H))
        sd[pre + "mlp.up_proj.weight"] = 0.1 * rng.normal(size=(I, H))
        sd[pre + "mlp.down_proj.weight"] = 0.1 * rng.normal(size=(H, I))
    sd = {k: v.astype(np.float32) for k, v in sd.items()}
    hf = types.SimpleNamespace(
        config=types.SimpleNamespace(
            architectures=["OuroForCausalLM"], hidden_size=H,
            num_hidden_layers=L, num_attention_heads=2, num_key_value_heads=2,
            head_dim=32, vocab_size=V, intermediate_size=I,
            max_position_embeddings=64, rms_norm_eps=1e-6, rope_theta=1e6,
            tie_word_embeddings=False, total_ut_steps=3,
            early_exit_threshold=1.0),
        state_dict=lambda: sd)
    model, params = from_hf(hf)
    cfg = model.config
    assert (cfg.loop_steps, cfg.post_norms, cfg.pool_layers) == (3, True, 6)
    want = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    assert jax.tree.map(lambda a: a.shape, params) \
        == jax.tree.map(lambda a: a.shape, want)
    blocks = params["blocks"]
    for leaf, name in (("ln1_scale", "input_layernorm"),
                       ("post_attn_scale", "input_layernorm_2"),
                       ("ln2_scale", "post_attention_layernorm"),
                       ("post_mlp_scale", "post_attention_layernorm_2")):
        assert np.array_equal(blocks[leaf][1],
                              sd[f"model.layers.1.{name}.weight"])
    assert np.array_equal(blocks["w_down"][0],
                          sd["model.layers.0.mlp.down_proj.weight"].T)
    assert np.array_equal(blocks["wq"][1],
                          sd["model.layers.1.self_attn.q_proj.weight"].T)
    assert np.array_equal(params["loop"]["norm_scale"][0],
                          sd["model.norm.weight"])
    assert np.array_equal(params["loop"]["exit_w"][0],
                          sd["model.early_exit_gate.weight"][0])
    assert np.array_equal(params["lm_head"], sd["lm_head.weight"].T)
    lg = model.logits(params, jnp.arange(8, dtype=jnp.int32)[None])
    assert lg.shape == (1, 8, V) and bool(jnp.all(jnp.isfinite(lg)))
    hf.config.total_ut_steps = 1
    with pytest.raises(ValueError, match="total_ut_steps"):
        from_hf_ouro(hf)
