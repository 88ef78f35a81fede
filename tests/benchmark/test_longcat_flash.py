"""LongCat-Flash through the program against the plain reference
(``benchmark/reference/longcat_flash.py``), at a small size on the CPU: hidden
128, 4 heads, latents 48 / 32 (both scaled), head sizes 16 + 8 + 16, two
double layers with dense feed-forwards 192 wide, 24 router outputs of which
the first 16 are experts 64 wide and the last 8 identity, top 6, scale 6.

(a) ``TransformerLM.logits`` against the reference; (b) prefill in chunks then
decode through the latent paged pool (two pool layers a double layer) against
the reference's full forward; (c) the shares of an expert-parallel layer add
up to the uncut layer with the identity part counted once; (d) routing and
identity experts; (e) the absorbed form equals the un-absorbed one with both
latent scales on; (f) the cell's configuration and its parameter count.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness import weights
from benchmark.harness.cell import load_json
from benchmark.harness.serve import engine_logits
from benchmark.reference import ein_f32
from benchmark.reference import longcat_flash as ref
from deepspeed_tpu.models.transformer import (TransformerConfig,
                                              TransformerLM, sublayer)
from deepspeed_tpu.moe.layer import held_experts_ffn
from deepspeed_tpu.moe.sharded_moe import softmax_topk_gating
from deepspeed_tpu.ops.transformer import paged_attention as pa

ROUTER, REAL, ZERO, TOP, SCALE = 24, 16, 8, 6, 6.0


def model_config(held=REAL, offset=0, **kw):
    return TransformerConfig(**{**dict(
        vocab_size=256, hidden_size=128, num_layers=2, num_heads=4,
        max_seq_len=256, pos_embedding="rope", norm="rmsnorm",
        activation="swiglu", tie_embeddings=False, norm_eps=1e-5,
        attention="mla", q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, rope_theta=1e7,
        mla_scale_q_lora=True, mla_scale_kv_lora=True, layer_kind="scmoe",
        dense_intermediate_size=192, intermediate_size=64, num_experts=held,
        moe_expert_offset=offset, moe_top_k=TOP, moe_router="softmax_topk",
        moe_router_width=ROUTER, moe_zero_experts=ZERO, moe_norm_topk=False,
        moe_score_scale=SCALE), **kw})


def published(offset=0, **kw):
    return dict(
        hidden_size=128, num_attention_heads=4, q_lora_rank=48,
        kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, rms_norm_eps=1e-5, rope_theta=1e7,
        mla_scale_q_lora=True, mla_scale_kv_lora=True, moe_topk=TOP,
        routed_scaling_factor=SCALE, zero_expert_num=ZERO, num_layers=2,
        expert_offset=offset, **kw)


def seeded(model, seed=7, std=0.05):
    return weights.Seeded(
        jax.eval_shape(model.init_params, jax.random.PRNGKey(0)), seed, std,
        model.config.num_layers).tree()


def reference_logits(w, ids, cfg):
    return ref.logits(w, ref.hidden(w, jnp.asarray(ids), cfg, ein_f32), ein_f32)


@pytest.fixture(scope="module")
def model():
    return TransformerLM(model_config())


@pytest.fixture(scope="module")
def tree(model):
    return seeded(model)


def layer_of(tree, l=0):
    return {k: v[l] for k, v in tree["blocks"].items()}


# -- (a) ------------------------------------------------------------------

@pytest.mark.parametrize("held,offset", [(16, 0), (4, 8)])
def test_logits_match_the_reference(held, offset):
    """The full-sequence forward (un-absorbed attention, grouped experts,
    identity experts), float32: with every real expert held and with the four
    from output 8 on."""
    m = TransformerLM(model_config(held, offset))
    w = seeded(m)
    ids = np.random.default_rng(0).integers(0, 256, (2, 40)).astype(np.int32)
    got = m.logits(w, jnp.asarray(ids))
    want = jnp.stack([reference_logits(w, i, published(offset)) for i in ids])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-5)


def test_parameter_counts_follow_the_tree(model, tree):
    cfg = model.config
    assert cfg.num_parameters == sum(x.size for x in jax.tree.leaves(tree))
    per_expert = 3 * cfg.hidden_size * cfg.mlp_dim
    assert cfg.num_parameters - cfg.num_active_parameters == \
        2 * (16 - 6) * per_expert
    # q.k over 16 + 8, p.v over 16, 4 heads, 2 x 2 attentions, fwd + bwd
    assert cfg.pool_layers == 4 and cfg.sublayers == 2
    assert cfg.flops_per_token(100) == 6 * cfg.num_active_parameters \
        + 6 * 4 * 4 * (16 + 8 + 16) * 100
    # every leaf has a partition spec of its own rank
    specs = model.tp_specs
    assert jax.tree.structure(specs, is_leaf=lambda s: not isinstance(s, dict)) \
        == jax.tree.structure(tree)
    for name, spec in specs["blocks"].items():
        assert len(spec) == tree["blocks"][name].ndim, name
    assert specs["blocks"]["s1_wo"] == specs["blocks"]["s0_wo"] \
        and specs["blocks"]["s1_wo"][1] == "model"


def test_the_cells_configuration_is_3_96b_parameters():
    """The served tree of ``benchmark/configs/longcat-flash-chat.json``: four
    double layers, 8 of 512 real experts held, an eighth of the vocabulary;
    every published width kept."""
    file = load_json("configs", "longcat-flash-chat.json")
    cfg = TransformerConfig(**file["model"])
    attn = (6144 * 1536 + 1536 * 64 * 192 + 6144 * 576 + 512 * 64 * 256
            + 64 * 128 * 6144 + 1536 + 512)
    dense, expert = 3 * 6144 * 12288, 3 * 6144 * 2048
    assert (attn, dense, expert) == (90_572_800, 226_492_416, 37_748_736)
    double = 2 * attn + 2 * dense + 4 * 6144 + 6144 * 768 + 768 + 8 * expert
    assert double == 940_864_256
    assert cfg.num_parameters == 4 * double + 2 * 16384 * 6144 + 6144 \
        == 3_964_789_760
    assert cfg.kv_row == (512, 128) and cfg.pool_heads == 1
    assert cfg.pool_layers == 8
    assert cfg.mla_latent_scales == (2.0, 12 ** 0.5)
    for key, want in dict(
            hidden_size=6144, num_attention_heads=64, qk_nope_head_dim=128,
            qk_rope_head_dim=64, v_head_dim=128, q_lora_rank=1536,
            kv_lora_rank=512, ffn_hidden_size=12288,
            expert_ffn_hidden_size=2048, router_outputs=768, moe_topk=12,
            routed_scaling_factor=6, zero_expert_num=256, num_layers=4,
            n_routed_experts=8, vocab_size=16384).items():
        assert file[key] == want, key
    assert file["published"] == {"num_layers": 28, "n_routed_experts": 512,
                                 "vocab_size": 131072}
    assert sorted(file["reduced"]) == ["n_routed_experts", "num_layers",
                                       "vocab_size"]


# -- (b) ------------------------------------------------------------------

#: float32 through the pool agrees to rounding. In bfloat16 the limit is an
#: error relative to the logits' spread, as the cell's ``logits_rel_err``
PAGED = [(jnp.float32, False, 3e-5), (jnp.float32, True, 3e-5),
         (jnp.bfloat16, False, 0.1)]


@pytest.mark.parametrize("dtype,kernel,tol", PAGED)
def test_chunked_prefill_then_decode_through_the_latent_pool(
        model, tree, monkeypatch, dtype, kernel, tol):
    """Prompts of 70, 33 and 5 tokens prefilled in segment tiles (chunks of
    32 in a 36-row budget) and three forced tokens decoded, full logits at
    every step, against the reference's full causal forward; the pool has two
    layers a double layer. With the Pallas kernels in interpret mode and
    without."""
    from deepspeed_tpu.inference.v2 import InferenceEngineV2

    if kernel:
        monkeypatch.setenv("DSTPU_FORCE_PAGED_KERNEL", "1")
    served = jax.tree.map(lambda a: a.astype(dtype), tree)
    eng = InferenceEngineV2(model, served, dtype=dtype, max_seqs=4,
                            max_seq_len=256, block_size=16, token_budget=36,
                            prefill_chunk=32, num_blocks=40)
    assert eng.kv.shape[0] == model.config.pool_layers == 4
    rng = np.random.default_rng(1)
    samples = [(rng.integers(0, 256, n).tolist(),
                rng.integers(0, 256, 3).tolist()) for n in (70, 33, 5)]
    got = engine_logits(eng, samples)
    for k, (p, f) in enumerate(samples):
        want = np.asarray(reference_logits(tree, p + f, published()))[
            len(p) - 1:len(p) + len(f)]
        if dtype == jnp.float32:
            np.testing.assert_allclose(got[k], want, atol=tol)
        else:
            spread = want - want.mean(-1, keepdims=True)
            err = np.sqrt(np.sum((got[k] - want) ** 2) / np.sum(spread ** 2))
            assert err < tol, err


def test_the_greedy_program_counts_identity_picks(model, tree):
    """A greedy dispatch fetches three counts behind its tokens: rows on held
    experts, the busiest expert's, and the live rows' identity picks; with
    every real expert held the first and the last add up to rows x top-k x
    expert layers."""
    from deepspeed_tpu.inference.v2 import InferenceEngineV2

    eng = InferenceEngineV2(model, tree, dtype=jnp.float32,
                            max_seqs=4, max_seq_len=256, block_size=16,
                            token_budget=36, prefill_chunk=32, num_blocks=40)
    ids = jnp.asarray(np.arange(36).reshape(36, 1) % 256, jnp.int32)
    tables = np.zeros((36, 16), np.int32)
    tables[:3, 0] = (1, 2, 3)                  # three live one-token rows
    lg, _, stats = model.forward_paged(
        tree, ids, eng.kv, jnp.asarray(tables), jnp.zeros((36,), jnp.int32),
        logit_rows=jnp.arange(4), seg_from=4, moe_stats=True)
    rows, most, zero = (int(s) for s in stats)
    assert stats.shape == (3,) and rows + zero == 3 * TOP * 2
    assert 0 < zero < 3 * TOP * 2 and 0 < most <= 3


# -- (c) ------------------------------------------------------------------

def test_the_shares_add_up_to_the_uncut_layer(tree):
    """Four shares of four real experts: the routed parts of all shares plus
    the identity part, counted once, equal the uncut reference layer."""
    b = layer_of(tree)
    x = jax.random.normal(jax.random.PRNGKey(3), (50, 128), jnp.float32)
    cfg = published()
    whole = ref.experts(x, b, cfg, ein_f32)
    identity = whole - ref.experts(x, b, cfg, ein_f32, identity=False)
    assert float(jnp.abs(identity).max()) > 0
    routed = jnp.zeros_like(x)
    kw = dict(k=TOP, normalize=False, scale=SCALE, router="softmax_topk")
    for r in range(4):
        sl = slice(4 * r, 4 * r + 4)
        part, (rows, _) = held_experts_ffn(
            x, b["moe_wg"], b["moe_bias"], b["wi"][sl], b["w_gate"][sl],
            b["w_down"][sl], None, first=4 * r, **kw)
        routed = routed + part
        # and the reference, given the same share, computes the same part
        share = {**b, **{k: b[k][sl] for k in ("wi", "w_gate", "w_down")}}
        want = ref.experts(x, share, {**cfg, "expert_offset": 4 * r}, ein_f32,
                           identity=False)
        np.testing.assert_allclose(np.asarray(part), np.asarray(want),
                                   atol=2e-5)
        # with the identity experts on, a share adds the identity part too
        both, (_, _, picks) = held_experts_ffn(
            x, b["moe_wg"], b["moe_bias"], b["wi"][sl], b["w_gate"][sl],
            b["w_down"][sl], None, first=4 * r, zero_experts=ZERO, **kw)
        np.testing.assert_allclose(np.asarray(both - part),
                                   np.asarray(identity), atol=2e-5)
    np.testing.assert_allclose(np.asarray(routed + identity),
                               np.asarray(whole), atol=2e-5)


# -- (d) ------------------------------------------------------------------

def test_routing_is_softmax_selects_by_bias_and_does_not_normalise():
    logits = jnp.asarray(np.random.default_rng(5).normal(size=(64, ROUTER)),
                         jnp.float32)
    p = jax.nn.softmax(logits, -1)
    plain, _ = softmax_topk_gating(logits, None, k=TOP, scale=SCALE)
    # a bias of 24 uniform scores (1.0 on a probability) forces output 3 and
    # the identity output 20 into every token's choice; selection only
    bias = jnp.zeros((ROUTER,)).at[jnp.asarray([3, 20])].set(float(ROUTER))
    chosen, w = softmax_topk_gating(logits, bias, k=TOP, scale=SCALE)
    assert chosen.shape == (64, TOP)
    assert bool(jnp.all(jnp.any(chosen == 3, 1) & jnp.any(chosen == 20, 1)))
    assert not bool(jnp.all(jnp.any(plain == 3, 1)))      # the bias flipped it
    # weights are 6 p of the chosen: from p, not p + b, and not normalised
    np.testing.assert_allclose(
        np.asarray(w), SCALE * np.asarray(jnp.take_along_axis(p, chosen, 1)),
        rtol=1e-6)
    assert not np.allclose(np.asarray(w.sum(1)), SCALE)
    # a smaller bias, in units of 1 / E: 0.5 uniform scores flip a choice for
    # some tokens and not for all
    small = jnp.zeros((ROUTER,)).at[3].set(0.5)
    some, _ = softmax_topk_gating(logits, small, k=TOP, scale=SCALE)
    gained = jnp.any(some == 3, 1) & ~jnp.any(plain == 3, 1)
    assert 0 < int(gained.sum()) < 64
    want = jax.lax.top_k(p + small / ROUTER, TOP)[1]
    np.testing.assert_array_equal(np.asarray(some), np.asarray(want))
    # and against the reference's dense weights (eye @ W: the logits as given)
    dense = ref.route(jnp.eye(ROUTER), {"moe_wg": logits[:ROUTER],
                                        "moe_bias": bias},
                      dict(moe_topk=TOP, routed_scaling_factor=SCALE), ein_f32)
    got = jnp.zeros((ROUTER, ROUTER)).at[
        jnp.arange(ROUTER)[:, None], chosen[:ROUTER]].set(w[:ROUTER])
    np.testing.assert_allclose(np.asarray(got), np.asarray(dense), rtol=1e-6)


def test_a_choice_of_an_identity_expert_adds_weight_times_h():
    """A token whose six choices are all identity costs no expert row and
    still gets its output: (sum of its weights) * h. A token that mixes real
    and identity choices gets both parts."""
    key = jax.random.split(jax.random.PRNGKey(4), 5)
    x = jax.random.normal(key[0], (10, 32))
    wi, wgate = (jax.random.normal(k, (REAL, 32, 16)) * 0.2 for k in key[1:3])
    wd = jax.random.normal(key[3], (REAL, 16, 32)) * 0.2
    wg = jax.random.normal(key[4], (32, ROUTER)) * 0.5
    # rows 0..4: a bias that puts the 8 identity outputs above every real one
    all_zero = jnp.zeros((ROUTER,)).at[REAL:].set(100.0 * ROUTER)
    kw = dict(k=TOP, normalize=False, scale=SCALE, router="softmax_topk",
              zero_experts=ZERO)
    y, (rows, most, picks) = held_experts_ffn(x, wg, all_zero, wi, wgate, wd,
                                              **kw)
    p = jax.nn.softmax(x @ wg, -1)
    chosen = jax.lax.top_k(p + all_zero / ROUTER, TOP)[1]
    assert bool(jnp.all(chosen >= REAL))
    assert (int(rows), int(most), int(picks)) == (0, 0, 10 * TOP)
    w0 = SCALE * jnp.take_along_axis(p, chosen, 1).sum(1)
    np.testing.assert_allclose(np.asarray(y), np.asarray(w0[:, None] * x),
                               rtol=1e-5, atol=1e-6)
    # no bias: real and identity choices mixed; the identity part is what is
    # left when the layer runs without identity experts
    y2, (rows2, _, picks2) = held_experts_ffn(x, wg, None, wi, wgate, wd, **kw)
    real, (rows3, _) = held_experts_ffn(x, wg, None, wi, wgate, wd,
                                        **{**kw, "zero_experts": 0})
    chosen = jax.lax.top_k(p, TOP)[1]
    w = SCALE * jnp.take_along_axis(p, chosen, 1)
    w0 = jnp.where(chosen >= REAL, w, 0).sum(1)
    assert int(rows2) == int(rows3) and int(rows2) + int(picks2) == 10 * TOP
    assert 0 < int(picks2) < 10 * TOP
    np.testing.assert_allclose(np.asarray(y2 - real),
                               np.asarray(w0[:, None] * x), atol=1e-5)
    # masked rows route nowhere: no identity pick, no output
    y4, (_, _, picks4) = held_experts_ffn(
        x, wg, all_zero, wi, wgate, wd, token_mask=jnp.arange(10) < 4, **kw)
    assert int(picks4) == 4 * TOP and not bool(jnp.any(y4[4:]))


# -- (e) ------------------------------------------------------------------

def test_the_absorbed_form_equals_the_unabsorbed_one_with_both_scales(
        model, tree):
    """One sublayer's attention for the tokens of a sequence: the paged path
    (scaled ``c_kv`` in the pool, queries through ``W_uk``, the weighted
    latent through ``W_uv``) against the full-sequence path (keys and values
    up-projected from the same scaled latent), and both against the
    reference's attention."""
    b = sublayer(layer_of(tree, 1), 1)
    S = 37
    x = jax.random.normal(jax.random.PRNGKey(12), (1, S, 128), jnp.float32)
    pos = jnp.arange(S, dtype=jnp.int32)
    plain, _ = model._mla_attention(x, b, positions=pos[None])
    pool = model.init_kv_pool(5, 16, dtype=jnp.float32)
    tables = jnp.broadcast_to(jnp.asarray([[1, 2, 3, 4]], jnp.int32), (S, 4))
    absorbed, pool = model._mla_attention(
        x[0][:, None], b, positions=pos[:, None], paged=(pool, 3, tables))
    np.testing.assert_allclose(np.asarray(absorbed[:, 0]),
                               np.asarray(plain[0]), atol=2e-5)
    from benchmark.reference.deepseek_v3 import rms_norm

    want = ref.attention(rms_norm(x[0], b["ln1_scale"], 1e-5), b, published(),
                         ein_f32)
    np.testing.assert_allclose(np.asarray(plain[0]), np.asarray(want),
                               atol=2e-5)
    # the pool's rows of layer 3 hold the SCALED latent: sqrt(128 / 32) = 2
    # times a unit-RMS norm's output (times its scale leaf)
    rows = pa.gather_context(pool, jnp.int32(3), tables[:1], 32)[0][0, :S, 0]
    rms = jnp.sqrt(jnp.mean((rows / b["kv_a_scale"]) ** 2, -1))
    np.testing.assert_allclose(np.asarray(rms), 2.0, rtol=1e-3)
    assert not bool(jnp.any(pool[:3])) and model.config.mla_latent_scales == (
        (128 / 48) ** 0.5, 2.0)
