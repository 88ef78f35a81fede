"""GigaChat3.1 / DeepSeek-V3 through the program against the plain reference
(``benchmark/reference/deepseek_v3.py``), at a small size on the CPU: hidden
128, 4 heads, latents 48 / 32, head sizes 16 + 8 + 24, 16 router outputs in 4
groups of which 2 are kept, top 4, one dense and two expert layers.

(a) ``TransformerLM.logits`` against the reference; (b) prefill in chunks then
decode through the latent paged pool against the reference's full forward;
(c) the shares of an expert-parallel layer add up to the uncut layer;
(d) routing; (e) the absorbed form equals the un-absorbed one; (f) YaRN
frequencies against hand-computed values; (g) the ``mla_decode`` kernel in
interpret mode against ``gather_context`` + plain attention.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness import weights
from benchmark.harness.serve import engine_logits
from benchmark.reference import deepseek_v3 as ref
from benchmark.reference import ein_f32
from deepspeed_tpu.models.transformer import (TransformerConfig,
                                              TransformerLM, yarn_inv_freq)
from deepspeed_tpu.moe.layer import grouped_experts, held_experts_ffn
from deepspeed_tpu.moe.sharded_moe import group_limited_gating
from deepspeed_tpu.ops.transformer import paged_attention as pa

ROUTER, HELD_ALL = 16, 16
SCALING = dict(beta_fast=32, beta_slow=1, factor=64, mscale=1, mscale_all_dim=1,
               original_max_position_embeddings=64, rope_type="yarn")


def model_config(held=HELD_ALL, offset=0, **kw):
    return TransformerConfig(**{**dict(
        vocab_size=256, hidden_size=128, num_layers=3, num_heads=4,
        max_seq_len=256, pos_embedding="rope", norm="rmsnorm",
        activation="swiglu", tie_embeddings=False, norm_eps=1e-6,
        attention="mla", q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=24, rope_theta=100000.0,
        rope_factor=64.0, rope_original_max=64, rope_mscale=1.0,
        rope_mscale_all_dim=1.0, num_dense_layers=1,
        dense_intermediate_size=192, intermediate_size=64, num_experts=held,
        moe_expert_offset=offset, moe_top_k=4, moe_router="group_limited",
        moe_router_width=ROUTER, moe_n_group=4, moe_topk_group=2,
        moe_score_scale=2.5, moe_shared_size=64), **kw})


def published(offset=0):
    return dict(
        hidden_size=128, num_attention_heads=4, q_lora_rank=48,
        kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=24, rms_norm_eps=1e-6, rope_theta=100000.0,
        rope_scaling=SCALING, n_group=4, topk_group=2, num_experts_per_tok=4,
        routed_scaling_factor=2.5, norm_topk_prob=True,
        first_k_dense_replace=1, num_hidden_layers=3, expert_offset=offset)


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


# -- (a) ------------------------------------------------------------------

@pytest.mark.parametrize("held,offset", [(16, 0), (4, 8)])
def test_logits_match_the_reference(held, offset):
    """The full-sequence forward (un-absorbed attention, grouped experts),
    float32: with every expert held and with the four from output 8 on."""
    m = TransformerLM(model_config(held, offset))
    w = seeded(m)
    ids = np.random.default_rng(0).integers(0, 256, (2, 40)).astype(np.int32)
    got = m.logits(w, jnp.asarray(ids))
    want = jnp.stack([reference_logits(w, i, published(offset)) for i in ids])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_parameter_counts_follow_the_tree(model, tree):
    cfg = model.config
    assert cfg.num_parameters == sum(x.size for x in jax.tree.leaves(tree))
    per_expert = 3 * cfg.hidden_size * cfg.mlp_dim
    assert cfg.num_parameters - cfg.num_active_parameters == \
        2 * (16 - 4) * per_expert
    # q.k over 16 + 8, p.v over 24, 4 heads, 3 layers, fwd + bwd
    assert cfg.flops_per_token(100) == 6 * cfg.num_active_parameters \
        + 6 * 3 * 4 * (16 + 8 + 24) * 100


def test_the_cells_configuration_is_4_29b_parameters():
    """The served tree of ``benchmark/configs/gigachat3.1-702b-a36b.json``:
    five layers, 16 of 256 experts held, an eighth of the vocabulary."""
    from benchmark.harness.cell import load_json

    cfg = TransformerConfig(
        **load_json("configs", "gigachat3.1-702b-a36b.json")["model"])
    attn = (7168 * 1536 + 1536 * 64 * 192 + 7168 * 576 + 512 * 64 * 320
            + 64 * 192 * 7168 + 1536 + 512)
    expert = 3 * 7168 * 2048
    dense = attn + 2 * 7168 + 3 * 7168 * 18432
    moe = attn + 2 * 7168 + 17 * expert + 7168 * 256 + 256
    assert cfg.num_parameters == dense + 4 * moe + 2 * 16032 * 7168 + 7168
    assert round(cfg.num_parameters / 1e9, 2) == 4.29
    assert cfg.num_active_parameters == cfg.num_parameters - 4 * 8 * expert
    assert cfg.kv_row == (512, 128) and cfg.pool_heads == 1


# -- (b) ------------------------------------------------------------------

#: float32 through the pool agrees to rounding. In bfloat16 the limit is an
#: error relative to the logits' spread, as the cell's ``logits_rel_err``:
#: weights, activations and the cached latent are rounded to 8 bits of
#: mantissa and a route that flips on rounding swaps an expert's whole
#: output, so at three layers the error is a few per cent (0.02-0.04 read
#: here), far under the 0.15 of a wrong position or a dropped expert
PAGED = [(jnp.float32, False, 2e-5), (jnp.float32, True, 2e-5),
         (jnp.bfloat16, False, 0.08)]


@pytest.mark.parametrize("dtype,kernel,tol", PAGED)
def test_chunked_prefill_then_decode_through_the_latent_pool(
        model, tree, monkeypatch, dtype, kernel, tol):
    """Prompts of 70, 33 and 5 tokens prefilled in segment tiles (chunks of
    32 in a 36-row budget, so a prompt spans several mixed steps) and three
    forced tokens decoded, full logits at every step, against the reference's
    full causal forward. With the Pallas kernels in interpret mode and
    without."""
    from deepspeed_tpu.inference.v2 import InferenceEngineV2

    if kernel:
        monkeypatch.setenv("DSTPU_FORCE_PAGED_KERNEL", "1")
    served = jax.tree.map(lambda a: a.astype(dtype), tree)
    eng = InferenceEngineV2(model, served, dtype=dtype, max_seqs=4,
                            max_seq_len=256, block_size=16, token_budget=36,
                            prefill_chunk=32, num_blocks=40)
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


def test_a_mixed_step_lays_segments_on_tile_boundaries(model, tree):
    """One-token rows fill from row 0, each chunk from the next boundary of a
    16-row tile after the ``max_seqs`` one-token rows; a step takes what the
    whole tiles hold."""
    from deepspeed_tpu.inference.v2 import InferenceEngineV2

    eng = InferenceEngineV2(model, tree, dtype=jnp.float32,
                            max_seqs=4, max_seq_len=256, block_size=16,
                            token_budget=52, prefill_chunk=32, num_blocks=40)
    eng.put([1], [[5, 6, 7]], greedy=True)               # 1 is decoding now
    for uid, n in ((2, 20), (3, 30)):
        eng.put([uid], [list(range(n))], greedy=True, max_steps=0)
    eng.put([1], [[9]], greedy=True, max_steps=0)
    work = [d for d in eng.state.seqs.values() if d.in_flight]
    T, plan, finals, feed = eng._build_ragged_step(work)
    ids, tables, starts = feed[:3]
    assert T == 52 and [(d.uid, t) for d, t in plan] == [(1, 1), (2, 20), (3, 16)]
    assert starts[0] == 3 and ids[0, 0] == 9              # the one-token row
    assert list(ids[4:24, 0]) == list(range(20))          # tile rows 4..35
    assert list(ids[36:52, 0]) == list(range(16))         # next boundary
    assert not tables[1:4].any() and not tables[24:36].any()   # padding rows
    assert (tables[4:24] == tables[4]).all() and tables[4, 0] > 0
    assert [d.uid for d in finals] == [1, 2]


# -- (c) ------------------------------------------------------------------

def test_the_shares_add_up_to_the_uncut_layer(tree):
    """Four shares of four experts: the routed parts of all shares plus the
    shared expert, counted once, equal the uncut reference layer."""
    b = {k: v[0] for k, v in tree["blocks"].items()}
    x = jax.random.normal(jax.random.PRNGKey(3), (50, 128), jnp.float32)
    cfg = published()
    whole = ref.experts(x, b, cfg, ein_f32)
    shared = ref.gated_mlp(x, b["shared_w_gate"], b["shared_w_up"],
                           b["shared_w_down"], ein_f32)
    routed = jnp.zeros_like(x)
    for r in range(4):
        sl = slice(4 * r, 4 * r + 4)
        part, (rows, _) = held_experts_ffn(
            x, b["moe_wg"], b["moe_bias"], b["wi"][sl], b["w_gate"][sl],
            b["w_down"][sl], None, k=4, n_group=4, topk_group=2, scale=2.5,
            first=4 * r)
        routed = routed + part
        # and the reference, given the same share, computes the same part
        share = {**b, **{k: b[k][sl] for k in ("wi", "w_gate", "w_down")}}
        want = ref.experts(x, share, {**cfg, "expert_offset": 4 * r},
                           ein_f32) - shared
        np.testing.assert_allclose(np.asarray(part), np.asarray(want),
                                   atol=2e-5)
    np.testing.assert_allclose(np.asarray(routed + shared), np.asarray(whole),
                               atol=2e-5)


# -- (d) ------------------------------------------------------------------

def test_routing_keeps_groups_selects_by_bias_and_weighs_by_score():
    logits = jnp.asarray(np.random.default_rng(5).normal(size=(64, 16)),
                         jnp.float32)
    bias = jnp.zeros((16,)).at[3].set(10.0)     # selection only
    chosen, w = group_limited_gating(logits, bias, k=4, n_group=4,
                                     topk_group=2, scale=2.5)
    s = jax.nn.sigmoid(logits)
    assert chosen.shape == (64, 4) and bool(jnp.all(jnp.any(chosen == 3, 1)))
    # at most two groups of four outputs each
    assert all(len(set(np.asarray(row) // 4)) <= 2 for row in chosen)
    # weights come from s, not s + bias, normalised over all four, scaled
    picked = jnp.take_along_axis(s, chosen, 1)
    np.testing.assert_allclose(
        np.asarray(w), np.asarray(2.5 * picked / picked.sum(1, keepdims=True)),
        rtol=1e-6)
    # and against the reference's dense weights (eye @ W: the logits as given)
    dense = ref.route(jnp.eye(16), {"moe_wg": logits[:16], "moe_bias": bias},
                      dict(n_group=4, topk_group=2, num_experts_per_tok=4,
                           routed_scaling_factor=2.5), ein_f32)
    got = jnp.zeros((16, 16)).at[jnp.arange(16)[:, None], chosen[:16]].set(w[:16])
    np.testing.assert_allclose(np.asarray(got), np.asarray(dense), rtol=1e-6)


def test_no_token_is_dropped_when_every_token_picks_one_expert():
    """A skew that sends every token's first choice to expert 2: all 96 rows
    land there, none is dropped, and the result is that expert's FFN."""
    key = jax.random.split(jax.random.PRNGKey(4), 4)
    x = jax.random.normal(key[0], (96, 32))
    wi, wg = (jax.random.normal(k, (4, 32, 16)) * 0.2 for k in key[1:3])
    wd = jax.random.normal(key[3], (4, 16, 32)) * 0.2
    chosen = jnp.stack([jnp.full((96,), 2), jnp.full((96,), 9)], 1)
    w = jnp.stack([jnp.full((96,), 0.75), jnp.full((96,), 0.25)], 1)
    y, (rows, most) = grouped_experts(x, chosen, w, wi, wg, wd)
    want = 0.75 * ((jax.nn.silu(x @ wg[2]) * (x @ wi[2])) @ wd[2])
    assert (int(rows), int(most)) == (96, 96)       # expert 9 is not held
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=1e-5)
    # a layer of a stack, indexed where it lies, is the same product
    stack = [jnp.stack([a * 0, a]) for a in (wi, wg, wd)]
    y2, _ = grouped_experts(x, chosen, w, *stack, layer=jnp.int32(1))
    np.testing.assert_allclose(np.asarray(y2), np.asarray(y), atol=1e-6)
    # masked rows route nowhere
    y3, (rows3, _) = held_experts_ffn(
        x, jnp.zeros((32, 4)), None, wi, wg, wd, None, k=2,
        token_mask=jnp.arange(96) < 10)
    assert int(rows3) == 20 and not bool(jnp.any(y3[10:]))


def test_grouped_experts_is_differentiable(tree):
    """With every expert held it is the layer training uses: a gradient
    reaches the experts' matrices, the router's scores and the input."""
    b = {k: v[0] for k, v in tree["blocks"].items()}
    x = jax.random.normal(jax.random.PRNGKey(6), (24, 128), jnp.float32)

    def loss(x, wi, wg):
        y, _ = held_experts_ffn(x, wg, b["moe_bias"], wi, b["w_gate"],
                                b["w_down"], None, k=4, n_group=4, topk_group=2)
        return jnp.sum(y ** 2)

    gx, gwi, gwg = jax.grad(loss, argnums=(0, 1, 2))(x, b["wi"], b["moe_wg"])
    assert all(bool(jnp.all(jnp.isfinite(g))) and float(jnp.abs(g).max()) > 0
               for g in (gx, gwi, gwg))


# -- (e), (g) -------------------------------------------------------------

def latent_case(tiles, q_tile, lens, dtype=jnp.float32, BS=16, MAXB=6,
                nh=4, rank=32, rope=8):
    """Absorbed queries, a latent pool of 2 layers and tables for ``tiles``
    sequences whose last ``q_tile`` tokens are the rows."""
    ks = jax.random.split(jax.random.PRNGKey(11), 3)
    row = sum(pa.latent_row(rank, rope))
    NB = 1 + tiles * MAXB
    pool = jax.random.normal(ks[0], (2, 1, NB, BS, row), dtype)
    q_lat = jax.random.normal(ks[1], (tiles * q_tile, nh, rank), dtype)
    q_rope = jax.random.normal(ks[2], (tiles * q_tile, nh, rope), dtype)
    tables = np.zeros((tiles, MAXB), np.int32)
    tables[:, :] = 1 + np.arange(tiles * MAXB).reshape(tiles, MAXB)
    limits = np.concatenate([n - q_tile + 1 + np.arange(q_tile) for n in lens])
    return q_lat, q_rope, pool, jnp.asarray(tables), jnp.asarray(limits, jnp.int32)


@pytest.mark.parametrize("q_tile,lens", [
    (1, (7, 33, 96)), (1, (1, 64, 65)), (16, (16, 61)), (16, (90, 17))])
def test_mla_decode_kernel_matches_gather_plus_plain_attention(q_tile, lens):
    """The Pallas kernel (interpret mode) against ``gather_context`` and a
    plain softmax: one-token rows and 16-row segment tiles, contexts that end
    inside, at and just past a group of pool blocks, a table width (6) that
    is not a multiple of the group."""
    q_lat, q_rope, pool, tables, limits = latent_case(len(lens), q_tile, lens)
    got = pa.mla_decode(q_lat, q_rope, pool, jnp.int32(1), tables, limits,
                        scale=0.3, q_tile=q_tile)
    c_kv, k_rope = pa.gather_context(pool, jnp.int32(1), tables, 32)
    c_kv = jnp.repeat(c_kv[:, :, 0], q_tile, 0)          # (N, T, rank)
    k_rope = jnp.repeat(k_rope[:, :, 0, :8], q_tile, 0)
    s = (jnp.einsum("nhr,ntr->nht", q_lat, c_kv)
         + jnp.einsum("nhr,ntr->nht", q_rope, k_rope)) * 0.3
    seen = jnp.arange(c_kv.shape[1])[None, None] < limits[:, None, None]
    want = jnp.einsum("nht,ntr->nhr",
                      jax.nn.softmax(jnp.where(seen, s, -jnp.inf), -1), c_kv)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    xla = pa.mla_attend_xla(q_lat, q_rope, pool, jnp.int32(1), tables, limits,
                            scale=0.3, q_tile=q_tile)
    np.testing.assert_allclose(np.asarray(xla), np.asarray(want), atol=2e-5)


def test_the_absorbed_form_equals_the_unabsorbed_one(tree):
    """One layer's attention for the last token of a sequence: queries
    through ``W_uk`` against the cached latent and the weighted latent
    through ``W_uv`` (both views of ``wkv_b``), against keys and values
    up-projected from the same latent and attended to head by head."""
    b = {k: v[0] for k, v in tree["dense_blocks"].items()}
    S, nh, rank, nope, rope, vd = 37, 4, 32, 16, 8, 24
    ks = jax.random.split(jax.random.PRNGKey(12), 3)
    c_kv = jax.random.normal(ks[0], (S, rank))
    k_rope = jax.random.normal(ks[1], (S, rope))
    q = jax.random.normal(ks[2], (nh, nope + rope))
    wkv_b = b["wkv_b"].reshape(rank, nh, nope + vd)
    kv = jnp.einsum("sr,rhd->shd", c_kv, wkv_b)
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(k_rope[:, None], (S, nh, rope))], -1)
    p = jax.nn.softmax(jnp.einsum("hd,shd->hs", q, k) * 0.2, -1)
    plain = jnp.einsum("hs,shd->hd", p, kv[..., nope:])
    row = jnp.pad(jnp.concatenate([c_kv, k_rope], -1),
                  ((0, 64 - S), (0, sum(pa.latent_row(rank, rope)) - 40)))
    pool = jnp.zeros((1, 1, 5, 16, row.shape[-1])).at[0, 0, 1:].set(
        row.reshape(4, 16, -1))
    q_lat = jnp.einsum("hd,rhd->hr", q[:, :nope], wkv_b[..., :nope])
    o_lat = pa.mla_attend_xla(q_lat[None], q[None, :, nope:], pool, 0,
                              jnp.asarray([[1, 2, 3, 4]]), jnp.asarray([S]),
                              scale=0.2)
    absorbed = jnp.einsum("hr,rhd->hd", o_lat[0], wkv_b[..., nope:])
    np.testing.assert_allclose(np.asarray(absorbed), np.asarray(plain),
                               atol=1e-5)


def test_latent_blocks_move_whole_through_the_block_programs():
    """get_block / set_block on a latent pool: the payload is the whole rows
    (1, L, 1, BS, row), and a ``[k | v]`` pool keeps its (2, ...) payload."""
    pool = jax.random.normal(jax.random.PRNGKey(2), (2, 1, 6, 16, 128))
    blk = pa.get_block(pool, jnp.int32(4), 32)
    assert blk.shape == pa.payload_shape(pool, 32) == (1, 2, 1, 16, 128)
    moved = pa.set_block(pool, jnp.int32(2), blk)
    np.testing.assert_array_equal(np.asarray(moved[:, :, 2]),
                                  np.asarray(pool[:, :, 4]))
    assert pa.get_block(pool, 4).shape == pa.payload_shape(pool) == \
        (2, 2, 1, 16, 64)


# -- (f) ------------------------------------------------------------------

def test_yarn_frequencies_against_hand_computed_values():
    """The cell's rope head: 64 dimensions, theta 1e5, factor 64 over 4096.
    The correction range is floor / ceil of 64 ln(4096 / (2 pi b)) / (2 ln 1e5)
    at b = 32 and 1: 8 and 19. Dimension 0 keeps its frequency, dimension 31
    is divided by 64, dimension 12 sits 4/11 up the ramp."""
    cfg = TransformerConfig(attention="mla", qk_rope_head_dim=64,
                            rope_theta=100000.0, rope_factor=64.0,
                            rope_original_max=4096, rope_mscale=1.0,
                            rope_mscale_all_dim=1.0)
    lo = 64 * math.log(4096 / (2 * math.pi * 32)) / (2 * math.log(1e5))
    hi = 64 * math.log(4096 / (2 * math.pi * 1)) / (2 * math.log(1e5))
    assert (math.floor(lo), math.ceil(hi)) == (8, 19)
    inv = yarn_inv_freq(cfg)
    f = [1e5 ** (-2 * i / 64) for i in range(32)]
    want12 = f[12] / 64 * (4 / 11) + f[12] * (1 - 4 / 11)
    np.testing.assert_allclose(inv[[0, 8, 12, 19, 31]],
                               [1.0, f[8], want12, f[19] / 64, f[31] / 64],
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(ref.yarn_inv_freq(
        64, 1e5, {**SCALING, "original_max_position_embeddings": 4096})), inv,
        rtol=1e-6)
    # at positions 1 and 1000 the angle of pair 12 is position * inv[12];
    # cos and sin carry mscale(64, 1) / mscale(64, 1) = 1
    x = jnp.zeros((1, 2, 1, 64)).at[..., 24].set(1.0)
    from deepspeed_tpu.models.transformer import _rope_interleaved

    out = _rope_interleaved(x, jnp.asarray([[1, 1000]]), cfg)
    for j, pos in enumerate((1, 1000)):
        np.testing.assert_allclose(
            np.asarray(out[0, j, 0, 24:26]),
            [math.cos(pos * want12), math.sin(pos * want12)], atol=1e-5)
    # the softmax scale carries mscale(64, 1)^2: 192^-1/2 * (0.1 ln 64 + 1)^2
    full = TransformerConfig(attention="mla", qk_nope_head_dim=128,
                             qk_rope_head_dim=64, rope_factor=64.0,
                             rope_original_max=4096, rope_mscale_all_dim=1.0)
    from deepspeed_tpu.models.transformer import mla_softmax_scale

    assert abs(mla_softmax_scale(full) - 0.14468) < 1e-5
