"""A served layer reads its weights where they lie in the stack.

Structure, on the jaxpr of the paged forward's scanned layer body: every
matrix leaf of a layer reaches, through ``convert_element_type`` / ``reshape``
only, exactly one ``dot_general``, and is never sliced, transposed or
concatenated; the products whose result is split into heads (``wq``, ``wk``,
``wv``; the latent attention's ``wq_b``) go through an
``optimization_barrier`` first. Without it the TPU compiler pushes the split
into the matrix: it slices the layer's matrix out of the stack and relays a
transposed copy of it before the product, every layer of every dispatch
(PERF.md 5, PR 41). The one leaf that is contracted twice, by head, is the
latent attention's ``wkv_b`` (the absorbed pair): pinned here as it is.

Arithmetic: a barrier changes no value, so the paged forward with every
barrier taken out gives the same logits and the same pool, bit for bit.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models.transformer import (EXPERT_LEAVES, TransformerConfig,
                                              TransformerLM)
from deepspeed_tpu.ops.transformer import paged_attention as pa

THROUGH = ("convert_element_type", "reshape")
BLOCK, NUM_BLOCKS, MAXB = 16, 24, 4
BF16_TOL = 0.02      # tests/benchmark/test_deepseek_v3.py holds bfloat16 to 0.04


def gpt2_family():
    return TransformerConfig(
        vocab_size=256, hidden_size=128, num_layers=2, num_heads=2,
        max_seq_len=64, qkv_bias=True)


def latent():
    return TransformerConfig(
        vocab_size=256, hidden_size=128, num_layers=3, num_heads=4,
        max_seq_len=256, pos_embedding="rope", norm="rmsnorm",
        activation="swiglu", tie_embeddings=False, norm_eps=1e-6,
        attention="mla", q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=24, rope_theta=100000.0,
        num_dense_layers=1, dense_intermediate_size=192, intermediate_size=64,
        num_experts=16, moe_top_k=4, moe_router="group_limited",
        moe_router_width=16, moe_n_group=4, moe_topk_group=2,
        moe_score_scale=2.5, moe_shared_size=64)


def double_layers():
    return TransformerConfig(
        vocab_size=256, hidden_size=128, num_layers=2, num_heads=4,
        max_seq_len=256, pos_embedding="rope", norm="rmsnorm",
        activation="swiglu", tie_embeddings=False, norm_eps=1e-5,
        attention="mla", q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, rope_theta=1e7,
        mla_scale_q_lora=True, mla_scale_kv_lora=True, layer_kind="scmoe",
        dense_intermediate_size=192, intermediate_size=64, num_experts=16,
        moe_top_k=6, moe_router="softmax_topk", moe_router_width=24,
        moe_zero_experts=8, moe_norm_topk=False, moe_score_scale=6.0)


def typed():
    return TransformerConfig(
        vocab_size=256, hidden_size=128, num_layers=4, num_heads=8,
        num_kv_heads=2, intermediate_size=192, max_seq_len=128,
        pos_embedding="rope", norm="rmsnorm", activation="swiglu",
        tie_embeddings=False, norm_eps=1e-6,
        layer_types=("sparse_attn", "linear_attn", "linear_attn",
                     "sparse_attn"), qk_norm=True, attn_output_gate=True,
        sparse_kernel_size=8, sparse_kernel_stride=4, sparse_window=32,
        sparse_init_blocks=1, sparse_topk=4, sparse_dense_len=64,
        sparse_block_size=16, linear_chunk=16, embed_scale=12.0,
        scale_depth=1.4, scale_depth_layers=32, dim_model_base=32)


#: config -> (the products split into heads behind a barrier,
#:            what may stand between such a product and its barrier)
CASES = {
    "gpt2_family": (gpt2_family, ("wq", "wk", "wv"), ("add",)),
    "latent": (latent, ("wq_b",), ()),
    "double_layers": (double_layers, ("s0_wq_b", "s1_wq_b"), ()),
    "typed": (typed, ("wq", "wk", "wv"), ()),
}


def step(model, dtype=jnp.float32, rows=4, tile_rows=0, seed=0):
    """Arguments of one ``forward_paged`` dispatch: ``rows`` one-token rows
    of sequences of their own at positions 5.., then ``tile_rows`` rows of
    one more sequence's consecutive tokens, over a pool of random rows."""
    cfg = model.config
    rng = np.random.default_rng(seed)
    T = rows + tile_rows
    params = jax.tree.map(lambda a: a.astype(dtype),
                          model.init_params(jax.random.PRNGKey(seed)))
    pool = model.init_kv_pool(NUM_BLOCKS, BLOCK, dtype=dtype)
    pool = jnp.asarray(rng.normal(0, 0.5, pool.shape), dtype)
    tables = np.zeros((T, MAXB), np.int32)
    starts = np.zeros((T,), np.int32)
    for r in range(rows):
        tables[r] = 1 + MAXB * r + np.arange(MAXB)
        starts[r] = 5 + 7 * r
    if tile_rows:
        tables[rows:] = 1 + MAXB * rows + np.arange(MAXB)
        starts[rows:] = 3 + np.arange(tile_rows)
    ids = rng.integers(0, cfg.vocab_size, (T, 1)).astype(np.int32)
    kw = dict(rows_apart=not tile_rows,
              seg_from=rows if tile_rows else None)
    if cfg.layer_types is not None:
        kw.update(state=model.init_state_cache(T + 1, cfg.max_seq_len,
                                               dtype=dtype),
                  row_slots=jnp.arange(1, T + 1, dtype=jnp.int32))
    return (params, jnp.asarray(ids), pool, jnp.asarray(tables),
            jnp.asarray(starts)), kw


# -- the structure ----------------------------------------------------------

def sub_jaxprs(eqn):
    for v in eqn.params.values():
        inner = getattr(v, "jaxpr", v)
        if hasattr(inner, "eqns"):
            yield inner


def uses(jaxpr, var, through=THROUGH):
    """The equations that consume ``var`` in ``jaxpr``, looking through the
    primitives of ``through`` and into calls whose operands are the callee's
    (``jit``, ``custom_jvp_call``, ...): [(primitive name, equation)]."""
    found = []
    for eqn in jaxpr.eqns:
        where = [i for i, v in enumerate(eqn.invars) if v is var]
        if not where:
            continue
        name = eqn.primitive.name
        inner = [j for j in sub_jaxprs(eqn)
                 if len(j.invars) == len(eqn.invars)]
        if name in through:
            found += uses(jaxpr, eqn.outvars[0], through)
        elif inner and name not in ("scan", "while", "cond"):
            for i in where:
                found += uses(inner[0], inner[0].invars[i], through)
        else:
            found.append((name, eqn))
    return found


def layer_scans(jaxpr):
    """Every ``scan`` of the program that carries stacked leaves as ``xs``."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan" and \
                len(eqn.invars) > eqn.params["num_consts"] \
                + eqn.params["num_carry"]:
            yield eqn
        else:
            for inner in sub_jaxprs(eqn):
                yield from layer_scans(inner)


def scanned_leaves(model, args, kw):
    """[(leaf name, its variable in the layer body, the body)] for every
    stacked leaf a layer scan slices, in the program's order of scans."""
    params = args[0]
    jaxpr = jax.make_jaxpr(
        lambda *a: model.forward_paged(*a, **kw))(*args).jaxpr
    groups = [key for key, *_ in model.config.type_runs]
    scans = list(layer_scans(jaxpr))
    assert len(scans) == len(groups), (len(scans), groups)
    out = []
    for eqn, group in zip(scans, groups):
        body = eqn.params["jaxpr"].jaxpr
        xs = body.invars[eqn.params["num_consts"] + eqn.params["num_carry"]:]
        # an expert group's stacked expert matrices stay out of the scan
        held = EXPERT_LEAVES if "moe_wg" in params[group] else ()
        names = sorted(k for k in params[group] if k not in held)
        assert len(names) == len(xs), (group, names, len(xs))
        out += [(n, v, body) for n, v in zip(names, xs)]
    return out


@pytest.mark.parametrize("case", sorted(CASES))
def test_every_matrix_of_a_served_layer_enters_one_product_whole(case):
    make, split, between = CASES[case]
    model = TransformerLM(make())
    args, kw = step(model)
    leaves = scanned_leaves(model, args, kw)
    matrices = [(n, v, b) for n, v, b in leaves if len(v.aval.shape) >= 2]
    assert {n for n, _, _ in matrices} >= set(split)
    for name, var, body in matrices:
        if name.endswith("wkv_b"):
            # the absorbed pair: ``q_nope W_uk`` before the attention and
            # ``o W_uv`` after it, both batched by head over views of the one
            # stored matrix. XLA's head-batched product wants the head major,
            # and in the stored (rank, heads * (nope + v)) it lies inside the
            # minor dimension: the relaid copy a layer is inherent to the
            # stored layout (PERF.md 5), and this pins the pair as it is
            seen = uses(body, var, THROUGH + ("slice",))
            assert [n for n, _ in seen] == ["dot_general"] * 2, (name, seen)
            continue
        seen = uses(body, var)
        # no slice, dynamic_slice, transpose, concatenate or gather of it,
        # and no second product
        assert [n for n, _ in seen] == ["dot_general"], (name, seen)
        if name in split:
            dot = seen[0][1]
            behind = uses(body, dot.outvars[0], between)
            assert behind and all(n == "optimization_barrier"
                                  for n, _ in behind), (name, behind)


def test_the_training_forward_keeps_its_fusions():
    """The barrier belongs to the paged branch alone: the full-sequence
    forward (training, the references) traces none for ``wq``/``wk``/``wv``."""
    model = TransformerLM(gpt2_family())
    params = model.init_params(jax.random.PRNGKey(0))
    ids = jnp.zeros((2, 16), jnp.int32)
    text = str(jax.make_jaxpr(lambda p, i: model.logits(p, i))(params, ids))
    assert "optimization_barrier" not in text


# -- the arithmetic ---------------------------------------------------------

def both_ways(model, args, kw, monkeypatch):
    """``forward_paged`` as it is, and with every barrier taken out."""
    def run():
        return jax.jit(lambda *a: model.forward_paged(*a, **kw))(*args)

    with_barriers = run()
    monkeypatch.setattr(jax.lax, "optimization_barrier", lambda x: x)
    return with_barriers, run()


def assert_same_bits(got, want):
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_array_equal(
            np.asarray(a.astype(jnp.float32)), np.asarray(b.astype(jnp.float32)))


@pytest.mark.parametrize("kernel", [True, False],
                         ids=["kernel", "gather"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_block_paged_is_the_same_arithmetic(monkeypatch, kernel, dtype):
    """``_block``'s paged branch with the decode kernel (interpreted) and
    with the table gather: logits and pool equal bit for bit."""
    if kernel:
        monkeypatch.setenv("DSTPU_FORCE_PAGED_KERNEL", "1")
    else:
        monkeypatch.delenv("DSTPU_FORCE_PAGED_KERNEL", raising=False)
    model = TransformerLM(gpt2_family())
    args, kw = step(model, dtype)
    got, want = both_ways(model, args, kw, monkeypatch)
    assert_same_bits(got, want)
    assert np.isfinite(np.asarray(got[0], np.float32)).all()
    assert not np.array_equal(np.asarray(got[1], np.float32),
                              np.asarray(args[2], np.float32))


@pytest.mark.parametrize("make", [latent, double_layers],
                         ids=["latent", "double_layers"])
@pytest.mark.parametrize("tile_rows", [0, pa.SEGMENT_TILE],
                         ids=["round", "segment_tile"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_latent_attention_paged_is_the_same_arithmetic(monkeypatch, make,
                                                       tile_rows, dtype):
    """``_mla_attention``'s paged branch in a round of one-token rows and in
    a mixed step with a segment tile, kernels interpreted: logits, pool and
    the expert counts equal bit for bit."""
    monkeypatch.setenv("DSTPU_FORCE_PAGED_KERNEL", "1")
    model = TransformerLM(make())
    args, kw = step(model, dtype, tile_rows=tile_rows)
    got, want = both_ways(model, args, {**kw, "moe_stats": True}, monkeypatch)
    assert np.isfinite(np.asarray(got[0], np.float32)).all()
    if dtype == jnp.float32:
        assert_same_bits(got, want)
        return
    # bfloat16 on the CPU: with the older barriers gone too (around the
    # products the norms read), XLA fuses those products into their readers
    # and keeps float32 where the barrier had rounded: the last bit moves
    a, b = (np.asarray(x[0], np.float32) for x in (got, want))
    spread = b - b.mean(-1, keepdims=True)
    assert np.sqrt(np.sum((a - b) ** 2) / np.sum(spread ** 2)) < BF16_TOL
