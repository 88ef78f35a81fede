"""A layer kind is described once: its record in ``models/transformer.py``
``LAYER_KINDS``, which the tree, the specs, the counts, the cache declaration,
the paged forward and the tracer read.

For each of the ten kinds, a model of that kind alone at tiny widths:
(a) ``num_parameters`` is the size of the tree ``init_params`` builds;
(b) ``tp_specs`` has that tree's structure, leaf for leaf, a spec entry an
axis (before PR 64 a ``layer_types`` model got the GPT-2 tree's specs under
the key ``blocks`` beside its own ``blocks_0``, ...);
(c) the ``jax.named_scope`` names on the name stacks of its traced decode
round that are no model or serving scope are the ones its record declares
(and the held experts', which ``moe/layer.py`` declares), and
``tracing.classify`` gives each back.
And (d) what the serving configurations' layer patterns declare to the
engine, as literals read off the parent commit (the sixth, PR 66's, off its
own: a kind that keeps KV blocks and a slot of two arrays; the seventh, PR
69's, off its own: state slots of two arrays beside a latent pool of one
layer).
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.analysis.program_audit import _iter_eqns
from deepspeed_tpu.models.transformer import (LAYER_KINDS, TransformerConfig,
                                              TransformerLM)
from deepspeed_tpu.utils import tracing
from tests.unit.test_block_classes import tiny as window_pattern
from tests.unit.test_served_weight_reads import (double_layers, gpt2_family,
                                                 latent, typed)

BLOCK, NUM_BLOCKS, MAXB, ROWS = 16, 24, 4, 4
#: declared by ``moe/layer.py``: the feed-forward's, whatever the kind
MOE_SCOPES = {"moe_route", "moe_experts", "moe_shared", "moe_zero"}


def hybrid():
    """The ``falcon-h1-34b-instruct`` rehearsal's pattern: attention and an
    SSD mixer side by side in every layer, every muP scalar on."""
    return TransformerConfig(
        vocab_size=256, hidden_size=128, num_layers=3, num_heads=4,
        num_kv_heads=2, head_dim_override=32, intermediate_size=256,
        max_seq_len=128, pos_embedding="rope", norm="rmsnorm",
        activation="swiglu", tie_embeddings=False, rope_theta=1e11,
        layer_types=("hybrid_ssm",) * 3, linear_chunk=16, ssm_heads=4,
        ssm_head_dim=32, ssm_groups=2, ssm_state=16, ssm_conv=4,
        embed_scale=5.657, attn_in_mult=1.0, key_mult=0.011,
        attn_out_mult=0.0375, ssm_in_mult=0.25,
        ssm_zone_mults=(0.354, 0.25, 0.177, 0.5, 0.354), ssm_out_mult=0.0884,
        mlp_mults=(0.177, 0.0112), head_mult=0.0078125)


def delta_latent(layer_types=("delta_attn", "delta_attn", "delta_attn",
                              "latent_attn"), **over):
    """The ``ling-3.0-flash`` rehearsal's pattern: delta-rule (KDA) layers
    on state slots and a latent layer on the latent pool, the first layer's
    feed-forward dense and the others' held experts; ``over`` to make a model
    of one of the kinds alone."""
    return TransformerConfig(**{**dict(
        vocab_size=256, hidden_size=128, num_layers=len(layer_types),
        num_heads=4, head_dim_override=32, intermediate_size=64,
        dense_intermediate_size=192, num_dense_layers=1, max_seq_len=128,
        pos_embedding="rope", norm="rmsnorm", activation="swiglu",
        tie_embeddings=False, norm_eps=1e-6, rope_theta=6e6,
        layer_types=layer_types, linear_chunk=16, ssm_conv=4,
        attn_head_gate=True, kv_lora_rank=32, qk_nope_head_dim=32,
        qk_rope_head_dim=16, v_head_dim=32, num_experts=4, moe_top_k=4,
        moe_router="group_limited", moe_router_width=16, moe_n_group=4,
        moe_topk_group=2, moe_score_scale=2.5, moe_shared_size=64), **over})


def of_types(*types):
    """A ``layer_types`` model of ``types`` around held experts, the first
    layer's feed-forward dense."""
    return window_pattern(layer_types=types, num_layers=len(types)).config


#: kind -> a model of that kind alone (``full``: the LLaMA style, whose
#: tree has no leaf ``num_parameters`` leaves out, as GPT-2's biases are)
ALONE = {
    "full": lambda: TransformerConfig(
        vocab_size=256, hidden_size=64, num_layers=2, num_heads=4,
        num_kv_heads=2, intermediate_size=96, max_seq_len=64,
        pos_embedding="rope", norm="rmsnorm", activation="swiglu",
        tie_embeddings=False),
    "latent": latent,
    "scmoe": double_layers,
    "sparse_attn": lambda: TransformerConfig(**{
        **typed().__dict__, "num_layers": 2,
        "layer_types": ("sparse_attn", "sparse_attn")}),
    "linear_attn": lambda: TransformerConfig(**{
        **typed().__dict__, "num_layers": 2,
        "layer_types": ("linear_attn", "linear_attn")}),
    "window_attn": lambda: of_types("window_attn", "window_attn"),
    "full_attn": lambda: of_types("full_attn", "full_attn"),
    "hybrid_ssm": hybrid,
    # dense feed-forwards: the kind's record declares ``dense_ffn``
    "delta_attn": lambda: delta_latent(("delta_attn",) * 2, num_experts=0),
    "latent_attn": lambda: delta_latent(("latent_attn",) * 2, num_experts=0),
}


def test_every_kind_has_its_case():
    assert sorted(ALONE) == sorted(LAYER_KINDS)
    for kind, make in ALONE.items():
        assert {k for _, k, _, _ in make().type_runs} == {kind}


@pytest.mark.parametrize("kind", sorted(ALONE))
def test_num_parameters_is_the_size_of_the_tree(kind):
    cfg = ALONE[kind]()
    tree = jax.eval_shape(TransformerLM(cfg).init_params,
                          jax.random.PRNGKey(0))
    assert cfg.num_parameters == sum(
        math.prod(leaf.shape) for leaf in jax.tree.leaves(tree))


@pytest.mark.parametrize("kind", sorted(ALONE))
def test_tp_specs_have_the_trees_structure(kind):
    model = TransformerLM(ALONE[kind]())
    tree = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    specs = model.tp_specs

    def is_spec(x):
        return isinstance(x, P)

    assert jax.tree.structure(specs, is_leaf=is_spec) \
        == jax.tree.structure(tree)
    ranks = jax.tree.map(lambda spec, leaf: (len(spec), leaf.ndim), specs,
                         tree, is_leaf=is_spec)
    assert all(a == b for a, b in jax.tree.leaves(
        ranks, is_leaf=lambda x: isinstance(x, tuple)))


def decode_round(model):
    """Abstract arguments of one ``forward_paged`` decode round: ``ROWS``
    one-token rows, each a sequence of its own."""
    cfg = model.config
    blocks = NUM_BLOCKS if not cfg.bounded_cache \
        else dict.fromkeys(cfg.class_layers, NUM_BLOCKS)
    tables = np.stack([1 + MAXB * r + np.arange(MAXB) for r in range(ROWS)])
    starts = 5 + 7 * np.arange(ROWS)
    kw = dict(rows_apart=True)
    if cfg.holds_state:
        kw.update(state=model.init_state_cache(ROWS, cfg.max_seq_len),
                  row_slots=jnp.arange(1, ROWS + 1, dtype=jnp.int32))
    if cfg.bounded_cache:
        kw["window"] = (jnp.asarray(tables, jnp.int32),
                        jnp.zeros((ROWS,), jnp.int32))
    args = (jax.eval_shape(model.init_params, jax.random.PRNGKey(0)),
            jnp.zeros((ROWS, 1), jnp.int32),
            jax.eval_shape(lambda: model.init_kv_pool(blocks, BLOCK)),
            jnp.asarray(tables, jnp.int32), jnp.asarray(starts, jnp.int32))
    return args, kw


@pytest.mark.parametrize("kind", sorted(ALONE))
def test_the_rounds_scopes_are_the_kinds_own(kind):
    model = TransformerLM(ALONE[kind]())
    args, kw = decode_round(model)
    closed = jax.make_jaxpr(lambda *a: model.forward_paged(*a, **kw))(*args)
    stacks = {str(eqn.source_info.name_stack)
              for eqn in _iter_eqns(closed.jaxpr)}
    known = {"kv_carry", *tracing.MODEL_SCOPES, *tracing.SERVE_SCOPES}
    # an einsum opens a scope named by its subscripts: no identifier
    own = {name for stack in stacks for name in stack.split("/")
           if name.isidentifier()} - known
    assert own - MOE_SCOPES == set(LAYER_KINDS[kind].scopes)
    assert bool(own & MOE_SCOPES) == model.config.holds_experts
    # the tracer gives each back, from the module that opens it
    given = {tracing.classify(
        f"jit(ragged)/kv_carry/while/body/closed_call/{stack}/dot_general")
        for stack in stacks}
    assert own <= given
    assert given <= own | {"model", "kv_carry", "unscoped",
                           *tracing.SERVE_SCOPES}


def test_a_kda_layers_round_binds_the_convolutions_kernel_under_its_scope(
        monkeypatch):
    """Where the paged programs take kernels a ``delta_attn`` layer's round
    holds ONE ``conv_decode``, under the sublayer's own scope (the tracer gives its time to ``delta_attn``, as it
    gave the gather, the product and the scatter it replaces); the SSD
    mixer's biased convolution binds none."""
    monkeypatch.setenv("DSTPU_FORCE_PAGED_KERNEL", "1")

    def kernels(kind):
        model = TransformerLM(ALONE[kind]())
        args, kw = decode_round(model)
        closed = jax.make_jaxpr(lambda *a: model.forward_paged(*a, **kw))(
            *args)
        return [(e.params["name"], tracing.classify(
            "jit(ragged)/kv_carry/while/body/closed_call/"
            f"{e.source_info.name_stack}/pallas_call"))
            for e in _iter_eqns(closed.jaxpr)
            if e.primitive.name == "pallas_call"]

    # (four heads: the recurrence keeps its XLA form at these widths)
    assert kernels("delta_attn") == [("conv_decode", "delta_attn")]
    assert "conv_decode" not in dict(kernels("hybrid_ssm"))


#: the five serving configurations' layer patterns at tiny widths -> what
#: they declare, read off commit e6c1671 (PR 63)
DECLARED = {
    "gpt2-medium": (gpt2_family, dict(
        type_runs=(("blocks", "full", 2, 1),),
        cache_kinds={"attn": (("kv_blocks", 512),)},
        pool_layers=2, class_layers={"full": 2}, kv_row=(64, 64),
        pool_heads=2, segment_tile=1, step_counts=())),
    "gigachat3.1-702b-a36b": (latent, dict(
        type_runs=(("dense_blocks", "latent", 1, 1),
                   ("blocks", "latent", 2, 1)),
        cache_kinds={"attn": (("kv_blocks", 256),)},
        pool_layers=3, class_layers={"full": 3}, kv_row=(32, 96),
        pool_heads=1, segment_tile=16,
        step_counts=("moe_rows", "moe_rows_max"))),
    "longcat-flash-chat": (double_layers, dict(
        type_runs=(("blocks", "scmoe", 2, 2),),
        cache_kinds={"attn": (("kv_blocks", 256),)},
        pool_layers=4, class_layers={"full": 4}, kv_row=(32, 96),
        pool_heads=1, segment_tile=16,
        step_counts=("moe_rows", "moe_rows_max", "moe_zero_picks"))),
    "minicpm-sala": (typed, dict(
        type_runs=(("blocks_0", "sparse_attn", 1, 1),
                   ("blocks_1", "linear_attn", 2, 0),
                   ("blocks_2", "sparse_attn", 1, 1)),
        cache_kinds={"sparse_attn": (("kv_blocks", 128),
                                     ("state_slot", 2048)),
                     "linear_attn": (("state_slot", 8192),)},
        pool_layers=2, class_layers={"full": 2}, kv_row=(16, 16),
        pool_heads=2, segment_tile=16,
        step_counts=("sel_blocks", "ctx_blocks"))),
    "trinity-mini": (lambda: window_pattern().config, dict(
        type_runs=(("blocks_0", "window_attn", 1, 1),
                   ("blocks_1", "window_attn", 1, 1),
                   ("blocks_2", "full_attn", 1, 1),
                   ("blocks_3", "window_attn", 1, 1)),
        cache_kinds={"window_attn": (("kv_blocks", 128, 32),),
                     "full_attn": (("kv_blocks", 128),)},
        pool_layers=4, class_layers={"full": 1, "window": 3},
        kv_row=(16, 16), pool_heads=2, segment_tile=16,
        step_counts=("moe_rows", "moe_rows_max"))),
    # KV blocks of 2 heads of 32 + 32, and a slot of two arrays: the float32
    # state (4 heads x 16 x 32) and the window's 3 rows of 128 + 2 x 2 x 16
    "falcon-h1-34b-instruct": (hybrid, dict(
        type_runs=(("blocks_0", "hybrid_ssm", 3, 1),),
        cache_kinds={"hybrid_ssm": (("kv_blocks", 256),
                                    ("state_slot", 8192 + 3 * 192 * 2))},
        pool_layers=3, class_layers={"full": 3}, kv_row=(32, 32),
        pool_heads=2, segment_tile=16, step_counts=())),
    # a slot of two arrays a KDA layer (the float32 state of 4 heads x 32 x
    # 32 and the window's 3 rows of [q | k | v]) and ONE pool layer of latent
    # rows (32 + 16, padded to 128 lanes) for the model's one latent layer
    "ling-3.0-flash": (delta_latent, dict(
        type_runs=(("blocks_0", "delta_attn", 1, 0),
                   ("blocks_1", "delta_attn", 2, 0),
                   ("blocks_2", "latent_attn", 1, 1)),
        cache_kinds={"delta_attn": (("state_slot", 16384 + 3 * 384 * 2),),
                     "latent_attn": (("kv_blocks", 256),)},
        pool_layers=1, class_layers={"full": 1}, kv_row=(32, 96),
        pool_heads=1, segment_tile=16,
        step_counts=("moe_rows", "moe_rows_max"))),
}


def test_a_slot_may_be_a_small_tree_of_arrays():
    """``init_state_cache`` builds what the record's ``slots`` gives, a slot
    a row index into every array of it; ``cache_kinds`` sums their bytes."""
    model = TransformerLM(hybrid())
    state = model.init_state_cache(5, 128, dtype=jnp.bfloat16)
    assert jax.tree.map(lambda a: (a.shape, a.dtype.name), state) == {
        "blocks_0": {"ssm": ((3, 6, 4, 16, 32), "float32"),
                     "conv": ((3, 6, 3, 192), "bfloat16")}}
    assert dict(model.config.cache_kinds["hybrid_ssm"])["state_slot"] \
        == 8192 + 3 * 192 * 2


def test_slots_and_a_latent_pool_in_one_model():
    """``ling-3.0-flash``'s pattern: the pool's row is the latent layer's
    (the first kind keeps no KV blocks), the slot arrays are the KDA groups'
    alone, and a latent layer without a low-rank query step has one ``wq``."""
    model = TransformerLM(delta_latent())
    cfg = model.config
    assert cfg.holds_state and not cfg.is_mla
    pool = jax.eval_shape(lambda: model.init_kv_pool(NUM_BLOCKS, BLOCK))
    assert pool.shape == (1, 1, NUM_BLOCKS, BLOCK, 128)
    state = model.init_state_cache(5, 128, dtype=jnp.bfloat16)
    assert jax.tree.map(lambda a: (a.shape, a.dtype.name), state) == {
        key: {"state": ((n, 6, 4, 32, 32), "float32"),
              "conv": ((n, 6, 3, 384), "bfloat16")}
        for key, n in (("blocks_0", 1), ("blocks_1", 2))}
    groups, _ = cfg.tree_shapes()
    latent_leaves = groups["blocks_2"][1]
    assert latent_leaves["wq"] == (128, 4 * 48) and "wq_a" not in latent_leaves
    assert latent_leaves["w_ogate"] == (128, 4) \
        == groups["blocks_1"][1]["w_ogate"]
    with pytest.raises(ValueError, match="latent_attn layer needs"):
        delta_latent(kv_lora_rank=0)
    with pytest.raises(ValueError, match="delta_attn layer needs"):
        delta_latent(ssm_conv=1)
    # a floor the blocked form's sub-tiles could not hold in float32
    with pytest.raises(ValueError, match="delta_attn layer needs"):
        delta_latent(kda_log_floor=-8.0)


@pytest.mark.parametrize("name", sorted(DECLARED))
def test_a_pattern_declares_what_it_did(name):
    make, declared = DECLARED[name]
    cfg = make()
    model = TransformerLM(cfg)
    found = {k: getattr(model if k in ("segment_tile", "step_counts")
                        else cfg, k) for k in declared}
    assert found == declared
    assert list(found["cache_kinds"]) == list(declared["cache_kinds"])
    assert cfg.bounded_cache == ("window" in declared["class_layers"])
    assert cfg.holds_state == any(
        kept == "state_slot" for kinds in declared["cache_kinds"].values()
        for kept, *_ in kinds)
