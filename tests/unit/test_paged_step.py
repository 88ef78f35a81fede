"""The seam of the paged forward: one layer pattern, one step, one loop.

(a) ``TransformerConfig.type_runs`` is the one statement of a model's layer
pattern: the tree ``init_params`` builds, the pool's layer axis, the slot
arrays and ``cache_kinds`` all agree with it, for each kind of layer.
(b) ``forward_paged`` builds its ``PagedStep`` once: ``paged_limits`` is
called once a trace, whatever the kind and however many layers.
(c) The ragged serving programs of the latent, double-layer and typed
configurations at the benchmark's rehearsal sizes are the programs they were
before the three paged entries became one loop: their ``fingerprint`` digests
(``analysis/program_audit``) as recorded at the parent commit.
"""

import jax
import jax.numpy as jnp
import pytest

from deepspeed_tpu.models import transformer
from deepspeed_tpu.models.transformer import TransformerLM
from tests.unit.test_served_weight_reads import (double_layers, gpt2_family,
                                                 latent, step, typed)

#: config -> its groups as (key, kind, layers, pool layers a layer)
KINDS = {
    "gpt2": (gpt2_family, (("blocks", "full", 2, 1),)),
    "deepseek_v3": (latent, (("dense_blocks", "latent", 1, 1),
                             ("blocks", "latent", 2, 1))),
    "longcat": (double_layers, (("blocks", "scmoe", 2, 2),)),
    "minicpm_sala": (typed, (("blocks_0", "sparse_attn", 1, 1),
                             ("blocks_1", "linear_attn", 2, 0),
                             ("blocks_2", "sparse_attn", 1, 1))),
}


@pytest.mark.parametrize("name", sorted(KINDS))
def test_the_layer_pattern_is_said_once(name):
    make, groups = KINDS[name]
    cfg = make()
    model = TransformerLM(cfg)
    assert cfg.type_runs == groups
    assert sum(n for _, _, n, _ in groups) == cfg.num_layers
    # the tree: the groups in forward order, each leaf stacked by layer
    params = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    stacked = {k: v for k, v in params.items() if isinstance(v, dict)}
    assert sorted(stacked) == sorted(key for key, *_ in groups)
    for key, _, n, _ in groups:
        assert {leaf.shape[0] for leaf in stacked[key].values()} == {n}
    # the pool's layer axis
    assert cfg.pool_layers == sum(n * per for _, _, n, per in groups)
    pool = jax.eval_shape(lambda: model.init_kv_pool(4, 16))
    assert pool.shape[0] == cfg.pool_layers
    # what each kind keeps: KV blocks where it has pool layers, and a slot
    # array a group where it keeps a state slot
    kept = cfg.cache_kinds
    slots = jax.eval_shape(lambda: model.init_state_cache(3, cfg.max_seq_len))
    for key, kind, n, per in groups:
        caches = dict(kept[kind if kind in kept else "attn"])
        assert ("kv_blocks" in caches) == bool(per)
        assert ("state_slot" in caches) == (key in slots)
        if key in slots:
            assert slots[key].shape[:2] == (n, 1 + 3)
    assert cfg.holds_state == bool(slots)


@pytest.mark.parametrize("kernel", [True, False], ids=["kernel", "gather"])
@pytest.mark.parametrize("name", sorted(KINDS))
def test_a_step_computes_its_limits_once(monkeypatch, name, kernel):
    """One trace of a mixed step: ``paged_limits`` runs once, in front of
    the layer loop, and every layer reads the step's."""
    if kernel:
        monkeypatch.setenv("DSTPU_FORCE_PAGED_KERNEL", "1")
    else:
        monkeypatch.delenv("DSTPU_FORCE_PAGED_KERNEL", raising=False)
    model = TransformerLM(KINDS[name][0]())
    args, kw = step(model, tile_rows=model.segment_tile
                    if model.segment_tile > 1 else 0)
    calls = []
    real = transformer.paged_limits

    def counted(tables, positions):
        calls.append(positions.shape)
        return real(tables, positions)

    monkeypatch.setattr(transformer, "paged_limits", counted)
    out = jax.eval_shape(lambda *a: model.forward_paged(*a, **kw), *args)
    assert calls == [(args[1].shape[0],)]
    assert out[0].shape == (args[1].shape[0], model.config.vocab_size)


#: (configuration, traffic) -> {rows of the ragged program: the digest of
#: ``fingerprint`` at commit bb35a42 (PR 48), CPU trace, greedy}. The two
#: expert models' are PR 52's: a step of no more rows than one expert tile
#: (both rehearsal shapes) holds ``grouped_experts``' one-tile form, and the
#: auditor's drift line for each reads "new op(s) ['custom_vjp_call',
#: 'while']; dropped op(s) ['lt_to']" (the sampler keeps a sort and a scatter
#: of its own in the coarse op set). ``longcat-flash-chat``'s are PR 72's:
#: the softmax router's picks are rounds of ``argmax`` (``_first_k``) and
#: the drift line reads "dropped op(s) ['top_k']"; ``gigachat3.1``'s group
#: choice lost its two ``top_k``s in the same PR and keeps the picks' own
PARENT_DIGESTS = {
    ("gigachat3.1-702b-a36b", "serve-longdoc"): {
        4: "dec7ad3207f54d29", 36: "dec7ad3207f54d29"},
    ("longcat-flash-chat", "serve-longout"): {
        4: "e06298792437a06e", 36: "e06298792437a06e"},
    ("minicpm-sala", "serve-doc16k"): {
        4: "c40041c09888b92c", 36: "7f53eb0b12012808"},
}
#: a mixed step of more rows than one expert tile (a token budget of 164) is
#: still PR 48's program (``longcat-flash-chat``'s less its ``top_k``, PR 72)
LARGER_STEP = (164, {"gigachat3.1-702b-a36b": "e23956cf2c4bc759",
                     "longcat-flash-chat": "acb4cd3de02b5390"})


def ragged_digests(cell, **engine_kw):
    """{rows: ``fingerprint`` digest} of both ragged programs of the cell's
    rehearsal-size engine."""
    from benchmark.harness.cell import load_json
    from deepspeed_tpu.analysis.program_audit import fingerprint
    from deepspeed_tpu.inference.v2 import InferenceEngineV2
    from deepspeed_tpu.models.transformer import TransformerConfig

    config, traffic = cell
    cfg = load_json("configs", config + ".json")
    mix = load_json("traffic", traffic + ".json")
    model = TransformerLM(TransformerConfig(**{
        **cfg["model"], **mix.get("model", {}), **cfg["rehearsal"]["model"],
        **mix["rehearsal"].get("model", {})}))
    engine = InferenceEngineV2(
        model, model.init_params(jax.random.PRNGKey(0)), paged=True,
        dtype=jnp.float32, **{**mix["rehearsal"]["engine"], **engine_kw})
    fn = engine._get_ragged()

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32)

    found = {}
    for rows in (engine.max_seqs, engine.token_budget):
        closed = jax.make_jaxpr(fn._fun, static_argnums=(5,))(
            engine.params, engine.kv, i32(engine._feed_layout(rows)[1]),
            i32(*engine._prev_shape()), engine._bias(), True,
            *((engine.slot_cache,) if engine._stateful else ()))
        found[rows] = fingerprint(closed, fn._donate)["digest"]
    return found


@pytest.mark.parametrize("cell", sorted(PARENT_DIGESTS), ids=lambda c: c[0])
def test_the_ragged_programs_are_the_parents(cell):
    assert ragged_digests(cell) == PARENT_DIGESTS[cell]


@pytest.mark.parametrize("cell", sorted(PARENT_DIGESTS)[:2], ids=lambda c: c[0])
def test_a_step_of_more_than_one_expert_tile_is_the_parents(cell):
    rows, digests = LARGER_STEP
    assert ragged_digests(cell, token_budget=rows)[rows] == digests[cell[0]]
