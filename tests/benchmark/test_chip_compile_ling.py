"""The ``ling-3.0-flash.serve-reason`` cell's two programs at its real shapes
(7 layers at the published widths in three scanned groups: a dense KDA layer,
five KDA layers with 64 held experts, a latent layer with them; 128 one-token
rows, three segment tiles of 128 rows in a 512-row budget, a latent pool of
one layer, tables 96 wide, a slot of two arrays: 129 float32 states of 32 x
128 x 128 and 129 windows of 3 x 12288 a KDA layer), compiled by the TPU
compiler for a described v5e:2x2 with no chip attached: the decode round and
the mixed step through ``forward_paged`` as the serving program calls it, and
the recurrence's decode kernel alone in its delta form. In
``test_chip_compile_falcon.py``'s manner."""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from benchmark.harness.cell import load_json

MODEL = {**load_json("configs", "ling-3.0-flash.json")["model"],
         **load_json("traffic", "serve-reason.json")["model"]}
ENGINE = load_json("traffic", "serve-reason.json")["engine"]
TABLES = ENGINE["max_seq_len"] // ENGINE["block_size"]
HEADS, HEAD = MODEL["num_heads"], MODEL["head_dim_override"]
#: a slot of a KDA layer: the float32 state and three rows of [q | k | v]
SLOT_BYTES = HEADS * HEAD * HEAD * 4 + 3 * 3 * HEADS * HEAD * 2


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    from jax.experimental.compilation_cache import compilation_cache as cc

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    cc.reset_cache()


@pytest.fixture
def as_tpu(monkeypatch):
    monkeypatch.delenv("DSTPU_PALLAS_INTERPRET", raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def aval(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_the_delta_rules_kernel_at_the_cells_slots(one_chip, no_compile_cache,
                                                   as_tpu):
    """Mosaic takes the delta form: the channels' decays laid out as the keys
    are, ``beta`` over a head's lanes, the read-out before the write inside
    the buffer, in place on the slot array, under its own name."""
    from deepspeed_tpu.ops.transformer import linear_attention as la

    rows = ENGINE["max_seqs"]
    heads = aval(one_chip, (rows, HEADS, HEAD), jnp.float32)

    def call(state, layer, slots, q, k, v, fresh, decay, beta):
        return la.decode_rows(state, layer, slots, q, k, v, fresh,
                              log_decay=decay, scope="delta_scan", beta=beta)

    compiled = jax.jit(call, donate_argnums=(0,)).lower(
        aval(one_chip, (5, 1 + rows, HEADS, HEAD, HEAD), jnp.float32),
        aval(one_chip, (), jnp.int32), aval(one_chip, (rows,), jnp.int32),
        heads, heads, heads, aval(one_chip, (rows,), jnp.bool_), heads,
        aval(one_chip, (rows, HEADS), jnp.float32)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "delta_decode" in text
    assert "input_output_alias" in text
    # in place: no second slot array, no gathered copy of the rows' states
    assert compiled.memory_analysis().temp_size_in_bytes < 4 * 1024 * 1024


@pytest.mark.parametrize("rows", [ENGINE["max_seqs"], ENGINE["token_budget"]])
def test_the_cells_programs_through_the_paged_program(
        one_chip, no_compile_cache, as_tpu, rows):
    """All seven layers at the published widths through ``forward_paged``:
    the decode round (128 one-token rows: one ``delta_decode`` call a KDA
    layer body, one ``mla_decode`` call in the latent layer's) and the mixed
    step (128 one-token rows through both kernels, then three tiles of 128
    through the blocked delta rule and, 16 rows at a time, the latent
    kernel's segment form). The pool and the slot arrays are updated in
    place."""
    from deepspeed_tpu.models import TransformerLM
    from deepspeed_tpu.models.transformer import TransformerConfig

    model = TransformerLM(TransformerConfig(**MODEL))
    cfg = model.config
    seqs = ENGINE["max_seqs"]
    assert [(k, n, per) for _, k, n, per in cfg.type_runs] == [
        ("delta_attn", 1, 0), ("delta_attn", 5, 0), ("latent_attn", 1, 1)]
    assert cfg.cache_kinds == {
        "delta_attn": (("state_slot", SLOT_BYTES),),
        "latent_attn": (("kv_blocks", 2 * 640),)}

    def on_chip(tree, dtype=None):
        return jax.tree.map(
            lambda a: aval(one_chip, a.shape, dtype or a.dtype), tree)

    params = on_chip(jax.eval_shape(model.init_params, jax.random.PRNGKey(0)),
                     jnp.bfloat16)
    pool = on_chip(jax.eval_shape(lambda: model.init_kv_pool(
        ENGINE["num_blocks"], ENGINE["block_size"], dtype=jnp.bfloat16)))
    state = on_chip(jax.eval_shape(lambda: model.init_state_cache(
        seqs, ENGINE["max_seq_len"], dtype=jnp.bfloat16)))
    assert pool.shape == (1, 1, ENGINE["num_blocks"], 64, 640)
    assert jax.tree.map(lambda a: (a.shape, a.dtype.name), state) == {
        key: {"state": ((n, 129, 32, 128, 128), "float32"),
              "conv": ((n, 129, 3, 12288), "bfloat16")}
        for key, n in (("blocks_0", 1), ("blocks_1", 5))}

    def program(params, ids, pool, state, tables, starts, slots, logit_rows):
        return model.forward_paged(
            params, ids, pool, tables, starts, logit_rows=logit_rows,
            seg_from=seqs if rows > seqs else None, moe_stats=True,
            rows_apart=rows == seqs, state=state, row_slots=slots)

    compiled = jax.jit(program, donate_argnums=(2, 3)).lower(
        params, aval(one_chip, (rows, 1), jnp.int32), pool, state,
        aval(one_chip, (rows, TABLES), jnp.int32),
        aval(one_chip, (rows,), jnp.int32), aval(one_chip, (rows,), jnp.int32),
        aval(one_chip, (seqs,), jnp.int32)).compile()
    text = compiled.as_text()
    assert "delta_decode" in text and "mla_decode" in text
    assert ("mla_decode_segment" in text) == (rows > seqs)
    assert "input_output_alias" in text
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 6 * 129 * SLOT_BYTES + pool.size * 2
    # nothing as large as a layer's states
    assert mem.temp_size_in_bytes < 129 * HEADS * HEAD * HEAD * 4, \
        mem.temp_size_in_bytes
    print(rows, "temps", mem.temp_size_in_bytes, "args",
          mem.argument_size_in_bytes)
