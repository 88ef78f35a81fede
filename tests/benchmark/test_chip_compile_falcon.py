"""The ``falcon-h1-34b-instruct.serve-crowd`` cell's two programs at its real
shapes (9 layers at the published widths in one scanned group, 64 one-token
rows, three segment tiles of 128 rows in a 512-row budget, a pool of 9 layers
of 4 KV heads, tables 32 wide, a slot of two arrays: 65 float32 states of 32
x 256 x 128 and 65 windows of 3 x 5120 a layer), compiled by the TPU compiler
for a described v5e:2x2 with no chip attached: the decode round and the mixed
step through ``forward_paged`` as the serving program calls it, and the
recurrence's decode kernel alone with the decay as an operand, keys of 256
beside values of 128, sixteen heads a group. In ``test_chip_compile_sala.py``'s
manner."""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from benchmark.harness.cell import load_json

MODEL = {**load_json("configs", "falcon-h1-34b-instruct.json")["model"],
         **load_json("traffic", "serve-crowd.json")["model"]}
ENGINE = load_json("traffic", "serve-crowd.json")["engine"]
TABLES = ENGINE["max_seq_len"] // ENGINE["block_size"]
HEADS, GROUPS, STATE, HEAD = (MODEL["ssm_heads"], MODEL["ssm_groups"],
                              MODEL["ssm_state"], MODEL["ssm_head_dim"])


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


def test_the_recurrences_kernel_at_the_cells_slots(one_chip, no_compile_cache,
                                                   as_tpu):
    """Mosaic takes the state block of 256 x 128 a head (two pieces of its
    key axis), a group's keys and queries and the decay's block, in place on
    the slot array."""
    from deepspeed_tpu.ops.transformer import linear_attention as la

    rows = ENGINE["max_seqs"]
    keys = aval(one_chip, (rows, GROUPS, STATE), jnp.bfloat16)

    def call(state, layer, slots, q, k, v, fresh, decay):
        return la.decode_rows(state, layer, slots, q, k, v, fresh,
                              log_decay=decay, scope="ssm_scan")

    compiled = jax.jit(call, donate_argnums=(0,)).lower(
        aval(one_chip, (9, 1 + rows, HEADS, STATE, HEAD), jnp.float32),
        aval(one_chip, (), jnp.int32), aval(one_chip, (rows,), jnp.int32),
        keys, keys, aval(one_chip, (rows, HEADS, HEAD), jnp.float32),
        aval(one_chip, (rows,), jnp.bool_),
        aval(one_chip, (rows, HEADS), jnp.float32)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "linear_decode" in text
    assert "input_output_alias" in text
    # in place: no second slot array, no gathered copy of the rows' states
    assert compiled.memory_analysis().temp_size_in_bytes < 4 * 1024 * 1024


@pytest.mark.parametrize("rows", [ENGINE["max_seqs"], ENGINE["token_budget"]])
def test_the_cells_programs_through_the_paged_program(
        one_chip, no_compile_cache, as_tpu, rows):
    """All nine layers at the published widths through ``forward_paged``: the
    decode round (64 one-token rows: one ``paged_decode`` call that writes
    its rows on the way and one ``linear_decode`` call a layer body) and the
    mixed step (64 one-token rows through both kernels, then three tiles of
    128 through the gather path and the chunk form, the rows written by the
    scatter). The pool and both slot arrays are updated in place."""
    from deepspeed_tpu.models import TransformerLM
    from deepspeed_tpu.models.transformer import TransformerConfig

    model = TransformerLM(TransformerConfig(**MODEL))
    cfg = model.config
    seqs = ENGINE["max_seqs"]
    assert cfg.num_parameters == 4_205_319_008
    assert cfg.cache_kinds == {"hybrid_ssm": (
        ("kv_blocks", 2 * 4 * 256),
        ("state_slot", 32 * 256 * 128 * 4 + 3 * 5120 * 2))}

    def on_chip(tree, dtype=None):
        return jax.tree.map(
            lambda a: aval(one_chip, a.shape, dtype or a.dtype), tree)

    params = on_chip(jax.eval_shape(model.init_params, jax.random.PRNGKey(0)),
                     jnp.bfloat16)
    pool = on_chip(jax.eval_shape(lambda: model.init_kv_pool(
        ENGINE["num_blocks"], ENGINE["block_size"], dtype=jnp.bfloat16)))
    state = on_chip(jax.eval_shape(lambda: model.init_state_cache(
        seqs, ENGINE["max_seq_len"], dtype=jnp.bfloat16)))
    assert pool.shape == (9, 4, ENGINE["num_blocks"], 64, 256)
    assert jax.tree.map(lambda a: (a.shape, a.dtype.name), state) == {
        "blocks_0": {"ssm": ((9, 65, 32, 256, 128), "float32"),
                     "conv": ((9, 65, 3, 5120), "bfloat16")}}

    def program(params, ids, pool, state, tables, starts, slots, logit_rows):
        return model.forward_paged(
            params, ids, pool, tables, starts, logit_rows=logit_rows,
            seg_from=seqs if rows > seqs else None,
            rows_apart=rows == seqs, state=state, row_slots=slots)

    compiled = jax.jit(program, donate_argnums=(2, 3)).lower(
        params, aval(one_chip, (rows, 1), jnp.int32), pool, state,
        aval(one_chip, (rows, TABLES), jnp.int32),
        aval(one_chip, (rows,), jnp.int32), aval(one_chip, (rows,), jnp.int32),
        aval(one_chip, (seqs,), jnp.int32)).compile()
    text = compiled.as_text()
    assert "paged_decode" in text and "linear_decode" in text
    # the one scanned group's body holds one call of each kernel
    assert text.count("tpu_custom_call") == 2
    assert "input_output_alias" in text
    mem = compiled.memory_analysis()
    slots = 9 * 65 * (32 * 256 * 128 * 4 + 3 * 5120 * 2)
    assert mem.alias_size_in_bytes >= slots + pool.size * 2
    # nothing as large as a layer's states: the round's temporaries are a
    # few rows' worth, the mixed step's a tile's gathered context and scores
    assert mem.temp_size_in_bytes < 65 * 32 * 256 * 128 * 4, \
        mem.temp_size_in_bytes
    print(rows, "temps", mem.temp_size_in_bytes)
