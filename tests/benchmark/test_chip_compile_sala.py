"""The ``minicpm-sala.serve-doc16k`` cell's two programs at its real shapes
(8 layers at the published widths, 48 one-token rows, 15 segment tiles of 128
rows in a 2048-row budget, a pool of 2 sparse layers x 13568 blocks of 64,
tables 280 wide, slot arrays of 49 slots), compiled by the TPU compiler for a
described v5e:2x2 with no chip attached: the decode round and the mixed step
through ``forward_paged`` as the serving program calls it, and the lightning
decode kernel alone. In ``test_chip_compile_longcat.py``'s manner."""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from benchmark.harness.cell import load_json

MODEL = load_json("configs", "minicpm-sala.json")["model"]
ENGINE = load_json("traffic", "serve-doc16k.json")["engine"]
TABLES = ENGINE["max_seq_len"] // ENGINE["block_size"]


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


def test_linear_decode_at_the_cells_slots(one_chip, no_compile_cache, as_tpu):
    from deepspeed_tpu.ops.transformer import linear_attention as la

    rows, heads, hd = ENGINE["max_seqs"], MODEL["num_heads"], 128
    state = aval(one_chip, (6, 1 + rows, heads, hd, hd), jnp.float32)
    act = aval(one_chip, (rows, heads, hd), jnp.bfloat16)
    compiled = jax.jit(la.linear_decode, donate_argnums=(0,)).lower(
        state, aval(one_chip, (), jnp.int32), aval(one_chip, (rows,), jnp.int32),
        act, act, act, aval(one_chip, (rows,), jnp.bool_)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "linear_decode" in text
    assert "input_output_alias" in text
    # in place: no second slot array, no gathered copy of the rows' states
    assert compiled.memory_analysis().temp_size_in_bytes < 2 * 1024 * 1024


@pytest.mark.parametrize("rows", [ENGINE["max_seqs"], ENGINE["token_budget"]])
def test_the_cells_programs_through_the_paged_program(
        one_chip, no_compile_cache, as_tpu, rows):
    """All eight layers (three scanned groups) at the published widths through
    ``forward_paged``: the decode round (48 one-token rows: the KV write, the
    selector, ``paged_decode`` over the compacted tables of both sparse
    layers, ``linear_decode`` on the slot array) and the mixed step (48
    one-token rows, then 15 tiles of 128). The pool and the slot arrays are
    updated in place: no temporary is as large as the lightning slot array or
    the pool, and the decode round's are smaller than one compressed-key
    cache."""
    from deepspeed_tpu.models import TransformerLM
    from deepspeed_tpu.models.transformer import TransformerConfig

    model = TransformerLM(TransformerConfig(**MODEL))
    seqs = ENGINE["max_seqs"]

    def on_chip(tree, dtype=None):
        return jax.tree.map(
            lambda a: aval(one_chip, a.shape, dtype or a.dtype), tree)

    params = on_chip(jax.eval_shape(model.init_params, jax.random.PRNGKey(0)),
                     jnp.bfloat16)
    pool = on_chip(jax.eval_shape(lambda: model.init_kv_pool(
        ENGINE["num_blocks"], ENGINE["block_size"], dtype=jnp.bfloat16)))
    state = on_chip(jax.eval_shape(lambda: model.init_state_cache(
        seqs, ENGINE["max_seq_len"], dtype=jnp.bfloat16)))
    assert pool.shape == (2, 2, ENGINE["num_blocks"], 64, 256)
    assert {k: v.shape for k, v in state.items()} == {
        "blocks_0": (1, 49, 1120, 256), "blocks_1": (6, 49, 32, 128, 128),
        "blocks_2": (1, 49, 1120, 256)}

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
    assert "paged_decode" in text and "linear_decode" in text
    # a decode round: kv_write and paged_decode of each sparse group and the
    # linear group's linear_decode; a mixed step writes its rows by scatter
    assert text.count("tpu_custom_call") == (5 if rows == seqs else 3)
    assert "input_output_alias" in text
    mem = compiled.memory_analysis()
    lin = 6 * 49 * 32 * 128 * 128 * 4
    keys = 49 * 1120 * 256 * 2
    assert mem.alias_size_in_bytes >= lin + 2 * keys + 2 * 2 * ENGINE[
        "num_blocks"] * 64 * 256 * 2
    assert mem.temp_size_in_bytes < (keys if rows == seqs else lin), \
        mem.temp_size_in_bytes
