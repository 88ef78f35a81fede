"""The ``trinity-mini.serve-win16k`` cell's two programs at its real shapes
(13 layers at the published widths in seven scanned groups, 32 one-token rows,
three segment tiles of 128 rows in a 512-row budget, a pool a class of blocks:
3 full layers and 10 window layers, tables 274 and 42 wide), compiled by the
TPU compiler for a described v5e:2x2 with no chip attached: the decode round
and the mixed step through ``forward_paged`` as the serving program calls it.
In ``test_chip_compile_sala.py``'s manner."""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from benchmark.harness.cell import load_json

MODEL = {**load_json("configs", "trinity-mini.json")["model"],
         **load_json("traffic", "serve-win16k.json")["model"]}
ENGINE = load_json("traffic", "serve-win16k.json")["engine"]
TABLES = ENGINE["max_seq_len"] // ENGINE["block_size"]
WINDOW_TABLES = 42


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


def test_the_engine_sizes_the_window_table_as_the_cell_says():
    """42 entries: the window, a step's chunk and two provisional tokens in
    whole blocks, both ends partial (``InferenceEngineV2``'s rule, computed
    here from the traffic file as the engine computes it)."""
    tile, seqs = MODEL["linear_chunk"], ENGINE["max_seqs"]
    chunk = min(ENGINE["prefill_chunk"],
                (ENGINE["token_budget"] - seqs) // tile * tile)
    assert chunk == 384
    assert (MODEL["sliding_window"] + 1 + chunk) // ENGINE["block_size"] + 2 \
        <= WINDOW_TABLES
    assert (MODEL["sliding_window"] + 1 + ENGINE["prefill_chunk"]) \
        // ENGINE["block_size"] + 2 == WINDOW_TABLES


@pytest.mark.parametrize("rows", [ENGINE["max_seqs"], ENGINE["token_budget"]])
def test_the_cells_programs_through_the_paged_program(
        one_chip, no_compile_cache, as_tpu, rows):
    """All 13 layers at the published widths through ``forward_paged``: the
    decode round (32 one-token rows: one bounded ``paged_decode`` call a
    window layer and one unbounded a full layer, each writing its rows on the
    way, the held experts as a loop over the touched ones) and the mixed step
    (32 one-token rows through the kernel, then three tiles of 128 through
    the gather path, the rows written by the scatter). Both pools are updated
    in place."""
    from deepspeed_tpu.models import TransformerLM
    from deepspeed_tpu.models.transformer import TransformerConfig

    model = TransformerLM(TransformerConfig(**MODEL))
    cfg = model.config
    seqs = ENGINE["max_seqs"]
    assert cfg.class_layers == {"full": 3, "window": 10}
    assert cfg.sliding_window == 2048
    assert [n for _, _, n, _ in cfg.type_runs] == [1, 3, 1, 3, 1, 3, 1]

    def on_chip(tree, dtype=None):
        return jax.tree.map(
            lambda a: aval(one_chip, a.shape, dtype or a.dtype), tree)

    params = on_chip(jax.eval_shape(model.init_params, jax.random.PRNGKey(0)),
                     jnp.bfloat16)
    pool = on_chip(jax.eval_shape(lambda: model.init_kv_pool(
        ENGINE["num_blocks"], ENGINE["block_size"], dtype=jnp.bfloat16)))
    assert {k: v.shape for k, v in pool.items()} == {
        "full": (3, 4, ENGINE["num_blocks"]["full"], 64, 256),
        "window": (10, 4, ENGINE["num_blocks"]["window"], 64, 256)}

    def program(params, ids, pool, tables, starts, wtables, wbase, logit_rows):
        return model.forward_paged(
            params, ids, pool, tables, starts, logit_rows=logit_rows,
            seg_from=seqs if rows > seqs else None, moe_stats=True,
            rows_apart=rows == seqs, window=(wtables, wbase))

    compiled = jax.jit(program, donate_argnums=(2,)).lower(
        params, aval(one_chip, (rows, 1), jnp.int32), pool,
        aval(one_chip, (rows, TABLES), jnp.int32),
        aval(one_chip, (rows,), jnp.int32),
        aval(one_chip, (rows, WINDOW_TABLES), jnp.int32),
        aval(one_chip, (rows,), jnp.int32),
        aval(one_chip, (seqs,), jnp.int32)).compile()
    text = compiled.as_text()
    assert "paged_decode" in text
    # one kernel call a layer group's body (seven scanned groups), in the
    # round and, for the one-token rows, in the mixed step
    assert text.count("tpu_custom_call") == 7
    assert "input_output_alias" in text
    mem = compiled.memory_analysis()
    pools = sum(v.size * 2 for v in pool.values())
    assert mem.alias_size_in_bytes >= pools
    # the round's temporaries are small beside the pools; the mixed step's
    # hold a full layer's gathered context and a tile's scores
    assert mem.temp_size_in_bytes < (pools // 8 if rows == seqs else 2 ** 31), \
        mem.temp_size_in_bytes
