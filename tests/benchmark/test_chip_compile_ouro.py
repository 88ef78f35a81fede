"""The ``ouro-2.6b.serve-qa`` cell's two programs at its real shapes (48
layers at the published widths in one scanned group walked four times, 16
one-token rows, a chunk of 256 in a 272-row budget, a pool of 192 cache layers
of 16 KV heads, tables 20 wide), compiled by the TPU compiler for a described
v5e:2x2 with no chip attached: the decode round and the mixed step through
``forward_paged`` as the serving program calls it. In
``test_chip_compile_falcon.py``'s manner.

The walk is four scans one behind the other, not a loop around one: the pool
(8 GB) and the stacked matrices (5 GB) ride no outer carry. What that buys is
counted here, by shape: the compiled programs hold NO copy, gather or scatter
of an array of the pool's shape or of a stacked matrix's."""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from benchmark.harness.cell import load_json

MODEL = {**load_json("configs", "ouro-2.6b.json")["model"],
         **load_json("traffic", "serve-qa.json")["model"]}
ENGINE = load_json("traffic", "serve-qa.json")["engine"]
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


@pytest.mark.parametrize("rows", [ENGINE["max_seqs"], ENGINE["token_budget"]])
def test_the_cells_programs_hold_no_copy_of_the_pool_or_a_stack(
        one_chip, no_compile_cache, as_tpu, rows):
    """All 48 layers, four times, through ``forward_paged``: the decode round
    (16 one-token rows: one ``paged_decode`` call a layer body that writes
    its rows on the way) and the mixed step (272 one-token rows through the
    kernel, written by the scatter). One kernel call a walk; the pool updated
    in place; no mover of a pool-shaped or stacked-matrix-shaped array."""
    from deepspeed_tpu.models import TransformerLM
    from deepspeed_tpu.models.transformer import TransformerConfig
    from tests.unit.test_chip_compile import window_movers

    model = TransformerLM(TransformerConfig(**MODEL))
    cfg = model.config
    seqs = ENGINE["max_seqs"]

    def on_chip(tree, dtype=None):
        return jax.tree.map(
            lambda a: aval(one_chip, a.shape, dtype or a.dtype), tree)

    params = on_chip(jax.eval_shape(model.init_params, jax.random.PRNGKey(0)),
                     jnp.bfloat16)
    pool = on_chip(jax.eval_shape(lambda: model.init_kv_pool(
        ENGINE["num_blocks"], ENGINE["block_size"], dtype=jnp.bfloat16)))
    assert pool.shape == (192, 16, ENGINE["num_blocks"], 64, 256)

    def program(params, ids, pool, tables, starts, logit_rows):
        return model.forward_paged(
            params, ids, pool, tables, starts, logit_rows=logit_rows,
            rows_apart=rows == seqs)

    compiled = jax.jit(program, donate_argnums=(2,)).lower(
        params, aval(one_chip, (rows, 1), jnp.int32), pool,
        aval(one_chip, (rows, TABLES), jnp.int32),
        aval(one_chip, (rows,), jnp.int32),
        aval(one_chip, (seqs,), jnp.int32)).compile()
    text = compiled.as_text()
    assert "paged_decode" in text
    # a walk is a scan of its own: its body holds the one kernel call
    assert text.count("tpu_custom_call") == cfg.loop_steps == 4
    assert "input_output_alias" in text
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= pool.size * 2
    # nothing as large as one layer's matrices beside the arguments
    assert mem.temp_size_in_bytes < 2048 * 5632 * 2, mem.temp_size_in_bytes
    stacks = [a.shape for a in jax.tree.leaves(params["blocks"])
              if len(a.shape) == 3]
    assert len(stacks) == 7
    assert window_movers(text, [pool.shape] + stacks) == []
