"""The latent-attention kernel at the ``serve-longdoc`` cell's shapes (32
one-token rows, 480 rows in segment tiles of 16, a pool of 3072 blocks of 64
rows of 640 lanes, tables 140 wide, 64 heads against rank 512 + rope 64),
compiled by the TPU compiler for a described v5e:2x2 with no chip attached,
and one layer of each kind through ``forward_paged`` as the serving program
calls it. In ``test_chip_compile_cells.py``'s manner."""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from benchmark.harness.cell import load_json

MODEL = load_json("configs", "gigachat3.1-702b-a36b.json")["model"]
ENGINE = load_json("traffic", "serve-longdoc.json")["engine"]
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


@pytest.mark.parametrize("rows,q_tile", [
    (ENGINE["max_seqs"], 1),
    (ENGINE["token_budget"] - ENGINE["max_seqs"], 16)])
def test_mla_decode_at_the_cells_pool(one_chip, no_compile_cache, as_tpu,
                                      rows, q_tile):
    from deepspeed_tpu.ops.transformer import paged_attention as pa

    heads, rank, rope = (MODEL["num_heads"], MODEL["kv_lora_rank"],
                         MODEL["qk_rope_head_dim"])
    pool = aval(one_chip, (MODEL["num_layers"], 1, ENGINE["num_blocks"],
                           ENGINE["block_size"],
                           sum(pa.latent_row(rank, rope))), jnp.bfloat16)

    def attend(q_lat, q_rope, pool, layer, tables, limits):
        return pa.mla_decode(q_lat, q_rope, pool, layer, tables, limits,
                             scale=0.14468, q_tile=q_tile)

    text = jax.jit(attend).lower(
        aval(one_chip, (rows, heads, rank), jnp.bfloat16),
        aval(one_chip, (rows, heads, rope), jnp.bfloat16), pool,
        aval(one_chip, (), jnp.int32),
        aval(one_chip, (rows // q_tile, TABLES), jnp.int32),
        aval(one_chip, (rows,), jnp.int32)).compile().as_text()
    assert "tpu_custom_call" in text
    assert ("mla_decode_segment" if q_tile > 1 else "mla_decode") in text


@pytest.mark.parametrize("rows", [ENGINE["max_seqs"], ENGINE["token_budget"]])
def test_both_kinds_of_layer_through_the_paged_program(
        one_chip, no_compile_cache, as_tpu, rows):
    """One dense and one expert layer at the published widths through
    ``forward_paged``: the decode round (32 one-token rows) and the mixed
    step (32 one-token rows, then 30 segment tiles). The kernels are in the
    program, the pool is updated in place, and no layer of experts is sliced
    out of the stack: no temporary is as large as one expert's matrices."""
    from deepspeed_tpu.models import TransformerLM
    from deepspeed_tpu.models.transformer import TransformerConfig

    model = TransformerLM(TransformerConfig(**{**MODEL, "num_layers": 2}))

    def on_chip(tree, dtype=None):
        return jax.tree.map(
            lambda a: aval(one_chip, a.shape, dtype or a.dtype), tree)

    params = on_chip(jax.eval_shape(model.init_params, jax.random.PRNGKey(0)),
                     jnp.bfloat16)
    pool = on_chip(jax.eval_shape(lambda: model.init_kv_pool(
        ENGINE["num_blocks"], ENGINE["block_size"], dtype=jnp.bfloat16)))
    seqs = ENGINE["max_seqs"]

    def program(params, ids, pool, tables, starts, logit_rows):
        return model.forward_paged(
            params, ids, pool, tables, starts, logit_rows=logit_rows,
            seg_from=seqs if rows > seqs else None, moe_stats=True)

    compiled = jax.jit(program, donate_argnums=(2,)).lower(
        params, aval(one_chip, (rows, 1), jnp.int32), pool,
        aval(one_chip, (rows, TABLES), jnp.int32),
        aval(one_chip, (rows,), jnp.int32),
        aval(one_chip, (seqs,), jnp.int32)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= (4 if rows > seqs else 2)
    assert "input_output_alias" in text
    one_expert = 3 * MODEL["hidden_size"] * MODEL["intermediate_size"] * 2
    assert compiled.memory_analysis().temp_size_in_bytes < max(
        one_expert, 3 * rows * 8 * MODEL["hidden_size"] * 2)
