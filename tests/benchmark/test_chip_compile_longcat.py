"""The ``longcat-flash-chat.serve-longout`` cell's shapes (96 one-token rows,
416 rows in segment tiles of 16, a pool of 8 attention layers x 2560 blocks of
64 rows of 640 lanes, tables 48 wide, 64 heads against rank 512 + rope 64),
compiled by the TPU compiler for a described v5e:2x2 with no chip attached:
the latent-attention kernel over the deeper pool, and one double layer through
``forward_paged`` as the serving program calls it. In
``test_chip_compile_latent.py``'s manner."""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from benchmark.harness.cell import load_json

MODEL = load_json("configs", "longcat-flash-chat.json")["model"]
ENGINE = load_json("traffic", "serve-longout.json")["engine"]
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
    from deepspeed_tpu.models.transformer import (TransformerConfig,
                                                  mla_softmax_scale)
    from deepspeed_tpu.ops.transformer import paged_attention as pa

    cfg = TransformerConfig(**MODEL)
    heads, rank, rope = cfg.num_heads, cfg.kv_lora_rank, cfg.qk_rope_head_dim
    assert cfg.pool_layers == 8 and rows % q_tile == 0
    pool = aval(one_chip, (cfg.pool_layers, 1, ENGINE["num_blocks"],
                           ENGINE["block_size"],
                           sum(pa.latent_row(rank, rope))), jnp.bfloat16)

    def attend(q_lat, q_rope, pool, layer, tables, limits):
        return pa.mla_decode(q_lat, q_rope, pool, layer, tables, limits,
                             scale=mla_softmax_scale(cfg), q_tile=q_tile)

    text = jax.jit(attend).lower(
        aval(one_chip, (rows, heads, rank), jnp.bfloat16),
        aval(one_chip, (rows, heads, rope), jnp.bfloat16), pool,
        aval(one_chip, (), jnp.int32),
        aval(one_chip, (rows // q_tile, TABLES), jnp.int32),
        aval(one_chip, (rows,), jnp.int32)).compile().as_text()
    assert "tpu_custom_call" in text
    assert ("mla_decode_segment" if q_tile > 1 else "mla_decode") in text


@pytest.mark.parametrize("rows", [ENGINE["max_seqs"], ENGINE["token_budget"]])
def test_a_double_layer_through_the_paged_program(
        one_chip, no_compile_cache, as_tpu, rows):
    """Two double layers (a scan that slices its layers' leaves) at the
    published widths through ``forward_paged``: the decode round (96
    one-token rows) and the mixed step (96 one-token rows, then 26 segment
    tiles). Both attentions' kernels are in the program, the pool (four
    layers) is updated in place, and neither a layer of experts nor a
    sublayer's matrices are copied out of the stack: beside the expert
    layer's own row buffers (the landed rows in and out, and the float32
    gather of every token's ``moe_top_k`` rows) no temporary is as large as
    one expert's matrices (a sublayer's ``wo`` is 1.3 times that, a dense
    feed-forward matrix twice)."""
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
    assert pool.shape[0] == 4
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
    # a scanned layer's two attentions: one kernel each a decode round, two
    # (one-token rows, segment tiles) a mixed step
    assert text.count("tpu_custom_call") >= (4 if rows > seqs else 2)
    assert "input_output_alias" in text
    hidden, k, held = (MODEL["hidden_size"], MODEL["moe_top_k"],
                       MODEL["num_experts"])
    one_expert = 3 * hidden * MODEL["intermediate_size"] * 2
    row_buffer = (rows * min(k, held) + held * 128) * hidden * 2
    gathered = rows * k * hidden * 4
    temps = compiled.memory_analysis().temp_size_in_bytes
    assert temps < one_expert + (2 * row_buffer + gathered
                                 if rows > seqs else 0), temps
