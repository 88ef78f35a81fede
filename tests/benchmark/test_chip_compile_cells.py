"""The kernels of the benchmark's cells at real widths that
``tests/unit/test_chip_compile.py`` does not cover, compiled by the TPU
compiler for a described v5e:2x2 with no chip attached (on-chip-measurement
guide, section 2): flash attention at Pythia-1.4B's (B, 2048, 16, 128), forward
and backward, and paged decode at the serving cells' pool (832 blocks of 64,
head 64, tables 16 wide) at both row counts of the serving program (64, 256).

The topology is described inside a module fixture, never at import. The
driver's test command lets several processes load libtpu; where that is not
so and another file's worker holds it, the fixture skips these tests.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from benchmark.harness.cell import load_json

PYTHIA = load_json("configs", "pythia-1.4b.json")
ENGINE = load_json("traffic", "serve-chat.json")["engine"]
GPT2 = load_json("configs", "gpt2-medium.json")


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
    """A compile for a described device cannot be read back from the
    persistent cache without a chip; keep these out of it."""
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


@pytest.mark.parametrize("pass_", ["fwd", "fwd+bwd"])
def test_flash_attention_at_pythia_widths(one_chip, no_compile_cache, as_tpu,
                                          pass_):
    from deepspeed_tpu.ops.transformer.flash_attention import flash_attention

    heads = PYTHIA["num_attention_heads"]
    q = aval(one_chip, (2, PYTHIA["max_position_embeddings"], heads,
                        PYTHIA["hidden_size"] // heads), jnp.bfloat16)

    def attn(q, k, v):
        return flash_attention(q, k, v, causal=True)

    def loss(q, k, v):
        return jnp.sum(attn(q, k, v).astype(jnp.float32) ** 2)

    fn = attn if pass_ == "fwd" else jax.grad(loss, argnums=(0, 1, 2))
    text = jax.jit(fn).lower(q, q, q).compile().as_text()
    assert text.count("tpu_custom_call") >= (1 if pass_ == "fwd" else 2)


@pytest.mark.parametrize("rows", [ENGINE["max_seqs"], ENGINE["token_budget"]])
def test_paged_decode_at_the_cells_pool(one_chip, no_compile_cache, as_tpu,
                                        rows):
    from deepspeed_tpu.ops.transformer.paged_attention import \
        paged_decode_attention

    heads = GPT2["n_head"]
    pool = aval(one_chip, (heads, ENGINE["num_blocks"], ENGINE["block_size"],
                           GPT2["n_embd"] // heads), jnp.bfloat16)
    tables = ENGINE["max_seq_len"] // ENGINE["block_size"]
    text = jax.jit(paged_decode_attention).lower(
        aval(one_chip, (rows, heads, GPT2["n_embd"] // heads), jnp.bfloat16),
        pool, pool, aval(one_chip, (rows, tables), jnp.int32),
        aval(one_chip, (rows,), jnp.int32)).compile().as_text()
    assert "tpu_custom_call" in text
