"""The main path's kernels, compiled for the real chip at GPT-2 350M widths —
no chip attached.

``test_tpu_lowering.py`` exports StableHLO, which stops before the Mosaic
compiler's checks of fast memory and tiling. Here the TPU compiler that ships
with libtpu compiles each kernel for a *described* v5e (on-chip-measurement
guide, section 2), both called directly and reached through the dispatch the
model uses (``attention()``, the paged branch of ``forward_paged``), with the
``jax.default_backend()`` gate steered inside the test. Shapes are the ones
``chip_smoke.py`` runs: flash attention at (8, 1024, 16, 64) bf16; paged
decode at its server's pool (129 blocks of 64) and block tables (16 wide), at
both row counts of the serving program (8 and 256).

All of it lives in this one file, and the topology is described inside a
fixture: only the worker that runs this file loads libtpu, and every worker
collects the same tests.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

B, S, NH, HD = 8, 1024, 16, 64          # the trainer's attention shape
NB, BS, MAXB = 129, 64, 16              # the server's pool and block tables
ROWS = (8, 256)                         # decode round, mixed prefill+decode


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described device is written to the persistent cache
    but cannot be read back without a chip; keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    cc.reset_cache()


@pytest.fixture
def as_tpu(monkeypatch):
    """Steer the code's backend gates (``pallas_interpret``, ``_auto_impl``,
    the paged branch) the way the chip would: they ask
    ``jax.default_backend()``, which here still answers "cpu"."""
    monkeypatch.delenv("DSTPU_PALLAS_INTERPRET", raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def compile_text(fn, *avals):
    return jax.jit(fn).lower(*avals).compile().as_text()


def aval(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def sq_loss(attn):
    def loss(q, k, v):
        return jnp.sum(attn(q, k, v).astype(jnp.float32) ** 2)
    return loss


@pytest.mark.parametrize("via", ["direct", "attention()"])
@pytest.mark.parametrize("pass_", ["fwd", "fwd+bwd"])
def test_flash_attention_compiles(one_chip, no_compile_cache, as_tpu, via,
                                  pass_):
    from deepspeed_tpu.ops.transformer.attention import attention
    from deepspeed_tpu.ops.transformer.flash_attention import flash_attention

    def attn(q, k, v):
        if via == "direct":
            return flash_attention(q, k, v, causal=True)
        return attention(q, k, v, causal=True)

    fn = attn if pass_ == "fwd" else jax.grad(sq_loss(attn), argnums=(0, 1, 2))
    q = aval(one_chip, (B, S, NH, HD), jnp.bfloat16)
    text = compile_text(fn, q, q, q)
    # forward is one kernel, the fused backward another
    assert text.count("tpu_custom_call") >= (1 if pass_ == "fwd" else 2), \
        "flash attention gave way to the XLA path"


@pytest.mark.parametrize("rows", ROWS)
def test_paged_decode_compiles(one_chip, no_compile_cache, as_tpu, rows):
    from deepspeed_tpu.ops.transformer.paged_attention import \
        paged_decode_attention

    pool = aval(one_chip, (NH, NB, BS, HD), jnp.bfloat16)
    text = compile_text(
        paged_decode_attention,
        aval(one_chip, (rows, NH, HD), jnp.bfloat16), pool, pool,
        aval(one_chip, (rows, MAXB), jnp.int32),
        aval(one_chip, (rows,), jnp.int32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("rows", ROWS)
def test_paged_branch_of_the_model_compiles(one_chip, no_compile_cache, as_tpu,
                                            rows):
    """One layer of GPT-2 350M through ``forward_paged``, as the serving
    program calls it: every row one query token against the block pool."""
    from deepspeed_tpu.models import TransformerLM, gpt2_config

    model = TransformerLM(gpt2_config("350m", num_layers=1))

    def on_chip(tree, dtype=None):
        return jax.tree.map(
            lambda a: aval(one_chip, a.shape, dtype or a.dtype), tree)

    params = on_chip(jax.eval_shape(model.init_params, jax.random.PRNGKey(0)),
                     jnp.bfloat16)
    pool = on_chip(jax.eval_shape(
        lambda: model.init_kv_pool(NB, BS, dtype=jnp.bfloat16)))
    text = compile_text(
        model.forward_paged, params, aval(one_chip, (rows, 1), jnp.int32),
        pool, aval(one_chip, (rows, MAXB), jnp.int32),
        aval(one_chip, (rows,), jnp.int32))
    assert "tpu_custom_call" in text, \
        "paged decode took the XLA gather path at the smoke's own shapes"


def test_flash_refusal_is_loud(as_tpu, monkeypatch):
    """On a TPU backend a shape the kernel cannot take gives way to the XLA
    path only with a logged reason, and not at all when the caller named the
    kernel. (Nothing compiles here: the refusal comes before any kernel.)"""
    from deepspeed_tpu.ops.transformer.attention import (UnsupportedShape,
                                                         attention,
                                                         xla_attention)

    from deepspeed_tpu.utils.logging import logger

    warned = []
    monkeypatch.setattr(logger, "warning", warned.append)
    q = jnp.ones((1, 100, 2, 64), jnp.float32)  # 100 is no multiple of 128
    out = attention(q, q, q, causal=True)
    assert jnp.allclose(out, xla_attention(q, q, q, causal=True))
    assert len(warned) == 1 and "multiples of 128" in warned[0]
    with pytest.raises(UnsupportedShape):
        attention(q, q, q, causal=True, impl="pallas_flash")
