"""The served head of a tied table: ``ops/transformer/fused_ce.py``
``head_logits`` and ``TransformerLM._head``'s choice of it.

The kernel interpreted against the float32 product; which heads take it, read
off the jaxpr (one ``head_logits`` call, or today's equations to the letter);
a tiny tied model prefilled and decoded through the paged cache against the
full forward with XLA's head; and what the serving engine's
``engine.enqueue`` spans say of it. What the TPU compiler makes of
``serve-chat``'s programs with the call in them (no cross-program prefetch of
the table) is ``test_chip_compile.py``'s.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.v2 import InferenceEngineV2
from deepspeed_tpu.models import transformer as tf
from deepspeed_tpu.models.transformer import TransformerConfig, TransformerLM
from deepspeed_tpu.ops.pallas_utils import kernel_mesh
from deepspeed_tpu.ops.transformer import fused_ce
from deepspeed_tpu.utils import tracing

ROWS = (1, 8, 64, 96, 256)
TABLES = ((384, 128), (1536, 256))


def operands(rows, vocab, width, dtype, vocab_major):
    """The rows of a step and a table, the same table whatever the rows."""
    x = jax.random.normal(jax.random.PRNGKey(rows), (rows, width),
                          jnp.float32).astype(dtype)
    w = jax.random.normal(jax.random.PRNGKey(vocab), (vocab, width)
                          if vocab_major else (width, vocab), jnp.float32)
    return x, (w * 0.05).astype(dtype)


@pytest.mark.parametrize("vocab_major", [True, False],
                         ids=["tied", "untied"])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("vocab, width", TABLES)
def test_the_kernel_gives_the_float32_product_rounded_once(
        vocab, width, dtype, vocab_major):
    """Every served row count (1 and 8 are padded to the sublane tile, 64 and
    96 are a round's, 256 the bound) in both layouts: float32 sums of the
    operands' products, one rounding to the activations' dtype."""
    @jax.jit
    def all_rows(xs, w):
        return [fused_ce.head_logits(x, w, vocab_major=vocab_major,
                                     block_v=block_v) for x in xs]

    xs = [operands(rows, vocab, width, dtype, vocab_major)[0]
          for rows in ROWS]
    w = operands(1, vocab, width, dtype, vocab_major)[1]
    block_v = 384 if vocab == 384 else 512       # what ``stream_block`` gives
    if vocab_major:
        assert fused_ce.stream_block(xs[-1], w, True) == block_v
    for x, lg in zip(xs, all_rows(xs, w)):
        want = jnp.dot(x.astype(jnp.float32),
                       (w.T if vocab_major else w).astype(jnp.float32),
                       precision="highest")
        assert lg.shape == want.shape and lg.dtype == dtype
        ulp = 2.0 ** -8 if dtype == jnp.bfloat16 else 1e-6
        np.testing.assert_allclose(
            np.asarray(lg.astype(jnp.float32)), np.asarray(want), rtol=ulp,
            atol=ulp * float(jnp.max(jnp.abs(want))))


def test_a_bind_says_what_it_streams():
    """``tracing.builds()``' ``kernel_attrs`` of the program that binds it."""
    x, w = operands(64, 1536, 256, jnp.bfloat16, True)
    before = tracing.clock_ns()
    jax.jit(lambda x, w: fused_ce.head_logits(   # a shape no other test binds
        x, w, vocab_major=True, block_v=256))(x, w).block_until_ready()
    said = [b.attrs["kernel_attrs"]["head_logits"] for b in tracing.builds()
            if b.end > before and "head_logits" in b.attrs["kernel_attrs"]]
    assert said == [dict(rows=64, block_v=256, table_bytes=1536 * 256 * 2,
                         vocab_major=True, operand_dtype="bfloat16")]


# -- which heads take it ---------------------------------------------------

def lm(**kw):
    cfg = dict(vocab_size=384, hidden_size=128, num_layers=1, num_heads=2,
               max_seq_len=64, qkv_bias=True)
    cfg.update(kw)
    model = TransformerLM(TransformerConfig(**cfg))
    return model, model.init_params(jax.random.PRNGKey(0))


def head_of_today(model, params, x):
    """``_head`` as it was before the kernel, equation for equation."""
    x, w, bias, vocab_major = model._head_operands(params, x)
    out = x @ (w.T if vocab_major else w).astype(x.dtype)
    if bias is not None:
        out = out + bias.astype(x.dtype)
    return tf._times(out, model.config.head_mult)


def model_mesh():
    from deepspeed_tpu.comm.topology import MeshTopology

    return MeshTopology(data=4, model=2).mesh


@pytest.mark.parametrize("case, kw, rows, dtype, streams", [
    ("tied round", {}, 64, jnp.bfloat16, True),
    ("tied, one row", {}, 1, jnp.float32, True),
    ("tied, a head bias of another dtype", {"bias": jnp.float32}, 96,
     jnp.bfloat16, True),
    ("untied", {"tie_embeddings": False}, 64, jnp.bfloat16, False),
    ("odd vocabulary", {"vocab_size": 500}, 64, jnp.bfloat16, False),
    ("odd width", {"hidden_size": 64}, 64, jnp.bfloat16, False),
    ("activations of another dtype", {"x_dtype": jnp.float32}, 64,
     jnp.bfloat16, False),
    ("a model axis", {"mesh": True}, 64, jnp.bfloat16, False),
    ("a prefill's rows", {}, 1024, jnp.bfloat16, False),
], ids=lambda v: v.replace(" ", "_").replace(",", "") if isinstance(v, str)
    else None)
def test_the_head_streams_a_tied_table_for_a_served_steps_rows(
        case, kw, rows, dtype, streams):
    """One ``_head``, one test on what it is handed: a vocabulary-major
    table, widths multiples of 128, one dtype, at most ``STREAM_ROWS`` rows,
    one device. Where it applies the jaxpr holds ONE ``head_logits`` call and
    no product of the table; where it does not, today's equations."""
    kw = dict(kw)
    x_dtype, mesh = kw.pop("x_dtype", dtype), kw.pop("mesh", False)
    bias = kw.pop("bias", None)
    model, params = lm(**kw)
    params = jax.tree.map(lambda a: a.astype(dtype), params)
    if bias is not None:
        params["lm_head_bias"] = jnp.ones((model.config.vocab_size,), bias)
    x = jnp.ones((rows, 1, model.config.hidden_size), x_dtype)
    said = {}

    def trace(fn):
        with tracing.program_attrs(said):
            if mesh:
                with kernel_mesh(model_mesh()):
                    return jax.make_jaxpr(fn)(params, x)
            return jax.make_jaxpr(fn)(params, x)

    closed = trace(model._head)
    calls = [e.params["name"] for e in tf_eqns(closed.jaxpr)
             if e.primitive.name == "pallas_call"]
    assert calls == (["head_logits"] if streams else [])
    assert said == {"head": "stream" if streams else "xla"}
    products = [e for e in tf_eqns(closed.jaxpr)
                if e.primitive.name == "dot_general"]
    if streams:
        assert not products      # the only one is inside the kernel's body
        got = model._head(params, x)
        want = head_of_today(model, params, x)
        assert got.shape == want.shape and got.dtype == want.dtype
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=2.0 ** -7, atol=2.0 ** -7)
    else:
        assert str(closed) == str(trace(
            lambda p, x: head_of_today(model, p, x)))


def test_logits_through_the_kernel_can_be_differentiated():
    """An evaluation's logits under somebody's own loss (``model.logits``
    of a short batch): the backward is XLA's two products, and gives what
    differentiating XLA's head gives."""
    model, params = lm()
    x = jax.random.normal(jax.random.PRNGKey(1), (24, 1, 128))

    def loss(head):
        return lambda p, x: jnp.sum(jnp.sin(head(p, x)))

    got = jax.grad(loss(model._head), argnums=(0, 1))(params, x)
    want = jax.grad(loss(lambda p, x: head_of_today(model, p, x)),
                    argnums=(0, 1))(params, x)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4,
                                   atol=1e-5)


def tf_eqns(jaxpr):
    """The equations of ``jaxpr``, those of its inner jaxprs too but not a
    kernel's own body."""
    for e in jaxpr.eqns:
        yield e
        if e.primitive.name == "pallas_call":
            continue
        for v in e.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from tf_eqns(inner)


# -- through the paged cache -----------------------------------------------

BLOCK, NUM_BLOCKS, MAXB = 16, 12, 4


def test_prefill_then_decode_gives_the_full_forwards_logits(monkeypatch):
    """A tiny tied model: a prompt's rows, then three one-token steps,
    through ``forward_paged`` with the streamed head, against the full
    forward's logits with XLA's product."""
    model, params = lm(num_layers=2)
    ids = jax.random.randint(jax.random.PRNGKey(3), (1, 20), 0, 384)
    with monkeypatch.context() as m:
        m.setattr(tf, "stream_block", lambda *a: None)
        want = model.logits(params, ids)[0]                  # (20, V)
    pool = model.init_kv_pool(NUM_BLOCKS, BLOCK, dtype=jnp.float32)
    tables = jnp.arange(1, 1 + MAXB, dtype=jnp.int32)[None]

    said = {}
    with tracing.program_attrs(said):
        step = jax.jit(lambda pool, ids, starts: model.forward_paged(
            params, ids, pool, jnp.repeat(tables, ids.shape[0], axis=0),
            starts))
        prompt = 17
        lg, pool = step(pool, ids[0, :prompt, None],
                        jnp.arange(prompt, dtype=jnp.int32))
        np.testing.assert_allclose(np.asarray(lg), np.asarray(want[:prompt]),
                                   rtol=2e-4, atol=2e-4)
        for at in range(prompt, 20):
            lg, pool = step(pool, ids[0, at:at + 1, None],
                            jnp.array([at], jnp.int32))
            np.testing.assert_allclose(np.asarray(lg[0]),
                                       np.asarray(want[at]),
                                       rtol=2e-4, atol=2e-4)
    assert said == {"head": "stream"}


# -- what a trace says -----------------------------------------------------

@pytest.mark.parametrize("tied, head", [(True, "stream"), (False, "xla")])
def test_every_enqueue_of_the_serving_engine_says_its_head(tied, head,
                                                           tmp_path):
    """``head`` on the serving engine's ``engine.enqueue`` spans, set where
    the program is traced: the first launch (traced there) and every later
    one (not traced anew), the decode round and the mixed step."""
    model, params = lm(tie_embeddings=tied)
    engine = InferenceEngineV2(model, params, max_seqs=4, max_seq_len=64,
                               prefill_chunk=16, block_size=16,
                               token_budget=16, num_blocks=24,
                               prefix_cache=False)
    tracing.clear()
    jax.profiler.start_trace(str(tmp_path))
    try:
        lg = engine.put([0, 1], [list(range(1, 21)), [5, 6, 7]])
        for _ in range(3):
            engine.put([0, 1], [[int(np.argmax(lg[0]))],
                                [int(np.argmax(lg[1]))]])
        spans = tracing.snapshot()
    finally:
        jax.profiler.stop_trace()
        tracing.clear()
    enqueue = [s for s in spans if s.name == "engine.enqueue"]
    assert len(enqueue) >= 4
    assert {s.attrs.get("head") for s in enqueue} == {head}
