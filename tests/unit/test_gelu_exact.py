"""The exact GELU's unit (``ops/transformer/gelu_exact.py``) against float64
over every finite bfloat16 value, tails included, beside the expression it
replaced: ``jax.nn.gelu(approximate=False)`` and its autodiff."""

import math

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np
import pytest

from deepspeed_tpu.ops.transformer.gelu_exact import (gelu_exact,
                                                      gelu_exact_pair)

#: below this a term of ``g`` is a float32 denormal (``Phi(-12.4)``), which
#: the CPU client flushes and the chip may not: values are held to an
#: absolute error there
TINY = 2.0 ** -100
DTYPES = [jnp.bfloat16, jnp.float32]


def every_finite_bf16():
    values = np.arange(1 << 16, dtype=np.uint16).view(ml_dtypes.bfloat16)
    return values[np.isfinite(values.astype(np.float32))]


def in_float64(h):
    """``(a, g)`` of float64: ``h Phi(h)`` and ``Phi(h) + h phi(h)`` through
    ``math.erfc``, which keeps the lower tail's relative accuracy."""
    h = h.astype(np.float64)
    cdf = 0.5 * np.vectorize(math.erfc)(-h / math.sqrt(2.0))
    with np.errstate(over="ignore", under="ignore"):
        bell = np.exp(-0.5 * h * h) / math.sqrt(2.0 * math.pi)
    return h * cdf, cdf + h * bell


def spacing(value, mantissa_bits):
    """The distance between neighbours of ``value`` in a binary float of
    ``mantissa_bits`` stored bits, ``TINY``'s below ``TINY``."""
    exponent = np.floor(np.log2(np.maximum(np.abs(value), TINY)))
    return 2.0 ** (exponent - mantissa_bits)


def magnitude(readings, which):
    """What an error is measured against: the value's size, and for ``g``,
    which changes sign at h = -0.7518, no less than a quarter of ``|h|``
    (its two terms, ``Phi(h)`` and ``h phi(h)``, cancel there)."""
    h, exact = readings[:2]
    return np.maximum(np.abs(exact[which]), 0.25 * np.abs(h) if which else 0)


def present(h):
    """What ``_block`` called before PR 58, and its derivative by autodiff."""
    def gelu(x):
        return jax.nn.gelu(x, approximate=False)
    return gelu(h), jax.vmap(jax.grad(gelu))(h)


@pytest.fixture(scope="module", params=DTYPES, ids=lambda d: d.__name__)
def readings(request):
    """``(h, float64 pair, the unit's pair, the present pair)`` in a dtype,
    over every finite bfloat16 value: 65,280 inputs, +-3.4e38 the ends."""
    h = every_finite_bf16()
    x = jnp.asarray(h).astype(request.param)
    as64 = lambda pair: tuple(np.asarray(v).astype(np.float64) for v in pair)
    return (h.astype(np.float64), in_float64(h),
            as64(jax.jit(gelu_exact_pair)(x)), as64(jax.jit(present)(x)))


@pytest.mark.parametrize("which", [0, 1], ids=["a", "g"])
def test_within_one_bf16_rounding_of_float64(readings, which):
    h, exact, unit, _ = readings
    err = np.abs(unit[which] - exact[which])
    bound = spacing(magnitude(readings, which), 7)
    worst = np.argmax(err / bound)
    assert np.all(err <= bound), (h[worst], err[worst])


@pytest.mark.parametrize("which", [0, 1], ids=["a", "g"])
def test_nowhere_further_from_float64_than_the_present_expression(
        readings, which):
    """Input by input, tails included. The slack is float32 arithmetic's own
    rounding (four float32 spacings of :func:`magnitude`): the unit rounds a
    float32 result once, the present expression rounds at every operation
    of the dtype, so in bfloat16 it is a hundred times coarser wherever
    they differ."""
    h, exact, unit, old = readings
    ours = np.abs(unit[which] - exact[which])
    theirs = np.abs(old[which] - exact[which])
    further = ours > theirs + 4 * spacing(magnitude(readings, which), 23)
    assert not further.any(), (h[further][:8], ours[further][:8],
                               theirs[further][:8])
    assert ours.sum() <= theirs.sum()


def test_float32_is_the_erfc_expression_to_the_bit():
    """A 32-bit ``h`` keeps ``0.5 * h * erfc(-h / sqrt(2))``: halving is
    exact, so the order of the two multiplies does not show."""
    h = jnp.asarray(every_finite_bf16()).astype(jnp.float32)
    ours = np.asarray(jax.jit(gelu_exact)(h))
    theirs = np.asarray(jax.jit(present)(h)[0])
    normal = np.abs(theirs) >= TINY
    np.testing.assert_array_equal(ours[normal], theirs[normal])


@pytest.mark.parametrize("dtype", DTYPES, ids=lambda d: d.__name__)
def test_grad_is_g_times_the_cotangent_bit_for_bit(dtype):
    h = jnp.asarray(every_finite_bf16()).astype(dtype)
    d = jax.random.normal(jax.random.PRNGKey(0), h.shape, jnp.float32
                          ).astype(dtype)
    # op by op on both sides: inside one jitted loop the CPU client contracts
    # a float32 multiply and add into one rounding
    a, g = gelu_exact_pair(h)
    out, vjp = jax.vjp(gelu_exact, h)
    np.testing.assert_array_equal(np.asarray(out, np.float32),
                                  np.asarray(a, np.float32))
    assert out.dtype == dtype and g.dtype == dtype
    np.testing.assert_array_equal(np.asarray(vjp(d)[0], np.float32),
                                  np.asarray(d * g, np.float32))
    by_grad = jax.grad(lambda x: jnp.sum(
        gelu_exact(x).astype(jnp.float32) * d.astype(jnp.float32)))(h)
    np.testing.assert_array_equal(np.asarray(by_grad, np.float32),
                                  np.asarray(d * g, np.float32))


def test_the_forward_traces_no_derivative():
    """Undifferentiated, the unit is ``a`` alone behind its barrier: the
    primal's jaxpr holds no second ``[tokens, 4H]`` result."""
    jaxpr = jax.make_jaxpr(gelu_exact)(jnp.zeros((8, 128), jnp.bfloat16))
    (call,) = jaxpr.jaxpr.eqns
    inner = call.params["call_jaxpr"].jaxpr
    barrier = [e for e in inner.eqns
               if e.primitive.name == "optimization_barrier"]
    assert len(barrier) == 1 and len(barrier[0].outvars) == 1


# ---- in the model: Pythia-1.4B's rehearsal size, bfloat16, on the CPU -------

def rehearsal_model(**kw):
    import json
    import pathlib

    from deepspeed_tpu.models import TransformerLM
    from deepspeed_tpu.models.transformer import TransformerConfig

    spec = json.loads((pathlib.Path(__file__).parents[2] / "benchmark" /
                       "configs" / "pythia-1.4b.json").read_text())
    model = {**spec["model"], **spec["rehearsal"]["model"], **kw}
    model.pop("name")
    assert model["activation"] == "gelu_exact"
    return TransformerLM(TransformerConfig(**model))


def loss_and_grads(model, dtype, seed=0):
    """Of the model at bfloat16-valued weights, computed in ``dtype``."""
    cfg = model.config
    params = jax.tree.map(lambda a: a.astype(jnp.bfloat16).astype(dtype),
                          model.init_params(jax.random.PRNGKey(seed)))
    ids = jax.random.randint(jax.random.PRNGKey(seed + 1),
                             (2, cfg.max_seq_len), 0, cfg.vocab_size)

    def loss(p):
        out = model.apply(p, {"input_ids": ids}, train=True)
        return out[0] if isinstance(out, tuple) else out

    loss, grads = jax.jit(jax.value_and_grad(loss))(params)
    return float(loss), {jax.tree_util.keystr(path): np.asarray(g, np.float64)
                         for path, g in
                         jax.tree_util.tree_leaves_with_path(grads)}


@pytest.mark.parametrize("policy", ["full", "dots"])
def test_the_model_equals_the_present_expression_within_bf16_rounding(
        policy, monkeypatch):
    """Loss and every gradient leaf of the scanned, rematerialised model in
    bfloat16: the unit beside ``jax.nn.gelu(approximate=False)`` under
    autodiff, which ``_block`` called before PR 58, each against the same
    model in float32. Both lie 0.3-2.5% of a leaf's norm from it (bfloat16's
    rounding through two layers); the unit no further than the expression it
    replaced, give or take a quarter of that noise."""
    from deepspeed_tpu.models import transformer

    model = rehearsal_model(remat=True, remat_policy=policy)
    exact, exact_grads = loss_and_grads(model, jnp.float32)
    loss, grads = loss_and_grads(model, jnp.bfloat16)
    monkeypatch.setattr(transformer, "gelu_exact",
                        lambda h: jax.nn.gelu(h, approximate=False))
    was, grads_were = loss_and_grads(model, jnp.bfloat16)
    assert abs(loss - was) <= 2.0 ** -8 * abs(was)
    assert abs(loss - exact) <= 2.0 ** -8 * abs(exact)
    for leaf, ref in exact_grads.items():
        ours = np.linalg.norm(grads[leaf] - ref)
        theirs = np.linalg.norm(grads_were[leaf] - ref)
        assert ours <= 1.25 * theirs <= 0.04 * np.linalg.norm(ref), leaf


def test_dots_elem_still_saves_the_named_activation():
    """``mlp_up`` and ``mlp_act`` name what they named: under ``dots_elem``
    the block saves the activation by name and ``h``'s product as a dot, and
    neither the derivative nor a float32 array of the MLP's width."""
    import inspect
    import re

    from jax._src.ad_checkpoint import saved_residuals

    from deepspeed_tpu.models import transformer

    model = rehearsal_model(remat=True, remat_policy="dots_elem",
                            scan_layers=False, num_layers=1)
    cfg = model.config
    params = jax.tree.map(lambda a: a.astype(jnp.bfloat16),
                          model.init_params(jax.random.PRNGKey(0)))
    blk = jax.tree.map(lambda a: a[0], params["blocks"])
    x = jnp.ones((2, cfg.max_seq_len, cfg.hidden_size), jnp.bfloat16)
    positions = jnp.broadcast_to(jnp.arange(cfg.max_seq_len), x.shape[:2])

    def block(blk, x):
        return model._block(x, blk, positions=positions, rng=None,
                            train=True)[0]

    saved = saved_residuals(model._ckpt(block), blk, x)
    wide = [(aval, why) for aval, why in saved
            if aval.shape == (2, cfg.max_seq_len, cfg.intermediate_size)]
    assert [str(aval.dtype) for aval, _ in wide] == ["bfloat16"] * 2
    # each is reported by the source line that made it
    source = inspect.getsource(transformer).splitlines()
    made_by = [
        source[int(re.search(r"transformer\.py:(\d+):", why).group(1)) - 1]
        for _, why in wide]
    assert 'blk["w_up"]' in made_by[0] and '"mlp_act"' in made_by[1], made_by
    jaxpr = str(jax.make_jaxpr(block)(blk, x))
    assert "name=mlp_up" in jaxpr and "name=mlp_act" in jaxpr
