"""A step whose rows fit one tile computes its held experts over the touched
experts alone, each over all the step's rows (``moe/layer.py``
``grouped_experts``, ``T <= EXPERT_TILE_ROWS``).

The same inputs go through the sorted row buffer, the form every larger step
keeps: it is reached here by padding the step past one tile with rows that
route nowhere (``chosen`` -1, or ``token_mask`` False), which land on no
expert and are counted nowhere. Both forms must agree on the output rows and
on the counts, the gradients too; and the jaxpr of a round must hold no sort
over the pairs, no scatter and no gather, while a 512-row step holds what it
held before.
"""

import collections
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.moe.layer import (EXPERT_TILE_ROWS, grouped_experts,
                                     held_experts_ffn)

H, I = 64, 32
PAD_TO = 160        # more than one tile: the sorted row buffer


def matrices(E, dtype=jnp.float32, seed=0, stack=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    lead = (stack, E) if stack else (E,)
    wi, wg = (jax.random.normal(k, lead + (H, I), jnp.float32) * 0.2
              for k in ks[:2])
    wd = jax.random.normal(ks[2], lead + (I, H), jnp.float32) * 0.2
    return tuple(a.astype(dtype) for a in (wi, wg, wd))


def step(T, k, outs, dtype=jnp.float32, seed=0):
    """x, each row's ``k`` distinct picks of ``outs`` router outputs, and
    weights that do not sum to one."""
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(T, H)), dtype)
    chosen = np.stack([rng.choice(outs, k, replace=False) for _ in range(T)])
    weights = rng.uniform(0.1, 1.0, size=(T, k))
    return x, jnp.asarray(chosen, jnp.int32), jnp.asarray(weights, jnp.float32)


@functools.partial(jax.jit, static_argnames=("first", "pad_to"))
def run(x, chosen, weights, ws, layer=None, *, first=0, pad_to=0):
    """``grouped_experts`` on the step as it is, or (``pad_to``) on the step
    padded with rows that route nowhere; the step's own rows of the result."""
    T = x.shape[0]
    if pad_to:
        more = pad_to - T
        x = jnp.concatenate([x, jnp.ones((more, H), x.dtype)])
        chosen = jnp.concatenate(
            [chosen, jnp.full((more, chosen.shape[1]), -1, jnp.int32)])
        weights = jnp.concatenate(
            [weights, jnp.ones((more, weights.shape[1]), weights.dtype)])
    y, counts = grouped_experts(x, chosen, weights, *ws, first=first,
                                layer=layer)
    return y[:T], counts


def same(got, want, dtype):
    (y, counts), (y0, counts0) = got, want
    assert y.dtype == y0.dtype == dtype and y.shape == y0.shape
    assert [int(c) for c in counts] == [int(c) for c in counts0]
    y, y0 = (np.asarray(a, np.float32) for a in (y, y0))
    if dtype == jnp.float32:
        np.testing.assert_allclose(y, y0, atol=1e-5)
    else:       # the float32 sum is cast once: one rounding of bfloat16 apart
        np.testing.assert_allclose(y, y0, rtol=2 ** -7,
                                   atol=2 ** -7 * float(np.abs(y0).max()))


# -- the two forms on the same inputs ---------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("T", [1, 32, 96, EXPERT_TILE_ROWS])
def test_a_round_equals_the_sorted_row_buffer(T, dtype):
    x, chosen, weights = step(T, 8, 40, dtype, seed=T)
    ws = matrices(16, dtype)
    got = run(x, chosen, weights, ws)
    assert int(got[1][0]) > 0
    same(got, run(x, chosen, weights, ws, pad_to=PAD_TO + T), dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_one_row_more_than_a_tile_takes_the_row_buffer(dtype):
    """Rows do not mix, so 129 rows through the row buffer are 128 rows and
    one row through the one-tile form."""
    T = EXPERT_TILE_ROWS + 1
    x, chosen, weights = step(T, 8, 40, dtype, seed=7)
    ws = matrices(16, dtype)
    y, (rows, most) = run(x, chosen, weights, ws)
    head, (rows_h, _) = run(x[:-1], chosen[:-1], weights[:-1], ws)
    tail, (rows_t, _) = run(x[-1:], chosen[-1:], weights[-1:], ws)
    same((y, (rows,)), (jnp.concatenate([head, tail]), (rows_h + rows_t,)),
         dtype)
    assert int(most) == int(np.bincount(
        np.asarray(chosen).ravel(), minlength=16)[:16].max())


def _case(name):
    """(x, chosen, weights, ws, kwargs of ``run``, (rows, most) or None)."""
    T, E = 32, 16
    x, chosen, weights = step(T, 4, 40, seed=3)
    ws, kw, counts = matrices(E), {}, None
    if name == "no_pair_lands":
        chosen, counts = chosen % 20 + 16, (0, 0)
    elif name == "every_row_on_one_expert":
        chosen = jnp.stack([jnp.full((T,), 5), jnp.full((T,), 30)], 1)
        weights, counts = weights[:, :2], (T, T)
    elif name == "one_expert_picked_twice":
        chosen = chosen.at[:, 1].set(chosen[:, 0] % E).at[:, 0].set(
            chosen[:, 0] % E)
    elif name == "held_range_in_the_middle":
        # picks over 0..39 with 12..19 held: below, inside and above
        ws, kw = matrices(8), dict(first=12)
        assert bool(jnp.any(chosen < 12)) and bool(jnp.any(chosen > 19))
        counts = (int(jnp.sum((chosen >= 12) & (chosen < 20))), None)
    elif name == "stacked_leaves":
        ws, kw = matrices(E, stack=3), dict(layer=jnp.int32(2))
    else:
        raise KeyError(name)
    return x, chosen, weights, ws, kw, counts


@pytest.mark.parametrize("name", [
    "no_pair_lands", "every_row_on_one_expert", "one_expert_picked_twice",
    "held_range_in_the_middle", "stacked_leaves"])
def test_a_round_equals_the_sorted_row_buffer_where_routing_is_odd(name):
    x, chosen, weights, ws, kw, counts = _case(name)
    got = run(x, chosen, weights, ws, **kw)
    same(got, run(x, chosen, weights, ws, pad_to=PAD_TO, **kw), jnp.float32)
    if counts is not None:
        rows, most = counts
        assert int(got[1][0]) == rows
        assert most is None or int(got[1][1]) == most
    if name == "no_pair_lands":
        assert not np.asarray(got[0]).any()
    if name == "one_expert_picked_twice":
        # both weights of the doubled pick count: not the same as one of them
        once = run(x, chosen.at[:, 1].set(-1), weights, ws)
        assert float(jnp.abs(got[0] - once[0]).max()) > 1e-3
    if name == "stacked_leaves":
        plain = run(x, chosen, weights, tuple(a[2] for a in ws))
        same(got, plain, jnp.float32)


@functools.partial(jax.jit, static_argnames=("zero_experts", "pad_to"))
def run_layer(x, wg, ws, mask, layer=None, *, zero_experts=0, pad_to=0):
    """``held_experts_ffn`` (softmax router over 40 + ``zero_experts``
    outputs, 16 held) on the step, or on the step padded with masked rows."""
    T = x.shape[0]
    if pad_to:
        x = jnp.concatenate([x, jnp.ones((pad_to - T, H), x.dtype)])
        mask = jnp.concatenate([mask, jnp.zeros((pad_to - T,), bool)])
    y, counts = held_experts_ffn(
        x, wg, None, *ws, None, k=6, router="softmax_topk", normalize=False,
        token_mask=mask, zero_experts=zero_experts, layer=layer)
    return y[:T], counts


@pytest.mark.parametrize("zero_experts", [0, 24], ids=["plain", "zero_experts"])
def test_masked_rows_and_identity_experts_through_the_layer(zero_experts):
    T = 32
    x = jax.random.normal(jax.random.PRNGKey(1), (T, H))
    wg = jax.random.normal(jax.random.PRNGKey(2), (H, 40 + zero_experts))
    mask = jnp.arange(T) % 3 != 1
    ws = matrices(16)
    got = run_layer(x, wg, ws, mask, zero_experts=zero_experts)
    want = run_layer(x, wg, ws, mask, zero_experts=zero_experts, pad_to=PAD_TO)
    same(got, want, jnp.float32)
    assert len(got[1]) == (3 if zero_experts else 2) and int(got[1][0]) > 0
    # a masked row routes nowhere: exact zeros out, counted nowhere
    assert not np.asarray(got[0])[~np.asarray(mask)].any()
    alone = run_layer(x[mask], wg, ws, jnp.ones((int(mask.sum()),), bool),
                      zero_experts=zero_experts)
    assert [int(c) for c in alone[1]] == [int(c) for c in got[1]]
    np.testing.assert_allclose(np.asarray(got[0])[np.asarray(mask)],
                               np.asarray(alone[0]), atol=1e-5)


def test_an_unchosen_rows_overflow_stays_out_of_the_sum():
    """Every row goes through every touched expert's product. Row 3 chooses
    no held expert and overflows in each: the other rows stay as they were
    without it, and its own output is untouched zeros."""
    x, chosen, weights = step(32, 4, 40, seed=5)
    ws = matrices(16)
    chosen = chosen.at[3].set(jnp.asarray([20, 21, 22, 23]))
    calm, _ = run(x, chosen, weights, ws)
    wild = x.at[3].set(3e38)
    out = (jax.nn.silu(wild[3] @ ws[1][0]) * (wild[3] @ ws[0][0])) @ ws[2][0]
    assert not bool(jnp.all(jnp.isfinite(out)))      # the product does overflow
    y, _ = run(wild, chosen, weights, ws)
    assert np.isfinite(np.asarray(y)).all() and not np.asarray(y[3]).any()
    np.testing.assert_array_equal(np.asarray(y), np.asarray(calm))


# -- gradients ---------------------------------------------------------------

@pytest.mark.parametrize("stack", [0, 2], ids=["a_layers_leaves", "stacked"])
def test_the_gradients_are_the_row_buffers(stack):
    T = 24
    x = jax.random.normal(jax.random.PRNGKey(1), (T, H))
    wg = jax.random.normal(jax.random.PRNGKey(2), (H, 40))
    wi, w_gate, w_down = matrices(16, stack=stack)
    mask = jnp.arange(T) != 5
    layer = jnp.int32(1) if stack else None

    def loss(pad_to, x, wi, wg):
        y, _ = run_layer(x, wg, (wi, w_gate, w_down), mask, layer,
                         pad_to=pad_to)
        return jnp.sum(y ** 2)

    grads = [jax.jit(jax.grad(functools.partial(loss, pad_to),
                              argnums=(0, 1, 2)))(x, wi, wg)
             for pad_to in (0, PAD_TO)]
    for got, want in zip(*grads):
        got, want = np.asarray(got), np.asarray(want)
        assert np.isfinite(got).all() and np.abs(got).max() > 0
        np.testing.assert_allclose(got, want, atol=1e-3 * np.abs(want).max())
    assert not np.asarray(grads[0][0])[5].any()       # the masked row's input
    if stack:                               # and the other layer's experts'
        assert not np.asarray(grads[0][1])[0].any()


# -- what the program holds --------------------------------------------------

def primitives(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs its equations hold."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for inner in (v if isinstance(v, (tuple, list)) else (v,)):
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    yield from primitives(inner)


def held(T, k, E):
    """Counter of the sorts (by the elements sorted along the axis),
    scatters and gathers of ``grouped_experts`` at these sizes."""
    x, chosen, weights = step(T, k, 4 * E)
    jaxpr = jax.make_jaxpr(
        lambda *a: grouped_experts(*a[:3], *a[3], layer=jnp.int32(1)))(
            x, chosen, weights, matrices(E, stack=2)).jaxpr
    found = collections.Counter()
    for eqn in primitives(jaxpr):
        name = eqn.primitive.name
        if name == "sort":
            found["sort", eqn.invars[0].aval.shape[eqn.params["dimension"]]] += 1
        elif name.startswith("scatter") or name == "gather":
            found[name] += 1
    return found


@pytest.mark.parametrize("T,k,E", [(32, 8, 16), (96, 12, 8)],
                         ids=["longdoc_round", "longout_round"])
def test_a_round_sorts_no_pairs_scatters_and_gathers_nothing(T, k, E):
    """Fails on the parent, whose round sorted its ``T * k`` pairs, scattered
    them into a row buffer and gathered rows in and out of it."""
    assert held(T, k, E) == {("sort", E): 1}


def test_a_larger_step_holds_what_it_held():
    assert held(512, 8, 16) == {("sort", 512 * 8): 1, "scatter": 2,
                                "gather": 7}
