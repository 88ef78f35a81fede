"""``group_limited_gating`` chooses its groups without a sort (PR 72): a
group's score is its maximum plus the maximum with one occurrence of it taken
out, and a group is kept iff fewer than ``topk_group`` groups stand ahead of
it. The rule as the program wrote it until then, two ``top_k``s, is kept
here as its plain statement; the picks, their ORDER (the normalising sum runs
over it) and the weights must be the same bits, ties included. So for
``softmax_topk_gating``, whose 12 picks of 768 are rounds of the first
``argmax`` where they were a ``top_k``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from deepspeed_tpu.moe.sharded_moe import (group_limited_gating,
                                           softmax_topk_gating)
from tests.unit.test_held_experts_round import primitives

#: (T, E, n_group, topk_group, k) of serve-reason, serve-longdoc, serve-win16k
CELLS = {"reason": (128, 512, 8, 4, 8), "longdoc": (32, 256, 8, 4, 8),
         "win16k": (32, 128, 1, 1, 8)}


def by_sort(logits, bias, *, k, n_group, topk_group, scale=2.5):
    """The rule, said with sorts: the parent's lines."""
    T, E = logits.shape
    s = jax.nn.sigmoid(logits.astype(jnp.float32))
    biased = s if bias is None else s + bias.astype(jnp.float32)
    if n_group > 1:
        by_group = biased.reshape(T, n_group, E // n_group)
        group_score = jnp.sum(lax.top_k(by_group, 2)[0], axis=-1)
        kept = lax.top_k(group_score, topk_group)[1]
        group_ok = jnp.any(
            kept[:, :, None] == jnp.arange(n_group)[None, None, :], axis=1)
        biased = jnp.where(jnp.repeat(group_ok, E // n_group, axis=1),
                           biased, -jnp.inf)
    chosen = lax.top_k(biased, k)[1].astype(jnp.int32)
    w = jnp.take_along_axis(s, chosen, axis=1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return chosen, w * scale


def scores(kind, T, E, n_group):
    """(logits (T, E), bias (E,) or None) of one kind of case."""
    rng = np.random.default_rng(len(kind) * 1000 + T + E)
    logits = rng.normal(size=(T, E)).astype(np.float32)
    bias = None
    size = E // n_group
    if kind == "eighths":           # many ties inside and across groups
        logits = np.round(logits * 8) / 8
    elif kind == "twin_maxima":     # a group's two largest are one value
        at = rng.integers(0, size - 1, size=(T, n_group))
        grouped = logits.reshape(T, n_group, size)
        peak = grouped.max(axis=-1) + 0.5
        np.put_along_axis(grouped, at[:, :, None], peak[:, :, None], axis=-1)
        np.put_along_axis(grouped, at[:, :, None] + 1, peak[:, :, None],
                          axis=-1)
        logits = grouped.reshape(T, E)
    elif kind == "equal_groups":    # equal group scores at the boundary
        grouped = logits.reshape(T, n_group, size)
        # the first half of the rows: every group one copy; the others:
        # groups (1, 2), (3, 4), ... equal, 0 and the last alone, so that in
        # some rows a pair straddles ``topk_group``
        grouped[:T // 2] = grouped[:T // 2, :1]
        for g in range(1, n_group - 1, 2):
            grouped[T // 2:, g + 1] = grouped[T // 2:, g]
        logits = grouped.reshape(T, E)
    elif kind == "bias":
        bias = (rng.normal(size=(E,)) * 0.2).astype(np.float32)
    elif kind == "bias_eighths":    # ties made by the bias as well
        logits = np.round(logits * 4) / 4
        bias = (np.round(rng.normal(size=(E,)) * 4) / 8).astype(np.float32)
    return jnp.asarray(logits), None if bias is None else jnp.asarray(bias)


@pytest.mark.parametrize("kind", ["drawn", "eighths", "twin_maxima",
                                  "equal_groups", "bias", "bias_eighths"])
@pytest.mark.parametrize("cell", list(CELLS))
def test_the_choice_is_the_sorts_bit_for_bit(cell, kind):
    T, E, n_group, topk_group, k = CELLS[cell]
    logits, bias = scores(kind, T, E, n_group)
    kw = dict(k=k, n_group=n_group, topk_group=topk_group)
    want = jax.jit(lambda l, b: by_sort(l, b, **kw))(logits, bias)
    got = jax.jit(lambda l, b: group_limited_gating(
        l, b, scale=2.5, **kw))(logits, bias)
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))
    if n_group > 1 and kind in ("twin_maxima", "equal_groups"):
        # the case holds what it says: the kept groups turn on a tie
        s = np.asarray(jax.nn.sigmoid(logits))
        top2 = np.sort(s.reshape(T, n_group, -1), axis=-1)[..., -2:]
        if kind == "twin_maxima":
            assert (top2[..., 0] == top2[..., 1]).all()
        else:
            score = np.sort(top2.sum(-1), axis=-1)
            assert (score[:, -topk_group] == score[:, -topk_group - 1]).any()


@pytest.mark.parametrize("cell", ["reason", "longdoc"])
def test_nothing_is_sorted_to_choose_the_groups(cell):
    """Fails on the parent, whose group score was a ``top_k`` (on the chip a
    full sort of ``(rows, n_group, E / n_group)``) and whose kept groups were
    a second one. What is left is the picks' own ``top_k`` over ``E``."""
    T, E, n_group, topk_group, k = CELLS[cell]
    jaxpr = jax.make_jaxpr(lambda l, b: group_limited_gating(
        l, b, k=k, n_group=n_group, topk_group=topk_group))(
            jnp.zeros((T, E)), jnp.zeros((E,))).jaxpr
    found = [(e.primitive.name, e.invars[0].aval.shape)
             for e in primitives(jaxpr)
             if e.primitive.name in ("sort", "top_k", "argsort")]
    assert found == [("top_k", (T, E))]


def softmax_by_sort(logits, bias, *, k, scale=6.0):
    """``softmax_topk_gating`` with its picks a ``top_k``: the parent's."""
    E = logits.shape[1]
    p = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    biased = p if bias is None else p + bias.astype(jnp.float32) / E
    chosen = lax.top_k(biased, k)[1].astype(jnp.int32)
    return chosen, jnp.take_along_axis(p, chosen, axis=1) * scale


@pytest.mark.parametrize("kind", ["drawn", "eighths", "bias", "bias_eighths"])
@pytest.mark.parametrize("rows", [96, 512], ids=["round", "mixed_step"])
def test_the_softmax_routers_picks_are_the_sorts_bit_for_bit(rows, kind):
    """``serve-longout``'s shapes: 12 of 768 outputs. A softmax over logits
    in eighths holds every score many times over, so the order among equal
    scores decides most rows."""
    logits, bias = scores(kind, rows, 768, 1)
    want = jax.jit(lambda l, b: softmax_by_sort(l, b, k=12))(logits, bias)
    got = jax.jit(lambda l, b: softmax_topk_gating(
        l, b, k=12, scale=6.0))(logits, bias)
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))


def test_the_softmax_router_sorts_nothing():
    """Fails on the parent: its picks were a ``top_k`` over (rows, 768)."""
    jaxpr = jax.make_jaxpr(lambda l, b: softmax_topk_gating(l, b, k=12))(
        jnp.zeros((96, 768)), jnp.zeros((768,))).jaxpr
    assert not [e for e in primitives(jaxpr)
                if e.primitive.name in ("sort", "top_k")]
