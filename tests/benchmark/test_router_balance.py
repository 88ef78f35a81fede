"""``"router_bias": "balanced"``: a configuration's selection bias is fitted to
the seed's own tokens, so that no seed's router has favourites and every seed
gives the held experts the same share of the work (PERF.md, PR 60). (CPU,
rehearsal sizes.)

(a) the fit evens a skewed router out, on the fitted tokens and on fresh ones;
(b) the fitted bias is what every way of asking for the weights hands out, and
a served tree that holds the drawn one is a mismatch; (c) the reference pass
fits each expert layer once, a later pass finds the bias made, and the same
seed makes the same bias; (d) a configuration that does not ask keeps its
drawn bias."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness import check, serve, train
from benchmark.harness.cell import Cell
from benchmark.reference import deepseek_v3 as ref
from benchmark.reference import ein_fp8

CELL = "gigachat3.1-702b-a36b.serve-longdoc"
ROUTERS = {"rehearsal": dict(n_group=4, topk_group=2, num_experts_per_tok=4,
                             outputs=16, held=4),
           "published": dict(n_group=8, topk_group=4, num_experts_per_tok=8,
                             outputs=256, held=16)}


def skewed_scores(rng, n, outputs, lean, hidden=64):
    """Scores of ``n`` tokens whose hidden states share a direction: the
    router's outputs that lie along it are every token's favourites."""
    h = rng.normal(size=(n, hidden)) + lean
    h /= np.sqrt(np.mean(h ** 2, axis=-1, keepdims=True))
    return np.asarray(jax.nn.sigmoid(h @ w_of(outputs, hidden)), np.float32)


def w_of(outputs, hidden):
    return np.random.default_rng(5).normal(size=(hidden, outputs)) * (
        1.7 / np.sqrt(hidden))


def loads(s, bias, cfg):
    chosen = np.asarray(ref.picks(jnp.asarray(s) + bias, cfg))
    return np.bincount(chosen.reshape(-1), minlength=s.shape[1]) / (
        chosen.size / s.shape[1])


@pytest.mark.parametrize("size", ROUTERS)
def test_the_fit_evens_a_skewed_router_out(size):
    cfg = dict(ROUTERS[size])
    outputs, held = cfg.pop("outputs"), cfg.pop("held")
    rng = np.random.default_rng(11)
    lean = rng.normal(size=64) * 0.7
    n = 64 * outputs
    s, fresh = (skewed_scores(rng, n, outputs, lean) for _ in range(2))
    before = loads(s, 0.0, cfg)
    assert before.max() > 1.5 and before.min() < 0.5      # a router of favourites
    # padding after the valid tokens is not fitted to
    padded = np.concatenate([s, np.ones((n // 4, outputs), np.float32) * 0.5])
    valid = np.arange(len(padded)) < n
    bias = jax.jit(lambda s, v: ref.balanced_bias(s, v, cfg))(padded, valid)
    assert bias.dtype == jnp.float32 and bias.shape == (outputs,)
    assert np.array_equal(bias, bias.astype(jnp.bfloat16).astype(jnp.float32))
    assert abs(float(jnp.mean(bias))) < 0.01
    after = loads(s, bias, cfg)
    assert np.abs(after - 1.0).max() < 0.1
    # tokens it has not seen: each output within sampling noise of its share
    # (64 * k picks an output), the held block closer
    assert np.abs(loads(fresh, bias, cfg) - 1.0).max() < 0.5
    assert abs(loads(fresh, bias, cfg)[:held].mean() - 1.0) < 0.1
    assert abs(before[:held].mean() - 1.0) > 0.1
    # the same scores make the same bias
    again = jax.jit(lambda s, v: ref.balanced_bias(s, v, cfg))(padded, valid)
    assert np.array_equal(bias, again)


def seeded(seed, **config):
    cell = Cell(CELL)
    cell.config.update(config)
    model = train.build_model(cell, True)
    return cell, model, train.seeded(cell, model, seed)


def reference_pass(cell, s, seed, ein=None):
    mix = cell.mix(True)
    vocab = cell.config["rehearsal"]["model"]["vocab_size"]
    recs, _ = serve.plan(mix, seed, 2.0, vocab, mix["engine"]["max_seq_len"])
    _, ids, rows = serve.check_samples(mix, recs, seed, vocab)
    kw = {} if ein is None else {"ein": ein}
    return check.serve_reference(train.reference_config(cell, True), s, ids,
                                 rows, **kw)


def test_the_reference_pass_fits_each_expert_layer_once():
    seed = 2**31 + 29
    cell, _, s = seeded(seed)
    assert cell.config["router_bias"] == "balanced" and s.made == {}
    drawn = {l: s.layer("blocks", l)["moe_bias"] for l in range(2)}
    want = reference_pass(cell, s, seed)
    assert sorted(s.made) == [("blocks", 0), ("blocks", 1)]
    made = {k: v["moe_bias"] for k, v in s.made.items()}
    for (group, l), bias in made.items():
        assert not np.array_equal(bias, drawn[l])
        assert np.array_equal(s.layer(group, l)["moe_bias"], bias)
        # the other leaves are the drawn ones
        assert set(s.layer(group, l)) == set(s.groups[group])
    # a later pass (the control's) holds the same bias and fits nothing
    reference_pass(cell, s, seed, ein_fp8)
    assert all(s.made[k]["moe_bias"] is v for k, v in made.items())
    assert np.array_equal(reference_pass(cell, s, seed), want)
    # the same seed makes the same bias, another seed another
    _, _, again = seeded(seed)
    reference_pass(cell, again, seed)
    assert all(np.array_equal(again.made[k]["moe_bias"], v)
               for k, v in made.items())
    _, _, other = seeded(seed + 1)
    reference_pass(cell, other, seed + 1)
    assert not np.array_equal(other.made["blocks", 0]["moe_bias"],
                              made["blocks", 0])


def test_a_long_pass_is_fitted_to_every_nth_position(monkeypatch):
    seed = 2**31 + 29
    cell, _, whole = seeded(seed)
    reference_pass(cell, whole, seed)
    monkeypatch.setattr(check, "FIT_POSITIONS", 8)
    cell, _, thinned = seeded(seed)
    reference_pass(cell, thinned, seed)
    assert sorted(thinned.made) == sorted(whole.made)
    a, b = (s.made["blocks", 0]["moe_bias"] for s in (whole, thinned))
    assert not np.array_equal(a, b) and abs(float(jnp.mean(b))) < 0.01


def test_the_served_tree_holds_the_fitted_bias():
    seed = 2**31 + 31
    cell, _, s = seeded(seed)
    dtype = jnp.dtype(cell.config["dtype"])
    unfitted = s.tree_as(dtype)
    reference_pass(cell, s, seed)
    served = s.tree_as(dtype)
    for l in range(2):
        assert np.array_equal(served["blocks"]["moe_bias"][l].astype(jnp.float32),
                              s.made["blocks", l]["moe_bias"])
    same = [k for k in served["blocks"] if np.array_equal(
        served["blocks"][k], unfitted["blocks"][k])]
    assert sorted(same) == sorted(set(served["blocks"]) - {"moe_bias"})
    assert check.weights_mismatch_share(served, s, dtype) == 0.0
    # a program served the drawn bias is not serving the seed's weights
    n = len(jax.tree.leaves(served))
    assert check.weights_mismatch_share(unfitted, s, dtype) == 1 / n


def test_a_made_leaf_of_another_shape_is_refused():
    _, _, s = seeded(3)
    s.made["blocks", 0] = {"moe_bias": jnp.zeros((3,), jnp.float32)}
    with pytest.raises(ValueError, match="made leaf"):
        s.layer("blocks", 0)


def test_a_configuration_that_does_not_ask_keeps_its_drawn_bias():
    seed = 2**31 + 37
    cell, _, s = seeded(seed, router_bias=None)
    reference_pass(cell, s, seed)
    assert s.made == {}
    cell, _, s = seeded(seed, router_bias="uniform")
    with pytest.raises(ValueError, match="router_bias"):
        reference_pass(cell, s, seed)
