"""A serving run holds one copy of the weights: the seed's weights are drawn
slice by slice, the float32 reference walks the model layer by layer, and the
three ways of asking for the weights give the same bits. (CPU, rehearsal
sizes, both architectures.)"""

import gc
import weakref

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import run
from benchmark.harness import check, train, weights
from benchmark.harness.cell import Cell
from benchmark.harness.setup import SetupClock, setup_line
from benchmark.reference import causal_attention, ein_f32

CELLS = {"gpt2": "gpt2-medium.serve-chat",
         "gptneox": "pythia-1.4b.zero3-train-4chip"}


def seeded(arch, seed):
    cell = Cell(CELLS[arch])
    model = train.build_model(cell, True)
    return cell, model, train.seeded(cell, model, seed)


def same(a, b):
    return np.array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("arch", CELLS)
def test_three_ways_of_asking_give_the_same_bits(arch):
    _, _, s = seeded(arch, 2**31 + 3)
    w = s.tree()
    for dtype in (jnp.bfloat16, jnp.float32):
        cast = s.tree_as(dtype)
        assert jax.tree.structure(cast) == jax.tree.structure(w)
        for a, b in zip(jax.tree.leaves(w), jax.tree.leaves(cast)):
            assert b.dtype == dtype and same(a.astype(dtype), b)
    for group, n in check.architecture(Cell(CELLS[arch]).config).groups(
            train.reference_config(Cell(CELLS[arch]), True)):
        for l in range(n):
            layer = s.layer(group, l)
            assert set(layer) == set(w[group])
            for k, v in layer.items():
                assert v.dtype == jnp.float32 and same(v, w[group][k][l]), k
    top = s.unstacked()
    assert set(top) == set(w) - {"blocks"}
    assert all(same(v, w[k]) for k, v in top.items())
    assert float(jnp.abs(w["blocks"]["ln1_scale"] - 1).max()) < 0.2
    assert float(jnp.std(w["blocks"]["wo"])) < float(jnp.std(w["blocks"]["wq"]))


def test_the_unit_of_drawing_is_a_slice():
    """A stacked matrix comes a layer at a time, slice ``j`` of leaf ``i``
    under ``fold_in(fold_in(key, i), j)``; a bias or an embedding whole under
    ``fold_in(key, i)``."""
    _, _, s = seeded("gpt2", 2**31 + 3)
    w = s.tree()
    i, wte, bias = (s.groups["blocks"]["w_up"], s.top["wte"],
                    s.groups["blocks"]["mlp_bias"])
    shape = {k: s.rules[k][1] for k in (i, wte, bias)}
    assert len(shape[i]) == 3 and len(shape[wte]) == 2 == len(shape[bias])

    @jax.jit              # in one program, as every draw is: XLA folds the
    def by_the_rule():    # scale into the normal's own constant
        key = weights._key(*s.seed, 0)

        def normal(k, shape):
            return jax.random.normal(k, shape, jnp.float32) * 0.02

        return (normal(jax.random.fold_in(jax.random.fold_in(key, i), 1),
                       shape[i][1:]),
                normal(jax.random.fold_in(key, wte), shape[wte]),
                normal(jax.random.fold_in(key, bias), shape[bias]))

    slice_1, whole, stacked_bias = by_the_rule()
    assert same(w["blocks"]["w_up"][1], slice_1)
    assert same(w["wte"], whole) and same(w["blocks"]["mlp_bias"], stacked_bias)


def test_a_layer_is_the_same_whatever_was_drawn_before_it():
    """... and whatever the compiled programs have seen: a new seed or
    another layer adds no program."""
    _, _, a = seeded("gpt2", 2**31 + 3)
    want = a.tree()
    first = a.layer("blocks", 1)
    programs = weights._parts_fn.cache_info().misses
    _, _, other = seeded("gpt2", 77)
    other.tree_as(jnp.float32)                  # the same programs, another seed
    other.layer("blocks", 0)
    _, _, again = seeded("gpt2", 2**31 + 3)
    assert same(again.layer("blocks", 0)["wk"], want["blocks"]["wk"][0])
    for k, v in again.layer("blocks", 1).items():
        assert same(v, first[k]) and same(v, want["blocks"][k][1])
    assert not same(other.layer("blocks", 1)["w_up"], first["w_up"])
    assert not same(again.layer("blocks", 0)["w_up"], first["w_up"])
    # the seed and the layer enter a group's one program as arguments
    assert weights._parts_fn.cache_info().misses <= programs + 1    # unstacked
    rules = tuple(a.rules[i] for i in a.groups["blocks"].values())
    assert weights._parts_fn(rules, True)._cache_size() == 1


@pytest.mark.parametrize("arch", CELLS)
def test_streamed_serving_reference_equals_the_whole_tree_forward(arch):
    cell, _, s = seeded(arch, 5)
    cfg = train.reference_config(cell, True)
    mod = check.architecture(cfg)
    ids = (np.arange(72, dtype=np.int32).reshape(3, 24) * 7) % 512
    rows = np.stack([np.arange(4, 24, 5)] * 3)
    got = check.serve_reference(cfg, s, ids, rows)
    w = s.tree()
    want = np.stack([np.asarray(mod.logits(
        w, mod.hidden(w, jnp.asarray(seq), cfg, ein_f32)[pos], ein_f32))
        for seq, pos in zip(ids, rows)])
    assert got.shape == want.shape == (3, 4, 512)
    # the same float32 operations on the same bits; XLA may fuse a layer
    # compiled alone otherwise than the scan's body, hence not exactly 0
    assert np.abs(got - want).max() <= 1e-6 * np.abs(want).max()


class Recording(weights.Seeded):
    """Stands in for the seed's weights: counts the requests for the whole
    float32 tree, and how many layers' float32 leaves are alive whenever
    another is asked for."""

    def _log(self):
        return self.__dict__.setdefault(
            "log", {"tree": 0, "layers": [], "alive": [], "refs": []})

    def tree(self, shardings=None):
        self._log()["tree"] += 1
        return super().tree(shardings)

    def layer(self, group, l, dtype=jnp.float32):
        log = self._log()
        gc.collect()
        log["alive"].append(sum(any(r() is not None for r in refs)
                                for refs in log["refs"]))
        out = super().layer(group, l, dtype)
        log["refs"].append([weakref.ref(v) for v in out.values()])
        log["layers"].append(l)
        return out


@pytest.mark.parametrize("arch", CELLS)
def test_serving_reference_holds_one_layer_of_float32_weights(arch, monkeypatch):
    monkeypatch.setattr(weights, "Seeded", Recording)
    cell, _, s = seeded(arch, 9)
    cfg = train.reference_config(cell, True)
    ids = (np.arange(48, dtype=np.int32).reshape(2, 24) * 5) % 512
    check.serve_reference(cfg, s, ids, np.stack([np.arange(20, 24)] * 2))
    log = s.log
    assert log["tree"] == 0 and log["layers"] == [0, 1]
    # when layer l is asked for, no earlier layer's leaves are alive
    assert log["alive"] == [0, 0]
    gc.collect()
    assert not any(r() is not None for refs in log["refs"] for r in refs)


def test_serve_run_never_asks_for_the_whole_float32_tree(monkeypatch):
    made = []

    class Spy(Recording):
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            made.append(self)

    monkeypatch.setattr(weights, "Seeded", Spy)
    cell = Cell("gpt2-medium.serve-chat")
    out, _ = run.run_cell(cell, 2**31 + 11, 1.0, 0, jax.devices()[:1],
                          rehearsal=True)
    assert out["correct"] is True and out["failed"] == 0
    assert len(made) == 1
    log = made[0].log
    # the reference's two float32 layers, then the served tree and its
    # comparison layer by layer; never two layers at once
    assert log["tree"] == 0 and log["alive"] == [0] * 6
    assert log["layers"] == [0, 1] * 3


def test_a_run_that_serves_one_altered_weight_is_not_correct(monkeypatch):
    """The rest of a run past its look for a chip, with the served tree bent
    underneath it in one element of one layer."""
    class Bent(weights.Seeded):
        def tree_as(self, dtype):
            w = super().tree_as(dtype)
            wq = w["blocks"]["wq"]
            return {**w, "blocks": {**w["blocks"],
                                    "wq": wq.at[1, 3, 5].set(wq[1, 3, 5] + 1)}}

    monkeypatch.setattr(weights, "Seeded", Bent)
    out, _ = run.run_cell(Cell("gpt2-medium.serve-chat"), 2**31 + 12, 1.0, 0,
                          jax.devices()[:1], rehearsal=True)
    assert out["correct"] is False and out["failed"] == 0


def test_weights_mismatch_share_streamed():
    from deepspeed_tpu.inference.quantization import quantize_param_tree

    _, _, s = seeded("gpt2", 2**31 + 9)
    served = s.tree_as(jnp.bfloat16)
    n = len(jax.tree.leaves(served))
    assert check.weights_mismatch_share(served, s, jnp.bfloat16) == 0.0
    assert check.weights_mismatch_share(
        quantize_param_tree(served, num_bits=8), s, jnp.bfloat16) == 1.0
    # the float32 tree is not the served dtype; one altered element of one
    # layer of one leaf is one leaf; another seed's tree is every leaf
    assert check.weights_mismatch_share(s.tree(), s, jnp.bfloat16) == 1.0
    wq = served["blocks"]["wq"]
    bent = {**served, "blocks": {**served["blocks"],
                                 "wq": wq.at[1, 3, 5].set(wq[1, 3, 5] + 1)}}
    assert check.weights_mismatch_share(bent, s, jnp.bfloat16) == 1 / n
    _, _, other = seeded("gpt2", 2**31 + 10)
    assert check.weights_mismatch_share(served, other, jnp.bfloat16) == 1.0


def test_attention_by_blocks_of_queries_is_the_whole():
    q, k, v = (jax.random.normal(jax.random.PRNGKey(i), (64, 2, 16))
               for i in range(3))
    whole = causal_attention(q, k, v, ein_f32)
    blocks = causal_attention(q, k, v, ein_f32, q_block=16)
    assert np.abs(np.asarray(blocks - whole)).max() <= 1e-6
    with pytest.raises(ValueError):
        causal_attention(q, k, v, ein_f32, q_block=24)


def test_setup_line_sums_to_setup_s():
    clock = SetupClock()
    t0 = clock.marks[0][1] - 2.0
    clock.mark("weights", jnp.ones(3))
    clock.marks.append(("ramp", clock.marks[-1][1] + 8.0))
    line = setup_line(t0, clock.marks)
    assert line.startswith("[setup] imports 2.00 + weights ")
    assert " + ramp 8.00 = " in line and line.endswith(" s")
    total = float(line.split(" = ")[1].split()[0])
    assert abs(total - (clock.marks[-1][1] - t0)) < 0.006
