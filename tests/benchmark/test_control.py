"""The controls of ``correct`` at a size a test run can hold: the reference
recomputed in fp8 in the program's place must read far above the bf16 program
on the numbers compared, and the program's own int8 weight-only path must fail
the exact weights comparison. (On the chip, at the cells' own sizes, the same
readings set the limits: PERF.md section 2.)"""

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import calibrate
from benchmark.harness import check
from benchmark.harness.cell import Cell
from benchmark.reference import ein_f32, ein_fp8


def test_fp8_contraction_is_coarser_than_float32_and_keeps_gradients():
    a = jax.random.normal(jax.random.PRNGKey(0), (64, 64))
    b = jax.random.normal(jax.random.PRNGKey(1), (64, 64)) * 0.02
    exact = ein_f32("ij,jk->ik", a, b)
    err = jnp.linalg.norm(ein_fp8("ij,jk->ik", a, b) - exact) / jnp.linalg.norm(exact)
    assert 0.01 < float(err) < 0.1
    g = jax.grad(lambda x: jnp.sum(ein_fp8("ij,jk->ik", x, b)))(a)
    assert float(jnp.abs(g).sum()) > 0


def test_training_control_reads_far_above_the_program():
    cell = Cell("gpt2-medium.train-seq1024")
    r = calibrate.train_readings(cell, 2**31 + 9, jax.devices(), True, True)
    prog, ctl = r["program"], r["control_fp8"]
    assert ctl["update_sign_mismatch"] > 10 * prog["update_sign_mismatch"] > 0
    limit = cell.config["tolerances"]["train"]["update_sign_mismatch"]["limit"]
    assert prog["update_sign_mismatch"] < limit


def test_serving_controls_fail():
    cell = Cell("gpt2-medium.serve-chat")
    r = calibrate.serve_readings(cell, 2**31 + 9, jax.devices()[:1], True, True, {})
    assert r["control_fp8"]["logits_rel_err"] > 3 * r["program"]["logits_rel_err"]
    assert r["control_program_int8_woq"]["weights_mismatch_share"] == 1.0
    assert r["program"]["weights_mismatch_share"] == 0.0
    tol = cell.config["tolerances"]["serve"]
    assert r["program"]["logits_rel_err"] < tol["logits_rel_err"]["limit"]


def test_weights_are_a_function_of_the_seed_alone():
    from benchmark.harness import train, weights

    cell = Cell("pythia-1.4b.zero3-train-4chip")
    model = train.build_model(cell, True)
    a = train.seeded_weights(cell, model, 2**31 + 3, jax.devices()[:1])
    b = train.seeded_weights(cell, model, 2**31 + 3, jax.devices()[:4])
    c = train.seeded_weights(cell, model, 2**31 + 4, jax.devices()[:1])
    for x, y, z in zip(jax.tree.leaves(a), jax.tree.leaves(b), jax.tree.leaves(c)):
        assert np.array_equal(np.asarray(x), np.asarray(y))
        assert not np.array_equal(np.asarray(x), np.asarray(z))
    assert len(b["blocks"]["wq"].sharding.device_set) == 4
    assert float(jnp.abs(a["blocks"]["ln1_scale"] - 1).max()) < 0.2
    ids = weights.make_ids(2**31 + 3, 1, (2, 4, 16), 512)
    assert ids.shape == (2, 4, 16) and int(ids.max()) < 512


def test_reference_matches_the_programs_float32_forward():
    """Both architectures: the plain reference against ``TransformerLM`` run
    in float32 (an independent text of the same equations)."""
    from benchmark.harness import train

    for name in ("gpt2-medium.train-seq1024", "pythia-1.4b.zero3-train-4chip"):
        cell = Cell(name)
        model = train.build_model(cell, True)
        seeded = train.seeded(cell, model, 5)
        w = seeded.tree()
        ids = np.arange(24, dtype=np.int32)[None] * 7 % 512
        want = check.serve_reference(train.reference_config(cell, True), seeded,
                                     ids, np.arange(24)[None])
        with jax.default_matmul_precision("highest"):
            got = model.logits(w, jnp.asarray(ids))
        assert check.logits_rel_err(got, want) < 1e-4, name
