"""The always-on account of set-up (docs/TRACING.md "Set-up and recompiles"):
with no profiler session anywhere in this file but the last test, every
program the process makes runnable leaves one ``compile`` record with its
trace, lowering and compile-or-load seconds, the package function that asked
for it and the kernels bound while it was traced, and each engine's
constructor leaves one ``engine.init`` record."""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.comm import topology as topo_mod
from deepspeed_tpu.inference.v2 import InferenceEngineV2
from deepspeed_tpu.models import TransformerLM, build_model
from deepspeed_tpu.models.transformer import gpt2_config
from deepspeed_tpu.serve import ContinuousBatchScheduler
from deepspeed_tpu.utils import tracing


def since(mark, records=tracing.builds):
    """The always-on records that ended after ``mark`` (``clock_ns``)."""
    return [r for r in records() if r.end > mark]


def ours(records):
    return [r for r in records if r.attrs["site"].startswith("deepspeed_tpu.")]


def named(records, part):
    return [r for r in records if part in r.attrs["program"]]


def batch_of(seq):
    rng = np.random.default_rng(seq)
    return {"input_ids": jnp.asarray(rng.integers(0, 128, (8, seq),
                                                  dtype=np.int32))}


def test_a_training_engine_leaves_its_init_and_its_steps_programs():
    assert not tracing.enabled()
    mark = tracing.clock_ns()
    topo_mod.reset_topology()
    model = TransformerLM(gpt2_config(
        "125m", vocab_size=128, hidden_size=64, num_layers=2, num_heads=4,
        max_seq_len=32))
    engine = deepspeed_tpu.initialize(model=model, config={
        "train_batch_size": 8, "steps_per_print": 0,
        "bf16": {"enabled": True},
        "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
        "mesh": {"data": 8}})[0]
    init, = since(mark, tracing.inits)
    assert init.attrs["engine"] == "train" and init.start > mark
    phases = {k: v for k, v in init.attrs.items() if k.endswith("_s")}
    assert set(phases) == {"model_s", "params_s", "optimizer_s",
                           "functions_s", "preflight_s"}
    assert all(v >= 0 for v in phases.values())
    assert abs(sum(phases.values()) - (init.end - init.start) / 1e9) < 1e-3
    # placing the parameters and their master copies built programs, and
    # each says which function of the package asked for it
    placed = ours(since(mark))
    assert placed and all(r.end <= init.end for r in placed)

    built = tracing.clock_ns()
    engine.train_batch(iter([batch_of(32)]))
    step, = named(since(built), "fused_step")
    a = step.attrs
    assert a["site"].startswith("deepspeed_tpu.runtime.engine.DeepSpeedEngine.")
    assert a["trace_s"] > 0 and a["lower_s"] > 0 and a["load_s"] > 0
    assert a["cached"] in (False, True) and a["kernels"] == {}
    assert step.end - step.start == int(a["load_s"] * 1e9)
    # the record ends when the program became runnable: after its trace and
    # lowering, which lie before its span
    assert step.start - built > (a["trace_s"] + a["lower_s"]) * 1e9 * 0.99

    again = tracing.clock_ns()
    engine.train_batch(iter([batch_of(32)]))
    assert ours(since(again)) == []            # a second identical step: none

    reshaped = tracing.clock_ns()
    engine.train_batch(iter([batch_of(16)]))   # a recompile, seen with no profiler
    one, = named(since(reshaped), "fused_step")
    assert one.attrs["trace_s"] > 0 and one.attrs["site"] == a["site"]
    topo_mod.reset_topology()


@pytest.fixture(scope="module")
def lm():
    m = build_model("llama-tiny", vocab_size=128, hidden_size=64,
                    num_layers=2, num_heads=4, num_kv_heads=2,
                    intermediate_size=128, max_seq_len=128)
    return m, m.init_params(jax.random.PRNGKey(0))


def test_a_serving_engine_leaves_its_init_and_both_ragged_variants(lm):
    assert not tracing.enabled()
    m, params = lm
    mark = tracing.clock_ns()
    eng = InferenceEngineV2(m, params, paged=True, max_seqs=4, max_seq_len=128,
                            prefill_chunk=16, block_size=16, token_budget=16,
                            num_blocks=64)
    init, = since(mark, tracing.inits)
    assert init.attrs["engine"] == "serve" and "rebuild" not in init.attrs
    assert {k for k in init.attrs if k.endswith("_s")} == {
        "weights_s", "state_s", "pool_s"}
    sched = ContinuousBatchScheduler(eng)
    rng = np.random.default_rng(0)
    sched.submit(rng.integers(0, 128, 40).tolist(), max_new_tokens=4)
    sched.run_until_complete()
    ragged = named(since(mark), "ragged")
    # the mixed step and the decode round, each made runnable once
    assert len(ragged) == 2
    for r in ragged:
        assert r.attrs["site"].startswith(
            "deepspeed_tpu.inference.v2.engine_v2.InferenceEngineV2.")
        assert min(r.attrs["trace_s"], r.attrs["lower_s"],
                   r.attrs["load_s"]) > 0
    again = tracing.clock_ns()
    sched.submit(rng.integers(0, 128, 40).tolist(), max_new_tokens=4)
    sched.run_until_complete()
    assert named(since(again), "ragged") == []

    eng.rebuild()
    rebuilt, = since(again, tracing.inits)
    assert rebuilt.attrs["rebuild"] == 1 and {
        k for k in rebuilt.attrs if k.endswith("_s")} == {"state_s", "pool_s"}


def test_a_program_of_the_callers_own_has_no_site():
    mark = tracing.clock_ns()

    @jax.jit
    def the_tests_own(x):
        time.sleep(0.02)                    # a trace no one would sum away
        return x * 3 + 1

    the_tests_own(jnp.ones((5,)))
    rec, = named(since(mark), "the_tests_own")
    assert rec.attrs["site"] == "" and rec.attrs["caller"] == __name__
    assert rec.attrs["trace_s"] >= 0.02


def test_nested_jits_are_one_record_with_the_outer_trace():
    @jax.jit
    def inner_a(x):
        time.sleep(0.03)
        return jnp.sin(x)

    @jax.jit
    def inner_b(x):
        time.sleep(0.03)
        return jnp.cos(x)

    @jax.jit
    def nest_outer(x):
        time.sleep(0.03)
        return inner_a(x) + inner_b(x)

    seen = []
    listener = lambda name, s, **kw: seen.append(    # noqa: E731
        (name.rsplit("/", 1)[1], kw.get("fun_name"), s))
    jax.monitoring.register_event_duration_secs_listener(listener)
    try:
        mark = tracing.clock_ns()
        nest_outer(jnp.ones((6,)))
    finally:
        from jax._src import monitoring
        monitoring.unregister_event_duration_listener(listener)
    traces = {fun: s for kind, fun, s in seen if kind == "jaxpr_trace_duration"}
    assert {"inner_a", "inner_b", "nest_outer"} <= set(traces)
    recs = since(mark)
    rec, = named(recs, "nest_outer")
    assert not named(recs, "inner_a") and not named(recs, "inner_b")
    # JAX's outer event already holds the inner ones: not the three's sum
    assert rec.attrs["trace_s"] == pytest.approx(traces["nest_outer"], abs=2e-3)
    assert rec.attrs["trace_s"] < (traces["inner_a"] + traces["inner_b"]
                                   + traces["nest_outer"]) - 0.05
    # the inner intervals went with it
    assert [p for p in tracing._pending() if p[3] > mark] == []

    # the inner function alone, later: a program of its own, its trace cached
    mark = tracing.clock_ns()
    inner_a(jnp.ones((6,)))
    alone, = named(since(mark), "inner_a")
    assert alone.attrs["trace_s"] < 0.03 and alone.attrs["lower_s"] > 0


def test_short_builds_of_the_callers_own_are_summed_by_module(monkeypatch):
    monkeypatch.setattr(tracing, "SMALL_BUILD_S", 60.0)
    before = tracing.small_builds().get(__name__, (0, 0.0, 0.0, 0.0))
    mark = tracing.clock_ns()
    jax.jit(lambda x: x - 7)(jnp.ones((7,)))
    assert named(since(mark), "<lambda>") == []
    after = tracing.small_builds()[__name__]
    assert after[0] >= before[0] + 1 and after[3] > before[3]
    assert tracing.listener_seconds() > 0


def test_clear_keeps_the_set_up():
    jax.jit(lambda x: x * 11)(jnp.ones((11,)))
    kept = len(tracing.builds()), len(tracing.inits())
    tracing.clear()
    assert (len(tracing.builds()), len(tracing.inits())) == kept


def flash_loss(q):
    from deepspeed_tpu.ops.transformer.flash_attention import flash_attention

    return jnp.sum(flash_attention(q, q, q, causal=True))


def test_kernel_bodies_are_on_the_record_of_the_program_that_traced_them():
    q = jnp.ones((1, 128, 2, 64), jnp.float32)
    # a trace nobody compiles leaves its kernels to no later program
    jax.eval_shape(jax.grad(flash_loss), q)
    assert [p for p in tracing._pending() if p[0] == "kernel"]
    mark = tracing.clock_ns()
    jax.jit(lambda x: x + 13)(jnp.ones((13,)))
    unrelated, = named(since(mark), "<lambda>")
    assert unrelated.attrs["kernels"] == unrelated.attrs["kernel_attrs"] == {}

    mark = tracing.clock_ns()
    jax.jit(jax.grad(flash_loss)).lower(jnp.ones((1, 256, 2, 64))).compile()
    rec, = named(since(mark), "flash_loss")
    kernels = rec.attrs["kernels"]
    assert set(kernels) == {"flash_fwd", "flash_bwd"}
    for calls, seconds in kernels.values():
        assert calls == 1 and 0 < seconds < rec.attrs["trace_s"]
    # what each kernel said of its work when it was bound: at 256 positions a
    # head multiplies the three 128-tiles on and under the diagonal
    assert rec.attrs["kernel_attrs"] == dict.fromkeys(
        kernels, {"pairs_computed": 3 * 128 * 128, "pairs_causal": 256 * 257 // 2})


def test_an_eager_kernel_call_is_timed_as_no_bind():
    from deepspeed_tpu.ops.transformer.paged_attention import (
        paged_decode_attention)

    pool = jnp.ones((2, 8, 16, 64))
    tables, lens = jnp.zeros((3, 4), jnp.int32), jnp.ones((3,), jnp.int32)
    mark = tracing.clock_ns()
    paged_decode_attention(jnp.ones((3, 2, 64)), pool, pool, tables, lens)
    assert not [p for p in tracing._pending()
                if p[0] == "kernel" and p[3] > mark]
    # the program it became names the kernel's caller, not the helper
    sites = {r.attrs["site"] for r in ours(since(mark))}
    assert sites and all("paged_attention" in s for s in sites)


def test_under_a_session_the_same_record_is_the_compile_span(tmp_path):
    tracing.clear()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        mark = tracing.clock_ns()
        with tracing.span("outer.phase") as sp:
            jax.jit(lambda x: x * 17)(jnp.ones((17,)))
        spans = [s for s in tracing.snapshot() if s.name == "compile"
                 and "<lambda>" in s.attrs["program"]]
    finally:
        jax.profiler.stop_trace()
        tracing.clear()
    span, = spans
    assert span.parent == sp.id
    assert {"program", "cached", "site", "caller", "trace_s", "lower_s",
            "load_s", "kernels", "kernel_attrs"} == set(span.attrs)
    assert span.attrs["trace_s"] > 0 and span.attrs["lower_s"] > 0
    kept = [r for r in since(mark) if r.id == span.id]
    assert kept and kept[0] is span     # one record, two stores
