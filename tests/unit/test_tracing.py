"""The program's span recorder (docs/TRACING.md): off it records nothing; under
a real ``jax.profiler`` session the scheduler's, the serving engine's and the
training engine's spans nest in the recorder and on the trace's host plane,
carry the counts measured where the batch is built, and compiles and device
scopes are found. Kernel and scope names are guarded at the jaxpr level: no
TPU topology is described here."""

import glob
import os
import subprocess
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.analysis.program_audit import _iter_eqns
from deepspeed_tpu.comm import topology as topo_mod
from deepspeed_tpu.inference.v2 import InferenceEngineV2
from deepspeed_tpu.models import TransformerLM, build_model
from deepspeed_tpu.models.transformer import gpt2_config
from deepspeed_tpu.serve import ContinuousBatchScheduler
from deepspeed_tpu.utils import tracing

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


@pytest.fixture
def session(tmp_path):
    """A real profiler session; yields the directory its trace lands in."""
    tracing.clear()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        yield str(tmp_path)
    finally:
        if tracing.enabled():
            jax.profiler.stop_trace()
        tracing.clear()


def stop(session):
    """End the session; the host plane's events as (name, start, end)."""
    jax.profiler.stop_trace()
    found = glob.glob(os.path.join(session, "**", "*.xplane.pb"), recursive=True)
    profile = jax.profiler.ProfileData.from_file(found[0])
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for plane in profile.planes if plane.name == "/host:CPU"
            for line in plane.lines for e in line.events]


@pytest.fixture(scope="module")
def lm():
    m = build_model("llama-tiny", vocab_size=128, hidden_size=64,
                    num_layers=2, num_heads=4, num_kv_heads=2,
                    intermediate_size=128, max_seq_len=128)
    return m, m.init_params(jax.random.PRNGKey(0))


def serve(lm, prompts=(33, 30, 28), gen=6, warm=True, **sched_kw):
    """A scheduler over a tiny paged engine with ``prompts`` submitted; with
    ``warm`` both shapes of the program have run once already."""
    m, params = lm
    eng = InferenceEngineV2(m, params, paged=True, max_seqs=4, max_seq_len=128,
                            prefill_chunk=16, block_size=16, token_budget=16,
                            num_blocks=64)
    sched = ContinuousBatchScheduler(eng, **sched_kw)
    rng = np.random.default_rng(0)
    if warm:
        sched.submit(rng.integers(0, 128, 20).tolist(), max_new_tokens=3)
        sched.run_until_complete()
        tracing.clear()             # the warm-up's spans are not the test's
    reqs = [sched.submit(rng.integers(0, 128, n).tolist(), max_new_tokens=gen)
            for n in prompts]
    return eng, sched, reqs


def by_name(spans):
    out = {}
    for s in spans:
        out.setdefault(s.name, []).append(s)
    return out


def chain(spans, s):
    """Names from ``s`` up to its root."""
    ids = {x.id: x for x in spans}
    names = [s.name]
    while s.parent:
        s = ids[s.parent]
        names.append(s.name)
    return names


# -- the recorder ----------------------------------------------------------

def test_off_returns_the_shared_noop_and_records_nothing(lm):
    tracing.clear()
    assert not tracing.enabled()
    assert tracing.span("a", k=1) is tracing.NO_SPAN
    assert tracing.step_span("s", 3) is tracing.NO_SPAN
    with tracing.span("a") as sp:
        sp.set(rows=3)
        assert not sp.recording
    tracing.event("req.queue", 0, 5, uid=1)
    _, sched, _ = serve(lm, warm=False)
    sched.run_until_complete()
    assert tracing.snapshot() == []
    assert tracing.device_scopes() == {}


def test_timed_span_reads_the_clock_while_off():
    assert not tracing.enabled()
    with tracing.timed_span("sched.dispatch", rows=2) as sp:
        time.sleep(0.002)
    assert sp.seconds >= 0.002 and not sp.recording
    assert tracing.snapshot() == []


def test_span_records_parent_attrs_and_step_marker(session):
    with tracing.step_span("engine.train_batch", 7) as outer:
        with tracing.span("engine.enqueue", program="p") as inner:
            inner.set(rows=2)
        tracing.event("req.queue", 10, 30, uid=5)
    spans = by_name(tracing.snapshot())
    top, = spans["engine.train_batch"]
    sub, = spans["engine.enqueue"]
    ev, = spans["req.queue"]
    assert top.parent == 0 and top.attrs == {"step": 7}
    assert sub.parent == top.id and sub.attrs == {"program": "p", "rows": 2}
    assert ev.parent == top.id and (ev.start, ev.end) == (10, 30)
    assert top.start <= sub.start <= sub.end <= top.end
    assert outer.recording
    names = [n for n, _, _ in stop(session)]
    assert "engine.train_batch" in names and "engine.enqueue" in names
    assert not tracing.enabled() and tracing.span("x") is tracing.NO_SPAN


def test_descendants_are_everything_below_a_span():
    R = tracing.Record
    spans = [R(1, "step", 0, 100, 0, {}), R(2, "a", 10, 40, 1, {}),
             R(3, "b", 30, 60, 1, {}), R(4, "leaf", 35, 38, 3, {}),
             R(5, "late", 90, 130, 1, {})]
    assert sorted(s.id for s in tracing.descendants(spans, 1)) == [2, 3, 4, 5]
    assert [s.id for s in tracing.descendants(spans, 3)] == [4]
    assert not hasattr(tracing, "self_time")    # no reader ever asked (PR 40)


def test_an_exception_cannot_leave_inner_spans_open(session):
    with pytest.raises(RuntimeError):
        with tracing.span("outer"):
            inner = tracing.span("inner")
            inner.__enter__()           # never exited: the raise skips it
            raise RuntimeError("boom")
    with tracing.span("next") as nxt:
        pass
    assert nxt.parent == 0


# -- serving ---------------------------------------------------------------

def test_scheduler_spans_nest_in_the_recorder_and_on_the_host_plane(lm, session):
    # the synchronous twin: its fetch lies inside its dispatch
    _, sched, _ = serve(lm, pipelined=False)
    sched.run_until_complete()
    spans = tracing.snapshot()
    named = by_name(spans)
    for name in ("sched.step", "sched.admit", "sched.plan", "sched.dispatch",
                 "sched.absorb", "sched.postamble", "engine.dispatch",
                 "engine.build", "engine.enqueue", "engine.fetch"):
        assert named.get(name), name
    for fetch in named["engine.fetch"]:
        assert chain(spans, fetch) == ["engine.fetch", "engine.dispatch",
                                       "sched.dispatch", "sched.step"]
    for s in named["sched.admit"] + named["sched.plan"] + named["sched.absorb"]:
        assert chain(spans, s)[1] == "sched.step"
    assert {s.attrs["kind"] for s in named["sched.dispatch"]} <= {
        "decode", "mixed", "prefill"}
    events = stop(session)
    host = by_name([tracing.Record(0, n, a, b, 0, {}) for n, a, b in events])
    # the session also saw the warm-up, whose spans serve() cleared
    assert len(host["sched.step"]) >= len(named["sched.step"])
    assert len(host["engine.fetch"]) >= len(named["engine.fetch"])
    assert len(host["engine.fetch"]) == len(host["engine.dispatch"])
    steps = [(s.start, s.end) for s in host["sched.step"]]
    for name in ("engine.dispatch", "engine.fetch"):
        for s in host[name]:
            assert any(a <= s.start and s.end <= b for a, b in steps), name
    for f in host["engine.fetch"]:
        assert any(d.start <= f.start and f.end <= d.end
                   for d in host["engine.dispatch"])


def test_request_events_share_uid_and_add_up_to_ttft(lm, session):
    _, sched, reqs = serve(lm)
    sched.run_until_complete()
    named = by_name(tracing.snapshot())
    for r in reqs:
        queue, = [s for s in named["req.queue"] if s.attrs["uid"] == r.uid]
        prefill, = [s for s in named["req.prefill"] if s.attrs["uid"] == r.uid]
        decode, = [s for s in named["req.decode"] if s.attrs["uid"] == r.uid]
        assert queue.attrs["prompt_tokens"] == len(r.prompt)
        assert prefill.attrs["chunks"] >= 1 and prefill.attrs["cached_tokens"] == 0
        assert decode.attrs["tokens"] == len(r.tokens) == 6
        assert queue.end == prefill.start and prefill.end == decode.start
        ttft_ns = (queue.end - queue.start) + (prefill.end - prefill.start)
        assert abs(ttft_ns - (r.first_token_time - r.arrival_time) * 1e9) <= 2
        assert abs(decode.end - r.finish_time * 1e9) <= 1


def test_dispatch_counts_are_measured_where_the_batch_is_built(lm, session):
    eng, sched, reqs = serve(lm)
    before = (sum(sched.metrics.step_batch),
              sched.metrics.prefill["chunk_tokens"])
    sched.run_until_complete()
    disp = by_name(tracing.snapshot())["engine.dispatch"]
    assert disp
    for s in disp:
        a = s.attrs
        assert 0 < a["rows"] <= a["padded_rows"] and a["padded_rows"] in (4, 16)
        assert a["decode_rows"] + a["prefill_tokens"] == a["rows"]
        assert a["seqs"] <= a["rows"] and a["ctx_tokens"] <= a["ctx_tokens_by_row"]
        assert a["blocks_allocated"] >= 0 and a["cow_copies"] == 0
    advanced = (sum(sched.metrics.step_batch) - before[0]
                + sched.metrics.prefill["chunk_tokens"] - before[1])
    assert sum(s.attrs["rows"] for s in disp) == advanced
    # every prompt token and every fed token was a row exactly once
    assert advanced == sum(len(r.prompt) + len(r.tokens) - 1 for r in reqs)
    # a decode round of three sequences reads each context once per row
    three = [s.attrs for s in disp if s.attrs["padded_rows"] == 4
             and s.attrs["rows"] == 3]
    assert three and all(a["ctx_tokens"] == a["ctx_tokens_by_row"]
                         and a["decode_rows"] == 3 for a in three)
    assert sum(s.attrs["blocks_allocated"] for s in disp) >= 3 * 3


def test_pipelined_loop_uses_the_same_names_and_feeds_its_gauges(lm, session):
    _, sched, _ = serve(lm, pipelined=True)
    warm_ahead = sched.metrics.pipeline["ahead_dispatches"]
    sched.run_until_complete()
    spans = tracing.snapshot()
    named = by_name(spans)
    for name in ("sched.step", "sched.admit", "sched.plan", "sched.dispatch",
                 "sched.absorb", "sched.postamble", "sched.wait",
                 "engine.fetch"):
        assert named.get(name), name
    deferred = [s for s in named["engine.dispatch"] if s.attrs.get("deferred")]
    assert deferred and all(
        not [c for c in tracing.descendants(spans, s.id)
             if c.name == "engine.fetch"] for s in deferred)
    # the device wait comes after the dispatch, at the head of the absorb
    # phase, not inside the plan: a round is enqueued before the one ahead of
    # it is fetched
    waits = named["sched.wait"]
    assert all(chain(spans, s)[1] == "sched.step" for s in waits)
    assert all(
        [c.name for c in tracing.descendants(spans, s.id)] == ["engine.fetch"]
        for s in waits)
    # ``ahead``: 1 on a round enqueued with its predecessor unfetched, 0 on
    # a pipe restart and on every synchronous dispatch
    assert all("ahead" in s.attrs for s in named["engine.dispatch"])
    assert all(s.attrs["ahead"] == 0 for s in named["engine.dispatch"]
               if not s.attrs.get("deferred"))
    assert deferred[0].attrs["ahead"] == 0
    ahead = [s for s in deferred if s.attrs["ahead"]]
    assert ahead and len(ahead) == (
        sched.metrics.pipeline["ahead_dispatches"] - warm_ahead)
    for s in ahead:
        # the fetch of the round before it begins after it was enqueued
        later = [f for f in named["engine.fetch"] if f.start >= s.end]
        assert later and not [f for f in named["engine.fetch"]
                              if s.start < f.start < s.end]
    # the gauges are the spans' own clock readings, not a second pair
    last = [s for s in named["sched.absorb"] if chain(spans, s)[1] == "sched.step"][-1]
    assert sched.metrics.pipeline["absorb_ms"] == round(
        (last.end - last.start) / 1e9 * 1000, 3)


def test_a_new_shape_inside_the_session_is_one_compile_span(lm, session):
    _, sched, _ = serve(lm, prompts=(40,), warm=False)
    sched.run_until_complete()
    compiles = by_name(tracing.snapshot()).get("compile", [])
    ragged = [s for s in compiles if "ragged" in s.attrs["program"]]
    # the mixed shape and the decode-round shape, each compiled once
    assert len(ragged) == 2 and all(not s.attrs["cached"] for s in ragged)
    assert all(s.end > s.start for s in ragged)
    n = len(tracing.snapshot())
    sched.submit(list(range(40)), max_new_tokens=4)
    sched.run_until_complete()
    later = [s for s in tracing.snapshot()[n:] if s.name == "compile"]
    assert not [s for s in later if "ragged" in s.attrs["program"]]


def test_serving_scopes_reach_the_device_scope_map(lm, session):
    _, sched, _ = serve(lm)
    sched.run_until_complete()
    jax.profiler.stop_trace()
    found = set(tracing.device_scopes().values())
    assert {"kv_write", "paged_attn", "model", "kv_carry"} <= found


# -- the by-program table ---------------------------------------------------

@pytest.fixture
def two_variants(session):
    """One jitted function noted under two keys, as the ragged program's
    decode round and mixed step are: rows 4 and rows 16 over one weight."""
    @jax.jit
    def step(w, x):
        with jax.named_scope("embed"):
            z = jnp.cumsum(w, axis=0)           # the weight's shape: both
        with jax.named_scope("mlp"):
            y = jnp.tanh(x) @ z                 # the rows' shape: one
        return y, z

    w = jnp.ones((8, 8), jnp.float32)
    texts = {}
    for rows in (4, 16):
        x = jnp.ones((rows, 8), jnp.float32)
        tracing.note_program("step", step, (w, x), key=(rows, True))
        step(w, x)
        texts["step", (rows, True)] = step.lower(w, x).compile().as_text()
    jax.profiler.stop_trace()
    return texts


def test_device_programs_says_which_variant_holds_an_op(two_variants):
    small, large = ("step", (4, True)), ("step", (16, True))
    held = tracing.device_programs()
    of_small = [k for k in held if k.endswith(" f32[4,8]")]
    of_large = [k for k in held if k.endswith(" f32[16,8]")]
    of_both = [k for k in held if k.endswith(" f32[8,8]")]
    assert of_small and of_large and of_both
    assert all(held[k] == {small} for k in of_small)
    assert all(held[k] == {large} for k in of_large)
    # the same instruction over the weight in both: shared, never guessed
    assert all(held[k] == {small, large} for k in of_both)
    scopes = tracing.device_scopes()
    assert set(held) == set(scopes)
    assert {scopes[k] for k in of_both} >= {"model"}


def test_device_scopes_is_what_it_was_beside_the_by_program_table(two_variants):
    """Extending the table builder moved nothing in ``device_scopes()``: it
    is the merge of ``scopes_of_hlo`` over each noted program's text, an
    instruction two programs scope differently reading ``mixed``."""
    expected = {}
    for text in two_variants.values():
        for k, scope in tracing.scopes_of_hlo(text).items():
            expected[k] = scope if expected.get(k, scope) == scope else "mixed"
    before = tracing.device_scopes()
    tracing.device_programs()
    assert before == expected == tracing.device_scopes()
    assert set(tracing._scope_cache) == set(two_variants)   # one compile each
    tracing.clear()
    assert tracing.device_programs() == {} == tracing.device_scopes()


# -- training --------------------------------------------------------------

@pytest.fixture(scope="module")
def trainer():
    topo_mod.reset_topology()
    model = TransformerLM(gpt2_config(
        "125m", vocab_size=128, hidden_size=64, num_layers=2, num_heads=4,
        max_seq_len=32, remat=True))
    engine = deepspeed_tpu.initialize(model=model, config={
        "train_batch_size": 8, "steps_per_print": 0,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
        "gradient_clipping": 1.0, "mesh": {"data": 8}})[0]
    rng = np.random.default_rng(0)
    batch = {"input_ids": jnp.asarray(rng.integers(0, 128, (8, 32), dtype=np.int32))}
    return engine, batch


def test_train_step_spans_and_device_scopes(trainer, session):
    engine, batch = trainer
    step0 = engine.global_steps
    for _ in range(2):
        engine.train_batch(iter([batch]))
    spans = tracing.snapshot()
    named = by_name(spans)
    assert [s.attrs["step"] for s in named["engine.train_batch"]] == [
        step0, step0 + 1]
    for name in ("engine.next_batch", "engine.enqueue"):
        assert len(named[name]) == 2
        assert all(chain(spans, s)[-1] == "engine.train_batch"
                   for s in named[name])
    assert "engine.train_batch" in [n for n, _, _ in stop(session)]
    scopes = tracing.device_scopes()
    found = set(scopes.values())
    assert {"optimizer", "bwd", "fwd", "remat"} <= found
    assert tracing.device_scopes() is not scopes      # merged anew, cached below
    assert tracing.device_scopes() == scopes


def test_optimizer_scope_is_on_the_updates_equations(trainer):
    engine, batch = trainer
    closed = jax.make_jaxpr(
        lambda *a: engine._fused_step_fn(*a))(*engine._fused_step_args(batch))
    stacks = [str(e.source_info.name_stack) for e in _iter_eqns(closed.jaxpr)]
    opt = [s for s in stacks if "optimizer" in s.split("/")]
    assert opt and any("attn" in s for s in stacks) and any(
        "lm_head_loss" in s for s in stacks)
    # the moments' updates (sqrt of the second moment) sit under the scope
    sqrt = [str(e.source_info.name_stack) for e in _iter_eqns(closed.jaxpr)
            if e.primitive.name in ("sqrt", "rsqrt")
            and "attn" not in str(e.source_info.name_stack)
            and "mlp" not in str(e.source_info.name_stack)
            and "lm_head_loss" not in str(e.source_info.name_stack)]
    assert sqrt and all("optimizer" in s for s in sqrt)


# -- names -----------------------------------------------------------------

def kernel_names(fn, *args):
    return [e.params["name"] for e in _iter_eqns(jax.make_jaxpr(fn)(*args).jaxpr)
            if e.primitive.name == "pallas_call"]


def test_flash_kernels_are_named():
    from deepspeed_tpu.ops.transformer.flash_attention import flash_attention

    q = jnp.ones((1, 128, 2, 64), jnp.float32)
    loss = lambda x: jnp.sum(flash_attention(x, x, x, causal=True))   # noqa: E731
    assert kernel_names(jax.grad(loss), q) == ["flash_fwd", "flash_bwd"]


def test_paged_kernel_is_named():
    from deepspeed_tpu.ops.transformer.paged_attention import paged_decode_attention

    pool = jnp.ones((2, 8, 16, 64))
    tables, lens = jnp.zeros((3, 4), jnp.int32), jnp.ones((3,), jnp.int32)
    assert kernel_names(
        lambda q: paged_decode_attention(q, pool, pool, tables, lens),
        jnp.ones((3, 2, 64))) == ["paged_decode"]


def test_fused_ce_kernels_are_named():
    from deepspeed_tpu.ops.transformer.fused_ce import head_nll

    x, w = jnp.ones((1, 128, 128)), jnp.ones((256, 128))
    labels = jnp.zeros((1, 128), jnp.int32)
    names = kernel_names(
        jax.grad(lambda a: jnp.sum(head_nll(a, w, labels, vocab_major=True))),
        x)
    assert names == ["fused_ce_fwd", "fused_ce_bwd"]


def conv_round(window, x, taps):
    from deepspeed_tpu.ops.transformer import linear_attention as la

    with jax.named_scope("attn"), jax.named_scope("delta_attn"):
        return la.conv_decode(window, 0, jnp.asarray([2, 0], jnp.int32), x,
                              taps, None, jnp.asarray([False, True]))


def test_the_convolutions_kernel_is_named_and_says_what_it_moves():
    """``conv_decode`` by its name in the jaxpr, and on the record of the
    program that bound it with what it said of the call: the bytes of a
    slot's window, the channels, the taps, a cell's channels and the slots a
    cell holds."""
    from deepspeed_tpu.ops.transformer import linear_attention as la

    args = (jnp.ones((2, 5, 3, 256), jnp.bfloat16),
            jnp.ones((2, 256), jnp.bfloat16), jnp.ones((4, 256), jnp.bfloat16))
    assert kernel_names(lambda *a: conv_round(*a), *args) == ["conv_decode"]
    # the call sits behind a jit of its own: a trace it has cached binds
    # nothing again
    la._conv_call.clear_cache()
    mark = tracing.clock_ns()
    jax.jit(conv_round).lower(*args).compile()
    rec, = [r for r in tracing.builds()
            if r.end > mark and "conv_round" in r.attrs["program"]]
    (calls, seconds), = rec.attrs["kernels"].values()
    assert list(rec.attrs["kernels"]) == ["conv_decode"]
    assert calls == 1 and 0 < seconds < rec.attrs["trace_s"]
    assert rec.attrs["kernel_attrs"] == {"conv_decode": dict(
        window_slot_bytes=3 * 256 * 2, channels=256, taps=4, lanes=256,
        slots=5)}


@pytest.mark.parametrize("vocab, path", [(256, "fused"), (200, "plain")])
def test_the_train_step_says_which_head_loss_it_took(vocab, path, session):
    """``head_loss`` on the step's ``engine.enqueue`` spans: ``fused`` where
    the head's shapes fit its kernels, ``plain`` where they do not (here a
    vocabulary that is no multiple of 128)."""
    topo_mod.reset_topology()
    model = TransformerLM(gpt2_config(
        "125m", vocab_size=vocab, hidden_size=128, num_layers=1, num_heads=2,
        max_seq_len=128))
    engine = deepspeed_tpu.initialize(model=model, config={
        "train_batch_size": 8, "steps_per_print": 0,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
        "mesh": {"data": 8}})[0]
    batch = {"input_ids": jnp.zeros((8, 128), jnp.int32)}
    for _ in range(2):       # the second step is not traced anew and says it too
        engine.train_batch(iter([batch]))
    topo_mod.reset_topology()
    enqueue = by_name(tracing.snapshot())["engine.enqueue"]
    assert [s.attrs["head_loss"] for s in enqueue] == [path, path]


@pytest.mark.parametrize("op_name, scope", [
    ("jit(fused_step)/optimizer/sub", "optimizer"),
    ("jit(fused_step)/jvp(mlp)/dot_general", "fwd"),
    ("jit(fused_step)/jvp()/while/body/closed_call/attn/dot_general", "fwd"),
    ("jit(fused_step)/transpose(jvp(mlp))/dot_general", "bwd"),
    ("jit(s)/transpose(jvp())/while/body/closed_call/checkpoint/attn/mul", "bwd"),
    ("jit(s)/transpose(jvp())/while/body/closed_call/checkpoint/"
     "rematted_computation/attn/flash_fwd/pallas_call", "remat"),
    ("jit(ragged)/while/body/attn/kv_write/scatter", "kv_write"),
    ("jit(ragged)/while/body/attn/paged_attn/paged_decode/pallas_call",
     "paged_attn"),
    ("jit(ragged)/sample/cond/branch_0_fun/argmax", "sample"),
    ("jit(ragged)/while/body/mlp/dot_general", "model"),
    ("jit(ragged)/while/body/dynamic_update_slice", "unscoped"),
    ("jit(ragged)/kv_carry/while/body/dynamic_update_slice", "kv_carry"),
    ("jit(ragged)/kv_carry/while/body/closed_call/attn/kv_write/scatter",
     "kv_write"),
    ("jit(ragged)/kv_carry/while/body/closed_call/mlp/dot_general", "model"),
    # the convolution's kernel is its caller's: the KDA sublayer's, the mixer's
    ("jit(ragged)/kv_carry/while/body/closed_call/attn/delta_attn/conv_decode/"
     "pallas_call", "delta_attn"),
    ("jit(ragged)/kv_carry/while/body/closed_call/attn/ssm_mixer/conv_decode/"
     "pallas_call", "ssm_mixer"),
    ("jit(step)/optimizers/sub", "unscoped"),
    # the last component is the primitive and marks nothing: an array's
    # transpose is no backward pass, in a serving program or a forward
    ("jit(ragged)/kv_carry/while/body/closed_call/attn/linear_attn/transpose",
     "linear_attn"),
    ("jit(ragged)/while/body/attn/transpose", "model"),
    ("jit(fused_step)/jvp(attn)/transpose", "fwd"),
    ("jit(fused_step)/transpose(jvp(attn))/transpose", "bwd"),
    ("jit(s)/transpose(jvp())/while/body/closed_call/checkpoint/"
     "rematted_computation/attn/transpose", "remat"),
    ("jit(ragged)/transpose", "unscoped"),
    ("transpose", "unscoped"),
    ("optimizer", "unscoped"),
    ("", "unscoped"),
])
def test_classify(op_name, scope):
    assert tracing.classify(op_name) == scope


@pytest.mark.parametrize("line, key", [
    ("%fusion.12 = bf16[8,128]{1,0:T(8,128)(2,1)} fusion(%p0), kind=kLoop",
     "fusion.12 bf16[8,128]"),
    ("  ROOT %flash_fwd.1 = (bf16[8,16,1024,64]{3,2,1,0}, f32[8,16,1024,1]{3,2,1,0}) "
     'custom-call(%a), custom_call_target="tpu_custom_call"',
     "flash_fwd.1 bf16[8,16,1024,64]"),
    ("  all-gather-start.3 = f32[] all-gather-start(x)", "all-gather-start.3 f32[]"),
    ("dot_general.1", "dot_general.1"),
])
def test_op_key(line, key):
    assert tracing.op_key(line) == key


def test_scopes_of_hlo_reads_op_name_and_marks_conflicts():
    text = "\n".join([
        'ENTRY %main {',
        '  %fusion.1 = f32[4]{0} fusion(%p), kind=kLoop, metadata={op_name='
        '"jit(s)/optimizer/mul" source_file="x.py"}',
        '  %fusion.2 = f32[4]{0} fusion(%p), kind=kLoop, metadata={op_name='
        '"jit(s)/transpose(jvp(mlp))/dot_general"}',
        '  %copy.3 = f32[4]{0} copy(%fusion.2)',
        '}'])
    scopes = tracing.scopes_of_hlo(text)
    assert scopes["fusion.1 f32[4]"] == scopes["fusion.1"] == "optimizer"
    assert scopes["fusion.2 f32[4]"] == "bwd" and scopes["copy.3"] == "unscoped"


def test_only_the_recorder_touches_profiler_annotations():
    hits = subprocess.run(
        ["grep", "-rln", "TraceAnnotation", os.path.join(REPO, "deepspeed_tpu")],
        capture_output=True, text=True).stdout.split()
    assert [os.path.relpath(h, REPO) for h in hits] == [
        "deepspeed_tpu/utils/tracing.py"]
    assert not os.path.exists(os.path.join(REPO, "deepspeed_tpu/utils/nvtx.py"))
