"""One packed feed, one launch (docs/SERVING.md "One packed feed"): a ragged
step's whole feed is ONE int32 host buffer, transferred once, and the
run-ahead merge is the ragged program's first line. (a) the layout round-trips
every field bit for bit, the float fields through a bitcast; (b) a decode round
and a mixed step each make exactly one ``jax.device_put`` of one array and one
compiled call, and ``engine.dispatch`` says so (``feed_arrays``, ``launches``,
``one_feed``); a step with a copy-on-write reads ``one_feed`` 0; (c) rounds fed
from ``prev``, from the host, and from both in one round give ``decode_step``'s
tokens, greedy and sampled; (d) the trace bound holds and the manifest knows no
merge program."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.analysis.program_audit import (GLOBAL_REGISTRY,
                                                  assert_trace_bounds,
                                                  check_manifest,
                                                  registered_program_names)
from deepspeed_tpu.inference.v2 import InferenceEngineV2
from deepspeed_tpu.inference.v2.engine_v2 import feed_layout, unpack_feed
from deepspeed_tpu.models import build_model
from deepspeed_tpu.models.transformer import TransformerConfig, TransformerLM
from deepspeed_tpu.serve import SamplingParams
from deepspeed_tpu.utils import tracing

PKG = os.path.dirname(os.path.abspath(deepspeed_tpu.__file__))
FIELDS = ("ids", "tables", "starts", "logit_rows", "slots", "seeds", "poss",
          "temps", "top_ks", "top_ps", "src_rows", "row_slots")


@pytest.fixture(scope="module")
def lm():
    m = build_model("llama-tiny", vocab_size=128, hidden_size=64,
                    num_layers=2, num_heads=4, num_kv_heads=2,
                    intermediate_size=128, max_seq_len=128)
    return m, m.init_params(jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def stateful_lm():
    """Two mixers in one stack: the engine holds state slots beside the pool,
    so its feed carries ``row_slots``."""
    m = TransformerLM(TransformerConfig(
        vocab_size=256, hidden_size=128, num_layers=2, num_heads=8,
        num_kv_heads=2, intermediate_size=192, max_seq_len=128,
        pos_embedding="rope", norm="rmsnorm", activation="swiglu",
        tie_embeddings=False, norm_eps=1e-6,
        layer_types=("sparse_attn", "linear_attn"), qk_norm=True,
        attn_output_gate=True, sparse_kernel_size=8, sparse_kernel_stride=4,
        sparse_window=32, sparse_init_blocks=1, sparse_topk=4,
        sparse_dense_len=64, sparse_block_size=16, linear_chunk=16))
    return m, m.init_params(jax.random.PRNGKey(3))


def engine(lm, **kw):
    m, params = lm
    kw = {**dict(max_seqs=4, max_seq_len=128, prefill_chunk=16, block_size=16,
                 token_budget=16, num_blocks=64), **kw}
    return InferenceEngineV2(m, params, paged=True, **kw)


def stateful_engine(lm):
    return engine(lm, dtype=jnp.float32, token_budget=36, prefill_chunk=32,
                  num_blocks=40, prefix_cache=False)


def prompts(n=3):
    rng = np.random.default_rng(0)
    return [rng.integers(0, 128, ln).tolist() for ln in (33, 30, 28)][:n]


@pytest.fixture
def session(tmp_path):
    """A profiler session: the span recorder is on exactly while it lasts."""
    tracing.clear()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        yield
    finally:
        if tracing.enabled():
            jax.profiler.stop_trace()
        tracing.clear()


@pytest.fixture
def puts(monkeypatch):
    """Every ``jax.device_put`` the engine makes, as the list of the leaves
    each call transferred."""
    calls = []
    real = jax.device_put

    def counted(x, *a, **kw):
        calls.append(jax.tree_util.tree_leaves(x))
        return real(x, *a, **kw)

    monkeypatch.setattr(jax, "device_put", counted)
    return calls


def dispatches():
    return [s for s in tracing.snapshot() if s.name == "engine.dispatch"]


# -- (a) the layout ----------------------------------------------------------

@pytest.mark.parametrize("case", ["decode_round", "mixed_step", "stateful"])
def test_layout_round_trips_every_field_bit_for_bit(case, lm, stateful_lm):
    eng = stateful_engine(stateful_lm) if case == "stateful" else engine(lm)
    rows = eng.max_seqs if case == "decode_round" else eng.token_budget
    layout, length = eng._feed_layout(rows)
    assert tuple(layout) == FIELDS
    *views, buf = eng._feed_scratch(("test", rows), rows)
    assert buf.dtype == np.int32 and buf.shape == (length,)
    assert sum(v.size for v in views) == length
    named = dict(zip(FIELDS, views))
    assert named["row_slots"].size == (rows if case == "stateful" else 0)
    # a fresh scratch reads zero, but for the rows no round feeds
    assert all(not v.any() for n, v in named.items() if n != "src_rows")
    assert (named["src_rows"] == -1).all()
    # the views ARE the buffer: filling a field copies nothing
    assert all(np.shares_memory(v, buf) for v in views if v.size)
    rng = np.random.default_rng(7)
    for name, v in named.items():
        if v.dtype == np.float32:
            # 0.7 and 0.9 are no short bit patterns: a value conversion in
            # place of a bitcast would show
            v[...] = rng.choice(np.float32([0.7, 0.9, 0.0, 1.0]), v.shape)
        else:
            v[...] = rng.integers(-2**31, 2**31 - 1, v.shape, dtype=np.int32)
    assert named["temps"].dtype == named["top_ps"].dtype == np.float32
    frozen = {n: v.copy() for n, v in named.items()}
    got = jax.jit(lambda feed: unpack_feed(feed, layout))(jax.device_put(buf))
    assert set(got) == set(FIELDS)     # (jit hands a dict back sorted)
    for name, want in frozen.items():
        have = np.asarray(got[name])
        assert have.dtype == want.dtype and have.shape == want.shape, name
        np.testing.assert_array_equal(have.view(np.int32),
                                      want.view(np.int32), err_msg=name)
    # a second hand-out of the same scratch is the same memory, zeroed
    again = eng._feed_scratch(("test", rows), rows)
    assert again.buf is buf and not again.ids.any()


def test_layout_is_a_function_of_shapes_alone():
    a, n = feed_layout(64, 64, 16, False)
    b, m = feed_layout(64, 64, 16, True)
    assert n == 64 * (1 + 16 + 1 + 1) + 7 * 64 and m == n + 64
    # the fields tile the buffer in order, with no gap
    assert [a[k][0].start for k in FIELDS] == [0] + [a[k][0].stop
                                                     for k in FIELDS[:-1]]
    assert a["row_slots"][0] == slice(n, n) and b["row_slots"][0].stop == m
    assert {k: v for k, v in a.items() if k != "row_slots"} == \
        {k: v for k, v in b.items() if k != "row_slots"}


# -- (b) one transfer, one launch ---------------------------------------------

@pytest.mark.parametrize("case", ["decode_round", "mixed_step", "stateful"])
def test_a_step_is_one_device_put_of_one_array_and_one_launch(
        case, lm, stateful_lm, session, puts):
    eng = stateful_engine(stateful_lm) if case == "stateful" else engine(lm)
    launched = []
    fn = eng._get_ragged()
    real = fn._fn

    def counted(*a, **kw):
        launched.append(1)
        return real(*a, **kw)

    fn._fn = counted    # the compiled callable itself, under the audit wrapper
    tok = int(eng.put([1], [prompts(1)[0]], greedy=True)[1])
    if case == "mixed_step":
        assert len(launched) == len(puts) == 3    # 33 tokens in chunks of 16
    else:
        puts.clear(), launched.clear(), tracing.clear()
        h1 = eng.decode_dispatch({1: tok})
        h2 = eng.decode_dispatch({1: None}, prev=h1)   # fed on the device
        h1.fetch(), h2.fetch()
        assert len(launched) == len(puts) == 2
    for leaves in puts:
        assert len(leaves) == 1 and leaves[0].dtype == np.int32 \
            and leaves[0].ndim == 1
    disp = dispatches()
    assert len(disp) == len(puts)
    for s in disp:
        assert (s.attrs["feed_arrays"], s.attrs["launches"],
                s.attrs["one_feed"]) == (1, 1, 1)
        enq, = [x for x in tracing.snapshot()
                if x.name == "engine.enqueue" and x.parent == s.id]
        assert s.start <= enq.start <= enq.end <= s.end


def test_a_step_with_a_copy_on_write_reads_one_feed_0(lm, session):
    eng = engine(lm)
    p = np.random.default_rng(1).integers(0, 128, 32).tolist()  # 2 full blocks
    eng.put([1], [p], greedy=True)
    tracing.clear()
    eng.put([2], [p], greedy=True)   # uid 1 live: shared, so copied on write
    s, = dispatches()
    assert s.attrs["cow_copies"] == 1
    assert (s.attrs["feed_arrays"], s.attrs["launches"],
            s.attrs["one_feed"]) == (1, 2, 0)
    # the round after it is one feed again: the counts are a step's own
    tracing.clear()
    eng.decode_dispatch({1: 5, 2: 7}).fetch()
    s, = dispatches()
    assert s.attrs["one_feed"] == 1


def test_fused_program_counts_its_eight_arrays(lm, session):
    """The K-position programs keep their own staging (ROADMAP queue 3): the
    counter shows it."""
    eng = engine(lm, decode_horizon=4)
    tok = int(eng.put([1], [prompts(1)[0]], greedy=True)[1])
    tracing.clear()
    eng.decode_multi({1: tok}, 4)
    s, = dispatches()
    assert (s.attrs["feed_arrays"], s.attrs["launches"],
            s.attrs["one_feed"]) == (8, 1, 0)


# -- (c) the merge inside the program -----------------------------------------

def reference(lm, sampled):
    """(engine, first tokens, tokens by uid) of the synchronous path: uids
    1, 2 take four ``decode_step``s, uid 3 joins at the third."""
    ref = engine(lm)
    first = admit(ref, sampled)
    want = {1: [], 2: [], 3: []}
    feed = {1: first[1], 2: first[2]}
    for step in range(4):
        if step == 2:
            feed[3] = first[3]
        feed = {u: int(t) for u, t in
                ref.decode_step(feed, greedy=True).items()}
        for u, t in feed.items():
            want[u].append(t)
    return ref, first, want


def admit(eng, sampled):
    first = {}
    for i, p in enumerate(prompts(3)):
        if sampled:
            eng.set_sampling(i + 1, SamplingParams(
                seed=11 + i, temperature=0.7, top_k=0, top_p=0.9))
        first[i + 1] = int(eng.put([i + 1], [p], greedy=True)[i + 1])
    return first


@pytest.mark.parametrize("sampled", [False, True], ids=["greedy", "sampled"])
def test_run_ahead_rounds_give_decode_steps_tokens(sampled, lm):
    """Rounds fed from the host, from ``prev``, and from both in one round,
    against the synchronous ``decode_step`` on a twin engine."""
    ref, first, want = reference(lm, sampled)
    if sampled:     # the sampling path ran: the tokens left the argmax
        assert (first, want) != reference(lm, False)[1:]
    eng = engine(lm)
    assert admit(eng, sampled) == first
    h1 = eng.decode_dispatch({1: first[1], 2: first[2]})     # from the host
    h2 = eng.decode_dispatch({1: None, 2: None}, prev=h1)    # from prev
    got1 = h1.fetch()
    # both in one round: uid 3 joins with the token its prefill gave
    h3 = eng.decode_dispatch({1: None, 2: None, 3: first[3]}, prev=h2)
    got2 = h2.fetch()
    h4 = eng.decode_dispatch({1: None, 2: None, 3: None}, prev=h3)
    got3, got4 = h3.fetch(), h4.fetch()
    assert {u: [g[u] for g in (got1, got2, got3, got4) if u in g]
            for u in (1, 2, 3)} == want
    # a restart after the pipe drained feeds from the host again
    assert eng.decode_dispatch(got4).fetch() == {
        u: int(t) for u, t in ref.decode_step(got4, greedy=True).items()}


def test_stateful_rounds_give_decode_steps_tokens(stateful_lm):
    """``row_slots`` rides the packed feed and the slot arrays stay donated."""
    ref, eng = stateful_engine(stateful_lm), stateful_engine(stateful_lm)
    p = prompts(1)[0]
    first = int(ref.put([1], [p], greedy=True)[1])
    assert int(eng.put([1], [p], greedy=True)[1]) == first
    want, t = [], first
    for _ in range(3):
        t = int(ref.decode_step({1: t}, greedy=True)[1])
        want.append(t)
    h1 = eng.decode_dispatch({1: first})
    h2 = eng.decode_dispatch({1: None}, prev=h1)
    got = [h1.fetch()[1]]
    h3 = eng.decode_dispatch({1: None}, prev=h2)
    assert got + [h2.fetch()[1], h3.fetch()[1]] == want


# -- (d) traces and the manifest ----------------------------------------------

def test_trace_bound_holds_with_and_without_prev(lm):
    eng = engine(lm)
    tok = int(eng.put([1], [prompts(1)[0]], greedy=True)[1])   # mixed shape
    h1 = eng.decode_dispatch({1: tok})                         # no prev
    h2 = eng.decode_dispatch({1: None}, prev=h1)               # prev
    h1.fetch()
    h3 = eng.decode_dispatch({1: None}, prev=h2)
    h2.fetch(), h3.fetch()
    eng.decode_step({1: 3}, greedy=True)                       # zeros again
    # two shapes of one greedy mode: ``prev`` has one shape, so it adds none
    assert eng.ragged_cache_size == 2
    eng.decode_step({1: 3})                                    # full logits
    assert eng.ragged_cache_size == 3
    rows = assert_trace_bounds(eng)
    assert ("engine_v2.ragged", 3, 4) in rows


def test_no_merge_program_is_registered_or_pinned():
    assert check_manifest([PKG]) == []
    names = set(registered_program_names([PKG]))
    assert "engine_v2.ragged" in names and "engine_v2.feed_merge" not in names
    assert "engine_v2.feed_merge" not in GLOBAL_REGISTRY.manifest()["programs"]
    assert not hasattr(InferenceEngineV2, "_get_feed_merge")
