"""Two classes of KV blocks (docs/SERVING.md "Block classes"): the allocator
by class, ``paged_decode`` with a row's lower bound, the engine's feed and
dispatch attrs of a model with window layers, and what the engine refuses for
a bounded class."""

import functools
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.v2 import InferenceEngineV2
from deepspeed_tpu.inference.v2.engine_v2 import feed_layout
from deepspeed_tpu.inference.v2.ragged_manager import (
    BlockedKVCache, SequenceDescriptor, WindowBlocks)
from deepspeed_tpu.models import TransformerLM
from deepspeed_tpu.models.transformer import (TransformerConfig, window_frame)
from deepspeed_tpu.ops.transformer import paged_attention as pa
from deepspeed_tpu.resilience.errors import (ContextOverflowError,
                                             EngineUsageError,
                                             PoolExhaustedError)

BS, W = 16, 32


def tiny(**kw):
    base = dict(
        vocab_size=256, hidden_size=64, num_layers=4, num_heads=4,
        num_kv_heads=2, head_dim_override=16, intermediate_size=32,
        dense_intermediate_size=96, max_seq_len=160, pos_embedding="rope",
        norm="rmsnorm", activation="swiglu", tie_embeddings=False,
        layer_types=("window_attn", "window_attn", "full_attn", "window_attn"),
        sliding_window=W, qk_norm=True, attn_output_gate=True, post_norms=True,
        embed_scale=8.0, num_dense_layers=1, num_experts=4, moe_top_k=2,
        moe_router="group_limited", moe_router_width=8, moe_score_scale=2.826,
        moe_shared_size=32, linear_chunk=16)
    return TransformerLM(TransformerConfig(**{**base, **kw}))


def engine(model=None, **kw):
    base = dict(dtype=jnp.float32, max_seqs=4, max_seq_len=160, block_size=BS,
                token_budget=36, prefill_chunk=32, prefix_cache=False)
    return InferenceEngineV2(model or tiny(), **{**base, **kw})


# -- the model's declaration -------------------------------------------------

def test_the_model_declares_its_classes():
    cfg = tiny().config
    assert cfg.class_layers == {"full": 1, "window": 3}
    assert cfg.bounded_cache and not cfg.holds_state
    kinds = cfg.cache_kinds
    assert kinds["window_attn"] == (("kv_blocks", 2 * 2 * 32, W),)
    assert kinds["full_attn"] == (("kv_blocks", 2 * 2 * 32),)
    # a dense layer is a group of its own; equal types and feed-forwards run
    assert [(t, n) for _, t, n, _ in cfg.type_runs] == [
        ("window_attn", 1), ("window_attn", 1), ("full_attn", 1),
        ("window_attn", 1)]
    assert cfg.group_is_dense == (True, False, False, False)
    plain = TransformerConfig()
    assert plain.class_layers == {"full": plain.num_layers}
    assert not plain.bounded_cache
    with pytest.raises(ValueError, match="sliding_window"):
        TransformerConfig(num_layers=1, layer_types=("window_attn",))


def test_parameters_are_counted_as_built():
    model = tiny()
    params = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    assert sum(x.size for x in jax.tree.leaves(params)) \
        == model.config.num_parameters
    pools = jax.eval_shape(lambda: model.init_kv_pool(
        {"full": 9, "window": 5}, BS))
    assert {k: v.shape for k, v in pools.items()} == {
        "full": (1, 2, 9, BS, 32), "window": (3, 2, 5, BS, 32)}
    with pytest.raises(ValueError, match="each class"):
        model.init_kv_pool({"full": 9}, BS)


# -- the allocator by class ---------------------------------------------------

def test_a_sequence_never_holds_more_than_the_bounds_blocks():
    """Decoding far past the window: the held range slides, its length stays
    within the window's blocks plus the partial ends, and every block freed
    behind the window is one the pool can hand out again."""
    w = WindowBlocks(num_blocks=9, bound=W, width=6, block_size=BS)
    most = 0
    for n in range(1, 400):
        w.ensure(7, n)                 # the step writes token n - 1
        assert w.fill_row(7, np.zeros(6, np.int32)) \
            <= max(0, n - 1 - (W - 1)) // BS * BS   # the bound's block is held
        w.trim(7, n)                   # the next query sits at position n
        most = max(most, w.blocks_of(7))
        w.check_invariants([7])
    assert most <= W // BS + 1 == 3
    assert w.freed_behind == w.allocations - w.blocks_of(7)
    assert w.free_blocks == 8 - w.blocks_of(7)
    w.free(7)
    assert w.free_blocks == 8 and w.in_use == 0
    w.check_invariants()


def test_trim_keeps_what_the_oldest_possible_query_sees():
    w = WindowBlocks(num_blocks=20, bound=W, width=8, block_size=BS)
    w.ensure(1, 100)
    assert w.blocks_of(1) == 7
    # a query at 99 sees 68..99: blocks 4, 5, 6 (block 3 ends at 63)
    assert w.trim(1, 99) == 4 and w.blocks_of(1) == 3
    row = np.full(8, -1, np.int32)
    assert w.fill_row(1, row) == 4 * BS
    assert (row[:3] > 0).all() and (row[3:] == 0).all()
    # nothing more to free for an older query
    assert w.trim(1, 90) == 0
    # a step of more tokens than the table was sized for
    with pytest.raises(ContextOverflowError):
        w.ensure(1, 100 + 8 * BS)
    # a rollback gives trailing blocks back, not the leading ones
    w.ensure(1, 130)
    assert w.rollback(1, 100) == 2 and w.blocks_of(1) == 3
    w.check_invariants([1])


def test_the_manager_grows_frees_and_checks_both_classes():
    mgr = BlockedKVCache(12, BS, 10, window=(7, W, 6))
    a, b = SequenceDescriptor(1, 0), SequenceDescriptor(2, 1)
    mgr.ensure(a, 80)
    assert len(a.blocks) == 5 and mgr.window.blocks_of(1) == 5
    mgr.window.trim(1, 80)
    assert mgr.window.blocks_of(1) == 2
    mgr.check_invariants([a])
    # the window class is exhausted before the full one: a typed refusal,
    # and what the full class grew is kept for the retried step
    with pytest.raises(PoolExhaustedError, match="window-class"):
        mgr.ensure(b, 6 * BS)
    mgr.check_invariants([a, b])
    # preemption is a flush: both classes come back
    mgr.free(b)
    mgr.free(a)
    assert mgr.free_blocks == 11 and mgr.window.free_blocks == 6
    mgr.check_invariants([])
    with pytest.raises(ValueError, match="prefix_cache"):
        BlockedKVCache(12, BS, 10, prefix_cache=True, window=(7, W, 6))


def test_can_schedule_asks_the_scarcer_class():
    eng = engine(num_blocks={"full": 40, "window": 4})
    assert eng.can_schedule(1)          # a chunk of 32 tokens: two blocks
    assert not eng.can_schedule(2)      # three usable window blocks
    roomy = engine(num_blocks={"full": 3, "window": 40})
    assert roomy.can_schedule(1) and not roomy.can_schedule(2)
    assert eng.block_mgr.window.width == (W + 1 + 32) // BS + 2 == 6
    with pytest.raises(ValueError, match="a count a class"):
        engine(num_blocks={"full": 40, "window": 9, "other": 3})
    # one count for a model of two classes: refused by name, not in dict()
    with pytest.raises(ValueError, match=r"\['full', 'window'\]"):
        engine(num_blocks=4096)


# -- the engine ---------------------------------------------------------------

def test_the_feed_gains_the_window_fields_only_for_a_bounded_class():
    plain, n = feed_layout(36, 4, 10, False)
    bounded, m = feed_layout(36, 4, 10, False, 6)
    assert list(bounded)[:len(plain)] == list(plain)
    assert all(bounded[k] == plain[k] for k in plain)
    assert bounded["wtables"][1] == (36, 6) and bounded["wbase"][1] == (36,)
    assert m == n + 36 * 6 + 36
    assert "wtables" not in plain


@pytest.fixture
def session(tmp_path):
    """A profiler session: the span recorder is on exactly while it lasts."""
    from deepspeed_tpu.utils import tracing

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


def test_blocks_are_freed_behind_the_window_and_preemption_returns_them(
        session):
    """A prompt longer than the window in chunks, then decode: the window
    class frees as it goes, the full class follows the context, the dispatch
    spans carry the five attrs, and invariants hold after a preemption and a
    flush."""
    from deepspeed_tpu.utils import tracing

    eng = engine()
    mgr = eng.block_mgr
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, 256, 100).tolist()
    eng.put([5, 6], [prompt, prompt[:70]])
    for _ in range(20):
        eng.decode_step({5: 3, 6: 4})
    spans = [s for s in tracing.snapshot() if s.name == "engine.dispatch"]
    d = eng.state.seqs[5]
    assert len(d.blocks) == -(-d.seen_tokens // BS) == 8
    assert mgr.window.blocks_of(5) <= W // BS + 1
    assert mgr.window.freed_behind > 0
    last = spans[-1].attrs
    assert {"window_blocks", "full_blocks", "window_free", "full_free",
            "freed_behind"} <= set(last)
    assert last["window_blocks"] == mgr.window.in_use
    assert last["full_blocks"] + last["full_free"] == mgr.num_blocks - 1
    assert sum(s.attrs["freed_behind"] for s in spans) \
        == mgr.window.freed_behind
    mgr.check_invariants(eng.state.seqs.values())
    assert eng.preempt(5) == 8 + mgr.window.width - 3 \
        or mgr.window.blocks_of(5) == 0
    mgr.check_invariants(eng.state.seqs.values())
    eng.flush(6)
    mgr.check_invariants([])
    assert mgr.window.in_use == 0 and mgr.free_blocks == mgr.num_blocks - 1


def test_what_the_engine_refuses_for_a_bounded_class():
    model = tiny()
    for bad in (dict(prefix_cache=True), dict(decode_horizon=4),
                dict(prefix_cache=True, host_tier_blocks=8)):
        with pytest.raises(ValueError, match="bounded class"):
            engine(model, **bad)
    eng = engine(model)
    eng.put([1], [list(range(40))])
    assert eng.state.seqs[1].at_rest
    assert eng.swap_out(1) is False and eng.export_ready(1) is False
    with pytest.raises(EngineUsageError, match="bounded"):
        eng._get_fused()
    with pytest.raises(EngineUsageError, match="bounded"):
        eng._get_verify()
    with pytest.raises(ValueError, match="window="):
        model.forward_paged(eng.params, jnp.zeros((4, 1), jnp.int32), eng.kv,
                            jnp.zeros((4, 10), jnp.int32),
                            jnp.zeros((4,), jnp.int32))
    # held experts outside latent attention are a layer_types model's alone
    with pytest.raises(ValueError, match="held"):
        TransformerLM(TransformerConfig(num_experts=4,
                                        moe_router="group_limited"))


def test_chunks_and_rounds_agree_with_one_pass():
    """The same tokens through chunks of 32 then decode, and through decode
    alone from the first token: the logits agree, so what a chunk's tiles read
    from the window table is what the rounds read."""
    model = tiny()
    params = model.init_params(jax.random.PRNGKey(3))
    ids = np.random.default_rng(1).integers(0, 256, 90).tolist()
    a = engine(model, params=params)
    rows_a = [a.put([1], [ids[:80]])[1]]
    rows_a += [a.decode_step({1: t})[1] for t in ids[80:]]
    b = engine(model, params=params)
    rows_b = [b.put([1], [ids[:1]])[1]]
    rows_b += [b.decode_step({1: t})[1] for t in ids[1:]]
    np.testing.assert_allclose(np.stack(rows_a), np.stack(rows_b[79:]),
                               atol=2e-4, rtol=2e-4)


# -- the kernel's bound -------------------------------------------------------

def dense(q, pool, layer, tables, lens, first):
    """Masked dense attention over the rows' gathered contexts."""
    kvh, BS_, hd = pool.shape[1], pool.shape[3], pool.shape[4] // 2
    B, nh, _ = q.shape
    ctx = pool[layer][:, tables]                       # (kvh, B, MB, BS, row)
    ctx = jnp.moveaxis(ctx, 0, 3).reshape(B, -1, kvh, 2 * hd)
    k, v = ctx[..., :hd], ctx[..., hd:]
    at = jnp.arange(k.shape[1])[None]
    seen = (at < lens[:, None]) & (at >= first[:, None])
    s = jnp.einsum("bhgd,bthd->bhgt", q.reshape(B, kvh, nh // kvh, hd),
                   k) * hd ** -0.5
    p = jax.nn.softmax(jnp.where(seen[:, None, None], s, -1e30), -1)
    p = jnp.where((lens > 0)[:, None, None, None], p, 0.0)
    return jnp.einsum("bhgt,bthd->bhgd", p, v).reshape(B, nh, hd)


@pytest.fixture
def pool_and_rows(monkeypatch):
    monkeypatch.setenv("DSTPU_FORCE_PAGED_KERNEL", "1")
    B, nh, kvh, hd, MB, NB = 8, 8, 2, 64, 12, 40
    ks = jax.random.split(jax.random.PRNGKey(4), 4)
    pool = jax.random.normal(ks[0], (2, kvh, NB, 16, 2 * hd), jnp.float32)
    q = jax.random.normal(ks[1], (B, nh, hd), jnp.float32)
    tables = jax.random.permutation(ks[2], jnp.arange(1, NB))[:B * 3] \
        .reshape(B, 3)
    tables = jnp.concatenate(
        [tables, jax.random.randint(ks[3], (B, MB - 3), 1, NB)], axis=1)
    lens = jnp.asarray([1, 17, 0, 64, 129, 190, 192, 33], jnp.int32)
    first = jnp.asarray([0, 5, 0, 63, 100, 159, 64, 32], jnp.int32)
    return q, pool, tables.astype(jnp.int32), lens, first


def test_a_bounded_row_attends_from_its_bound(pool_and_rows):
    """Eight rows, bounds inside the first trip, on a trip's edge and trips
    in: the kernel (interpreted) against masked dense attention; a dead row
    gives zeros; the fold that writes the round's rows agrees with the write
    followed by the read."""
    q, pool, tables, lens, first = pool_and_rows
    assert pa.blocks_per_trip(pool) > 1         # bounds skip whole trips
    got = jax.jit(functools.partial(pa.paged_decode, layer=1))(
        q, pool, tables=tables, lens=lens, first=first)
    want = dense(q, pool, 1, tables, lens, first)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    assert not np.asarray(got[2]).any()
    # the XLA twin of the same call
    import os
    os.environ.pop("DSTPU_FORCE_PAGED_KERNEL")
    twin = pa.attend_rows(q, pool, 1, tables, lens, first=first)
    np.testing.assert_allclose(np.asarray(twin), np.asarray(want), atol=2e-5)


def test_the_fold_writes_a_bounded_rounds_rows(pool_and_rows):
    q, pool, tables, lens, first = pool_and_rows
    B, nh, hd = q.shape
    kvh = pool.shape[1]
    tables = tables.at[:, :].set(
        jnp.arange(1, 1 + B * 12, dtype=jnp.int32).reshape(B, 12) % 39 + 1)
    # rows apart: every row's last block its own
    last = (jnp.maximum(lens, 1) - 1) // 16
    tables = tables.at[jnp.arange(B), last].set(jnp.arange(1, B + 1) * 4)
    ks = jax.random.split(jax.random.PRNGKey(5), 2)
    k = jax.random.normal(ks[0], (B, kvh * hd), jnp.float32)
    v = jax.random.normal(ks[1], (B, kvh * hd), jnp.float32)
    assert pa.writes_live_rows(pool)
    out, new = jax.jit(lambda *a: pa.paged_decode(
        a[0], a[1], 1, a[2], a[3], new_rows=(a[4], a[5]), first=a[6]))(
        q.reshape(B, nh * hd), pool, tables, lens, k, v, first)
    kv = jnp.concatenate((k.reshape(B, kvh, hd), v.reshape(B, kvh, hd)), -1)
    blk = jnp.where(lens > 0, tables[jnp.arange(B), last], 0)
    written = pa.kv_write(pool, 1, blk, (jnp.maximum(lens, 1) - 1) % 16, kv)
    np.testing.assert_array_equal(np.asarray(new), np.asarray(written))
    want = dense(q, written, 1, tables, lens, first)
    np.testing.assert_allclose(np.asarray(out).reshape(B, nh, hd),
                               np.asarray(want), atol=2e-5)


def test_without_a_bound_the_kernel_is_bit_for_bit_the_parents(pool_and_rows):
    """The unbounded call traces to the program it was (no fourth scalar
    operand, the same equations in the kernel's body) and a bound of zero
    gives the same bits as no bound."""
    q, pool, tables, lens, _ = pool_and_rows
    def plain(*a):
        return pa.paged_decode(a[0], a[1], 1, a[2], a[3])

    def zero(*a):
        return pa.paged_decode(a[0], a[1], 1, a[2], a[3],
                               first=jnp.zeros_like(a[3]))

    np.testing.assert_array_equal(
        np.asarray(jax.jit(plain)(q, pool, tables, lens)),
        np.asarray(jax.jit(zero)(q, pool, tables, lens)))

    def call_of(fn):
        (eqn,) = [e for e in jax.make_jaxpr(fn)(q, pool, tables, lens).eqns
                  if e.primitive.name == "pallas_call"]
        return eqn

    unbounded, bounded = call_of(plain), call_of(zero)
    assert len(unbounded.invars) + 1 == len(bounded.invars)
    assert unbounded.params["grid_mapping"].num_index_operands == 3
    assert bounded.params["grid_mapping"].num_index_operands == 4
    B, nh, hd = q.shape
    k = jnp.zeros((B, pool.shape[1] * hd))
    fold = call_of(lambda *a: pa.paged_decode(
        a[0].reshape(B, nh * hd), a[1], 1, a[2], a[3], new_rows=(k, k)))
    # the kernel's traced body, every equation of it, at these shapes:
    # read-only and with the round's rows. PR 65 changed the unbounded kernel
    # on purpose (a trip multiplies in the pool's dtype, the fetches go round
    # a ring) and re-pinned it; before that it was the body PR 62's parent
    # (072ac8c) traced. Re-pin only when a PR changes the kernel on purpose
    digests = [hashlib.sha256(str(e.params["jaxpr"]).encode()).hexdigest()[:16]
               for e in (unbounded, fold)]
    assert digests == UNBOUNDED_BODY_SHA, digests
    assert str(bounded.params["jaxpr"]) != str(unbounded.params["jaxpr"])


UNBOUNDED_BODY_SHA = ["734eaa2fbc6f869c", "68fd655f3ec9e115"]


def test_the_window_frame_counts_from_the_first_held_block():
    tables = jnp.asarray([[3, 4, 0], [0, 0, 0]], jnp.int32)
    starts = jnp.asarray([70, 0], jnp.int32)
    limits = jnp.asarray([71, 0], jnp.int32)
    _, rel, lim, first = window_frame(tables, jnp.asarray([32, 0]), W, starts,
                                      limits)
    assert rel.tolist() == [38, 0] and lim.tolist() == [39, 0]
    assert first.tolist() == [70 - 31 - 32, 0]      # a dead row's are 0
