"""Block-level prefix caching tests (docs/PREFIX_CACHING.md): block-manager
invariants (refcounts, LRU eviction, copy-on-write, dedup), cache-hit vs cold
bitwise-equal logits, and the fixed-shape regression bound
(``ragged_cache_size <= 4``) under a shared-prefix serving workload."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.v2 import InferenceEngineV2
from deepspeed_tpu.inference.v2.ragged_manager import (BlockedKVCache,
                                                       SequenceDescriptor)
from deepspeed_tpu.models import build_model
from deepspeed_tpu.analysis import assert_trace_bounds


@pytest.fixture(scope="module")
def setup():
    m = build_model("llama-tiny", vocab_size=128, hidden_size=64, num_layers=2,
                    num_heads=4, num_kv_heads=2, intermediate_size=128,
                    max_seq_len=128)
    params = m.init_params(jax.random.PRNGKey(0))
    return m, params


def _engine(m, params, **kw):
    kw.setdefault("max_seqs", 4)
    kw.setdefault("max_seq_len", 128)
    kw.setdefault("prefill_chunk", 16)
    kw.setdefault("block_size", 16)
    kw.setdefault("token_budget", 16)
    return InferenceEngineV2(m, params, paged=True, **kw)


class TestBlockManagerInvariants:
    """Host-side manager semantics — no device work."""

    def _mgr(self, num_blocks=17, bs=4, maxb=8):
        return BlockedKVCache(num_blocks, bs, maxb, prefix_cache=True)

    def _prefill(self, mgr, desc, tokens):
        """Simulate the engine's bookkeeping for a full prefill of tokens."""
        skipped = mgr.lookup(desc, tokens)
        desc.history.extend(tokens[:skipped])
        mgr.ensure(desc, len(tokens))
        desc.history.extend(tokens[skipped:])
        desc.seen_tokens = len(tokens)
        mgr.register(desc)

    def test_refcount_lifecycle_and_full_release(self):
        mgr = self._mgr()
        toks = list(range(10))  # 2 full blocks + 2 tokens
        a = SequenceDescriptor(uid=1, slot=0)
        self._prefill(mgr, a, toks)
        assert all(mgr.refcount(b) == 1 for b in a.blocks)
        b = SequenceDescriptor(uid=2, slot=1)
        skipped = mgr.lookup(b, toks)
        assert skipped == 8 and b.blocks == a.blocks[:2]
        assert mgr.refcount(a.blocks[0]) == 2
        mgr.check_invariants([a, b])
        mgr.free(b)
        assert all(mgr.refcount(x) == 1 for x in a.blocks)
        mgr.free(a)
        assert not mgr._ref  # refcounts never negative, all released
        # cached blocks park in the LRU; forcing eviction returns the pool
        # to its initial capacity
        assert mgr.cached_blocks == 2
        mgr.flush_cache()
        assert mgr.free_blocks == mgr.num_blocks - 1
        assert mgr.cached_blocks == 0
        mgr.check_invariants([])

    def test_double_free_is_loud(self):
        mgr = self._mgr()
        d = SequenceDescriptor(uid=1, slot=0)
        mgr.ensure(d, 5)
        blocks = list(d.blocks)
        mgr.free(d)
        d.blocks = blocks  # simulate a bookkeeping bug
        with pytest.raises((AssertionError, KeyError)):
            mgr.free(d)

    def test_chained_keys_are_prefix_exact(self):
        """A block's key embeds its whole prefix: an identical block after a
        DIFFERENT first block must not hit."""
        mgr = self._mgr()
        a = SequenceDescriptor(uid=1, slot=0)
        self._prefill(mgr, a, [1, 1, 1, 1, 2, 2, 2, 2])
        probe = SequenceDescriptor(uid=2, slot=1)
        assert mgr.lookup(probe, [9, 9, 9, 9, 2, 2, 2, 2]) == 0
        probe2 = SequenceDescriptor(uid=3, slot=2)
        # matching first block, diverging second: one block mapped
        assert mgr.lookup(probe2, [1, 1, 1, 1, 9, 9, 9, 9, 9]) == 4
        mgr.check_invariants([a, probe2])

    def test_cow_never_mutates_shared_block(self):
        mgr = self._mgr()
        a = SequenceDescriptor(uid=1, slot=0)
        self._prefill(mgr, a, [1, 2, 3, 4, 5, 6, 7, 8])
        b = SequenceDescriptor(uid=2, slot=1)
        mgr.lookup(b, [1, 2, 3, 4, 5, 6, 7, 8])
        shared = list(a.blocks)
        src, dst = mgr.copy_on_write(b, 1)
        assert src == shared[1] and dst not in shared
        assert a.blocks == shared  # the sharer's mapping is untouched
        assert mgr.refcount(src) == 1 and mgr.refcount(dst) == 1
        assert b.blocks == [shared[0], dst]
        mgr.check_invariants([a, b])

    def test_dedup_collapses_identical_blocks(self):
        """Two sequences prefilling the same prompt concurrently (neither
        could hit the other's in-flight blocks) converge onto one copy when
        the second registers."""
        mgr = self._mgr()
        toks = [1, 2, 3, 4, 5, 6, 7, 8]
        a = SequenceDescriptor(uid=1, slot=0)
        b = SequenceDescriptor(uid=2, slot=1)
        mgr.ensure(a, 8)
        mgr.ensure(b, 8)  # distinct blocks
        assert not set(a.blocks) & set(b.blocks)
        for d in (a, b):
            d.history.extend(toks)
            d.seen_tokens = 8
        mgr.register(a)
        mgr.register(b)
        assert b.blocks == a.blocks  # adopted the canonical copy
        assert mgr.refcount(a.blocks[0]) == 2
        assert mgr.stats["dedup_blocks"] == 2
        mgr.check_invariants([a, b])

    def test_lru_eviction_is_leaf_first_and_exact(self):
        """Allocation pressure reclaims cached blocks leaf-first (a chain
        never dangles) and evicted prefixes stop hitting."""
        mgr = BlockedKVCache(num_blocks=9, block_size=4, max_blocks_per_seq=8,
                             prefix_cache=True)  # 8 usable
        a = SequenceDescriptor(uid=1, slot=0)
        self._prefill(mgr, a, [1, 1, 1, 1, 2, 2, 2, 2])  # chain of 2
        mgr.free(a)  # both cached, unreferenced
        assert mgr.free_blocks == 8
        # consume the pool: 6 truly-free blocks, then eviction must kick in
        b = SequenceDescriptor(uid=2, slot=1)
        mgr.ensure(b, 8 * 4 - 4)  # 7 blocks > 6 free → one eviction
        assert mgr.stats["evicted_blocks"] == 1
        # the LEAF (second chain block) went first: the root still hits
        probe = SequenceDescriptor(uid=3, slot=2)
        assert mgr.lookup(probe, [1, 1, 1, 1, 2, 2, 2, 2, 9]) == 4
        mgr.free(probe)
        mgr.free(b)
        mgr.flush_cache()
        assert mgr.free_blocks == 8
        mgr.check_invariants([])

    def test_lookup_caps_at_prompt_minus_one(self):
        """A full-prompt hit must leave one token to prefill — the engine
        needs its logits."""
        mgr = self._mgr()
        a = SequenceDescriptor(uid=1, slot=0)
        self._prefill(mgr, a, [5, 6, 7, 8])
        b = SequenceDescriptor(uid=2, slot=1)
        assert mgr.lookup(b, [5, 6, 7, 8]) == 3
        assert len(b.blocks) == 1
        mgr.check_invariants([a, b])


class TestPrefixCacheEngine:
    def test_hit_bitwise_equals_cold(self, setup):
        """Cached-prefix serving produces BITWISE-identical logits to a cold
        run of the same prompt: every row — prefill or decode — runs as its
        own length-1 sequence against the pool through the same compiled
        program, so skipping cached rows cannot perturb the rest."""
        m, params = setup
        rng = np.random.default_rng(0)
        prefix = rng.integers(0, 128, (32,)).tolist()  # 2 full blocks
        p1 = prefix + rng.integers(0, 128, (10,)).tolist()
        p2 = prefix + rng.integers(0, 128, (7,)).tolist()
        warm = _engine(m, params)
        cold = _engine(m, params, prefix_cache=False)
        w1, c1 = warm.put([1], [p1]), cold.put([1], [p1])
        np.testing.assert_array_equal(np.asarray(w1[1]), np.asarray(c1[1]))
        assert warm.prefix_cache_stats()["hits"] == 0  # nothing cached yet
        w2, c2 = warm.put([2], [p2]), cold.put([2], [p2])
        np.testing.assert_array_equal(np.asarray(w2[2]), np.asarray(c2[2]))
        s = warm.prefix_cache_stats()
        assert s["hits"] == 1 and s["skipped_prefill_tokens"] == 32
        # decode trajectories stay bitwise-equal for hit AND cold-admitted uid
        out_w = {1: w1[1], 2: w2[2]}
        out_c = {1: c1[1], 2: c2[2]}
        for _ in range(4):
            toks = {u: int(np.argmax(v)) for u, v in out_w.items()}
            assert toks == {u: int(np.argmax(v)) for u, v in out_c.items()}
            out_w = warm.decode_step(toks)
            out_c = cold.decode_step(toks)
            for u in toks:
                np.testing.assert_array_equal(np.asarray(out_w[u]),
                                              np.asarray(out_c[u]))
        warm.block_mgr.check_invariants(warm.state.seqs.values())

    def test_full_prompt_rehit_cow_bitwise(self, setup):
        """Admitting the EXACT prompt of a live sequence: every prompt block
        hits, the final token recomputes through a copy-on-write block, and
        both sequences keep bitwise-cold logits."""
        m, params = setup
        rng = np.random.default_rng(1)
        p = rng.integers(0, 128, (32,)).tolist()  # exactly 2 full blocks
        warm = _engine(m, params)
        cold = _engine(m, params, prefix_cache=False)
        w1, c1 = warm.put([1], [p]), cold.put([1], [p])
        w2 = warm.put([2], [p])  # uid 1 still live → shared → COW
        s = warm.prefix_cache_stats()
        assert s["cow_copies"] == 1 and s["skipped_prefill_tokens"] == 31
        np.testing.assert_array_equal(np.asarray(w2[2]), np.asarray(c1[1]))
        # the sharer's decode is unaffected by the other sequence's COW
        tok = {1: int(np.argmax(w1[1]))}
        ow, oc = warm.decode_step(dict(tok)), cold.decode_step(dict(tok))
        np.testing.assert_array_equal(np.asarray(ow[1]), np.asarray(oc[1]))
        warm.block_mgr.check_invariants(warm.state.seqs.values())

    def test_disable_flag_and_cold_path(self, setup):
        """prefix_cache=False keeps the original allocator behavior: no
        lookups, no index, stats empty (the bench's disable configuration)."""
        m, params = setup
        rng = np.random.default_rng(2)
        p = rng.integers(0, 128, (40,)).tolist()
        eng = _engine(m, params, prefix_cache=False)
        eng.put([1], [p])
        eng.put([2], [p])  # identical prompt: NO reuse when disabled
        assert eng.prefix_cache_stats() == {}
        assert eng.block_mgr.stats["lookups"] == 0
        assert eng.block_mgr.cached_blocks == 0
        assert not set(eng.state.seqs[1].blocks) & set(eng.state.seqs[2].blocks)

    def test_free_blocks_return_after_flush_with_eviction_forced(self, setup):
        m, params = setup
        rng = np.random.default_rng(3)
        eng = _engine(m, params, num_blocks=33)  # 32 usable
        for u in range(6):
            eng.put([u], [rng.integers(0, 128, (40,)).tolist()], greedy=True)
            eng.flush(u)
        eng.block_mgr.check_invariants([])
        eng.block_mgr.flush_cache()
        assert eng.block_mgr.free_blocks == 32
        assert eng.block_mgr.cached_blocks == 0

    def test_ragged_trace_bound_under_shared_prefix_workload(self, setup):
        """REGRESSION: the compiled ragged-step trace count must stay <= 4
        (two shapes × two greedy modes) under a mixed shared-prefix workload
        with hits, misses, COW, eviction, and flush/readmit churn — the cache
        is host-side bookkeeping and must add ZERO compiled programs."""
        m, params = setup
        rng = np.random.default_rng(4)
        eng = _engine(m, params, max_seqs=4, num_blocks=41,
                      token_budget=32)  # token_budget > max_seqs: both shapes
        prefix = rng.integers(0, 128, (32,)).tolist()
        uid = 0
        for round_ in range(3):
            uids = []
            for _ in range(3):
                tail = rng.integers(0, 128,
                                    (int(rng.integers(3, 20)),)).tolist()
                prompt = prefix + tail if round_ % 2 == 0 else \
                    rng.integers(0, 128, (24,)).tolist()  # miss rounds too
                uid += 1
                uids.append(uid)
                eng.put([uid], [prompt], greedy=True)
            out = {u: 1 for u in uids}
            for step in range(3):
                greedy = step % 2 == 0  # exercise BOTH greedy modes
                out = eng.decode_step(
                    {u: int(v) if np.ndim(v) == 0 else int(np.argmax(v))
                     for u, v in out.items()}, greedy=greedy)
            for u in uids:
                eng.flush(u)
        s = eng.prefix_cache_stats()
        assert s["hits"] > 0  # the workload really exercised the cache
        assert_trace_bounds(eng)
        eng.block_mgr.check_invariants(eng.state.seqs.values())

    def test_monitor_events_surface(self, setup):
        m, params = setup
        rng = np.random.default_rng(5)
        p = rng.integers(0, 128, (20,)).tolist()
        eng = _engine(m, params)
        eng.put([1], [p], greedy=True)
        eng.put([2], [p], greedy=True)
        events = eng.monitor_events(step=7)
        labels = {e[0] for e in events}
        assert "inference/prefix_cache/hit_rate" in labels
        assert "inference/prefix_cache/skipped_prefill_tokens" in labels
        assert all(isinstance(v, float) and s == 7 for _, v, s in events)
        # the event list feeds MonitorMaster.write_events directly
        from deepspeed_tpu.monitor import MonitorMaster

        MonitorMaster({}).write_events(events)  # all sinks disabled: no-op


def test_shared_prefix_serve_smoke():
    """Tier-1 smoke: one shared-prefix serve step end-to-end on CPU — a
    system-prompt workload admits two requests, the second hits the cache,
    skips its prefix prefill, and decodes one greedy token."""
    m = build_model("llama-tiny", vocab_size=64, hidden_size=32, num_layers=1,
                    num_heads=2, num_kv_heads=2, intermediate_size=64,
                    max_seq_len=64)
    params = m.init_params(jax.random.PRNGKey(0))
    eng = InferenceEngineV2(m, params, paged=True, max_seqs=2, max_seq_len=64,
                            prefill_chunk=8, block_size=8, token_budget=8)
    rng = np.random.default_rng(6)
    system_prompt = rng.integers(0, 64, (16,)).tolist()
    t1 = eng.put([1], [system_prompt + [3, 4]], greedy=True)
    t2 = eng.put([2], [system_prompt + [5]], greedy=True)
    s = eng.prefix_cache_stats()
    assert s["hits"] == 1 and s["skipped_prefill_tokens"] == 16
    out = eng.decode_step({1: int(t1[1]), 2: int(t2[2])}, greedy=True)
    assert set(out) == {1, 2}
    assert_trace_bounds(eng)
    eng.block_mgr.check_invariants(eng.state.seqs.values())
