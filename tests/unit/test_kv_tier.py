"""KV-cache tiering tests (docs/PREFIX_CACHING.md "Two-tier cache"):
host-tier allocator bookkeeping (demote/promote rekeying, leaf-first host
eviction, both-tier flush, the probe crossing the tier boundary), the
tier-conservation sanitizer with planted violations, engine swap-out /
swap-in round trips bitwise vs a never-swapped twin, the scheduler's
swap-vs-recompute cost model in all three ``swap_preemption`` modes
bitwise vs an unpressured untiered baseline, the tiering x resilience
matrix (engine loss with a live swap entry, detach/adopt migration of a
swap-resident victim, the v1->v2 rolling-update host-tier flush
regression), and the ``serve/kvtier/*`` metrics surface."""

from types import SimpleNamespace

import jax
import numpy as np
import pytest

from deepspeed_tpu.analysis.sanitizer import (SanitizerError,
                                              check_tier_conservation)
from deepspeed_tpu.inference.v2 import InferenceEngineV2
from deepspeed_tpu.inference.v2.ragged_manager import (_ROOT, BlockedKVCache,
                                                       SequenceDescriptor)
from deepspeed_tpu.models import build_model
from deepspeed_tpu.resilience import FaultInjector, RetryPolicy
from deepspeed_tpu.serve import (ContinuousBatchScheduler, EnginePool,
                                 RequestState, SamplingParams)
from deepspeed_tpu.serve.metrics import ServeMetrics
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


def _pressure_workload():
    """The swap-preemption pressure shape: four distinct prompts decoding
    long enough that a 12-block pool must preempt mid-decode, while a
    40-block pool never does (the bitwise baseline)."""
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, 127, 17).tolist() for _ in range(4)]
    return prompts, 40


def _run_sched(m, params, *, num_blocks, host_tier_blocks, swap=None,
               wrap=None, sampled=False, **sched_kw):
    eng = _engine(m, params, num_blocks=num_blocks,
                  host_tier_blocks=host_tier_blocks)
    sched_kw.setdefault("retry", RetryPolicy(max_attempts=5))
    sched = ContinuousBatchScheduler(
        eng if wrap is None else wrap(eng), sleep=lambda s: None,
        swap_preemption=swap, **sched_kw)
    prompts, gen = _pressure_workload()
    reqs = [sched.submit(p, max_new_tokens=gen, uid=100 + i,
                         sampling=(SamplingParams(temperature=0.8,
                                                  seed=200 + i)
                                   if sampled else None))
            for i, p in enumerate(prompts)]
    return sched, eng, reqs


_BASELINE = {}


def _baseline(m, params, sampled=False):
    """Untiered, unpressured oracle for the pressure workload (memoized:
    the counter-based per-request keys make pool size and preemption
    invisible in tokens, greedy or sampled — docs/SAMPLING.md)."""
    if sampled not in _BASELINE:
        sched, _, reqs = _run_sched(m, params, num_blocks=41,
                                    host_tier_blocks=0, sampled=sampled)
        sched.run_until_complete()
        assert all(r.state is RequestState.DONE for r in reqs)
        assert sched.metrics.preemptions == 0  # truly unpressured
        _BASELINE[sampled] = {r.uid: list(r.tokens) for r in reqs}
    return _BASELINE[sampled]


def _assert_bounds(eng):
    assert_trace_bounds(eng)


# ---------------------------------------------------------------------------
# allocator tier bookkeeping (host-side, no device work)
# ---------------------------------------------------------------------------

class TestTierAllocator:
    def _mgr(self, num_blocks=9, host=8):
        return BlockedKVCache(num_blocks, block_size=4, max_blocks_per_seq=8,
                              prefix_cache=True, host_tier_blocks=host)

    def _prefill(self, mgr, desc, tokens):
        skipped = mgr.lookup(desc, tokens)
        desc.history.extend(tokens[:skipped])
        mgr.ensure(desc, len(tokens))
        desc.history.extend(tokens[skipped:])
        desc.seen_tokens = len(tokens)
        mgr.register(desc)

    def test_eviction_demotes_instead_of_destroying(self):
        """Pool pressure moves the LRU leaf to the host tier (negative id,
        index entry rekeyed) instead of unlinking it; device accounting is
        unchanged — the freed device id really is allocatable."""
        mgr = self._mgr()
        a = SequenceDescriptor(uid=1, slot=0)
        self._prefill(mgr, a, [1, 1, 1, 1, 2, 2, 2, 2])  # chain of 2
        mgr.free(a)
        b = SequenceDescriptor(uid=2, slot=1)
        mgr.ensure(b, 7 * 4)  # 7 blocks > 6 truly free -> one reclaim
        assert mgr.stats["demoted_blocks"] == 1
        assert mgr.stats["evicted_blocks"] == 0  # nothing destroyed
        assert mgr.host_blocks == 1
        assert all(h < _ROOT for h in mgr._host)
        mgr.check_invariants([b])

    def test_promote_on_lookup_rechains_and_queues_payload(self):
        """A lookup that walks onto a demoted block promotes it: bookkeeping
        is rekeyed back to a fresh refcounted device block synchronously and
        the payload order lands in ``take_promotions``."""
        mgr = self._mgr()
        a = SequenceDescriptor(uid=1, slot=0)
        self._prefill(mgr, a, [1, 1, 1, 1, 2, 2, 2, 2])
        mgr.free(a)
        b = SequenceDescriptor(uid=2, slot=1)
        mgr.ensure(b, 7 * 4)  # demotes the leaf
        mgr.free(b)           # unindexed blocks: straight back to free
        # the probe sees BOTH tiers: the demoted leaf still scores
        assert mgr.probe([1, 1, 1, 1, 2, 2, 2, 2]) == 2
        probe = SequenceDescriptor(uid=3, slot=2)
        assert mgr.lookup(probe, [1, 1, 1, 1, 2, 2, 2, 2, 9]) == 8
        assert mgr.stats["promoted_blocks"] == 1 and mgr.host_blocks == 0
        orders = mgr.take_promotions()
        assert len(orders) == 1
        _, dst = orders[0]
        assert dst == probe.blocks[1] and mgr.refcount(dst) == 1
        assert mgr.take_promotions() == []  # drained exactly once
        mgr.check_invariants([probe])

    def test_host_tier_is_bounded_and_evicts_leaf_first(self):
        """A full host LRU destroys its oldest leaf to admit the next
        demotion — the one transition where indexed content actually dies."""
        mgr = self._mgr(host=1)
        a = SequenceDescriptor(uid=1, slot=0)
        self._prefill(mgr, a, [1, 1, 1, 1, 2, 2, 2, 2])
        mgr.free(a)
        b = SequenceDescriptor(uid=2, slot=1)
        mgr.ensure(b, 8 * 4)  # both chain blocks must leave the device
        assert mgr.stats["demoted_blocks"] == 2
        assert mgr.stats["host_evicted_blocks"] == 1  # leaf died for the root
        assert mgr.host_blocks == 1
        mgr.check_invariants([b])

    def test_flush_cache_destroys_both_tiers(self):
        mgr = self._mgr()
        a = SequenceDescriptor(uid=1, slot=0)
        self._prefill(mgr, a, [1, 1, 1, 1, 2, 2, 2, 2])
        mgr.free(a)
        b = SequenceDescriptor(uid=2, slot=1)
        mgr.ensure(b, 7 * 4)
        assert mgr.host_blocks == 1
        mgr.free(b)
        mgr.flush_cache()
        assert mgr.host_blocks == 0 and mgr.cached_blocks == 0
        assert mgr.free_blocks == mgr.num_blocks - 1
        probe = SequenceDescriptor(uid=3, slot=2)
        assert mgr.lookup(probe, [1, 1, 1, 1, 2, 2, 2, 2]) == 0  # truly gone
        mgr.check_invariants([probe])


# ---------------------------------------------------------------------------
# tier-conservation sanitizer: planted violations
# ---------------------------------------------------------------------------

def _stub_engine(mgr, seqs=None, swaps=None):
    return SimpleNamespace(block_mgr=mgr,
                           state=SimpleNamespace(seqs=seqs or {}),
                           _swaps=swaps or {})


class TestTierConservationSanitizer:
    def _tiered_mgr(self):
        mgr = BlockedKVCache(9, block_size=4, max_blocks_per_seq=8,
                             prefix_cache=True, host_tier_blocks=8)
        a = SequenceDescriptor(uid=1, slot=0)
        skipped = mgr.lookup(a, [1, 1, 1, 1, 2, 2, 2, 2])
        a.history.extend([1, 1, 1, 1, 2, 2, 2, 2][skipped:])
        mgr.ensure(a, 8)
        a.seen_tokens = 8
        mgr.register(a)
        mgr.free(a)
        b = SequenceDescriptor(uid=2, slot=1)
        mgr.ensure(b, 7 * 4)  # one demotion
        assert mgr.host_blocks == 1
        return mgr, b

    def test_clean_tiered_state_passes(self):
        mgr, _ = self._tiered_mgr()
        check_tier_conservation(_stub_engine(mgr))

    def test_dangling_demoted_index_entry_is_caught(self):
        mgr, _ = self._tiered_mgr()
        hid = next(iter(mgr._host))
        del mgr._host[hid]  # index still names it: lookup would promote junk
        with pytest.raises(SanitizerError, match="no tier residence"):
            check_tier_conservation(_stub_engine(mgr))

    def test_device_pool_leak_is_caught(self):
        mgr, b = self._tiered_mgr()
        del mgr._ref[b.blocks[-1]]  # the block vanishes from every set
        with pytest.raises(SanitizerError, match="not conserved"):
            check_tier_conservation(_stub_engine(mgr))

    def test_free_and_referenced_overlap_is_caught(self):
        mgr, b = self._tiered_mgr()
        mgr._free.append(b.blocks[0])
        with pytest.raises(SanitizerError, match="free AND referenced"):
            check_tier_conservation(_stub_engine(mgr))

    def test_resident_uid_with_swap_entry_is_caught(self):
        mgr, _ = self._tiered_mgr()
        eng = _stub_engine(mgr, seqs={5: object()},
                           swaps={5: ([], [], 0)})
        with pytest.raises(SanitizerError, match="engine-resident"):
            check_tier_conservation(eng)

    def test_swap_payload_count_mismatch_is_caught(self):
        mgr, _ = self._tiered_mgr()
        eng = _stub_engine(mgr, swaps={7: ([None], list(range(24)), 24)})
        with pytest.raises(SanitizerError, match="payload"):
            check_tier_conservation(eng)

    def test_unpinned_pending_promotion_is_caught(self):
        mgr, _ = self._tiered_mgr()
        # target the LRU-parked chain root: cached but NOT refcounted
        mgr._pending_promotions.append((None, next(iter(mgr._lru))))
        with pytest.raises(SanitizerError, match="promotion"):
            check_tier_conservation(_stub_engine(mgr))

    def test_armed_in_scheduler_step(self, setup):
        """DSTPU_SANITIZE (armed for this module by conftest) runs the tier
        check every scheduler step: a planted leak surfaces as a
        SanitizerError out of ``step()``, not as silent corruption."""
        m, params = setup
        eng = _engine(m, params, num_blocks=17, host_tier_blocks=8)
        sched = ContinuousBatchScheduler(eng, sleep=lambda s: None)
        sched.submit([1, 2, 3, 4, 5], max_new_tokens=3, uid=900)
        sched.step()
        eng.block_mgr._free.pop()
        with pytest.raises(SanitizerError, match="tier conservation"):
            sched.step()


# ---------------------------------------------------------------------------
# engine: demote/promote data path + swap round trips, bitwise
# ---------------------------------------------------------------------------

class TestEngineTier:
    def test_demoted_prefix_promotes_bitwise(self, setup):
        """A prefix pushed to host RAM by pool pressure and promoted back by
        a later content-index hit serves BITWISE-identical logits to a cold
        untiered engine — the payload really round-trips through the host
        buffers and back into the pool the compiled programs read."""
        m, params = setup
        rng = np.random.default_rng(7)
        a = rng.integers(0, 128, 32).tolist()      # 2 full blocks
        big = rng.integers(0, 128, 128).tolist()   # the whole 8-block pool
        tail = rng.integers(0, 128, 8).tolist()
        eng = _engine(m, params, num_blocks=9, host_tier_blocks=16)
        eng.put([1], [a], greedy=True)
        eng.flush(1)
        eng.put([2], [big], greedy=True)           # demotes a's chain
        eng.flush(2)
        s = eng.prefix_cache_stats()
        assert s["demoted_blocks"] >= 2 and s["host_blocks"] >= 2
        cold = _engine(m, params, num_blocks=9, host_tier_blocks=0)
        w = eng.put([3], [a + tail])
        c = cold.put([3], [a + tail])
        s = eng.prefix_cache_stats()
        assert s["promoted_blocks"] >= 2
        assert s["skipped_prefill_tokens"] >= 32  # the hit was real
        np.testing.assert_array_equal(np.asarray(w[3]), np.asarray(c[3]))
        eng.block_mgr.check_invariants(eng.state.seqs.values())
        check_tier_conservation(eng)
        _assert_bounds(eng)

    def test_swap_roundtrip_resumes_bitwise(self, setup):
        """swap_out parks a decoding sequence's KV in the host store (uid
        gone from the engine, blocks freed); swap_in restores it by block
        copy and the continuation is bitwise identical to a never-swapped
        twin — no replay dispatch in between."""
        m, params = setup
        rng = np.random.default_rng(8)
        prompt = rng.integers(0, 128, 20).tolist()
        eng = _engine(m, params, num_blocks=17, host_tier_blocks=8)
        twin = _engine(m, params, num_blocks=17, host_tier_blocks=8)
        w, t = eng.put([1], [prompt]), twin.put([1], [prompt])
        for _ in range(3):
            tok = {1: int(np.argmax(w[1]))}
            assert tok == {1: int(np.argmax(t[1]))}
            w, t = eng.decode_step(dict(tok)), twin.decode_step(dict(tok))
            np.testing.assert_array_equal(np.asarray(w[1]), np.asarray(t[1]))
        assert eng.swap_out(1)
        assert eng.swap_resident(1) and 1 not in eng.state.seqs
        s = eng.prefix_cache_stats()
        assert s["swap_out"] == 1 and s["swap_out_bytes"] > 0
        check_tier_conservation(eng)
        assert eng.swap_in(1)
        assert not eng.swap_resident(1) and 1 in eng.state.seqs
        assert eng.prefix_cache_stats()["swap_in"] == 1
        for _ in range(3):
            tok = {1: int(np.argmax(w[1]))}
            assert tok == {1: int(np.argmax(t[1]))}
            w, t = eng.decode_step(dict(tok)), twin.decode_step(dict(tok))
            np.testing.assert_array_equal(np.asarray(w[1]), np.asarray(t[1]))
        eng.block_mgr.check_invariants(eng.state.seqs.values())
        _assert_bounds(eng)

    def test_swap_edges_refuse_cleanly(self, setup):
        """swap_out refuses unknown/pending uids, a consumed entry cannot
        swap in twice, and flush of a swapped-out uid drops the payload —
        the store is a cache, every miss degrades to replay."""
        m, params = setup
        eng = _engine(m, params, num_blocks=17, host_tier_blocks=8)
        assert not eng.swap_out(99)               # unknown uid
        t = eng.put([1], [[5, 6, 7, 8]], greedy=True)
        eng.decode_step({1: int(t[1])}, greedy=True)
        assert eng.swap_out(1)
        assert not eng.swap_in(2)                 # no entry for uid 2
        eng.flush(1)                              # cancel while swapped out
        assert not eng.swap_resident(1)
        assert not eng.swap_in(1)                 # entry is gone
        untiered = _engine(m, params, num_blocks=17, host_tier_blocks=0)
        t = untiered.put([1], [[5, 6, 7, 8]], greedy=True)
        untiered.decode_step({1: int(t[1])}, greedy=True)
        assert not untiered.swap_out(1)           # tier off: always replay
        eng.block_mgr.check_invariants([])

    def test_rebuild_and_load_params_drop_tier_and_swaps(self, setup):
        """Both tiers and the swap store are caches of pool content: an
        engine loss (rebuild) or a weight swap (load_params) must leave
        nothing to promote or swap back in."""
        m, params = setup
        rng = np.random.default_rng(9)
        eng = _engine(m, params, num_blocks=9, host_tier_blocks=16)
        eng.put([1], [rng.integers(0, 128, 32).tolist()], greedy=True)
        eng.flush(1)
        eng.put([2], [rng.integers(0, 128, 128).tolist()], greedy=True)
        eng.flush(2)
        t = eng.put([3], [rng.integers(0, 128, 8).tolist()], greedy=True)
        eng.decode_step({3: int(t[3])}, greedy=True)
        assert eng.swap_out(3)
        assert eng.block_mgr.host_blocks > 0 and eng._swaps
        eng.rebuild()
        assert eng.block_mgr.host_blocks == 0 and not eng._swaps
        assert not eng.swap_in(3)  # journal replay is the only path now
        t = eng.put([4], [rng.integers(0, 128, 8).tolist()], greedy=True)
        eng.decode_step({4: int(t[4])}, greedy=True)
        assert eng.swap_out(4)
        eng.load_params(params)
        assert eng.block_mgr.host_blocks == 0 and not eng._swaps
        eng.block_mgr.check_invariants([])
        check_tier_conservation(eng)


# ---------------------------------------------------------------------------
# scheduler: swap-vs-recompute preemption, bitwise in all three modes
# ---------------------------------------------------------------------------

def _logical_kv(eng, uid):
    """``uid``'s KV as the pool holds it, in logical order: (2, L, kvh,
    tokens, hd) on the host, read block by block through its table."""
    from deepspeed_tpu.ops.transformer.paged_attention import get_block

    d = eng.state.seqs[uid]
    blocks = [np.asarray(get_block(eng.kv, b)) for b in d.blocks]
    return np.concatenate(blocks, axis=3)[:, :, :, :d.seen_tokens]


class TestPoolLayoutRoundTrips:
    """The pool is one (L, kvh, NB, BS, 2*hd) array of ``[k | v]`` rows;
    every block program goes through ``paged_attention.get_block`` /
    ``set_block`` and the (2, L, kvh, BS, hd) payload: each must hand a
    sequence's KV back bit for bit."""

    def _decoding(self, m, params, uid=1, **kw):
        rng = np.random.default_rng(11)
        eng = _engine(m, params, num_blocks=17, host_tier_blocks=8, **kw)
        out = eng.put([uid], [rng.integers(0, 128, 37).tolist()])
        for _ in range(2):
            out = eng.decode_step({uid: int(np.argmax(out[uid]))})
        return eng, _logical_kv(eng, uid)

    def test_pool_shape_and_payload_geometry(self, setup):
        m, params = setup
        eng = _engine(m, params, num_blocks=17)
        cfg = m.config
        assert eng.kv.shape == (cfg.num_layers, cfg.kv_heads, 17, 16,
                                2 * cfg.head_dim)
        assert eng._tier_buf_shape() == (
            eng.block_mgr.max_blocks_per_seq, 2, cfg.num_layers,
            cfg.kv_heads, 16, cfg.head_dim)
        assert eng.block_bytes == eng.kv.nbytes // 17

    def test_cow_copies_one_block_bitwise(self, setup):
        from deepspeed_tpu.ops.transformer.paged_attention import get_block

        m, params = setup
        eng, _ = self._decoding(m, params)
        src = eng.state.seqs[1].blocks[1]
        dst = next(b for b in range(1, 17)
                   if b not in eng.state.seqs[1].blocks)
        before = np.asarray(eng.kv)
        eng.kv = eng._get_cow()(eng.kv, jax.numpy.int32(src),
                                jax.numpy.int32(dst))
        after = np.asarray(eng.kv)
        np.testing.assert_array_equal(after[:, :, dst], before[:, :, src])
        keep = np.arange(17) != dst
        np.testing.assert_array_equal(after[:, :, keep], before[:, :, keep])
        np.testing.assert_array_equal(np.asarray(get_block(eng.kv, dst)),
                                      np.asarray(get_block(eng.kv, src)))

    def test_demote_then_promote_one_block_bitwise(self, setup):
        from deepspeed_tpu.ops.transformer.paged_attention import get_block

        m, params = setup
        eng, _ = self._decoding(m, params)
        src = eng.state.seqs[1].blocks[0]
        dst = next(b for b in range(1, 17)
                   if b not in eng.state.seqs[1].blocks)
        payload = eng._demote_block(src)        # device -> host ticket
        eng._scatter_blocks([payload], [dst])   # host -> device
        np.testing.assert_array_equal(np.asarray(get_block(eng.kv, dst)),
                                      np.asarray(get_block(eng.kv, src)))
        host = eng.transfer.drain_before([eng._demote_block(dst)])[0]
        assert host.shape == eng._tier_buf_shape()[1:]
        np.testing.assert_array_equal(
            host, np.asarray(get_block(eng.kv, src)))

    def test_swap_out_in_restores_kv_bitwise(self, setup):
        m, params = setup
        eng, kv = self._decoding(m, params)
        assert eng.swap_out(1) and eng.swap_in(1)
        np.testing.assert_array_equal(_logical_kv(eng, 1), kv)

    def test_export_import_restores_kv_bitwise(self, setup):
        m, params = setup
        src, kv = self._decoding(m, params, uid=5)
        payload = src.export_swap(5)
        assert payload["block_shape"] == tuple(src._tier_buf_shape()[1:])
        assert all(b.shape == payload["block_shape"]
                   for b in payload["blocks"])
        dst = _engine(m, params, num_blocks=17, host_tier_blocks=8)
        dst.put([9], [[3, 4, 5] * 7])  # the blocks it lands in are not src's
        dst.import_swap(5, payload)
        assert dst.swap_in(5)
        np.testing.assert_array_equal(_logical_kv(dst, 5), kv)


class TestSwapPreemption:
    @pytest.mark.parametrize("swap,sampled",
                             [(True, False), (None, False), (False, False),
                              (True, True)],
                             ids=["forced-swap", "auto", "forced-recompute",
                                  "forced-swap-temp0.8"])
    def test_pressure_workload_bitwise(self, setup, swap, sampled):
        """The acceptance core: a 12-block pool forces decode-time
        preemption on the pressure workload; with the host tier on, all
        three ``swap_preemption`` modes emit tokens bitwise identical to
        the unpressured untiered baseline. Forced-swap must complete a real
        swap_out -> hold -> swap_in round trip; auto's first swap is the
        bandwidth probe; forced-recompute must never touch the swap path.
        The sampled forced-swap twin proves swap-in resumes the stochastic
        stream bitwise (docs/SAMPLING.md: keys derive from position, not
        residency)."""
        m, params = setup
        ref = _baseline(m, params, sampled=sampled)
        sched, eng, reqs = _run_sched(m, params, num_blocks=13,
                                      host_tier_blocks=32, swap=swap,
                                      sampled=sampled)
        sched.run_until_complete()
        assert all(r.state is RequestState.DONE for r in reqs)
        assert {r.uid: list(r.tokens) for r in reqs} == ref
        assert sched.metrics.preemptions >= 1  # the pool really was short
        kv = sched.metrics.kvtier
        assert kv["demotions"] >= 1
        if swap is False:
            assert kv["recompute_preemptions"] >= 1
            assert kv["swap_out"] == 0 and kv["swap_in"] == 0
        else:
            assert kv["swap_preemptions"] >= 1
            assert kv["swap_out"] >= 1 and kv["swap_in"] >= 1
            assert kv["swap_in_bytes"] > 0
            assert kv["bw_bytes_per_s"] > 0  # the EMA got its sample
            assert len(sched.metrics.swap_readmit_s) >= 1
            assert sched._swap_s_per_byte > 0
        _assert_bounds(eng)
        eng.block_mgr.check_invariants(eng.state.seqs.values())

    def test_tier_off_is_pre_tier_scheduler(self, setup):
        """host_tier_blocks=0 keeps the original preemption path byte for
        byte: no kvtier traffic, no swap store, bitwise tokens."""
        m, params = setup
        ref = _baseline(m, params)
        sched, eng, reqs = _run_sched(m, params, num_blocks=13,
                                      host_tier_blocks=0)
        sched.run_until_complete()
        assert {r.uid: list(r.tokens) for r in reqs} == ref
        assert sched.metrics.preemptions >= 1
        kv = sched.metrics.kvtier
        assert kv["swap_preemptions"] == 0 and kv["recompute_preemptions"] == 0
        assert kv["demotions"] == 0 and not eng._swaps


# ---------------------------------------------------------------------------
# tiering x resilience matrix
# ---------------------------------------------------------------------------

class TestTierResilience:
    def test_engine_loss_with_live_swap_entry_bitwise(self, setup):
        """The engine dies while a victim's KV sits in the swap store: the
        rebuild drops the store (its payloads describe a dead pool), journal
        replay re-admits everyone — including the swap victim — and every
        token stream stays bitwise. The host tier is never a recovery
        source of truth."""
        m, params = setup
        ref = _baseline(m, params)
        inj = FaultInjector([])
        sched, eng, reqs = _run_sched(m, params, num_blocks=13,
                                      host_tier_blocks=32, swap=True,
                                      wrap=inj.wrap)
        for _ in range(400):
            if eng._swaps or not sched.step():
                break
        assert eng._swaps, "pressure workload must produce a swap victim"
        inj.device_lost = "device reset"  # dies between steps, entry live
        sched.run_until_complete()
        assert eng._swaps == {}  # rebuild dropped the store
        assert eng.rebuilds >= 1
        assert sched.metrics.faults["engine_losses"] >= 1
        assert all(r.state is RequestState.DONE for r in reqs)
        assert {r.uid: list(r.tokens) for r in reqs} == ref
        _assert_bounds(eng)

    def test_detach_adopt_swap_resident_victim_bitwise(self, setup):
        """A queued swap-preempted victim migrates: detach drops its swap
        entry on the source engine (payloads never cross engines), the
        adopting scheduler replays from the journal entry, and the full
        workload still matches the baseline bitwise. The source engine's
        demoted blocks stay consistent throughout."""
        m, params = setup
        ref = _baseline(m, params)
        sched_a, eng_a, reqs = _run_sched(m, params, num_blocks=13,
                                          host_tier_blocks=32, swap=True)
        for _ in range(400):
            if eng_a._swaps or not sched_a.step():
                break
        assert eng_a._swaps
        victim_uid = next(iter(eng_a._swaps))
        eng_b = _engine(m, params, num_blocks=41, host_tier_blocks=32)
        sched_b = ContinuousBatchScheduler(eng_b, sleep=lambda s: None,
                                           swap_preemption=True)
        entry = sched_a.detach(victim_uid)
        assert not eng_a.swap_resident(victim_uid)  # entry dropped at detach
        check_tier_conservation(eng_a)
        adopted = sched_b.adopt(entry)
        sched_a.run_until_complete()
        sched_b.run_until_complete()
        assert adopted.state is RequestState.DONE
        assert {r.uid: list(r.tokens) for r in reqs} == ref
        assert sched_a.metrics.kvtier["demotions"] >= 1
        eng_a.block_mgr.check_invariants(eng_a.state.seqs.values())
        sched_a.close()
        sched_b.close()

    def test_rolling_update_flushes_host_tier(self, setup):
        """REGRESSION (the drain/load_weights bugfix): a drained replica's
        weight swap must flush the HOST tier and the swap store too — a
        device-only flush would let a post-update index hit promote stale
        v1 KV under v2 weights, or a swap-in restore v1 blocks. After the
        update, a prompt whose prefix sat demoted in v1's host tier decodes
        exactly the fresh-v2 tokens."""
        m, params = setup
        params2 = m.init_params(jax.random.PRNGKey(1))
        rng = np.random.default_rng(10)
        prompt = rng.integers(0, 128, 32).tolist()
        big = rng.integers(0, 128, 128).tolist()

        def ref_tokens(p):
            s = ContinuousBatchScheduler(
                _engine(m, params if p is params else params2, num_blocks=41,
                        host_tier_blocks=0), sleep=lambda s_: None)
            r = s.submit(prompt, max_new_tokens=6, uid=1)
            s.run_until_complete()
            return list(r.tokens)

        v1, v2 = ref_tokens(params), ref_tokens(params2)
        assert v1 != v2  # otherwise staleness would be invisible

        pool = EnginePool.build(
            lambda i: _engine(m, params, num_blocks=9, host_tier_blocks=16),
            2, sleep=lambda s: None)
        rep0 = pool.replica(0)
        # park the prompt's prefix in replica 0's HOST tier (v1 content)
        rep0.engine.put([50], [prompt], greedy=True)
        rep0.engine.flush(50)
        rep0.engine.put([51], [big], greedy=True)
        rep0.engine.flush(51)
        assert rep0.engine.block_mgr.host_blocks >= 2
        assert rep0.engine.block_mgr.probe(prompt) >= 2
        # and a v1 swap entry
        t = rep0.engine.put([52], [[3, 4, 5]], greedy=True)
        rep0.engine.decode_step({52: int(t[52])}, greedy=True)
        assert rep0.engine.swap_out(52)
        pool.drain(0)
        pool.load_weights(0, params2, version="v2")
        assert rep0.engine.block_mgr.host_blocks == 0
        assert not rep0.engine._swaps
        assert rep0.engine.block_mgr.probe(prompt) == 0  # nothing to promote
        pool.undrain(0)
        pool.drain(1)  # force placement onto the updated replica
        req = pool.submit(prompt, max_new_tokens=6, uid=9100)
        assert pool.owner_of(req.uid) == 0  # only serving replica
        pool.run_until_complete()
        assert list(req.tokens) == v2  # fresh v2, no stale v1 KV surfaced
        pool.undrain(1)
        pool.close()


# ---------------------------------------------------------------------------
# metrics surface
# ---------------------------------------------------------------------------

class TestTierMetrics:
    def test_kvtier_events_are_replica_prefixed(self):
        m0, m1 = ServeMetrics(), ServeMetrics(replica_id=1)
        m1.observe_swap_preemption(True)
        m1.observe_swap_readmit(0.002, 1.0e6)
        labels0 = {label for label, _, _ in m0.events()}
        assert "serve/kvtier/swap_preemptions" in labels0
        ev1 = {label: v for label, v, _ in m1.events()}
        assert ev1["serve/replica1/kvtier/swap_preemptions"] == 1.0
        assert ev1["serve/replica1/kvtier/bw_bytes_per_s"] == 1.0e6
        assert ev1["serve/replica1/kvtier/swap_readmit_p95_ms"] == 2.0
        # pool members never alias into the unprefixed tree
        assert not any(label.startswith("serve/kvtier/") for label in ev1)
        from deepspeed_tpu.monitor import MonitorMaster

        MonitorMaster({}).write_events(m1.events(step=3))  # sinks off: no-op

    def test_observe_kvtier_maps_engine_stats(self, setup):
        m, params = setup
        eng = _engine(m, params, num_blocks=17, host_tier_blocks=8)
        sm = ServeMetrics()
        sm.observe_kvtier(eng.prefix_cache_stats())
        assert sm.kvtier["demotions"] == 0.0  # mapped, zero-valued
        eng.put([1], [[7, 8, 9]], greedy=True)
        sm.observe_kvtier(eng.prefix_cache_stats())
        assert sm.kvtier["host_blocks"] == 0.0

    def test_prefix_cache_stats_host_fields(self, setup):
        m, params = setup
        eng = _engine(m, params, num_blocks=17, host_tier_blocks=8)
        s = eng.prefix_cache_stats()
        for k in ("host_blocks", "host_capacity_blocks", "host_bytes",
                  "swap_out", "swap_in", "swap_out_bytes", "swap_in_bytes",
                  "demoted_blocks", "promoted_blocks", "host_evicted_blocks"):
            assert k in s, k
        assert s["host_capacity_blocks"] == 8
        labels = {e[0] for e in eng.monitor_events(step=2)}
        assert "inference/prefix_cache/host_blocks" in labels
        assert "inference/prefix_cache/swap_out_bytes" in labels

    def test_router_probe_counts_demoted_blocks(self, setup):
        """Placement affinity sees host-resident content: a replica whose
        prefix sits demoted scores the same as one holding it on device."""
        m, params = setup
        rng = np.random.default_rng(11)
        prompt = rng.integers(0, 128, 32).tolist()
        big = rng.integers(0, 128, 128).tolist()
        eng = _engine(m, params, num_blocks=9, host_tier_blocks=16)
        eng.put([1], [prompt], greedy=True)
        eng.flush(1)
        on_device = eng.prefix_probe(prompt)
        assert on_device == 2
        eng.put([2], [big], greedy=True)  # demotes the prefix
        eng.flush(2)
        assert eng.block_mgr.host_blocks >= 2
        assert eng.prefix_probe(prompt) == on_device  # score unchanged
