"""Chunked interleaved prefill (docs/SERVING.md): budget-bounded
``put(max_steps=...)`` semantics, decode tokens delivered BETWEEN the
prefill chunks of a concurrently admitted long prompt (dispatch-count
based, no wall clock), chunked-vs-monolithic bitwise identity, preempt →
re-admit of a mid-prefill request replaying through the prefix cache,
pool-pressure deferral trimming, the fused-horizon/backlog duty cycle,
and the sanitizer's prefill-ownership invariant. Runs under
``DSTPU_SANITIZE=1`` (tests/conftest.py)."""

import jax
import numpy as np
import pytest

from deepspeed_tpu.analysis.sanitizer import (SanitizerError,
                                              check_prefill_ownership)
from deepspeed_tpu.inference.v2 import InferenceEngineV2
from deepspeed_tpu.inference.v2.ragged_manager import SequenceDescriptor
from deepspeed_tpu.models import build_model
from deepspeed_tpu.resilience import PoolExhaustedError
from deepspeed_tpu.serve import ContinuousBatchScheduler, RequestState
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
    kw.setdefault("num_blocks", 33)
    return InferenceEngineV2(m, params, paged=True, **kw)


def _run_solo(m, params, prompt, max_new_tokens):
    """Uncontended greedy reference (ample pool, one request)."""
    eng = _engine(m, params, num_blocks=64)
    sched = ContinuousBatchScheduler(eng)
    req = sched.submit(prompt, max_new_tokens=max_new_tokens)
    sched.run_until_complete()
    assert req.state is RequestState.DONE
    return list(req.tokens)


class TestEngineMaxSteps:
    def test_register_only_then_stepwise_drain_bitwise(self, setup):
        """max_steps=0 registers without dispatching; max_steps=1 advances
        exactly one budget dispatch; the stepwise greedy result is bitwise
        the monolithic drain's."""
        m, params = setup
        rng = np.random.default_rng(3)
        prompt = rng.integers(0, 128, 40).tolist()
        eng = _engine(m, params)
        out = eng.put([7], [prompt], greedy=True, max_steps=0)
        assert out == {}
        d = eng.state.seqs[7]
        assert d.in_flight == 40 and d.seen_tokens == 0
        dispatches = 0
        out = {}
        while not out:
            before = d.in_flight
            out = eng.put([], [], greedy=True, max_steps=1)
            dispatches += 1
            assert d.in_flight < before  # every dispatch makes progress
        assert dispatches == -(-40 // 16)  # ceil(prompt / budget)
        mono = _engine(m, params)
        ref = mono.put([7], [prompt], greedy=True)
        assert out[7] == ref[7]
        assert_trace_bounds(eng)


class TestInterleaving:
    def test_decode_tokens_between_prefill_chunks(self, setup):
        """THE convoy-kill assertion, dispatch-count based: while a long
        prompt's chunks drain, a live decode request gains exactly one
        token per scheduler step — it never waits for the whole foreign
        prefill."""
        m, params = setup
        eng = _engine(m, params)
        rng = np.random.default_rng(11)
        vt = [0.0]
        sched = ContinuousBatchScheduler(eng, clock=lambda: vt[0])
        assert sched.chunked_prefill  # the default
        a = sched.submit(rng.integers(0, 128, 4).tolist(), max_new_tokens=12)
        while a.state is not RequestState.DECODE or len(a.tokens) < 1:
            sched.step()
        long_prompt = rng.integers(0, 128, 48).tolist()
        b = sched.submit(long_prompt, max_new_tokens=4)
        # budget 16 = 1 decode row + 15 chunk rows → 48 tokens take 4
        # mixed dispatches; A must advance on each of them
        for _ in range(3):
            n_a = len(a.tokens)
            sched.step()
            assert len(a.tokens) == n_a + 1
            assert b.state is RequestState.PREFILL
            assert eng.prefill_backlog() > 0
        sched.run_until_complete()
        assert a.state is RequestState.DONE and b.state is RequestState.DONE
        p = sched.metrics.prefill
        assert p["interleaved_steps"] >= 3 and p["chunks"] >= 3
        assert p["chunk_tokens"] >= 48 and p["backlog_peak"] >= 33
        assert b.tokens == _run_solo(m, params, long_prompt, 4)
        assert_trace_bounds(eng)
        events = dict((k, v) for k, v, _ in sched.monitor_events())
        assert events["serve/prefill/interleaved_steps"] >= 3

    def test_chunked_vs_monolithic_bitwise(self, setup):
        """The A/B: identical workload through the chunked and monolithic
        schedulers produces identical greedy streams; only the chunked one
        reports chunk/interleave activity."""
        m, params = setup
        rng = np.random.default_rng(23)
        prompts = [rng.integers(0, 128, int(n)).tolist()
                   for n in (40, 6, 33, 17)]
        streams = {}
        metrics = {}
        for chunked in (True, False):
            eng = _engine(m, params)
            vt = [0.0]
            sched = ContinuousBatchScheduler(eng, clock=lambda: vt[0],
                                             chunked_prefill=chunked)
            reqs = [sched.submit(p, max_new_tokens=6,
                                 arrival_time=0.1 * i)
                    for i, p in enumerate(prompts)]
            while sched.step():
                vt[0] += 0.05
            assert all(r.state is RequestState.DONE for r in reqs)
            streams[chunked] = [list(r.tokens) for r in reqs]
            metrics[chunked] = sched.metrics.prefill
            assert_trace_bounds(eng)
            sched.close()
        assert streams[True] == streams[False]
        assert metrics[True]["chunks"] > 0
        assert metrics[False]["chunks"] == 0  # monolithic path untouched


class TestMidPrefillPreemption:
    def test_preempt_readmit_replays_through_prefix_cache(self, setup):
        """A mid-prefill victim re-admits bitwise: its already-dispatched
        full blocks were registered per chunk, so the replay maps them
        straight back from the content index."""
        m, params = setup
        eng = _engine(m, params)
        rng = np.random.default_rng(31)
        long_prompt = rng.integers(0, 128, 48).tolist()
        vt = [0.0]
        sched = ContinuousBatchScheduler(eng, clock=lambda: vt[0])
        b = sched.submit(long_prompt, max_new_tokens=5)
        sched.step()  # one chunk (16 tokens = 1 full block) dispatched
        assert b.state is RequestState.PREFILL
        assert eng.state.seqs[b.uid].seen_tokens == 16
        sched._preempt(b)
        assert b.state is RequestState.QUEUED and b.preemptions == 1
        assert b.uid not in eng.state.seqs
        sched.run_until_complete()
        assert b.state is RequestState.DONE
        assert b.tokens == _run_solo(m, params, long_prompt, 5)
        stats = eng.prefix_cache_stats()
        assert stats["hits"] >= 1
        assert stats["skipped_prefill_tokens"] >= 16  # partial-prompt block


class TestDeferralTrimming:
    def test_pool_pressure_defers_prefill_rows_not_decodes(self, setup):
        """Under pool exhaustion, a mixed dispatch serves the rows whose
        blocks fit (the live decode) and defers the prefill chunk —
        raising only when nothing at all is dispatchable."""
        m, params = setup
        eng = _engine(m, params, num_blocks=5, prefix_cache=False)  # 4 usable
        rng = np.random.default_rng(5)
        ref = _engine(m, params, prefix_cache=False)  # ample pool reference
        prompt_a = rng.integers(0, 128, 20).tolist()
        tok = eng.put([1], [prompt_a], greedy=True)[1]  # 2 blocks held
        assert tok == ref.put([1], [prompt_a], greedy=True)[1]
        out = eng.put([2], [rng.integers(0, 128, 40).tolist()],
                      greedy=True, max_steps=0)
        assert out == {}
        db = eng.state.seqs[2]
        toks = [tok]
        # drive mixed dispatches: decode row for uid 1 + chunk rows for 2;
        # block demand grows until uid 2's next chunk cannot allocate
        for _ in range(3):
            out = eng.put([1], [[toks[-1]]], greedy=True, max_steps=1)
            toks.append(out[1])
        assert eng.plan_deferrals >= 1     # chunk trimmed, decode served
        assert db.in_flight > 0            # backlog persisted across steps
        assert toks[1:] == [ref.put([1], [[t]], greedy=True)[1]
                            for t in toks[:-1]]  # decodes bitwise on-track
        # freeing the decoder's blocks unblocks the deferred prefill
        eng.flush(1)
        ref.flush(1)
        out = eng.put([], [], greedy=True)
        assert db.in_flight == 0 and 2 in out

    def test_raises_when_nothing_dispatchable(self, setup):
        m, params = setup
        eng = _engine(m, params, num_blocks=2, prefix_cache=False)  # 1 usable
        with pytest.raises(PoolExhaustedError):
            eng.put([1], [list(range(40))], greedy=True)


class TestHorizonBacklogTrade:
    def test_fused_and_chunk_dispatches_alternate(self, setup):
        """With a prompt backlog pending, the fused horizon no longer
        hard-collapses: fused K-step dispatches and chunk-serving mixed
        dispatches alternate, and the result stays bitwise."""
        m, params = setup
        K = 4
        eng = _engine(m, params, decode_horizon=K, num_blocks=64)
        rng = np.random.default_rng(43)
        vt = [0.0]
        sched = ContinuousBatchScheduler(eng, clock=lambda: vt[0])
        a = sched.submit(rng.integers(0, 128, 4).tolist(), max_new_tokens=28)
        while sched.metrics.decode["fused_steps"] < 1:
            sched.step()  # steady-state fused decode reached
        long_prompt = rng.integers(0, 128, 48).tolist()
        b = sched.submit(long_prompt, max_new_tokens=4)
        fused0 = sched.metrics.decode["fused_steps"]
        chunks0 = sched.metrics.prefill["chunks"]
        while not b.finished and b.state is not RequestState.DECODE:
            sched.step()  # QUEUED -> PREFILL -> ... -> first token
        fused_during = sched.metrics.decode["fused_steps"] - fused0
        chunks_during = sched.metrics.prefill["chunks"] - chunks0
        assert chunks_during >= 2    # the backlog kept draining...
        assert fused_during >= 1     # ...and fused decode kept running
        sched.run_until_complete()
        assert a.state is RequestState.DONE and b.state is RequestState.DONE
        assert b.tokens == _run_solo(m, params, long_prompt, 4)
        assert a.tokens == _run_solo(m, params, list(a.prompt), 28)
        assert_trace_bounds(eng)


class TestSanitizerOwnership:
    class _Eng:
        def __init__(self, seqs):
            class _S:
                pass

            self.state = _S()
            self.state.seqs = seqs

    def test_orphaned_backlog_detected(self):
        d = SequenceDescriptor(uid=9, slot=0, pending=[1, 2, 3])
        with pytest.raises(SanitizerError, match="orphaned prefill backlog"):
            check_prefill_ownership(self._Eng({9: d}), live={})

    def test_lost_backlog_of_live_prefill_detected(self):
        from deepspeed_tpu.serve.request import Request

        req = Request(prompt=[1, 2])
        req.state = RequestState.PREFILL
        with pytest.raises(SanitizerError, match="no pending work"):
            check_prefill_ownership(self._Eng({}), live={req.uid: req})

    def test_consistent_state_passes(self):
        from deepspeed_tpu.serve.request import Request

        req = Request(prompt=[1, 2])
        req.state = RequestState.PREFILL
        d = SequenceDescriptor(uid=req.uid, slot=0, pending=[3])
        check_prefill_ownership(self._Eng({req.uid: d}),
                                live={req.uid: req})
