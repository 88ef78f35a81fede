"""Fused multi-token decode (docs/SERVING.md): bitwise K-vs-1 equivalence
under greedy — plain, under preemption churn, and under injected faults —
scheduler-side overrun rollback (EOS / max_new_tokens) with block/refcount/
prefix-index invariants, the adaptive horizon's collapse conditions, the
compiled-trace regression bound (ragged <= 4 plus exactly ONE fused
program), horizon-scaled watchdog budgets, and the host-side scratch-array
reuse micro-opt."""

import jax
import numpy as np
import pytest

from deepspeed_tpu.inference.v2 import InferenceEngineV2
from deepspeed_tpu.models import build_model
from deepspeed_tpu.resilience.errors import ContextOverflowError
from deepspeed_tpu.serve import (ContinuousBatchScheduler, FaultInjector,
                                 RequestState, StepWatchdog)
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
    kw.setdefault("num_blocks", 64)
    return InferenceEngineV2(m, params, paged=True, **kw)


def _prompts(n=3):
    rng = np.random.default_rng(0)
    return [rng.integers(0, 128, ln).tolist() for ln in (33, 30, 28)][:n]


def _run_sched(m, params, prompts, gen=16, eos=None, priorities=None, **ekw):
    eng = _engine(m, params, **ekw)
    sched = ContinuousBatchScheduler(eng)
    prios = priorities or [0] * len(prompts)
    reqs = [sched.submit(p, max_new_tokens=gen, eos_token=eos, priority=pr)
            for p, pr in zip(prompts, prios)]
    sched.run_until_complete()
    return eng, sched, reqs


class TestFusedEngine:
    def test_decode_multi_bitwise_vs_single_steps(self, setup):
        """K fused rounds == K single decode_steps, token for token, with
        identical seen_tokens advancement."""
        m, params = setup
        prompt = _prompts(1)[0]
        ref = _engine(m, params)
        t = int(ref.put([1], [prompt], greedy=True)[1])
        singles = []
        for _ in range(8):
            t = int(ref.decode_step({1: t}, greedy=True)[1])
            singles.append(t)
        fused = _engine(m, params, decode_horizon=4)
        t = int(fused.put([7], [prompt], greedy=True)[7])
        got = fused.decode_multi({7: t}, 4)[7]
        fused.rollback(7, 0)  # commit, as the scheduler does
        got += fused.decode_multi({7: got[-1]}, 4)[7]
        assert got == singles
        assert (fused.state.seqs[7].seen_tokens
                == ref.state.seqs[1].seen_tokens)

    def test_horizon_restriction_and_trace_bound(self, setup):
        """Horizons are {1, K}: anything else raises; horizon 1 delegates to
        the ragged round; the fused program holds exactly ONE trace and the
        ragged bound is unchanged — the compiled-program bound grows by
        exactly one shape."""
        m, params = setup
        eng = _engine(m, params, decode_horizon=4)
        t = int(eng.put([1], [_prompts(1)[0]], greedy=True)[1])
        with pytest.raises(ValueError, match="fixed-shape"):
            eng.decode_multi({1: t}, 3)
        out1 = eng.decode_multi({1: t}, 1)  # delegates, no fused trace
        assert len(out1[1]) == 1 and eng.fused_cache_size == 0
        eng.decode_multi({1: out1[1][0]}, 4)
        eng.decode_multi({1: 5}, 4)
        assert eng.fused_cache_size == 1
        assert_trace_bounds(eng)
        with pytest.raises(ValueError):
            _engine(m, params, decode_horizon=0)

    def test_rollback_frees_blocks_and_indexes_only_kept(self, setup):
        """After a fused step, rollback(n) shrinks seen_tokens/history,
        returns the over-allocated tail blocks refcount-exactly, and the
        prefix index covers ONLY the kept tokens' full blocks."""
        m, params = setup
        eng = _engine(m, params, decode_horizon=4)
        prompt = _prompts(1)[0][:17]
        t = int(eng.put([1], [prompt], greedy=True)[1])
        eng.decode_multi({1: t}, 4)
        d = eng.state.seqs[1]
        seen, blocks = d.seen_tokens, len(d.blocks)
        free_before = len(eng.block_mgr._free)
        freed = eng.rollback(1, 3)
        assert d.seen_tokens == seen - 3 and len(d.history) == seen - 3
        assert freed == blocks - len(d.blocks)
        assert len(eng.block_mgr._free) == free_before + freed
        eng.block_mgr.check_invariants(eng.state.seqs.values())
        hist = list(d.history)
        eng.flush(1)
        # a fresh lookup of the full history maps exactly the kept full
        # blocks — the discarded overrun tokens were never registered
        d2 = eng.state.get_or_create_sequence(2)
        assert (eng.block_mgr.lookup(d2, hist + [99] * 8)
                == (len(hist) // 16) * 16)
        eng.flush(2)
        eng.block_mgr.check_invariants([])

    def test_rollback_validation_and_idempotence(self, setup):
        m, params = setup
        eng = _engine(m, params, decode_horizon=4)
        eng.put([5], [_prompts(1)[0]], greedy=True)
        with pytest.raises(ValueError, match="roll back"):
            eng.rollback(5, 10_000)
        assert eng.rollback(424242) == 0  # unknown uid: counted no-op
        d = eng.state.seqs[5]
        with pytest.raises(ContextOverflowError):
            d.seen_tokens = eng.max_seq_len - 2  # 2 < K positions left
            eng.decode_multi({5: 1}, 4)

    def test_put_scratch_arrays_are_reused(self, setup):
        """The ragged/fused step inputs come from per-shape preallocated
        scratch (zeroed in place), not a fresh np.zeros per dispatch."""
        m, params = setup
        eng = _engine(m, params, decode_horizon=4)
        t = int(eng.put([1], [_prompts(1)[0]], greedy=True)[1])
        t2 = int(eng.decode_step({1: t}, greedy=True)[1])
        ids_before = {k: id(v[0]) for k, v in eng._scratch.items()}
        eng.decode_step({1: t2}, greedy=True)
        eng.decode_multi({1: 3}, 4)
        assert {k: id(v[0]) for k, v in eng._scratch.items()
                if k in ids_before} == ids_before
        # one scratch set per compiled shape: mixed budget, decode round,
        # fused — bounded like the trace cache itself
        assert len(eng._scratch) <= 3


class TestFusedScheduler:
    def test_bitwise_k_vs_1_end_to_end(self, setup):
        m, params = setup
        prompts = _prompts()
        _, s1, r1 = _run_sched(m, params, prompts)
        e4, s4, r4 = _run_sched(m, params, prompts, decode_horizon=4)
        assert [r.tokens for r in r4] == [r.tokens for r in r1]
        assert s4.metrics.decode["fused_steps"] > 0
        assert s1.metrics.decode["fused_steps"] == 0
        # kept-token accounting matches the single-step path exactly
        assert (s4.metrics.tokens_generated == s1.metrics.tokens_generated)
        assert_trace_bounds(e4)
        assert not e4.state.seqs

    def test_bitwise_under_preemption_churn(self, setup):
        """An undersized pool forces preempt/re-admit churn mid-fused-load;
        greedy output stays bitwise identical to uncontended runs. Mixed
        priorities make the churn deterministic under chunked prefill: the
        highest-priority (longest) prompt's starved chunks preempt the
        lower-priority residents instead of waiting for organic frees."""
        m, params = setup
        prompts = _prompts()
        refs = [_run_sched(m, params, [p])[2][0].tokens for p in prompts]
        eng, sched, reqs = _run_sched(m, params, prompts, decode_horizon=4,
                                      num_blocks=7, priorities=[2, 1, 0])
        assert sched.metrics.preemptions > 0
        assert sched.metrics.decode["fused_steps"] > 0
        assert [r.tokens for r in reqs] == refs
        assert_trace_bounds(eng)
        eng.block_mgr.check_invariants([])

    def test_bitwise_under_injected_faults(self, setup):
        """A transient fault mid-fused-step retries the WHOLE step (the
        injector raises before delegation, so no half-advanced horizon); a
        persistent fault quarantines only the culpable request while the
        rest finish bitwise."""
        m, params = setup
        prompts = _prompts()
        refs = [_run_sched(m, params, [p])[2][0].tokens for p in prompts]
        inj = FaultInjector(seed=3)
        inj.inject(site="decode_multi", kind="transient", nth=2, count=2)
        eng = _engine(m, params, decode_horizon=4)
        sched = ContinuousBatchScheduler(inj.wrap(eng))
        reqs = [sched.submit(p, max_new_tokens=16) for p in prompts]
        sched.run_until_complete()
        assert inj.fired["transient"] == 2
        assert [r.tokens for r in reqs] == refs

        inj2 = FaultInjector(seed=3)
        eng2 = _engine(m, params, decode_horizon=4)
        sched2 = ContinuousBatchScheduler(inj2.wrap(eng2))
        reqs2 = [sched2.submit(p, max_new_tokens=16) for p in prompts]
        inj2.inject(site="decode_multi", kind="persistent", uid=reqs2[1].uid)
        sched2.run_until_complete()
        assert reqs2[1].state is RequestState.FAILED
        assert reqs2[0].tokens == refs[0] and reqs2[2].tokens == refs[2]
        assert not eng2.state.seqs and not eng2.block_mgr._ref

    def test_eos_overrun_rollback_bitwise(self, setup):
        """A stop token landing mid-horizon: the fused run emits exactly the
        single-step tokens, rolls the ≤K−1 overrun tokens back, and returns
        the pool to a clean state."""
        m, params = setup
        prompt = _prompts(1)[0]
        ref = _run_sched(m, params, [prompt], gen=24)[2][0].tokens
        # first occurrence mid-horizon (index % K != 0 → guaranteed overrun)
        idx = next(j for j, t in enumerate(ref)
                   if ref.index(t) == j and j >= 2 and j % 4 != 0)
        expected = ref[:idx + 1]
        for K, want_rollback in ((1, False), (4, True)):
            eng, sched, (req,) = _run_sched(m, params, [prompt], gen=24,
                                            eos=ref[idx], decode_horizon=K)
            assert req.state is RequestState.DONE
            assert req.tokens == expected
            assert (sched.metrics.decode["rollback_tokens"] > 0) is want_rollback
            assert sched.metrics.tokens_generated == len(expected)
            assert not eng.state.seqs and not eng.block_mgr._ref
            eng.block_mgr.check_invariants([])

    def test_adaptive_horizon_collapse_conditions(self, setup):
        """The horizon collapses to 1 on: pending admissions, <K tokens
        remaining, a deadline inside the horizon's wall-clock budget, a
        stalled prefill, and <K context positions left."""
        m, params = setup
        eng = _engine(m, params, decode_horizon=4)
        # monolithic mode: these are the LEGACY collapse conditions (queued
        # arrivals included); the chunked-prefill horizon/backlog duty
        # cycle is covered in test_chunked_prefill.TestHorizonBacklogTrade
        sched = ContinuousBatchScheduler(eng, chunked_prefill=False)
        r1 = sched.submit(_prompts(1)[0], max_new_tokens=12)
        sched.step()
        assert r1.state is RequestState.DECODE
        feed = {r1.uid: r1.tokens[-1]}
        now = sched._clock()
        assert sched._effective_horizon(now, feed) == 4
        r2 = sched.submit([5, 6, 7], max_new_tokens=4, arrival_time=now)
        assert sched._effective_horizon(now, feed) == 1  # admission queued
        sched.cancel(r2.uid)
        assert sched._effective_horizon(now, feed) == 4
        r1.max_new_tokens = len(r1.tokens) + 2  # < K remaining
        assert sched._effective_horizon(now, feed) == 1
        r1.max_new_tokens = 12
        r1.deadline = now + 1.0
        sched._token_est_s = 10.0  # budget 40s >> 1s margin
        assert sched._effective_horizon(now, feed) == 1
        sched._token_est_s = 1e-9
        assert sched._effective_horizon(now, feed) == 4
        r1.deadline = None
        sched._stalled = True
        assert sched._effective_horizon(now, feed) == 1
        sched._stalled = False
        d = eng.state.seqs[r1.uid]
        seen = d.seen_tokens
        d.seen_tokens = eng.max_seq_len - 2  # < K positions left
        assert sched._effective_horizon(now, feed) == 1
        d.seen_tokens = seen
        sched.close()

    def test_scheduler_horizon_must_match_engine(self, setup):
        m, params = setup
        eng = _engine(m, params, decode_horizon=4)
        with pytest.raises(ValueError, match="compiled horizon"):
            ContinuousBatchScheduler(eng, decode_horizon=8)
        assert ContinuousBatchScheduler(eng).decode_horizon == 4
        assert ContinuousBatchScheduler(
            eng, decode_horizon=1).decode_horizon == 1

    def test_watchdog_budget_scales_with_horizon(self):
        wd = StepWatchdog(step_budget_s=0.1, escalate_after=2)
        assert wd.observe("decode", 0.5, scale=8) == (False, False)
        assert wd.observe("decode", 0.9, scale=8) == (True, False)
        assert wd.observe("decode", 0.11) == (True, True)  # escalates
        assert wd.breaches == 2 and wd.escalations == 1

    def test_decode_metrics_reach_monitor_events(self, setup):
        m, params = setup
        eng, sched, _ = _run_sched(m, params, _prompts(1), gen=12,
                                   decode_horizon=4)
        events = {e[0]: e[1] for e in sched.monitor_events(step=2)}
        assert events["serve/decode/fused_steps"] > 0
        assert events["serve/decode/horizon"] >= 1.0
        assert "serve/decode/rollback_tokens" in events
        # step_batch records batch × horizon (tokens per dispatch)
        assert max(sched.metrics.step_batch) >= 4
