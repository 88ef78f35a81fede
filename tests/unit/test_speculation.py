"""Speculative decoding (docs/SERVING.md): proposer units (prompt-lookup
self-drafting, draft-model greedy scan, acceptance-EMA policy), engine
``verify_multi`` bitwise equivalence with sequential greedy + its
validation surface and compiled-program bounds, the hardened ``rollback``
uncommitted contract, scheduler spec-vs-plain bitwise parity (EOS inside
an accepted draft, preemption churn mid-speculation, injected faults on
the ``verify_multi`` site, chunked-prefill composition, degrade-to-fused
on acceptance collapse), the ``serve/spec/*`` metrics surface, and the
speculation-aware sanitizer checks (seeded bugs)."""

import jax
import numpy as np
import pytest

from deepspeed_tpu.analysis.sanitizer import SanitizerError
from deepspeed_tpu.inference.v2 import InferenceEngineV2
from deepspeed_tpu.models import build_model
from deepspeed_tpu.resilience.errors import (ContextOverflowError,
                                             EngineUsageError)
from deepspeed_tpu.serve import (ContinuousBatchScheduler, DraftModelProposer,
                                 DraftProposer, FaultInjector,
                                 PromptLookupProposer, RequestState,
                                 SamplingParams, SpecPolicy)
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


def _run_sched(m, params, prompts, gen=16, eos=None, priorities=None,
               proposer=None, sampling=None, **ekw):
    eng = _engine(m, params, **ekw)
    sched = ContinuousBatchScheduler(eng, proposer=proposer)
    prios = priorities or [0] * len(prompts)
    reqs = [sched.submit(p, max_new_tokens=gen, eos_token=eos, priority=pr,
                         sampling=sampling)
            for p, pr in zip(prompts, prios)]
    sched.run_until_complete()
    return eng, sched, reqs


def _singles(m, params, prompt, n=8):
    """Reference: sequential single-step greedy continuation."""
    eng = _engine(m, params)
    t = int(eng.put([1], [prompt], greedy=True)[1])
    out = []
    for _ in range(n):
        t = int(eng.decode_step({1: t}, greedy=True)[1])
        out.append(t)
    return out


class _GarbageProposer(DraftProposer):
    """Always proposes tokens the target will reject (acceptance -> 0)."""

    def propose(self, uid, context, k):
        return [(int(context[-1]) + 1) % 100 + 1] * k


class TestProposers:
    def test_prompt_lookup_most_recent_match(self):
        p = PromptLookupProposer(max_ngram=2)
        # trailing bigram (1, 2) occurs twice; the MOST RECENT earlier
        # occurrence (index 4) wins, proposing its continuation (9, 9)
        ctx = [1, 2, 7, 8, 1, 2, 9, 9, 1, 2]
        assert p.propose(0, ctx, 3) == [9, 9, 1]
        # no earlier occurrence of the trailing n-gram at any n: no draft
        assert p.propose(0, [1, 2, 3, 4, 5], 3) == []
        assert p.propose(0, ctx, 0) == []

    def test_prompt_lookup_drafts_cycles_perfectly(self):
        # a period-2 cycle: the lookup extrapolates it for the full budget
        ctx = [40, 41] * 6
        assert PromptLookupProposer().propose(0, ctx, 5) == [40, 41, 40, 41,
                                                            40]

    def test_prompt_lookup_falls_back_to_shorter_ngrams(self):
        p = PromptLookupProposer(max_ngram=3)
        # the trailing trigram is unique, but the trailing unigram (5)
        # recurs — min_ngram=1 fallback still drafts
        ctx = [5, 6, 1, 2, 5]
        assert p.propose(0, ctx, 2) == [6, 1]
        with pytest.raises(ValueError, match="min_ngram"):
            PromptLookupProposer(max_ngram=0)

    def test_draft_model_matches_manual_greedy(self, setup):
        m, params = setup
        prop = DraftModelProposer(m, params, window=64, max_draft=3)
        ctx = list(np.random.default_rng(1).integers(0, 128, 40))
        got = prop.propose(1, ctx, 3)
        win = np.zeros((64,), np.int32)
        win[:40] = ctx
        cur, want = 40, []
        import jax.numpy as jnp
        for _ in range(3):
            lg = np.asarray(m.logits(params, jnp.asarray(win[None, :])))[0]
            nxt = int(np.argmax(lg[cur - 1]))
            want.append(nxt)
            win[cur] = nxt
            cur += 1
        assert got == want
        # the budget only slices the fixed-k scan: prefixes are stable
        assert prop.propose(1, ctx, 2) == want[:2]
        with pytest.raises(ValueError, match="window"):
            DraftModelProposer(m, params, window=4, max_draft=8)

    def test_policy_ema_budget_and_collapse(self):
        pol = SpecPolicy(PromptLookupProposer(), ema_alpha=0.5, floor=0.35,
                         revive_after=2)
        assert pol.budget(1, 7) == 7  # optimistic init: full draft width
        pol.observe(1, proposed=4, accepted=0)  # first sample replaces init
        assert pol.rate(1) == 0.0
        # collapsed: budget 0 for revive_after rounds, then a 1-token probe
        assert pol.budget(1, 7) == 0
        assert pol.budget(1, 7) == 0
        assert pol.budget(1, 7) == 1
        pol.observe(1, proposed=1, accepted=1)  # probe accepted: EMA 0.5
        assert pol.rate(1) == 0.5
        assert pol.budget(1, 7) == round(0.5 * 7)
        pol.forget(1)
        assert pol.rate(1) == 1.0  # fresh uid: optimistic again

    def test_policy_collect_skips_empty_and_zero_budget(self):
        pol = SpecPolicy(PromptLookupProposer(), floor=0.35)
        ctx = {1: [4, 5] * 6, 2: [1, 2, 3, 4, 5]}  # 2 has no repeats
        drafts = pol.collect([1, 2], lambda u: ctx[u], 3)
        assert 1 in drafts and 2 not in drafts
        pol.observe(1, proposed=3, accepted=0)  # collapse uid 1
        assert pol.collect([1, 2], lambda u: ctx[u], 3) == {}


class TestVerifyEngine:
    def test_verify_bitwise_vs_sequential_greedy(self, setup):
        """Perfect draft: all K tokens emitted, identical to sequential
        greedy. Garbage draft: 1 bonus token, still the sequential token.
        Empty draft: rides the dispatch emitting exactly 1 token."""
        m, params = setup
        prompt = _prompts(1)[0]
        singles = _singles(m, params, prompt)
        eng = _engine(m, params, decode_horizon=4)
        t0 = int(eng.put([7], [prompt], greedy=True)[7])
        out = eng.verify_multi({7: t0}, {7: singles[:3]})
        assert out[7] == singles[:4]
        d = eng.state.seqs[7]
        assert d.uncommitted == 4
        eng.rollback(7, 0)  # all accepted: commit everything
        assert d.uncommitted == 0
        bad = [(singles[4] + 1) % 128, 5, 9]
        out = eng.verify_multi({7: singles[3]}, {7: bad})
        assert out[7][0] == singles[4]  # the free verifier token
        eng.rollback(7, 3)  # keep only the bonus token
        out = eng.verify_multi({7: singles[4]}, {})
        assert out[7] == [singles[5]]
        eng.rollback(7, 3)
        assert d.seen_tokens == len(prompt) + 6
        assert len(d.history) == d.seen_tokens

    def test_verify_partial_acceptance_prefix(self, setup):
        """A draft right for m tokens then wrong: positions 0..m echo the
        sequential tokens and position m is the sequential token too (the
        scheduler's m+1 commit) — the acceptance math's whole basis."""
        m, params = setup
        prompt = _prompts(1)[0]
        singles = _singles(m, params, prompt)
        eng = _engine(m, params, decode_horizon=4)
        t0 = int(eng.put([7], [prompt], greedy=True)[7])
        draft = [singles[0], (singles[1] + 1) % 128, 0]
        g = eng.verify_multi({7: t0}, {7: draft})[7]
        assert g[0] == singles[0] and g[1] == singles[1]  # m=1, +1 bonus
        # commit the fed token + the m accepted drafts; the bonus token is
        # emitted but NOT cached (it is fed next round, like the fused path)
        eng.rollback(7, 4 - 2)
        assert eng.state.seqs[7].seen_tokens == len(prompt) + 2

    def test_verify_validation_surface(self, setup):
        m, params = setup
        eng = _engine(m, params, decode_horizon=4)
        t0 = int(eng.put([1], [_prompts(1)[0]], greedy=True)[1])
        with pytest.raises(EngineUsageError, match="exceed the verify"):
            eng.verify_multi({1: t0}, {1: [1, 2, 3, 4]})  # > K-1 drafts
        assert eng.verify_multi({}, {}) == {}
        with pytest.raises(KeyError):
            eng.verify_multi({99: 1}, {})
        d = eng.state.seqs[1]
        seen = d.seen_tokens
        d.seen_tokens = eng.max_seq_len - 2  # < K positions left
        with pytest.raises(ContextOverflowError):
            eng.verify_multi({1: t0}, {1: [1]})
        d.seen_tokens = seen
        # pending prefill tokens must drain before verification
        eng2 = _engine(m, params, decode_horizon=4)
        eng2.put([2], [_prompts(1)[0]], greedy=True, max_steps=0)
        with pytest.raises(EngineUsageError, match="pending prefill"):
            eng2.verify_multi({2: 5}, {2: [1]})
        # horizon-1 engines have no verify width
        eng3 = _engine(m, params)
        eng3.put([3], [[5, 6, 7]], greedy=True)
        with pytest.raises(EngineUsageError, match="decode_horizon"):
            eng3.verify_multi({3: 5}, {})

    def test_verify_trace_bound(self, setup):
        """The verification program compiles ONCE: any draft-length mix
        lands in the same (max_seqs, K) shape — verify_cache_size <= 1 on
        top of the unchanged ragged <= 4 and fused <= 1 bounds."""
        m, params = setup
        eng = _engine(m, params, decode_horizon=4)
        toks = {}
        for uid, p in zip((1, 2, 3), _prompts()):
            toks[uid] = int(eng.put([uid], [p], greedy=True)[uid])
        assert eng.verify_cache_size == 0  # lazy: no spec yet, no trace
        for drafts in ({1: [5, 6, 7]}, {1: [5], 2: [8, 9]}, {}):
            out = eng.verify_multi(toks, drafts)
            for uid in toks:
                assert len(out[uid]) == len(drafts.get(uid, ())) + 1
                eng.rollback(uid, 4 - len(out[uid]))
                toks[uid] = out[uid][-1]
        eng.decode_multi(toks, 4)
        for uid in toks:
            eng.rollback(uid, 0)
        assert eng.verify_cache_size == 1
        assert eng.fused_cache_size == 1
        assert_trace_bounds(eng)

    def test_drafts_never_reach_prefix_index(self, setup):
        """After verify + rollback, a fresh lookup of the history maps only
        the KEPT tokens' full blocks — rejected drafts and pad positions
        were never registered (docs/PREFIX_CACHING.md)."""
        m, params = setup
        eng = _engine(m, params, decode_horizon=4)
        prompt = _prompts(1)[0][:15]  # 15 + fed + kept lands mid-block 2
        t0 = int(eng.put([1], [prompt], greedy=True)[1])
        eng.verify_multi({1: t0}, {1: [3, 4, 5]})
        eng.rollback(1, 2)  # commit fed token + 1 draft: 17 committed
        d = eng.state.seqs[1]
        hist = list(d.history)
        assert len(hist) == 17
        eng.flush(1)
        d2 = eng.state.get_or_create_sequence(2)
        assert eng.block_mgr.lookup(d2, hist + [99] * 15) == 16
        eng.flush(2)
        eng.block_mgr.check_invariants([])


class TestRollbackContract:
    def test_rollback_rejects_n_beyond_uncommitted(self, setup):
        """rollback(n) with n > tokens generated by the last fused/verify
        dispatch raises typed EngineUsageError — committed tokens are
        immutable (the prefix index may already cover them). The legacy
        n >= seen_tokens ValueError still fires first."""
        m, params = setup
        eng = _engine(m, params, decode_horizon=4)
        t0 = int(eng.put([5], [_prompts(1)[0]], greedy=True)[5])
        with pytest.raises(ValueError, match="roll back"):
            eng.rollback(5, 10_000)
        with pytest.raises(EngineUsageError, match="committed tokens"):
            eng.rollback(5, 1)  # nothing uncommitted after put
        out = eng.decode_multi({5: t0}, 4)
        with pytest.raises(EngineUsageError, match="committed tokens"):
            eng.rollback(5, 5)  # only 4 generated this step
        eng.rollback(5, 2)  # legal partial commit
        with pytest.raises(EngineUsageError, match="committed tokens"):
            eng.rollback(5, 1)  # the commit consumed the allowance
        assert eng.state.seqs[5].uncommitted == 0
        del out

    def test_rollback_after_quarantine_is_idempotent(self, setup):
        """A quarantined (flushed) uid: rollback returns 0, repeatedly, and
        never resurrects state — the containment path may race a rollback
        against the flush."""
        m, params = setup
        eng = _engine(m, params, decode_horizon=4)
        t0 = int(eng.put([5], [_prompts(1)[0]], greedy=True)[5])
        eng.decode_multi({5: t0}, 4)
        eng.flush(5)  # quarantine reclaims the blocks mid-step
        assert eng.rollback(5, 3) == 0
        assert eng.rollback(5, 3) == 0
        assert eng.rollback(5, 0) == 0
        assert 5 not in eng.state.seqs
        eng.block_mgr.check_invariants([])


class TestSpecScheduler:
    def test_spec_bitwise_and_counters(self, setup):
        """Prompt-lookup speculation emits exactly the plain greedy tokens
        (the acceptance criterion's bitwise clause), populates the
        serve/spec/* counters, and keeps the program bounds."""
        m, params = setup
        prompts = _prompts()
        # 32 tokens, not the default 16: on the installed JAX the first draft
        # is a chance prompt match that misses, the per-request EMA collapses
        # for SpecPolicy.revive_after rounds, and only then does lookup meet
        # the cycle the greedy stream has fallen into
        _, s1, r1 = _run_sched(m, params, prompts, gen=32)
        eng, ss, rs = _run_sched(m, params, prompts, gen=32, decode_horizon=4,
                                 proposer=PromptLookupProposer())
        assert [r.tokens for r in rs] == [r.tokens for r in r1]
        assert ss.metrics.tokens_generated == s1.metrics.tokens_generated
        assert ss.metrics.spec["steps"] > 0
        assert ss.metrics.spec["accepted_tokens"] > 0
        assert 0.0 < ss.metrics.spec["acceptance_rate"] <= 1.0
        assert_trace_bounds(eng)
        ev = {k: v for k, v, _ in ss.monitor_events(step=3)}
        assert ev["serve/spec/steps"] > 0
        assert "serve/spec/acceptance_rate" in ev
        assert "serve/spec/draft_horizon" in ev
        assert not eng.state.seqs

    def test_spec_under_temperature_token_for_token(self, setup):
        """Rejection-sampling verification under temperature
        (docs/SAMPLING.md): the speculative sampled stream matches the
        non-speculative sampled stream token for token — the target's own
        per-(seed, position) categorical sample decides every position;
        drafts only move where the verify dispatch lands, never what it
        emits. Compiled-program bounds hold."""
        m, params = setup
        prompts = _prompts()
        sp = SamplingParams(temperature=0.8, seed=31)
        _, s1, r1 = _run_sched(m, params, prompts, sampling=sp)
        eng, ss, rs = _run_sched(m, params, prompts, decode_horizon=4,
                                 proposer=PromptLookupProposer(), sampling=sp)
        assert [r.tokens for r in rs] == [r.tokens for r in r1]
        # sampling was really on: the stream differs from plain greedy
        greedy = [r.tokens for r in _run_sched(m, params, prompts)[2]]
        assert [r.tokens for r in rs] != greedy
        assert ss.metrics.spec["steps"] > 0  # verification really ran
        assert_trace_bounds(eng)
        assert not eng.state.seqs

    def test_eos_inside_accepted_draft_prefix(self, setup):
        """The stop token arriving INSIDE an accepted draft prefix: emission
        stops at EOS, the rest of the verified horizon rolls back, output
        is bitwise the single-step run's."""
        m, params = setup
        prompt = _prompts(1)[0]
        ref = _run_sched(m, params, [prompt], gen=24)[2][0].tokens
        idx = next(j for j, t in enumerate(ref)
                   if ref.index(t) == j and j >= 2 and j % 4 != 0)
        expected = ref[:idx + 1]
        eng, sched, (req,) = _run_sched(
            m, params, [prompt], gen=24, eos=ref[idx], decode_horizon=4,
            proposer=PromptLookupProposer())
        assert req.state is RequestState.DONE
        assert req.tokens == expected
        assert sched.metrics.tokens_generated == len(expected)
        assert not eng.state.seqs and not eng.block_mgr._ref
        eng.block_mgr.check_invariants([])

    def test_bitwise_under_preemption_churn(self, setup):
        """Preempt mid-speculation -> re-admit replays through the prefix
        cache; the resumed request keeps drafting from its full history and
        output stays bitwise identical to uncontended runs."""
        m, params = setup
        prompts = _prompts()
        refs = [_run_sched(m, params, [p])[2][0].tokens for p in prompts]
        eng, sched, reqs = _run_sched(
            m, params, prompts, decode_horizon=4, num_blocks=7,
            priorities=[2, 1, 0], proposer=PromptLookupProposer())
        assert sched.metrics.preemptions > 0
        assert [r.tokens for r in reqs] == refs
        assert_trace_bounds(eng)
        eng.block_mgr.check_invariants([])

    def test_fault_during_verify_retries_step_verbatim(self, setup):
        """A transient fault on the verify_multi site: the injector raises
        before delegation, the scheduler retries with the SAME drafts, and
        the run stays bitwise. A persistent fault on the site quarantines
        only the culpable request."""
        m, params = setup
        prompts = _prompts()
        # 32 tokens: enough verify dispatches for the nth=2 fault (see
        # test_spec_bitwise_and_counters)
        refs = [_run_sched(m, params, [p], gen=32)[2][0].tokens
                for p in prompts]
        inj = FaultInjector(seed=3)
        inj.inject(site="verify_multi", kind="transient", nth=2, count=2)
        eng = _engine(m, params, decode_horizon=4)
        sched = ContinuousBatchScheduler(inj.wrap(eng),
                                         proposer=PromptLookupProposer())
        reqs = [sched.submit(p, max_new_tokens=32) for p in prompts]
        sched.run_until_complete()
        assert inj.fired["transient"] == 2
        assert inj.calls["verify_multi"] > 0
        assert [r.tokens for r in reqs] == refs

        inj2 = FaultInjector(seed=3)
        eng2 = _engine(m, params, decode_horizon=4)
        sched2 = ContinuousBatchScheduler(inj2.wrap(eng2),
                                          proposer=PromptLookupProposer())
        reqs2 = [sched2.submit(p, max_new_tokens=32) for p in prompts]
        inj2.inject(site="verify_multi", kind="persistent", uid=reqs2[1].uid)
        sched2.run_until_complete()
        assert reqs2[1].state is RequestState.FAILED
        assert reqs2[0].tokens == refs[0] and reqs2[2].tokens == refs[2]
        assert not eng2.state.seqs and not eng2.block_mgr._ref

    def test_acceptance_collapse_degrades_to_fused(self, setup):
        """A proposer whose drafts never verify: the per-request EMA
        collapses, budgets drop to 0, and the rounds degrade to the plain
        fused path (degraded_steps counts them) — output still bitwise."""
        m, params = setup
        prompts = _prompts(2)
        refs = [r.tokens for r in _run_sched(m, params, prompts)[2]]
        eng, sched, reqs = _run_sched(
            m, params, prompts, decode_horizon=4,
            proposer=SpecPolicy(_GarbageProposer(), ema_alpha=1.0,
                                revive_after=100))
        assert [r.tokens for r in reqs] == refs
        assert sched.metrics.spec["degraded_steps"] > 0
        assert sched.metrics.decode["fused_steps"] > 0
        # speculative rollback traffic is visible in both counter families
        assert (sched.metrics.spec["rollback_tokens"]
                <= sched.metrics.decode["rollback_tokens"])

    def test_composes_with_chunked_prefill(self, setup):
        """Speculation obeys the fused/prefill duty cycle: staggered
        arrivals prefill in chunks between verified rounds, and everyone's
        output is bitwise the solo single-step run's."""
        m, params = setup
        prompts = _prompts()
        refs = [_run_sched(m, params, [p])[2][0].tokens for p in prompts]
        eng = _engine(m, params, decode_horizon=4)
        sched = ContinuousBatchScheduler(eng,
                                         proposer=PromptLookupProposer())
        t0 = sched._clock()
        reqs = [sched.submit(p, max_new_tokens=16,
                             arrival_time=t0 + i * 1e-4)
                for i, p in enumerate(prompts)]
        sched.run_until_complete()
        assert [r.tokens for r in reqs] == refs
        assert sched.metrics.prefill["chunks"] > 0
        assert not eng.state.seqs

    def test_proposer_requires_horizon_engine(self, setup):
        m, params = setup
        with pytest.raises(ValueError, match="decode_horizon"):
            ContinuousBatchScheduler(_engine(m, params),
                                     proposer=PromptLookupProposer())


class TestSpecSanitizer:
    def test_register_during_speculation_is_caught(self, setup, monkeypatch):
        """Seeded bug: registering a descriptor while its verify dispatch
        is uncommitted — the prefix index would cover unverified drafts.
        The checked cache refuses."""
        monkeypatch.setenv("DSTPU_SANITIZE", "1")
        m, params = setup
        eng = _engine(m, params, decode_horizon=4)
        t0 = int(eng.put([1], [_prompts(1)[0]], greedy=True)[1])
        eng.verify_multi({1: t0}, {1: [3, 4, 5]})
        with pytest.raises(SanitizerError, match="uncommitted"):
            eng.block_mgr.register(eng.state.seqs[1])
        eng.rollback(1, 3)  # the legal path commits first
        eng.block_mgr.register(eng.state.seqs[1])

    def test_uncommitted_across_step_boundary_is_caught(self, setup,
                                                        monkeypatch):
        """Seeded bug: a scheduler that forgets to commit/rollback an
        absorbed verify dispatch trips check_speculation_commit at the
        step boundary."""
        monkeypatch.setenv("DSTPU_SANITIZE", "1")
        m, params = setup
        eng = _engine(m, params, decode_horizon=4)
        sched = ContinuousBatchScheduler(eng,
                                         proposer=PromptLookupProposer())
        sched.submit(_prompts(1)[0], max_new_tokens=8)
        monkeypatch.setattr(eng.__class__, "rollback",
                            lambda self, uid, n=0: 0)
        with pytest.raises(SanitizerError, match="uncommitted"):
            for _ in range(64):
                if not sched.step():
                    break
