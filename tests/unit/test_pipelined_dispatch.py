"""Pipelined dispatch (docs/SERVING.md "Pipelined dispatch"): the default
scheduler runs ONE decode round ahead of what the host knows — round N+1 is
enqueued, fed on the device from N's result, before N is fetched — and must
stay BITWISE identical to the synchronous twin (``pipelined=False``) across
the whole replay matrix: plain greedy, sampled, EOS / stop-sequence finishes
(the speculative-absorb rollback), ``max_new_tokens`` predicted by count,
preemption churn, KV swap, mid-step engine loss, migration detach/adopt,
and cancellation, preemption and engine loss with two rounds outstanding.
Plus: the
``check_pipeline_coherence`` sanitizer's planted violations, the relaxed
in-flight allowances on the existing checks, the per-replica heartbeat
regression (fed at each replica's OWN absorb), and the two-phase pool
step. Runs under ``DSTPU_SANITIZE=1`` in tier-1 via the conftest fixture."""

import jax
import numpy as np
import pytest

from deepspeed_tpu.analysis.sanitizer import (SanitizerError,
                                              check_pipeline_coherence,
                                              check_speculation_commit,
                                              checked_cache_cls)
from deepspeed_tpu.inference.v2 import InferenceEngineV2
from deepspeed_tpu.models import build_model
from deepspeed_tpu.resilience.errors import (DeviceLostError,
                                             EngineUsageError)
from deepspeed_tpu.resilience.recovery import RequestJournal
from deepspeed_tpu.serve import (ContinuousBatchScheduler, EnginePool,
                                 FaultInjector, FaultSpec, HealthMonitor,
                                 Request, RequestState, RetryPolicy,
                                 SamplingParams)
from deepspeed_tpu.utils import tracing


@pytest.fixture(scope="module")
def setup():
    m = build_model("llama-tiny", vocab_size=128, hidden_size=64,
                    num_layers=2, num_heads=4, num_kv_heads=2,
                    intermediate_size=128, max_seq_len=128)
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


def _prompts(n=3, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 128, ln).tolist() for ln in (33, 30, 28)][:n]


def _run(m, params, prompts, *, pipelined, gen=16, eos=None, sampling=None,
         uids=None, injector=None, eng_kw=None, sched_kw=None):
    """One full workload on a fresh engine; returns (engine, sched, reqs)."""
    eng = _engine(m, params, **(eng_kw or {}))
    wrapped = injector.wrap(eng) if injector is not None else eng
    kw = dict(sched_kw or {})
    kw.setdefault("sleep", lambda s: None)
    sched = ContinuousBatchScheduler(wrapped, pipelined=pipelined, **kw)
    reqs = [sched.submit(p, max_new_tokens=gen, eos_token=eos,
                         uid=None if uids is None else uids[i],
                         sampling=None if sampling is None else sampling[i])
            for i, p in enumerate(prompts)]
    sched.run_until_complete()
    return eng, sched, reqs


def _twin(m, params, prompts, **kw):
    """Run the synchronous and pipelined twins; assert bitwise identity and
    a clean drain; return (sync_reqs, pipe_reqs, pipe_sched)."""
    _, _, sync = _run(m, params, prompts, pipelined=False, **kw)
    eng, sched, pipe = _run(m, params, prompts, pipelined=True, **kw)
    assert [r.tokens for r in pipe] == [r.tokens for r in sync]
    assert sched._inflight is None
    assert not eng.state.seqs and not eng.block_mgr._ref
    return sync, pipe, sched


# ---------------------------------------------------------------------------
# bitwise twins across the replay matrix
# ---------------------------------------------------------------------------

class TestBitwiseTwins:
    def test_plain_greedy(self, setup):
        """max_new_tokens finishes are PREDICTED at plan time (never fed to
        the successor round) — no rollback traffic on a plain workload."""
        m, params = setup
        _, _, sched = _twin(m, params, _prompts())
        p = sched.metrics.pipeline
        assert p["dispatches"] > 0
        assert p["in_flight"] == 0.0  # pipe drained at close
        assert p["speculative_rollbacks"] == 0

    def test_eos_finish(self, setup):
        """An EOS is NOT predictable at plan time (the token is still on
        the device): the row is fed speculatively, finishes at its absorb,
        and its successor position is dropped there (``commit_step`` with
        ``drop`` 1) — counted; the remaining rows keep the pipe full."""
        m, params = setup
        _, _, sync = _run(m, params, _prompts(), pipelined=False, gen=16)
        # pick an eos that fires mid-stream for at least one request
        eos = sync[0].tokens[7]
        sref, pipe, sched = _twin(m, params, _prompts(), gen=16, eos=eos)
        cut = sum(len(r.tokens) < 16 for r in pipe)
        assert cut >= 1
        # (a row whose EOS came out of a synchronous round had no successor)
        assert 1 <= sched.metrics.pipeline["speculative_rollbacks"] <= cut

    def test_stop_sequence_speculative_rollback(self, setup):
        """A stop-sequence finish is NOT predictable at plan time (the scan
        is stateful): the row is fed speculatively and the successor
        position rolled back at absorb — the speculative-absorb rule."""
        m, params = setup
        _, _, sync = _run(m, params, _prompts(), pipelined=False, gen=16)
        # a 2-token stop ending mid-stream: matched only by the StopScanner
        stop = tuple(sync[1].tokens[5:7])
        sampling = [SamplingParams(stop=(stop,)) for _ in range(3)]
        sref, pipe, sched = _twin(m, params, _prompts(), gen=16,
                                  sampling=sampling)
        assert len(pipe[1].tokens) < 16  # cut at the match
        assert sched.metrics.sampling["stop_hits"] >= 1
        assert sched.metrics.pipeline["speculative_rollbacks"] >= 1

    def test_sampled(self, setup):
        """Counter-based per-request PRNG keys make the one-late absorb
        invisible to sampled decoding too."""
        m, params = setup
        sampling = [SamplingParams(temperature=0.8, top_k=40, seed=100 + i)
                    for i in range(3)]
        _twin(m, params, _prompts(), sampling=sampling,
              uids=[901, 902, 903])

    def test_preemption_churn(self, setup):
        """A starved pool preempts an IN-FLIGHT row: the engine declines to
        swap uncommitted sequences (flush+replay), and the replay
        regenerates the discarded in-flight token bitwise."""
        m, params = setup
        rng = np.random.default_rng(0)
        prompts = [rng.integers(1, 127, 17).tolist() for _ in range(4)]
        _, _, sched = _twin(m, params, prompts, gen=40,
                            eng_kw={"num_blocks": 13,
                                    "host_tier_blocks": 0},
                            sched_kw={"retry": RetryPolicy(max_attempts=5)})
        assert sched.metrics.preemptions > 0

    def test_kv_swap(self, setup):
        """Same churn with a host tier and forced swap preemption: victims
        leave through swap-out and re-admit through swap-in under the
        pipelined loop."""
        m, params = setup
        rng = np.random.default_rng(0)
        prompts = [rng.integers(1, 127, 17).tolist() for _ in range(4)]
        _, _, sched = _twin(m, params, prompts, gen=40,
                            eng_kw={"num_blocks": 13,
                                    "host_tier_blocks": 32},
                            sched_kw={"retry": RetryPolicy(max_attempts=5),
                                      "swap_preemption": True})
        assert sched.metrics.preemptions > 0
        kv = sched.metrics.kvtier
        assert kv["swap_out"] >= 1 and kv["swap_in"] >= 1

    def test_mid_step_engine_loss(self, setup):
        """A device loss with one step in flight: nothing of the in-flight
        round was absorbed, so journal replay from the last committed state
        regenerates every token bitwise."""
        m, params = setup
        _, _, sync = _run(m, params, _prompts(), pipelined=False, gen=12)
        inj = FaultInjector([FaultSpec(site="decode_step",
                                       kind="device_lost", nth=4)])
        eng, sched, pipe = _run(
            m, params, _prompts(), pipelined=True, gen=12, injector=inj,
            sched_kw={"retry": RetryPolicy(max_attempts=5)})
        assert inj.deaths == 1 and eng.rebuilds == 1
        assert all(r.state is RequestState.DONE for r in pipe)
        assert [r.tokens for r in pipe] == [r.tokens for r in sync]
        assert sched.metrics.faults["engine_losses"] == 1
        assert len(sched.journal) == 0

    def test_migration_detach_adopt(self, setup):
        """detach() is a drain boundary: the JournalEntry carries every
        device-produced token (including the one that was in flight), so
        the adopting scheduler resumes bitwise."""
        m, params = setup
        prompts = _prompts()
        _, _, sync = _run(m, params, prompts, pipelined=False, gen=12)
        src = ContinuousBatchScheduler(_engine(m, params), pipelined=True,
                                       sleep=lambda s: None)
        reqs = [src.submit(p, max_new_tokens=12) for p in prompts]
        for _ in range(30):  # past prefill, into pipelined decode
            src.step()
            if src._inflight is not None:
                break
        assert src._inflight is not None
        uid = reqs[0].uid
        entry = src.detach(uid)
        assert src._inflight is None  # detach drained the pipe
        dst = ContinuousBatchScheduler(_engine(m, params), pipelined=True,
                                       sleep=lambda s: None)
        moved = dst.adopt(entry)
        src.run_until_complete()
        dst.run_until_complete()
        assert moved.tokens == sync[0].tokens
        assert [r.tokens for r in reqs[1:]] == [r.tokens for r in sync[1:]]
        src.close()
        dst.close()

    def test_cancel_mid_flight(self, setup):
        """Cancelling a request whose row is in flight: the absorb skips it
        (flushed), survivors are unperturbed."""
        m, params = setup
        prompts = _prompts()
        _, _, sync = _run(m, params, prompts, pipelined=False, gen=12)
        eng = _engine(m, params)
        sched = ContinuousBatchScheduler(eng, pipelined=True,
                                         sleep=lambda s: None)
        reqs = [sched.submit(p, max_new_tokens=12) for p in prompts]
        for _ in range(30):
            sched.step()
            if (sched._inflight is not None
                    and reqs[2].uid in sched._inflight["rows"]):
                break
        assert sched._inflight is not None and reqs[2].uid in (
            sched._inflight["rows"])
        assert sched.cancel(reqs[2].uid)
        sched.run_until_complete()
        assert reqs[2].state is RequestState.CANCELLED
        assert [r.tokens for r in reqs[:2]] == [r.tokens for r in sync[:2]]
        sched.close()
        assert not eng.state.seqs and not eng.block_mgr._ref

    def test_max_new_tokens_predicted_by_count(self, setup):
        """A finish by ``max_new_tokens`` needs no token: the plan counts.
        No row is ever fed past its count, so nothing is rolled back, and
        nearly every round is enqueued with its predecessor unfetched."""
        m, params = setup
        prompts = _prompts()
        gens = [5, 9, 16]
        _, _, sync = _run(m, params, prompts, pipelined=False, gen=16)
        eng = _engine(m, params)
        sched = ContinuousBatchScheduler(eng, sleep=lambda s: None)
        assert sched.pipelined  # the run-ahead loop is the default
        reqs = [sched.submit(p, max_new_tokens=g)
                for p, g in zip(prompts, gens)]
        limit = {r.uid: len(r.prompt) + r.max_new_tokens - 1 for r in reqs}
        dispatch = eng.decode_dispatch

        def spy(tokens, prev=None):
            handle = dispatch(tokens, prev=prev)
            for uid in handle.uids:
                assert eng.state.seqs[uid].seen_tokens <= limit[uid], uid
            return handle

        eng.decode_dispatch = spy
        sched.run_until_complete()
        assert [r.tokens for r in reqs] == [r.tokens[:g]
                                            for r, g in zip(sync, gens)]
        p = sched.metrics.pipeline
        assert p["speculative_rollbacks"] == 0
        assert p["ahead_dispatches"] >= p["dispatches"] - 2
        sched.close()
        assert not eng.state.seqs and not eng.block_mgr._ref

    def _two_outstanding(self, m, params, gen=12):
        """A default scheduler stopped between its two phases: round N is
        staged and unfetched, round N+1 is enqueued behind it."""
        eng = _engine(m, params)
        sched = ContinuousBatchScheduler(eng, sleep=lambda s: None,
                                         retry=RetryPolicy(max_attempts=5))
        reqs = [sched.submit(p, max_new_tokens=gen) for p in _prompts()]
        for _ in range(30):
            sched.step()
            if (sched._inflight is not None
                    and len(sched._inflight["rows"]) == len(reqs)):
                break
        sched.step_dispatch()
        assert sched._pending_absorb is not None
        assert len(eng._unfetched) == 2
        for uid in (r.uid for r in reqs):
            assert uid in sched._pending_absorb["prev"]["rows"]
            assert uid in sched._inflight["rows"]
            assert eng.state.seqs[uid].uncommitted == 2
        return eng, sched, reqs

    def test_cancel_with_two_rounds_outstanding(self, setup):
        """A row cancelled while it rides BOTH outstanding rounds leaves
        both at the first absorb; survivors are unperturbed."""
        m, params = setup
        _, _, sync = _run(m, params, _prompts(), pipelined=False, gen=12)
        eng, sched, reqs = self._two_outstanding(m, params)
        assert sched.cancel(reqs[1].uid)
        assert sched.step_absorb()
        assert reqs[1].uid not in sched._inflight["rows"]
        sched.run_until_complete()
        assert reqs[1].state is RequestState.CANCELLED
        assert [reqs[0].tokens, reqs[2].tokens] == [sync[0].tokens,
                                                    sync[2].tokens]
        sched.close()
        assert not eng.state.seqs and not eng.block_mgr._ref

    def test_preempt_with_two_rounds_outstanding(self, setup):
        """A row preempted while it rides both outstanding rounds: both its
        in-flight tokens are discarded, and the replay from the committed
        history regenerates them bitwise."""
        m, params = setup
        _, _, sync = _run(m, params, _prompts(), pipelined=False, gen=12)
        eng, sched, reqs = self._two_outstanding(m, params)
        emitted = len(reqs[0].tokens)
        sched._preempt(reqs[0])
        sched.step_absorb()
        assert len(reqs[0].tokens) == emitted  # nothing absorbed for it
        assert reqs[0].uid not in sched._inflight["rows"]
        sched.run_until_complete()
        assert reqs[0].preemptions == 1
        assert [r.tokens for r in reqs] == [r.tokens for r in sync]
        sched.close()
        assert not eng.state.seqs and not eng.block_mgr._ref

    def test_engine_loss_with_two_rounds_outstanding(self, setup):
        """The device dies under two outstanding rounds (the fetch of the
        older one raises): nothing of either was absorbed, so the journal
        replay regenerates every token bitwise."""
        m, params = setup
        _, _, sync = _run(m, params, _prompts(), pipelined=False, gen=12)
        eng, sched, reqs = self._two_outstanding(m, params)

        class Lost:
            uids = sched._pending_absorb["prev"]["handle"].uids

            def fetch(self):
                raise DeviceLostError("injected: lost under two rounds")

        sched._pending_absorb["prev"]["handle"] = Lost()
        sched.step_absorb()
        assert eng.rebuilds == 1 and not eng._unfetched
        assert sched._inflight is None and sched._pending_absorb is None
        sched.run_until_complete()
        assert all(r.state is RequestState.DONE for r in reqs)
        assert [r.tokens for r in reqs] == [r.tokens for r in sync]
        assert sched.metrics.faults["engine_losses"] == 1
        sched.close()

    def test_stage_timing_split(self, setup):
        """observe_step's conflated number is split: the pipelined run
        populates the plan/wait/absorb gauges, the sync twin leaves them 0."""
        m, params = setup
        _, sync_sched, _ = _run(m, params, _prompts(), pipelined=False)
        assert sync_sched.metrics.pipeline["device_wait_ms"] == 0.0
        _, _, sched = _twin(m, params, _prompts())
        p = sched.metrics.pipeline
        assert p["device_wait_ms"] > 0.0 and p["absorb_ms"] > 0.0
        events = dict((k, v) for k, v, _ in sched.metrics.events())
        assert "serve/pipeline/dispatches" in events
        assert events["serve/pipeline/dispatches"] == p["dispatches"]


# ---------------------------------------------------------------------------
# engine seam: decode_dispatch / commit_step contracts
# ---------------------------------------------------------------------------

class TestEngineSeam:
    def test_dispatch_matches_decode_step_bitwise(self, setup):
        m, params = setup
        prompt = _prompts(1)[0]
        ref = _engine(m, params)
        t = int(ref.put([1], [prompt], greedy=True)[1])
        singles = []
        for _ in range(6):
            t = int(ref.decode_step({1: t}, greedy=True)[1])
            singles.append(t)
        eng = _engine(m, params)
        t = int(eng.put([7], [prompt], greedy=True)[7])
        got = []
        for _ in range(6):
            h = eng.decode_dispatch({7: t})
            t = h.fetch()[7]
            eng.commit_step(7, 0, 0)
            got.append(t)
        assert got == singles
        assert eng.state.seqs[7].uncommitted == 0
        eng.flush(7)

    def test_dispatch_fed_from_unfetched_handle_matches_decode_step(self,
                                                                    setup):
        """A round fed ON THE DEVICE from the unfetched round before it
        equals ``decode_step`` fed from the host, token for token; the
        history's placeholder is the real token once that round is
        fetched."""
        m, params = setup
        prompt = _prompts(1)[0]
        ref = _engine(m, params)
        t0 = t = int(ref.put([1], [prompt], greedy=True)[1])
        singles = []
        for _ in range(8):
            t = int(ref.decode_step({1: t}, greedy=True)[1])
            singles.append(t)
        eng = _engine(m, params)
        assert int(eng.put([7], [prompt], greedy=True)[7]) == t0
        d = eng.state.seqs[7]
        got = []
        h = eng.decode_dispatch({7: t0})
        for _ in range(7):
            nxt = eng.decode_dispatch({7: None}, prev=h)
            assert d.uncommitted == 2 and d.history[-1] == -1
            got.append(h.fetch()[7])
            assert d.history[-1] == got[-1]
            eng.commit_step(7, 0, retain=1)
            h = nxt
        got.append(h.fetch()[7])
        eng.commit_step(7, 0, 0)
        assert got == singles
        assert d.history == prompt + [t0] + singles[:-1]
        assert d.uncommitted == 0 and not eng._unfetched
        eng.flush(7)

    def test_device_feed_needs_a_row_of_an_unfetched_handle(self, setup):
        m, params = setup
        eng = _engine(m, params)
        t = int(eng.put([1], [_prompts(1)[0]], greedy=True)[1])
        u = int(eng.put([2], [_prompts(2)[1]], greedy=True)[2])
        with pytest.raises(EngineUsageError, match="there is none"):
            eng.decode_dispatch({1: None})
        h = eng.decode_dispatch({1: t})
        with pytest.raises(EngineUsageError, match="no row for it"):
            eng.decode_dispatch({1: None, 2: None}, prev=h)
        h.fetch()
        with pytest.raises(EngineUsageError, match="there is none"):
            eng.decode_dispatch({1: None}, prev=h)  # fetched: nothing to feed
        eng.commit_step(1, 0, 0)
        assert eng.state.seqs[1].uncommitted == 0  # the refusals fed nothing
        assert eng.state.seqs[2].uncommitted == 0 and u >= 0
        eng.flush(1)
        eng.flush(2)

    def test_third_dispatch_with_two_unfetched_raises(self, setup):
        m, params = setup
        eng = _engine(m, params)
        t = int(eng.put([1], [_prompts(1)[0]], greedy=True)[1])
        h1 = eng.decode_dispatch({1: t})
        h2 = eng.decode_dispatch({1: None}, prev=h1)
        seen = eng.state.seqs[1].seen_tokens
        with pytest.raises(EngineUsageError, match="two rounds"):
            eng.decode_dispatch({1: None}, prev=h2)
        assert eng.state.seqs[1].seen_tokens == seen  # nothing advanced
        h1.fetch()
        h3 = eng.decode_dispatch({1: None}, prev=h2)  # one fetched: room
        h2.fetch()
        h3.fetch()
        eng.commit_step(1, 0, 0)
        eng.flush(1)

    def test_scratch_sets_never_written_under_an_inflight_round(self, setup):
        """Two alternating scratch sets: a dispatch stages from the set no
        unfetched round was staged from, so the host arrays of a round in
        flight are never rewritten."""
        m, params = setup
        eng = _engine(m, params)
        t = int(eng.put([1], [_prompts(1)[0]], greedy=True)[1])
        u = int(eng.put([2], [_prompts(2)[1]], greedy=True)[2])

        def staged(handle):
            key, = [k for k in eng._scratch
                    if k[0] == "dispatch" and k[-1] == handle._set]
            return eng._scratch[key]

        h1 = eng.decode_dispatch({1: t, 2: u})
        frozen1 = [a.copy() for a in staged(h1)]
        h2 = eng.decode_dispatch({1: None, 2: None}, prev=h1)
        assert h2._set != h1._set
        assert all(np.array_equal(a, b)
                   for a, b in zip(staged(h1), frozen1))
        frozen2 = [a.copy() for a in staged(h2)]
        h1.fetch()
        h3 = eng.decode_dispatch({1: None, 2: None}, prev=h2)
        assert h3._set == h1._set  # the fetched round's set is free again
        assert all(np.array_equal(a, b)
                   for a, b in zip(staged(h2), frozen2))
        h2.fetch()
        h3.fetch()
        for uid in (1, 2):
            eng.commit_step(uid, 0, 0)
            eng.flush(uid)
        assert not eng.block_mgr._ref

    def test_commit_drop_rolls_back_the_fed_position(self, setup):
        m, params = setup
        eng = _engine(m, params)
        t = int(eng.put([1], [_prompts(1)[0]], greedy=True)[1])
        d = eng.state.seqs[1]
        seen0 = d.seen_tokens
        h = eng.decode_dispatch({1: t})
        assert d.seen_tokens == seen0 + 1 and d.uncommitted == 1
        h.fetch()
        eng.commit_step(1, drop=1, retain=0)
        assert d.seen_tokens == seen0 and d.uncommitted == 0
        eng.flush(1)
        assert not eng.block_mgr._ref


# ---------------------------------------------------------------------------
# check_pipeline_coherence: planted violations
# ---------------------------------------------------------------------------

class _FakeReq:
    def __init__(self, state=RequestState.DECODE):
        self.state = state


def _inflight_state(m, params):
    """A real engine with uid 1 in flight plus a coherent journal/live
    view — the fixture every planted violation perturbs."""
    eng = _engine(m, params)
    prompt = _prompts(1)[0]
    t = int(eng.put([1], [prompt], greedy=True)[1])
    journal = RequestJournal()
    req = Request(prompt=list(prompt), max_new_tokens=8, uid=1)
    journal.record(req)
    req.tokens.append(t)
    journal.commit(req)
    handle = eng.decode_dispatch({1: t})
    live = {1: _FakeReq()}
    return eng, journal, live, handle


class TestCoherenceSanitizer:
    def test_coherent_state_is_silent(self, setup):
        m, params = setup
        eng, journal, live, handle = _inflight_state(m, params)
        check_pipeline_coherence(eng, journal, live, {1: 1},
                                 dispatch_uids=[1])
        handle.fetch()
        eng.commit_step(1, 0, 0)
        check_pipeline_coherence(eng, journal, live, {})
        eng.flush(1)

    def test_double_feed_raises(self, setup):
        m, params = setup
        eng, journal, live, handle = _inflight_state(m, params)
        with pytest.raises(SanitizerError, match="double-feed"):
            check_pipeline_coherence(eng, journal, live, {1: 1},
                                     dispatch_uids=[1, 1])

    def test_ledger_drift_raises(self, setup):
        m, params = setup
        eng, journal, live, handle = _inflight_state(m, params)
        with pytest.raises(SanitizerError, match="ledger drift"):
            check_pipeline_coherence(eng, journal, live, {1: 2})

    def test_ledger_uid_without_live_request_raises(self, setup):
        m, params = setup
        eng, journal, live, handle = _inflight_state(m, params)
        with pytest.raises(SanitizerError, match="no live request"):
            check_pipeline_coherence(eng, journal, {}, {1: 1})

    def test_journal_ahead_of_absorb_raises(self, setup):
        """Committing the in-flight step's token before its absorb is THE
        corruption this sanitizer exists for (a recovery after it would
        replay a token the device never confirmed)."""
        m, params = setup
        eng, journal, live, handle = _inflight_state(m, params)
        journal.get(1).tokens.append(42)  # token from the un-absorbed step
        with pytest.raises(SanitizerError, match="journal ahead"):
            check_pipeline_coherence(eng, journal, live, {1: 1})

    def test_rollback_refcount_drift_raises(self, setup):
        """After absorb+commit an at-rest row's block list must cover its
        committed positions exactly (modulo the standing one-token
        over-allocation)."""
        m, params = setup
        eng, journal, live, handle = _inflight_state(m, params)
        handle.fetch()
        eng.commit_step(1, 0, 0)
        d = eng.state.seqs[1]
        d.blocks = d.blocks + [d.blocks[-1]] * 2  # leak two phantom blocks
        with pytest.raises(SanitizerError, match="refcount drift"):
            check_pipeline_coherence(eng, journal, live, {})

    def test_speculation_check_honours_inflight_allowance(self, setup):
        m, params = setup
        eng, journal, live, handle = _inflight_state(m, params)
        with pytest.raises(SanitizerError, match="uncommitted speculation"):
            check_speculation_commit(eng)  # no allowance declared
        check_speculation_commit(eng, inflight={1: 1})  # declared: silent
        handle.fetch()
        eng.commit_step(1, 0, 0)
        check_speculation_commit(eng)
        eng.flush(1)

    def test_checked_register_rejects_inflight_index(self, setup):
        """The checked cache's register() guard: a prefix-index limit that
        would cover in-flight positions is the bug, a bounded one is the
        designed pipelined commit."""
        m, params = setup
        cache = checked_cache_cls()(16, 16, 8, prefix_cache=True)
        from deepspeed_tpu.inference.v2.ragged_manager import (
            SequenceDescriptor)
        d = SequenceDescriptor(uid=1, slot=0)
        cache.ensure(d, 17)
        d.seen_tokens = 17
        d.history = list(range(17))
        d.uncommitted = 1
        with pytest.raises(SanitizerError):
            cache.register(d)  # unbounded: would index the in-flight tail
        cache.register(d, limit=16)  # bounded below the in-flight tail
        d.uncommitted = 0
        cache.free(d)


# ---------------------------------------------------------------------------
# pool: two-phase step + per-replica heartbeat regression
# ---------------------------------------------------------------------------

def _pool(m, params, n, *, pipelined, clock=None, eng_kw=None):
    def factory(i):
        return _engine(m, params, **(eng_kw or {}))
    kw = {} if clock is None else {"clock": clock}
    return EnginePool.build(factory, n, pipelined=pipelined,
                            sleep=lambda s: None, **kw)


class TestPoolTwoPhase:
    def test_pool_pipelined_bitwise(self, setup):
        """N pipelined replicas, dispatch-all then absorb-all: every
        request matches the fault-free single-engine synchronous oracle."""
        m, params = setup
        prompts = _prompts(3, seed=5) + _prompts(3, seed=6)
        uids = [700 + i for i in range(len(prompts))]
        ref = {}
        for p, u in zip(prompts, uids):
            _, _, reqs = _run(m, params, [p], pipelined=False, gen=8,
                              uids=[u])
            ref[u] = list(reqs[0].tokens)
        pool = _pool(m, params, 3, pipelined=True)
        reqs = [pool.submit(p, max_new_tokens=8, uid=u)
                for p, u in zip(prompts, uids)]
        pool.run_until_complete()
        for r in reqs:
            assert r.state is RequestState.DONE
            assert r.tokens == ref[r.uid], f"uid {r.uid} diverged"
        pool.close()

    def test_heartbeat_fed_at_each_replicas_own_absorb(self, setup):
        """Regression (the satellite bugfix): with dispatch-all/absorb-all
        the lease must be fed per replica AT ITS OWN ABSORB. A straggler
        burning wall-clock in its host phase must not stamp its
        neighbours' leases with a stale (or pool-end) timestamp: each
        replica's lease deadline reflects the clock at ITS absorb, so the
        deadlines strictly increase across the absorb order."""
        m, params = setup
        t = [0.0]
        pool = _pool(m, params, 3, pipelined=True, clock=lambda: t[0])
        mon = pool.enable_health(HealthMonitor(clock=lambda: t[0],
                                               lease_s=30.0))
        for rep in pool.replicas:
            orig = rep.scheduler.step_absorb

            def absorb(_orig=orig):
                out = _orig()
                t[0] += 10.0  # this replica's host phase burns 10s
                return out
            rep.scheduler.step_absorb = absorb
        pool.step()
        deadlines = [mon.lease_deadline_of(r.replica_id)
                     for r in pool.replicas]
        # fed at own absorb: replica i's lease was stamped after its own
        # 10s host phase — strictly increasing, 10s apart
        assert deadlines[1] == pytest.approx(deadlines[0] + 10.0)
        assert deadlines[2] == pytest.approx(deadlines[1] + 10.0)
        # and nobody's lease is stale relative to the pool-step end
        assert all(d > t[0] for d in deadlines)
        pool.close()

    def test_replica_lost_in_dispatch_phase_is_skipped_in_absorb(self,
                                                                 setup):
        """A replica dying in phase 1 is absorbed (journal replay onto
        survivors) and NOT stepped again in phase 2; its requests finish
        bitwise on the survivors."""
        m, params = setup
        prompts = _prompts(3, seed=9)
        uids = [810, 811, 812]
        ref = {}
        for p, u in zip(prompts, uids):
            _, _, reqs = _run(m, params, [p], pipelined=False, gen=6,
                              uids=[u])
            ref[u] = list(reqs[0].tokens)

        engines = {}

        def factory(i):
            eng = _engine(m, params)
            engines[i] = eng
            if i == 0:
                inj = FaultInjector([FaultSpec(site="decode_step",
                                               kind="device_lost", nth=2)])
                return inj.wrap(eng)
            return eng

        pool = EnginePool.build(factory, 2, pipelined=True,
                                sleep=lambda s: None,
                                retry=RetryPolicy(max_attempts=5))
        reqs = [pool.submit(p, max_new_tokens=6, uid=u)
                for p, u in zip(prompts, uids)]
        pool.run_until_complete()
        for r in reqs:
            assert r.state is RequestState.DONE
            assert r.tokens == ref[r.uid], f"uid {r.uid} diverged"
        pool.close()


# ---------------------------------------------------------------------------
# a step accounted by its kind (docs/TRACING.md): the barrier's reason, the
# engine's bubbles, a round the device had to wait for
# ---------------------------------------------------------------------------

@pytest.fixture
def session(tmp_path):
    """A real profiler session: the recorder is on exactly while it is."""
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


def _decoding(m, params, gen=24):
    """A warm scheduler (both shapes ran) with one request in steady decode:
    the pipe is full, a round is unfetched."""
    sched = ContinuousBatchScheduler(_engine(m, params), sleep=lambda s: None)
    sched.submit(_prompts()[2], max_new_tokens=3)
    sched.run_until_complete()
    sched.submit(_prompts()[1], max_new_tokens=gen)
    while sched._inflight is None:
        sched.step()
    return sched


def _named(name):
    return [s for s in tracing.snapshot() if s.name == name]


def _unfetched_intervals(spans):
    """[(launch returned, fetch returned)] of every engine step recorded:
    the end of its ``engine.enqueue`` to the end of its ``engine.fetch`` (a
    deferred round's fetch sits under the ``sched.wait`` of a later step: it
    is the next fetch in time that no earlier launch took)."""
    launches = sorted(s.end for s in spans if s.name == "engine.enqueue")
    fetches = sorted(s.end for s in spans if s.name == "engine.fetch")
    return list(zip(launches, fetches))


class TestStepAccounting:
    def test_backlog_barrier_names_itself_and_its_bubbles(self, setup,
                                                          session):
        """A prompt arriving under a full pipe: the round that carries its
        first chunk says ``barrier="backlog"`` on ``sched.dispatch``, the
        drain before it ends in an ``engine.bubble`` of that cause (under the
        mixed step's ``engine.dispatch``), and the first round after the run
        of chunk steps restarts the pipe: cause ``restart``."""
        m, params = setup
        sched = _decoding(m, params)
        tracing.clear()
        sched.submit(_prompts()[0], max_new_tokens=4)      # 33 tokens: chunks
        sched.run_until_complete()
        spans = tracing.snapshot()
        by_id = {s.id: s for s in spans}
        sync = [s for s in _named("sched.dispatch") if "barrier" in s.attrs]
        assert sync and {s.attrs["barrier"] for s in sync} == {"backlog"}
        assert all(s.attrs["kind"] in ("mixed", "prefill") for s in sync)
        assert not [s for s in _named("sched.dispatch")
                    if s.attrs["kind"] == "decode" and "barrier" in s.attrs]
        bubbles = _named("engine.bubble")
        causes = [b.attrs["cause"] for b in sorted(bubbles,
                                                   key=lambda b: b.start)]
        # one bubble a chunk step, then the restart; nothing else in between
        assert causes[:len(sync)] == ["backlog"] * len(sync)
        assert causes[len(sync)] == "restart"
        for b in bubbles:
            parent = by_id[b.parent]
            assert parent.name == "engine.dispatch" and b.end > b.start
            assert parent.start <= b.end <= parent.end
            assert parent.attrs["ahead"] == 0 and "starved" not in parent.attrs
        first = by_id[min(bubbles, key=lambda b: b.start).parent]
        assert by_id[first.parent].attrs["barrier"] == "backlog"

    def test_bubbles_never_overlap_an_unfetched_round(self, setup, session):
        """A bubble starts where a fetch returned with nothing unfetched and
        ends where the next launch returned: no instant of it lies between a
        launch and its fetch."""
        m, params = setup
        sched = _decoding(m, params)
        sched.submit(_prompts()[0], max_new_tokens=6)
        sched.run_until_complete()
        spans = tracing.snapshot()
        bubbles = _named("engine.bubble")
        flights = _unfetched_intervals(spans)
        assert len(bubbles) >= 3 and len(flights) > len(bubbles)
        for b in bubbles:
            # the launch that ends the bubble returns just before its clock
            # reading: that flight alone, the one enqueued under the bubble's
            # own ``engine.dispatch``, may begin inside the last instants
            # (how many is the host's to say: 50 us was a guess a busy
            # sandbox broke)
            own = {s.end for s in spans
                   if s.name == "engine.enqueue" and s.parent == b.parent}
            assert own
            assert not [(lo, hi) for lo, hi in flights
                        if lo not in own and lo < b.end and hi > b.start]
        # and they follow each other: no two bubbles overlap either
        ends = sorted((b.start, b.end) for b in bubbles)
        assert all(a[1] <= b[0] for a, b in zip(ends, ends[1:]))

    def test_an_idle_schedulers_gap_reads_empty(self, setup, session):
        """Nothing live or queued at the end of a step: whatever round ends
        the gap (here one with a backlog), the gap is the empty server's."""
        m, params = setup
        sched = _decoding(m, params, gen=5)
        sched.run_until_complete()
        assert not sched.queue_depth and not sched.live_count
        tracing.clear()
        sched.submit(_prompts()[0], max_new_tokens=3)
        sched.run_until_complete()
        bubbles = sorted(_named("engine.bubble"), key=lambda b: b.start)
        assert bubbles[0].attrs["cause"] == "empty"
        assert "empty" not in [b.attrs["cause"] for b in bubbles[1:]]
        sync = [s for s in _named("sched.dispatch") if "barrier" in s.attrs]
        assert sync[0].attrs["barrier"] == "backlog"    # the round says its own

    def test_barrier_reasons_count_with_tracing_off(self, setup):
        """``serve/pipeline/barriers/<reason>``: the one always-on addition,
        one dict increment a drain."""
        m, params = setup
        assert not tracing.enabled()
        sched = _decoding(m, params)
        assert "barriers/backlog" not in sched.metrics.pipeline
        stalls = sched.metrics.pipeline["pipeline_stalls"]
        sched.submit(_prompts()[0], max_new_tokens=4)
        sched.run_until_complete()
        p = sched.metrics.pipeline
        assert p["barriers/backlog"] == 1
        assert p["pipeline_stalls"] == stalls + 1
        events = {k: v for k, v, _ in sched.metrics.events()}
        assert events["serve/pipeline/barriers/backlog"] == 1.0
        assert tracing.snapshot() == []

    @pytest.mark.parametrize("reason", ["backlog", "stalled", "speculation",
                                        "horizon", "dynamic", None])
    def test_the_barrier_says_why(self, setup, reason):
        m, params = setup
        sched = ContinuousBatchScheduler(_engine(m, params),
                                         sleep=lambda s: None)
        req = sched.submit(_prompts()[2], max_new_tokens=8,
                           sampling=SamplingParams(temperature=0.7, seed=1))
        while req.state is not RequestState.DECODE:
            sched.step()
        feed = {req.uid: req.tokens[-1]}
        if reason == "stalled":
            sched._stalled = True
        elif reason == "speculation":
            sched.spec = object()
        elif reason == "horizon":
            sched._effective_horizon = lambda now, feed: 4
        elif reason == "dynamic":
            def mask(tokens, vocab):
                return None
            mask.dynamic = True
            req.sampling = SamplingParams(temperature=0.7, seed=1,
                                          processors=(mask,))
        assert sched._pipeline_barrier(0.0, feed,
                                       7 if reason == "backlog" else 0) == reason

    def test_every_run_ahead_round_says_whether_it_was_starved(self, setup,
                                                               session):
        m, params = setup
        sched = _decoding(m, params)
        sched.submit(_prompts()[0], max_new_tokens=6)
        sched.run_until_complete()
        rounds = _named("engine.dispatch")
        ahead = [s for s in rounds if s.attrs["ahead"] == 1]
        assert len(ahead) >= 10
        assert all(s.attrs["starved"] in (0, 1) for s in ahead)
        assert not [s for s in rounds
                    if s.attrs["ahead"] == 0 and "starved" in s.attrs]

    def test_off_no_span_no_bubble_and_no_is_ready(self, setup, monkeypatch):
        """Tracing off: ``span()`` is the shared no-op, the engine keeps no
        fetch time, and nobody asks the device whether a round finished."""
        m, params = setup
        assert not tracing.enabled()
        assert tracing.span("engine.dispatch") is tracing.NO_SPAN
        asked = []

        def note(disp, eng=None, real=InferenceEngineV2._note_launch):
            assert disp is tracing.NO_SPAN
            for h in eng._unfetched:
                monkeypatch.setattr(
                    type(h._dev), "is_ready",
                    lambda self: asked.append(1) or True, raising=False)
            return real(eng, disp)

        sched = ContinuousBatchScheduler(_engine(m, params),
                                         sleep=lambda s: None)
        eng = sched.engine
        monkeypatch.setattr(eng, "_note_launch",
                            lambda disp: note(disp, eng), raising=False)
        sched.submit(_prompts()[0], max_new_tokens=8)
        sched.run_until_complete()
        assert sched.metrics.pipeline["ahead_dispatches"] > 0
        assert not asked and eng._fetched_ns == 0 and eng._idle_cause is None
        assert tracing.snapshot() == []
