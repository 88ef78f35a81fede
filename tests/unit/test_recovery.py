"""Engine-loss recovery tests (docs/RESILIENCE.md): the request journal,
the ``device_lost`` fault kind's permanently-dead injector semantics, the
watchdog hard-breach escalation, breaker HALF_OPEN re-arm, the engine's
hot ``rebuild()`` hook, and the scheduler's full recovery orchestration —
bitwise-lossless replay across engine deaths at every lifecycle edge
(mid-prefill, mid-decode, mid-speculation, preempted, teardown), typed
deadline cancellation during rebuild, the stream() never-hang regression,
and the consecutive-rebuild budget."""

import jax
import numpy as np
import pytest

from deepspeed_tpu.analysis.sanitizer import SanitizerError, check_recovery
from deepspeed_tpu.inference.v2 import InferenceEngineV2
from deepspeed_tpu.models import build_model
from deepspeed_tpu.resilience import (BreakerState, CircuitBreaker,
                                      DeviceLostError, FaultInjector,
                                      FaultSpec, RecoveryPolicy,
                                      RequestFailedError, RequestJournal,
                                      RetryPolicy, StepWatchdog,
                                      TransientEngineError,
                                      UnrecoverableEngineError)
from deepspeed_tpu.serve import (ContinuousBatchScheduler,
                                 PromptLookupProposer, Request, RequestState,
                                 SamplingParams)
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


def _assert_pool_restored(eng):
    assert not eng.state.seqs
    assert eng.block_mgr.free_blocks == eng.block_mgr.num_blocks - 1
    assert_trace_bounds(eng)
    eng.block_mgr.check_invariants([])


def _run_workload(m, params, n_req, *, specs=None, seed=17, eng_kw=None,
                  sampled=False, **sched_kw):
    """Submit ``n_req`` seeded requests, run to completion, return
    (scheduler, engine, injector, requests in submission order).
    ``sampled=True`` gives each request its own seeded temperature-0.8
    :class:`SamplingParams` — the stochastic twin of the greedy workload
    (docs/SAMPLING.md: replay must stay bitwise either way)."""
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, 128, int(rng.integers(8, 25))).tolist()
               for _ in range(n_req)]
    gens = [int(rng.integers(4, 9)) for _ in range(n_req)]
    eng = _engine(m, params, **(eng_kw or {}))
    inj = None if specs is None else FaultInjector(specs)
    driven = eng if inj is None else inj.wrap(eng)
    sched_kw.setdefault("retry", RetryPolicy(max_attempts=5))
    sched = ContinuousBatchScheduler(driven, sleep=lambda s: None, **sched_kw)
    reqs = [sched.submit(p, max_new_tokens=g,
                         sampling=(SamplingParams(temperature=0.8,
                                                  seed=100 + i)
                                   if sampled else None))
            for i, (p, g) in enumerate(zip(prompts, gens))]
    sched.run_until_complete()
    return sched, eng, inj, reqs


class TestTaxonomy:
    def test_device_lost_is_unrecoverable_is_runtime(self):
        assert issubclass(DeviceLostError, UnrecoverableEngineError)
        assert issubclass(UnrecoverableEngineError, RuntimeError)
        # disjoint from the per-request/transient families: recovery
        # dispatch must never confuse an engine loss with either
        assert not issubclass(DeviceLostError, TransientEngineError)
        assert not issubclass(DeviceLostError, RequestFailedError)

    def test_device_lost_spec_validation(self):
        with pytest.raises(ValueError, match="nth"):
            FaultSpec(site="put", kind="device_lost")
        # arm sites are the dispatch surface only — teardown paths are
        # reached while dead anyway (the global-dead semantics)
        with pytest.raises(ValueError, match="dispatch surface"):
            FaultSpec(site="flush", kind="device_lost", nth=1)
        for site in ("put", "decode_multi", "verify_multi"):
            FaultSpec(site=site, kind="device_lost", nth=1)


class TestRequestJournal:
    def test_record_commit_resolve_lifecycle(self):
        j = RequestJournal()
        req = Request(prompt=[1, 2, 3], max_new_tokens=4, priority=2,
                      deadline=9.5, arrival_time=1.0, eos_token=7)
        e = j.record(req)
        assert len(j) == 1 and req.uid in j
        assert e.replay_tokens() == [1, 2, 3]
        assert (e.priority, e.deadline, e.arrival_time, e.eos_token,
                e.max_new_tokens) == (2, 9.5, 1.0, 7, 4)
        # write-ahead copy: mutating the request's prompt list cannot
        # retroactively edit the journal
        req.prompt.append(99)
        assert e.prompt == [1, 2, 3]
        req.tokens.extend([10, 11])
        j.commit(req)
        assert e.tokens == [10, 11] and e.commits == 1
        # append-only tail sync: only the new token is copied
        req.tokens.append(12)
        j.commit(req)
        assert e.tokens == [10, 11, 12] and e.commits == 2
        assert e.replay_tokens() == [1, 2, 3, 10, 11, 12]
        # no new tokens: commit is a no-op, not a counted commit point
        j.commit(req)
        assert e.commits == 2 and j.commit_points == 2
        j.resolve(req.uid)
        assert len(j) == 0 and j.resolutions == 1
        j.resolve(req.uid)  # idempotent
        assert j.resolutions == 1
        j.commit(req)  # resolved uid: silently ignored
        assert j.commit_points == 2

    def test_live_keeps_admission_order(self):
        j = RequestJournal()
        reqs = [Request(prompt=[i]) for i in range(5)]
        for r in reqs:
            j.record(r)
        j.resolve(reqs[2].uid)
        assert [e.uid for e in j.live()] == [
            r.uid for i, r in enumerate(reqs) if i != 2]
        assert j.uids() == [e.uid for e in j.live()]


class TestRecoveryPolicy:
    def test_budget_and_rearm(self):
        pol = RecoveryPolicy(max_consecutive_rebuilds=2)
        assert pol.enabled
        assert pol.admit(1.0, "DeviceLostError")
        pol.note_rebuilt(1.0, replayed=3, cancelled=0)
        assert pol.admit(2.0, "DeviceLostError")
        pol.note_rebuilt(2.0, replayed=3, cancelled=1)
        # third consecutive loss: budget spent
        assert not pol.admit(3.0, "DeviceLostError")
        # one proven-healthy dispatch re-arms the full budget
        pol.note_engine_ok()
        assert pol.admit(4.0, "DeviceLostError")
        events = [ev for _, ev in pol.trail]
        assert events.count("rebuild_budget_exhausted") == 1
        assert pol.rebuilds == 2

    def test_zero_budget_disables_recovery(self):
        pol = RecoveryPolicy(max_consecutive_rebuilds=0)
        assert not pol.enabled
        assert not pol.admit(0.0, "DeviceLostError")
        with pytest.raises(ValueError):
            RecoveryPolicy(max_consecutive_rebuilds=-1)


class _DummyEngine:
    """Duck-typed inner engine for proxy-level tests."""

    def __init__(self):
        self.rebuilds = 0

    def put(self, uids, tokens, **kw):
        return {}

    def decode_multi(self, feed, **kw):
        return {}

    def flush(self, uid):
        return None

    def rebuild(self):
        self.rebuilds += 1


class TestInjectorDeviceLost:
    def test_death_is_permanent_until_rebuild(self):
        inj = FaultInjector([FaultSpec(site="put", kind="device_lost", nth=2)])
        eng = inj.wrap(_DummyEngine())
        eng.put([1], [[1]])
        with pytest.raises(DeviceLostError):
            eng.put([1], [[1]])
        assert inj.deaths == 1 and inj.fired["device_lost"] == 1
        # EVERY site raises while dead — including teardown
        for call in (lambda: eng.decode_multi({1: 1}),
                     lambda: eng.flush(1),
                     lambda: eng.put([2], [[2]])):
            with pytest.raises(DeviceLostError):
                call()
        assert inj.dead_calls == 3
        # rebuild replaces the incarnation AND revives the injector
        eng.rebuild()
        assert eng.inner.rebuilds == 1 and inj.revivals == 1
        eng.put([3], [[3]])  # serves again
        assert inj.device_lost is None

    def test_random_plan_mixes_seeded_device_losses(self):
        a = FaultInjector.random_plan(5, horizon=200, rate=0.03,
                                      n_device_lost=3, sleep=lambda s: None)
        b = FaultInjector.random_plan(5, horizon=200, rate=0.03,
                                      n_device_lost=3, sleep=lambda s: None)
        assert a.specs == b.specs  # same seed, same plan
        dl = [s for s in a.specs if s.kind == "device_lost"]
        assert len(dl) == 3
        assert all(s.site in ("put", "decode_multi", "verify_multi")
                   and 1 <= s.nth <= 200 for s in dl)
        c = FaultInjector.random_plan(6, horizon=200, rate=0.03,
                                      n_device_lost=3, sleep=lambda s: None)
        assert c.specs != a.specs


class TestWatchdogHardBreach:
    def test_consecutive_escalations_raise(self):
        wd = StepWatchdog(step_budget_s=0.01, escalate_after=2,
                          hard_breach_after=2)
        # two breaches -> one escalation; repeat -> second escalation is
        # the hard breach
        assert wd.observe("decode", 1.0) == (True, False)
        assert wd.observe("decode", 1.0) == (True, True)
        assert wd.observe("decode", 1.0) == (True, False)
        with pytest.raises(UnrecoverableEngineError, match="wedged"):
            wd.observe("decode", 1.0)
        assert wd.hard_breaches == 1 and wd.escalations == 2

    def test_healthy_step_resets_the_escalation_streak(self):
        wd = StepWatchdog(step_budget_s=0.01, escalate_after=1,
                          hard_breach_after=2)
        assert wd.observe("decode", 1.0) == (True, True)
        assert wd.observe("decode", 0.0) == (False, False)  # resets
        assert wd.observe("decode", 1.0) == (True, True)
        assert wd.observe("decode", 0.0) == (False, False)
        assert wd.hard_breaches == 0

    def test_default_off_never_raises(self):
        wd = StepWatchdog(step_budget_s=0.01, escalate_after=1)
        for _ in range(10):
            assert wd.observe("decode", 1.0) == (True, True)
        assert wd.hard_breaches == 0
        with pytest.raises(ValueError):
            StepWatchdog(hard_breach_after=0)


class TestBreakerRearm:
    def test_rearm_from_any_state(self):
        b = CircuitBreaker(failure_threshold=1, cooldown_s=100.0)
        b.on_failure(1.0)
        assert b.state is BreakerState.OPEN
        # recovery skips the cooldown: the sick engine was replaced
        b.rearm_half_open(2.0)
        assert b.state is BreakerState.HALF_OPEN
        assert b.consecutive_failures == 0
        b.on_success(3.0)
        assert b.state is BreakerState.CLOSED
        b.rearm_half_open(4.0)  # from CLOSED too
        assert b.state is BreakerState.HALF_OPEN
        half_opens = b.half_opens
        b.rearm_half_open(5.0)  # idempotent while already HALF_OPEN
        assert b.half_opens == half_opens
        assert [s for _, s in b.transitions] == [
            "open", "half_open", "closed", "half_open"]


class TestEngineRebuild:
    def test_rebuild_replaces_pools_same_geometry(self, setup):
        m, params = setup
        eng = _engine(m, params)
        eng.put([1, 2], [[5, 6, 7], [9, 10]], greedy=True)
        assert eng.state.seqs and eng.block_mgr.free_blocks < 32
        old_mgr, old_kv = eng.block_mgr, eng.kv
        ragged_before = eng.ragged_cache_size
        eng.rebuild()
        assert eng.rebuilds == 1
        assert eng.block_mgr is not old_mgr and eng.kv is not old_kv
        assert (eng.block_mgr.num_blocks, eng.block_mgr.block_size) == (
            old_mgr.num_blocks, old_mgr.block_size)
        _assert_pool_restored(eng)
        # same shapes re-enter the SAME compiled programs: replaying the
        # identical work adds zero traces across incarnations
        eng.put([1, 2], [[5, 6, 7], [9, 10]], greedy=True)
        assert eng.ragged_cache_size == ragged_before
        eng.flush(1)
        eng.flush(2)
        _assert_pool_restored(eng)


class TestSchedulerRecovery:
    @pytest.mark.parametrize("sampled", [False, True],
                             ids=["greedy", "temp0.8"])
    def test_mid_decode_loss_bitwise(self, setup, sampled):
        """The acceptance core: seeded engine deaths mid-decode; every
        request completes with tokens bitwise identical to the fault-free
        run, the journal drains, the pool comes back whole, the breaker
        trail records the HALF_OPEN probe walk. The sampled twin proves
        the counter-based PRNG keys (docs/SAMPLING.md) re-derive the same
        tokens across the rebuild replay."""
        m, params = setup
        _, ref_eng, _, ref = _run_workload(m, params, 6, sampled=sampled)
        assert all(r.state is RequestState.DONE for r in ref)
        _assert_pool_restored(ref_eng)
        sched, eng, inj, reqs = _run_workload(
            m, params, 6, sampled=sampled,
            specs=[FaultSpec(site="decode_multi", kind="device_lost", nth=3),
                   FaultSpec(site="put", kind="device_lost", nth=11)],
            eng_kw={"decode_horizon": 4})
        assert inj.deaths == 2 and inj.revivals == 2
        assert eng.rebuilds == 2
        assert all(r.state is RequestState.DONE for r in reqs)
        assert [r.tokens for r in reqs] == [r.tokens for r in ref]
        f = sched.metrics.faults
        assert f["engine_losses"] == 2 and f["engine_rebuilds"] == 2
        assert f["recovery_replays"] > 0 and f["recovery_cancelled"] == 0
        assert len(sched.journal) == 0
        trans = [s for _, s in sched.breaker.transitions]
        assert any(trans[i:i + 2] == ["half_open", "closed"]
                   for i in range(len(trans)))
        events = [ev for _, ev in sched.recovery.trail]
        assert sum(ev.startswith("rebuilt:") for ev in events) == 2
        _assert_pool_restored(eng)

    def test_mid_prefill_loss_replays_from_prompt(self, setup):
        """Death on the very first engine call: requests die mid-prefill
        with zero committed tokens and replay whole from the journal."""
        m, params = setup
        _, _, _, ref = _run_workload(m, params, 4)
        _, eng, inj, reqs = _run_workload(
            m, params, 4,
            specs=[FaultSpec(site="put", kind="device_lost", nth=1)])
        assert inj.deaths == 1
        assert all(r.state is RequestState.DONE for r in reqs)
        assert [r.tokens for r in reqs] == [r.tokens for r in ref]
        _assert_pool_restored(eng)

    def test_mid_speculation_loss_bitwise(self, setup):
        """Death at the verify dispatch: uncommitted draft positions die
        with the engine (never journaled — only emitted tokens commit),
        and the speculative scheduler replays bitwise."""
        m, params = setup
        _, _, _, ref = _run_workload(m, params, 6)
        sched, eng, inj, reqs = _run_workload(
            m, params, 6,
            # nth=1: on the installed JAX this workload's greedy streams give
            # prompt lookup one draft worth verifying, so there is one verify
            specs=[FaultSpec(site="verify_multi", kind="device_lost", nth=1)],
            eng_kw={"decode_horizon": 4}, proposer=PromptLookupProposer())
        assert inj.deaths == 1
        assert all(r.state is RequestState.DONE for r in reqs)
        assert [r.tokens for r in reqs] == [r.tokens for r in ref]
        assert_trace_bounds(eng)
        _assert_pool_restored(eng)

    def test_preempted_and_queued_ride_through(self, setup):
        """A loss under pool pressure: preempted victims are already
        queued and simply meet the fresh engine; nothing is double-queued
        or dropped."""
        m, params = setup
        _, _, _, ref = _run_workload(m, params, 8,
                                     eng_kw={"num_blocks": 17})
        _, eng, inj, reqs = _run_workload(
            m, params, 8, eng_kw={"num_blocks": 17},
            specs=[FaultSpec(site="put", kind="device_lost", nth=13)])
        assert inj.deaths == 1
        assert all(r.state is RequestState.DONE for r in reqs)
        assert [r.tokens for r in reqs] == [r.tokens for r in ref]
        _assert_pool_restored(eng)

    def test_stream_sees_pause_not_error(self, setup):
        """A streaming consumer rides through an engine death: it receives
        every token, bitwise, and no exception."""
        m, params = setup
        rng = np.random.default_rng(3)
        prompt = rng.integers(0, 128, 12).tolist()

        eng0 = _engine(m, params, decode_horizon=4)
        s0 = ContinuousBatchScheduler(eng0, sleep=lambda s: None)
        ref = list(s0.stream(s0.submit(prompt, max_new_tokens=10)))

        # the death lands mid-stream: the consumer has already pulled the
        # first fused round's tokens when the second dispatch kills the
        # engine
        inj = FaultInjector([FaultSpec(site="decode_multi",
                                       kind="device_lost", nth=2)])
        eng = _engine(m, params, decode_horizon=4)
        sched = ContinuousBatchScheduler(inj.wrap(eng), sleep=lambda s: None)
        got = list(sched.stream(sched.submit(prompt, max_new_tokens=10)))
        assert inj.deaths == 1
        assert got == ref and len(got) == 10

    def test_deadline_cancel_during_rebuild_is_typed(self, setup):
        """Satellite regression: a request whose deadline passes while the
        engine is down is cancelled TYPED during recovery — its stream()
        consumer re-raises RequestFailedError, never hangs, never ends
        silently mid-output."""
        m, params = setup
        t = [0.0]
        eng = _engine(m, params)
        sched = ContinuousBatchScheduler(eng, clock=lambda: t[0],
                                         sleep=lambda s: None)
        rng = np.random.default_rng(5)
        survivor = sched.submit(rng.integers(0, 128, 10).tolist(),
                                max_new_tokens=6)
        doomed = sched.submit(rng.integers(0, 128, 10).tolist(),
                              max_new_tokens=6, deadline=5.0)
        for _ in range(3):
            sched.step()
        assert doomed.state in (RequestState.PREFILL, RequestState.DECODE)
        # the device dies; by the time recovery runs, the deadline passed
        # (the rebuild pause IS the time the clock skips over)
        t[0] = 10.0
        sched._engine_dead = DeviceLostError("device reset during step 3")
        sched.step()
        assert doomed.state is RequestState.CANCELLED
        assert doomed.cancel_reason == "deadline"
        assert isinstance(doomed.error, RequestFailedError)
        assert "recovery" in str(doomed.error)
        assert sched.metrics.faults["recovery_cancelled"] == 1
        with pytest.raises(RequestFailedError, match="recovery"):
            list(sched.stream(doomed))
        sched.run_until_complete()
        assert survivor.state is RequestState.DONE
        assert len(survivor.tokens) == 6
        _assert_pool_restored(eng)

    def test_teardown_loss_is_absorbed_then_recovered(self, setup):
        """An engine loss on a cancel's flush path must not fail the
        cancel: the terminal transition completes host-side and the NEXT
        step runs recovery."""
        m, params = setup
        inj = FaultInjector([])
        eng = _engine(m, params)
        sched = ContinuousBatchScheduler(inj.wrap(eng), sleep=lambda s: None)
        rng = np.random.default_rng(9)
        keep = sched.submit(rng.integers(0, 128, 10).tolist(),
                            max_new_tokens=5)
        victim = sched.submit(rng.integers(0, 128, 10).tolist(),
                              max_new_tokens=5)
        for _ in range(2):
            sched.step()
        inj.device_lost = "device reset"  # dies between steps
        assert sched.cancel(victim.uid) is True
        assert victim.state is RequestState.CANCELLED
        assert victim.error is None  # user cancel: no error to re-raise
        assert sched._engine_dead is not None
        sched.run_until_complete()
        assert keep.state is RequestState.DONE and len(keep.tokens) == 5
        assert sched.metrics.faults["engine_rebuilds"] == 1
        assert len(sched.journal) == 0
        _assert_pool_restored(eng)

    def test_rebuild_budget_exhausted_reraises(self, setup):
        """Back-to-back deaths with no healthy dispatch in between spend
        the consecutive-rebuild budget; the loss then propagates typed."""
        m, params = setup
        specs = [FaultSpec(site="put", kind="device_lost", nth=n)
                 for n in (1, 2, 3)]
        with pytest.raises(DeviceLostError):
            _run_workload(m, params, 2, specs=specs,
                          recovery=RecoveryPolicy(max_consecutive_rebuilds=1))

    def test_recovery_disabled_propagates_first_loss(self, setup):
        m, params = setup
        with pytest.raises(DeviceLostError):
            _run_workload(
                m, params, 2,
                specs=[FaultSpec(site="put", kind="device_lost", nth=1)],
                recovery=RecoveryPolicy(max_consecutive_rebuilds=0))

    def test_watchdog_hard_breach_drives_recovery(self, setup):
        """Satellite: a wedged dispatch (every step blows its budget) now
        triggers engine rebuilds instead of shedding forever — and when
        rebuilds cannot fix it, the hard breach escalates out typed."""
        m, params = setup
        eng = _engine(m, params)
        wd = StepWatchdog(step_budget_s=1e-9, escalate_after=1,
                          hard_breach_after=1)
        sched = ContinuousBatchScheduler(
            eng, watchdog=wd, sleep=lambda s: None,
            recovery=RecoveryPolicy(max_consecutive_rebuilds=2))
        rng = np.random.default_rng(11)
        sched.submit(rng.integers(0, 128, 10).tolist(), max_new_tokens=4)
        with pytest.raises(UnrecoverableEngineError, match="wedged"):
            sched.run_until_complete()
        assert sched.metrics.faults["engine_rebuilds"] == 2
        assert wd.hard_breaches == 3
        # the final, budget-exhausted step raises before its metrics sync
        assert sched.metrics.faults["watchdog_hard_breaches"] == 2


class TestCheckRecovery:
    def test_flags_dropped_and_leaked_uids(self):
        j = RequestJournal()
        queued = Request(prompt=[1])
        dropped = Request(prompt=[2])
        leaked = Request(prompt=[3])
        for r in (queued, dropped, leaked):
            j.record(r)
        leaked.state = RequestState.CANCELLED  # terminal but never resolved
        all_reqs = {r.uid: r for r in (queued, dropped, leaked)}
        with pytest.raises(SanitizerError) as ei:
            check_recovery(j, [queued], all_reqs)
        msg = str(ei.value)
        assert f"uid {dropped.uid}" in msg and "neither re-queued" in msg
        assert f"uid {leaked.uid}" in msg and "resolve() is missing" in msg
        # clean accounting passes: dropped re-queued, leaked resolved
        j.resolve(leaked.uid)
        check_recovery(j, [queued, dropped], all_reqs)
        # journaled-but-unknown uid is a drop too
        ghost = Request(prompt=[4])
        j.record(ghost)
        with pytest.raises(SanitizerError, match="unknown"):
            check_recovery(j, [queued, dropped], all_reqs)
