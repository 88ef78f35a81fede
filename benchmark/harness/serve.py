"""The serving runner: paged ``InferenceEngineV2`` under
``ContinuousBatchScheduler``, driven on the real clock by an open loop
(arrivals due at fixed times) or a closed loop (a fixed number outstanding)."""

import collections
import time

import jax
import jax.numpy as jnp
import numpy as np

from . import check, traffic
from .setup import SetupClock
from .stats import quantile
from .trace import Tracer
from .train import build_model, reference_config, seeded

CHECK_UID = 1 << 40          # uids of the correctness pass, clear of the scheduler's


class Rec:
    __slots__ = ("due", "prompt", "out_len", "submitted", "times", "req", "cut")

    def __init__(self, due, prompt, out_len):
        self.due, self.prompt, self.out_len = due, prompt, out_len
        self.submitted, self.times, self.req, self.cut = None, [], None, False


def engine_logits(engine, samples):
    """Prefill then decode through the paged cache, full logits each step:
    (K, R, V) for K samples of (prompt, forced tokens)."""
    out = []
    for k, (prompt, forced) in enumerate(samples):
        uid = CHECK_UID + k
        rows = [engine.put([uid], [prompt], greedy=False)[uid]]
        for tok in forced:
            rows.append(engine.decode_step({uid: int(tok)}, greedy=False)[uid])
        engine.flush(uid)
        out.append(np.stack(rows))
    return np.stack(out)


def check_samples(mix, recs, seed, vocab):
    """(samples, padded ids, logit rows) for the correctness pass: the first
    requests' prompts, each followed by seeded forced tokens. The ids are
    padded to the longest prompt the mix can send, so every seed runs the
    reference's programs at one shape and only a checkout's first run
    compiles them."""
    chk = mix["check"]
    rng = np.random.default_rng([int(seed), 2])
    samples = [(r.prompt, rng.integers(0, vocab, chk["forced_tokens"]).tolist())
               for r in recs[:chk["samples"]]]
    width = -(-(mix["prompt_len"]["hi"] + chk["forced_tokens"]) // 128) * 128
    ids = np.zeros((len(samples), width), np.int32)
    rows = np.zeros((len(samples), chk["forced_tokens"] + 1), np.int32)
    for k, (p, f) in enumerate(samples):
        ids[k, :len(p) + len(f)] = p + f
        rows[k] = np.arange(len(p) - 1, len(p) + len(f))
    return samples, ids, rows


def _snapshot(metrics):
    return {"dispatches": len(metrics.step_lat_s)
            + metrics.prefill["prefill_only_steps"],
            "decode_steps": len(metrics.step_batch),
            "decode_rows": float(sum(metrics.step_batch)),
            "prefill_tokens": metrics.prefill["chunk_tokens"],
            "interleaved_steps": metrics.prefill["interleaved_steps"],
            "tokens_generated": metrics.tokens_generated,
            "preemptions": metrics.preemptions}


def open_recs(mix, rate_rps, seed, ramp, seconds, vocab, ctx):
    """The open loop's requests: Poisson due times over the ramp, then over
    the window. The two are drawn apart, so every seed puts the same number of
    requests, with the same lengths, inside the window. A mix that says
    ``"cycle"`` also sends them in the same order at the same times
    (``traffic.cycle``)."""
    if "cycle" in mix:
        return [Rec(*r) for r in traffic.cycle(
            mix, rate_rps, [int(seed), 5], ramp, seconds, vocab, ctx)]
    recs = []
    for part, (start, length) in enumerate(((0.0, ramp), (ramp, seconds))):
        dues = start + traffic.poisson_dues(rate_rps, [int(seed), 1, part], length)
        plan = traffic.requests(mix, [int(seed), 5, part], len(dues), vocab, ctx)
        recs += [Rec(float(d), r["prompt"], r["out_len"])
                 for d, r in zip(dues, plan)]
    return recs


def plan(mix, seed, seconds, vocab, ctx):
    """(first requests, ``more``) of a mix: the open loop's whole schedule, or
    the closed loop's first round with ``more(k)`` for the k-th further round
    (every round has the same lengths in another order)."""
    if mix["kind"] == "serve_open":
        return open_recs(mix, mix["rate_rps"], seed, mix["ramp_s"], seconds,
                         vocab, ctx), None

    def more(k):
        return [Rec(None, r["prompt"], r["out_len"])
                for r in traffic.requests(mix, [int(seed), 3, k],
                                          mix["outstanding"], vocab, ctx)]

    return more(0), more


def warm_up(sched, mix, seed, vocab):
    """Both shapes of the greedy program: mixed rows, then a decode round."""
    from deepspeed_tpu.serve.request import RequestState

    rng = np.random.default_rng([int(seed), 4])
    warm = [sched.submit(rng.integers(0, vocab, mix["engine"]["prefill_chunk"]
                                      + 40).tolist(), max_new_tokens=4)
            for _ in range(2)]
    sched.run_until_complete()
    if not all(r.state is RequestState.DONE for r in warm):
        raise RuntimeError(f"warm-up requests did not finish: {warm}")


def drive(sched, recs, *, ramp, seconds, drain, tracer, outstanding=None,
          more=None):
    """Serve ``recs`` on the real clock. Open loop (``outstanding`` None):
    each request is submitted when it is due, and after the window the loop
    drains for at most ``drain`` seconds. Closed loop: ``outstanding``
    requests are kept in the system, ``more(k)`` supplying further rounds, and
    the loop ends with the window. The window is [ramp, ramp + seconds) on the
    loop's clock; counters are snapshot and the tracer run at its edges.
    Returns (sent records, counter deltas, wall time the window opened, loop
    time at the end)."""
    clock, horizon = time.perf_counter, ramp + seconds

    def submit(rec, now):
        rec.submitted = now
        rec.req = sched.submit(
            rec.prompt, max_new_tokens=rec.out_len,
            on_token=lambda _r, _t, rec=rec: rec.times.append(clock() - t0))

    pending = collections.deque(recs)
    sent, snaps, opened, rounds = [], {}, None, 0
    t0 = clock()
    while True:
        now = clock() - t0
        if opened is None and now >= ramp:
            opened = time.time()
            snaps["lo"] = _snapshot(sched.metrics)
            tracer.start()
        if now >= horizon and "hi" not in snaps:
            snaps["hi"] = _snapshot(sched.metrics)
            tracer.stop()
        if now < horizon:
            if outstanding is None:
                while pending and pending[0].due <= now:
                    sent.append(pending.popleft())
                    submit(sent[-1], now)
            else:
                for _ in range(outstanding - sched.queue_depth
                               - sched.live_count):
                    if not pending:
                        rounds += 1
                        pending.extend(more(rounds))
                    sent.append(pending.popleft())
                    submit(sent[-1], now)
        elif (outstanding is not None or now >= horizon + drain
              or all(r.req.state.finished for r in sent)):
            break               # a closed loop counts completions, not tails
        if sched.queue_depth or sched.live_count:
            with tracer.span("sched.step"):
                sched.step()
        else:
            with tracer.span("gen.wait"):
                time.sleep(max(0.0, min(pending[0].due - now, 0.005))
                           if pending else 0.005)
    end = clock() - t0
    for r in sent:                           # leave nothing resident
        if not r.req.state.finished:
            r.cut = True
            sched.cancel(r.req.uid)
    return (sent, {k: snaps["hi"][k] - snaps["lo"][k] for k in snaps["lo"]},
            opened, end)


def run(cell, seed, seconds, trace, devices, rehearsal=False):
    from deepspeed_tpu.inference.v2 import InferenceEngineV2
    from deepspeed_tpu.serve import ContinuousBatchScheduler
    from deepspeed_tpu.serve.request import RequestState

    mix = cell.mix(rehearsal)
    model = build_model(cell, rehearsal)
    vocab, ctx = model.config.vocab_size, mix["engine"]["max_seq_len"]
    open_loop = mix["kind"] == "serve_open"
    ramp = mix["ramp_s"]
    horizon = ramp + seconds
    recs, more = plan(mix, seed, seconds, vocab, ctx)

    # -- correctness. A serving run never holds the model's whole float32
    # tree: beside the engine's own (served weights, pool, temporaries) there
    # is at most one float32 layer or slice. The reference goes first, on an
    # otherwise empty chip, walking the model layer by layer: logits for the
    # first requests' prompts followed by seeded forced tokens
    setup = SetupClock()
    samples, ids, rows = check_samples(mix, recs, seed, vocab)
    weights = seeded(cell, model, seed)
    want = check.serve_reference(reference_config(cell, rehearsal), weights,
                                 ids, rows)
    setup.mark("reference")

    # the engine gets the tree already in its dtype, built slice by slice
    dtype = jnp.dtype(cell.config["dtype"])
    served = weights.tree_as(dtype)
    setup.mark("weights", served)
    engine = InferenceEngineV2(model, served, dtype=dtype, **mix["engine"])
    del served
    setup.mark("engine", engine.kv)
    verdict = check.Verdict(cell.config["tolerances"]["serve"])
    verdict.add("weights_mismatch_share",
                check.weights_mismatch_share(engine.params, weights, dtype))
    verdict.add("logits_rel_err",
                check.logits_rel_err(engine_logits(engine, samples), want))
    setup.mark("check")

    tracer = Tracer(trace)
    with ContinuousBatchScheduler(engine) as sched:
        warm_up(sched, mix, seed, vocab)
        setup.mark("warm_up")
        sent, counters, setup_done, end = drive(
            sched, recs, ramp=ramp, seconds=seconds, drain=mix["drain_s"],
            tracer=tracer, outstanding=mix.get("outstanding"), more=more)
    setup.marks.append(("ramp", setup_done))
    summary = tracer.summary()

    def done(r):
        return r.req.state is RequestState.DONE and len(r.times) == r.out_len

    # a request that ended is wrong unless it ended DONE with every token
    wrong = sum(r.req.state.finished and not r.cut and not done(r)
                for r in sent)
    counters["tokens_advanced"] = (counters["decode_rows"]
                                   + counters["prefill_tokens"])
    e2e = {}
    if open_loop:
        measured = [r for r in sent if ramp <= r.due < horizon]
        # a request still streaming when the drain ends has not failed: it
        # has a first token and its gaps so far count
        failed = sum(not r.times or (r.req.state.finished and not r.cut
                                     and not done(r)) for r in measured)
        ttft = [(r.times[0] if r.times else end) - r.due for r in measured]
        gaps = [b - a for r in measured for a, b in zip(r.times, r.times[1:])]
        late = [r.submitted - r.due for r in measured]
        e2e["ttft_p95_ms"] = 1e3 * quantile(ttft, 0.95)
        e2e["itl_p95_ms"] = 1e3 * quantile(gaps, 0.95)
        e2e["itl_p50_ms"] = 1e3 * quantile(gaps, 0.5)
        counters.update(late_p95_ms=1e3 * quantile(late, 0.95),
                        ttft_p50_ms=1e3 * quantile(ttft, 0.5),
                        ttft_p95_ms=e2e["ttft_p95_ms"],
                        itl_p95_ms=e2e["itl_p95_ms"], gaps=len(gaps))
    else:
        measured = [r for r in sent
                    if r.req.state.finished and r.times
                    and ramp <= r.times[-1] < horizon]
        failed = sum(not done(r) for r in measured)
        tokens = sum(len(r.prompt) + len(r.times) for r in measured if done(r))
        e2e["serve_tokens_per_s"] = tokens / seconds
        ttft = [r.times[0] - r.submitted for r in measured]
        counters.update(ttft_p50_ms=1e3 * quantile(ttft, 0.5),
                        ttft_p95_ms=1e3 * quantile(ttft, 0.95),
                        requests_per_s=len(measured) / seconds)
    print(f"[serve] {len(measured)} requests measured, {failed} failed; "
          f"window {seconds}s after a {ramp}s ramp; "
          + ", ".join(f"{k} {v:.6g}" for k, v in counters.items()), flush=True)
    return {
        "correct": verdict.correct and wrong == 0,
        "checks": verdict.rows,
        "attempted": len(measured), "failed": failed,
        "setup_done": setup_done, "setup_marks": setup.marks,
        "end_to_end": e2e, "counters": counters,
        "spans": tracer.spans, "trace": summary, "window_s": float(seconds),
    }
