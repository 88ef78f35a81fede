"""Builder's tool: find the knee of an open-loop serving cell, once.

    python3 benchmark/sweep.py --workload gpt2-medium.serve-chat

One process, one engine, ``--rates`` arrival rates from ``--first-rate``
requests/s, each ``--factor`` times the last, ``--seconds`` each with no ramp,
draining between. A rate is sustained when every arrived request got its first
token and the number of requests in the system stopped growing: at the end of
the window it is no more than a tenth above what it was at two thirds of it,
and below the engine's ``max_seqs`` (beyond that requests queue for a slot).
The window therefore has to be a few request lifetimes long. The knee is the
highest sustained rate; the traffic file's ``rate_rps`` is 0.8 of it, to two
significant figures; the table goes into PERF.md.
"""

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=2147484001)
    ap.add_argument("--first-rate", type=float, default=2.0)
    ap.add_argument("--rates", type=int, default=8)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--factor", type=float, default=1.4)
    args = ap.parse_args(argv)

    from benchmark.harness.cell import Cell, require_tpu

    cell = Cell(args.workload)
    require_tpu(cell.chips)

    import jax
    import jax.numpy as jnp

    from benchmark.harness import serve, train
    from benchmark.harness.stats import quantile
    from benchmark.harness.trace import Tracer
    from deepspeed_tpu.inference.v2 import InferenceEngineV2
    from deepspeed_tpu.serve import ContinuousBatchScheduler
    from deepspeed_tpu.serve.request import RequestState
    from deepspeed_tpu.utils.xla_env import enable_compile_cache

    enable_compile_cache()
    mix = cell.traffic
    model = train.build_model(cell, False)
    vocab, ctx = model.config.vocab_size, mix["engine"]["max_seq_len"]
    dtype = jnp.dtype(cell.config["dtype"])
    engine = InferenceEngineV2(
        model, train.seeded(cell, model, args.seed).tree_as(dtype),
        dtype=dtype, **mix["engine"])
    knee, secs = None, args.seconds
    with ContinuousBatchScheduler(engine) as sched:
        serve.warm_up(sched, mix, args.seed, vocab)
        for i in range(args.rates):
            rate = args.first_rate * args.factor ** i
            recs = serve.open_recs(mix, rate, args.seed + i, 0.0, secs, vocab, ctx)
            sent, _, _, end = serve.drive(
                sched, recs, ramp=0.0, seconds=secs, drain=mix["drain_s"],
                tracer=Tracer(False))
            done = [r for r in sent if r.req.state is RequestState.DONE]

            def in_system(t):
                return sum(r.submitted <= t and not
                           (r.times and r.req.state.finished and r.times[-1] <= t)
                           for r in sent)

            ttft = [r.times[0] - r.due for r in sent if r.times]
            gaps = [b - a for r in sent for a, b in zip(r.times, r.times[1:])]
            row = {"rate_rps": round(rate, 3), "arrived": len(sent),
                   "completed": len(done),
                   "in_system_two_thirds": in_system(2 * secs / 3),
                   "in_system_end": in_system(secs),
                   "drained_at_s": round(end, 2),
                   "ttft_p50_ms": 1e3 * quantile(ttft, 0.5),
                   "ttft_p95_ms": 1e3 * quantile(ttft, 0.95),
                   "itl_p95_ms": 1e3 * quantile(gaps, 0.95),
                   "tokens_per_s": sum(len(r.prompt) + len(r.times)
                                       for r in done) / secs}
            row["sustained"] = (
                len(ttft) == len(sent)
                and row["in_system_end"] <= 1.1 * row["in_system_two_thirds"] + 1
                and row["in_system_end"] < mix["engine"]["max_seqs"])
            if row["sustained"]:
                knee = rate
            print("[sweep] " + json.dumps(row), flush=True)
            sched.run_until_complete()
    print(f"[sweep] knee {knee}; rate_rps = 0.8 x knee = "
          f"{None if knee is None else float(f'{0.8 * knee:.2g}')}", flush=True)


if __name__ == "__main__":
    main()
