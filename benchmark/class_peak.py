"""Builder's tool: the most blocks of each class of KV blocks a serving cell's
traffic holds at once, which is what the traffic file's ``engine.num_blocks``
of a model with a bounded class is sized from.

    python3 benchmark/class_peak.py --workload trinity-mini.serve-win16k \
        --rate 1.25 --seconds 120 --blocks full=4096,window=768

One process, one engine, the cell's own schedule (``serve.open_recs``: ramp,
window, drain) at ``--rate`` requests/s (the traffic file's unless given) for
``--seconds`` of window, on pools of ``--blocks`` (the traffic file's unless
given: give them roomy, so that no request waits for a block and the peak is
the traffic's and not the pool's). The peaks are the engine's own
(``InferenceEngineV2.block_peaks``: the most blocks of each class in use at
any dispatch), over ramp, window and drain; ``alive`` is the most sequences in
the system at once. One ``[class_peak]`` line of JSON goes into PERF.md.
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
    ap.add_argument("--rate", type=float, default=None)
    ap.add_argument("--seconds", type=float, default=45.0)
    ap.add_argument("--blocks", default=None,
                    help="class=blocks,... in place of the traffic file's")
    ap.add_argument("--rehearsal", action="store_true",
                    help="the cell's tiny preset, on whatever device is there")
    args = ap.parse_args(argv)

    from benchmark.harness.cell import Cell, require_tpu

    cell = Cell(args.workload)
    if not args.rehearsal:
        require_tpu(cell.chips)

    import jax.numpy as jnp

    from benchmark.harness import serve, train
    from benchmark.harness.stats import quantile
    from benchmark.harness.trace import Tracer
    from deepspeed_tpu.inference.v2 import InferenceEngineV2
    from deepspeed_tpu.serve import ContinuousBatchScheduler
    from deepspeed_tpu.serve.request import RequestState
    from deepspeed_tpu.utils.xla_env import enable_compile_cache

    enable_compile_cache()
    mix = cell.mix(args.rehearsal)
    rate = mix["rate_rps"] if args.rate is None else args.rate
    knobs = dict(mix["engine"])
    if args.blocks:
        knobs["num_blocks"] = {c: int(n) for c, n in (
            part.split("=") for part in args.blocks.split(","))}
    model = train.build_model(cell, args.rehearsal)
    vocab, ctx = model.config.vocab_size, knobs["max_seq_len"]
    dtype = jnp.dtype(cell.config["dtype"])
    engine = InferenceEngineV2(
        model, train.seeded(cell, model, args.seed).tree_as(dtype),
        dtype=dtype, **knobs)
    with ContinuousBatchScheduler(engine) as sched:
        serve.warm_up(sched, mix, args.seed, vocab)
        engine.block_peaks = dict.fromkeys(engine.block_peaks, 0)
        recs = serve.open_recs(mix, rate, args.seed, mix["ramp_s"],
                               args.seconds, vocab, ctx)
        sent, _, _, end = serve.drive(
            sched, recs, ramp=mix["ramp_s"], seconds=args.seconds,
            drain=mix["drain_s"], tracer=Tracer(False))
        sched.run_until_complete()
    edges = sorted([(r.submitted, 1) for r in sent]
                   + [(r.times[-1], -1) for r in sent
                      if r.times and r.req.state is RequestState.DONE])
    alive = peak_alive = 0
    for _, step in edges:
        alive += step
        peak_alive = max(peak_alive, alive)
    lo, hi = mix["ramp_s"], mix["ramp_s"] + args.seconds
    gaps = [b - a for r in sent if lo <= r.due < hi
            for a, b in zip(r.times, r.times[1:])]
    found = {
        "rate_rps": rate, "seconds": args.seconds,
        "num_blocks": knobs["num_blocks"], "sent": len(sent),
        "completed": sum(r.req.state is RequestState.DONE for r in sent),
        "drained_at_s": round(end, 2), "alive": peak_alive,
        "peak_blocks": engine.block_peaks,
        "itl_p50_ms": 1e3 * quantile(gaps, 0.5) if gaps else None}
    print("[class_peak] " + json.dumps(found), flush=True)
    return found


if __name__ == "__main__":
    main()
