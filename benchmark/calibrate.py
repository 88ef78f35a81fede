"""Builder's tool: the readings the ``correct`` limits are set from.

    python3 benchmark/calibrate.py --workload <name> --seeds 12 --controls 3

One process on the cell's chips. For each seed it prints the numbers a run of
the cell compares (the program against the float32 reference), and for the
first ``--controls`` seeds the same numbers for the controls: the reference
itself recomputed in fp8 (``reference.ein_fp8``), one precision step below the
configuration's bfloat16, and for serving also the program with its own int8
weight-only path switched on. A limit goes above the largest sound reading and
below the smallest control reading (PERF.md holds both). Benchmark runs never
call this.
"""

import argparse
import gc
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


def train_readings(cell, seed, devices, control, rehearsal):
    import deepspeed_tpu
    from benchmark.harness import check, train, weights
    from benchmark.reference import ein_fp8
    from deepspeed_tpu.comm import topology

    job = cell.mix(rehearsal)
    model = train.build_model(cell, rehearsal)
    cfg = train.reference_config(cell, rehearsal)
    batch = weights.make_ids(
        seed, 1, (job["distinct_batches"],
                  job["engine"]["train_micro_batch_size_per_gpu"] * len(devices),
                  job["seq_len"]), model.config.vocab_size)[0]
    w = train.seeded_weights(cell, model, seed, devices)
    ref = check.train_reference(cfg, w, batch, devices=devices)
    out = {"seed": seed}
    opt = job["engine"]["optimizer"]["params"]

    def sign_mismatch(grads):
        """A gradient in the program's place: its AdamW step from zero."""
        zero = {k: 0.0 * g for k, g in grads.items()}
        moved = {k: -opt["lr"] * g / (abs(g) + 1e-8) for k, g in grads.items()}
        return check.update_sign_mismatch(zero, moved, ref[1], opt["lr"], 0.0)

    if control:
        ctl = check.train_reference(cfg, w, batch, ein=ein_fp8, devices=devices)
        out["control_fp8"] = {"loss_rel_err": check.rel_err(ctl[0], ref[0]),
                              "update_sign_mismatch": sign_mismatch(ctl[1])}
        del ctl
    topology.reset_topology()
    engine = deepspeed_tpu.initialize(model=model, model_parameters=w,
                                      config=job["engine"])[0]
    del w
    first = train.first_step(engine, iter([{"input_ids": batch}]))
    loss, gnorm = first["loss"], first["gnorm"]
    out["program"] = {"loss_rel_err": check.rel_err(loss, ref[0]),
                      "update_sign_mismatch": check.update_sign_mismatch(
                          first["before"], first["after"], ref[1], opt["lr"],
                          opt["weight_decay"])}
    del first
    out["raw"] = {"loss": loss, "gnorm": gnorm, "ref_loss": ref[0]}
    del engine
    gc.collect()
    return out


def serve_readings(cell, seed, devices, control, rehearsal, state):
    import jax.numpy as jnp

    from benchmark.harness import check, serve, train
    from benchmark.reference import ein_fp8
    from deepspeed_tpu.inference.quantization import quantize_param_tree
    from deepspeed_tpu.inference.v2 import InferenceEngineV2

    mix = cell.mix(rehearsal)
    model = train.build_model(cell, rehearsal)
    cfg = train.reference_config(cell, rehearsal)
    vocab, ctx = model.config.vocab_size, mix["engine"]["max_seq_len"]
    recs, _ = serve.plan(mix, seed, 45.0, vocab, ctx)    # as a run's own
    samples, ids, rows = serve.check_samples(mix, recs, seed, vocab)
    # as a run does: the reference streamed, then the tree in the serving
    # dtype; no float32 tree of the whole model at any point
    w = train.seeded(cell, model, seed)
    want = check.serve_reference(cfg, w, ids, rows)
    dtype = jnp.dtype(cell.config["dtype"])
    out = {"seed": seed, "prompt_lens": [len(p) for p, _ in samples]}
    if control:
        ctl = check.serve_reference(cfg, w, ids, rows, ein=ein_fp8)
        out["control_fp8"] = {"logits_rel_err": check.logits_rel_err(ctl, want)}
    engine = state.get("engine")
    if engine is not None:
        engine.params = None      # the last seed's tree goes before this one's comes
    served = w.tree_as(dtype)
    if engine is None:
        engine = state["engine"] = InferenceEngineV2(
            model, served, dtype=dtype, **mix["engine"])

    def readings():
        return {"weights_mismatch_share": check.weights_mismatch_share(
                    engine.params, w, dtype),
                "logits_rel_err": check.logits_rel_err(
                    serve.engine_logits(engine, samples), want)}

    if control:
        # the program's own int8 path, fed the tree the bf16 program is fed
        engine.load_params(quantize_param_tree(served, num_bits=8))
        out["control_program_int8_woq"] = readings()
    engine.load_params(served)
    out["program"] = readings()
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2147483700)
    ap.add_argument("--rehearsal", action="store_true",
                    help="tiny sizes on whatever device JAX finds (tests)")
    args = ap.parse_args(argv)

    from benchmark.harness.cell import Cell, require_tpu

    cell = Cell(args.workload)
    if not args.rehearsal:
        require_tpu(cell.chips)
    import jax

    from deepspeed_tpu.utils.xla_env import enable_compile_cache

    enable_compile_cache()
    devices = jax.devices()[:cell.chips]
    state = {}
    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        if cell.traffic["kind"] == "train":
            r = train_readings(cell, seed, devices, i < args.controls,
                               args.rehearsal)
        else:
            r = serve_readings(cell, seed, devices, i < args.controls,
                               args.rehearsal, state)
        print("[calibrate] " + json.dumps(r), flush=True)


if __name__ == "__main__":
    main()
