"""The training runner: ``deepspeed_tpu.initialize`` + ``train_batch`` on the
cell's job, measured over whole optimizer steps."""

import time

import jax
import jax.numpy as jnp
import numpy as np

from . import check, weights
from .setup import SetupClock
from .trace import Tracer


def build_model(cell, rehearsal):
    from deepspeed_tpu.models import TransformerLM
    from deepspeed_tpu.models.transformer import TransformerConfig

    kw = {**cell.config["model"], **cell.traffic.get("model", {})}
    if rehearsal:
        kw.update(cell.config["rehearsal"]["model"])
        kw.update(cell.traffic["rehearsal"].get("model", {}))
    return TransformerLM(TransformerConfig(**kw))


def reference_config(cell, rehearsal):
    """The published keys the reference reads, at the size being run."""
    cfg = dict(cell.config)
    if rehearsal:
        cfg.update(cell.config["rehearsal"]["published"])
    return cfg


def seeded(cell, model, seed):
    """The seed's weights of ``model``, addressed by slice."""
    return weights.Seeded(
        jax.eval_shape(model.init_params, jax.random.PRNGKey(0)), seed,
        cell.config["init_std"], model.config.num_layers)


def seeded_weights(cell, model, seed, devices):
    """The seed's whole float32 tree, for training: whole on one chip, spread
    over all of several."""
    drawn, shardings = seeded(cell, model, seed), None
    if len(devices) > 1:
        mesh = jax.sharding.Mesh(np.asarray(devices), ("all",))
        shardings = weights.spread(drawn.abstract, mesh)
    return drawn.tree(shardings)


def first_step(engine, it):
    """One ``train_batch``; the loss, the gradient norm and the float32
    master copies of ``check.UPDATE_LEAVES`` before and after it."""
    def leaves():
        blocks = engine.master_params["blocks"]
        return {k: jnp.copy(blocks[k]) for k in check.UPDATE_LEAVES}

    before = leaves()
    loss = float(engine.train_batch(it))
    return {"loss": loss, "gnorm": float(engine.get_global_grad_norm()),
            "before": before, "after": leaves()}


def run(cell, seed, seconds, trace, devices, rehearsal=False):
    import deepspeed_tpu
    from deepspeed_tpu.comm import topology
    from deepspeed_tpu.runtime.zero.partition import batch_spec

    job = cell.mix(rehearsal)
    model = build_model(cell, rehearsal)
    vocab, seq = model.config.vocab_size, job["seq_len"]
    n = len(devices)
    global_batch = job["engine"]["train_micro_batch_size_per_gpu"] * n

    setup = SetupClock()
    w = seeded_weights(cell, model, seed, devices)
    pool = weights.make_ids(seed, 1, (job["distinct_batches"], global_batch, seq),
                            vocab)
    setup.mark("weights", w, pool)
    ref_loss, ref_grads = check.train_reference(
        reference_config(cell, rehearsal), w, pool[0], devices=devices)
    setup.mark("reference", ref_grads)

    topology.reset_topology()
    engine = deepspeed_tpu.initialize(
        model=model, model_parameters=w, config=job["engine"])[0]
    del w
    setup.mark("engine", engine.params)
    sharding = jax.sharding.NamedSharding(engine.topology.mesh,
                                          batch_spec(engine.topology))
    batches = [{"input_ids": jax.device_put(pool[i], sharding)}
               for i in range(pool.shape[0])]
    del pool

    def feed():
        i = 0
        while True:
            yield batches[i % len(batches)]
            i += 1

    it = feed()
    # warm-up: the first step compiles (or loads) the one program of the
    # window and is the step compared with the reference
    first = first_step(engine, it)
    setup.mark("first_step")
    float(engine.train_batch(it))

    opt = job["engine"]["optimizer"]["params"]
    verdict = check.Verdict(cell.config["tolerances"]["train"])
    verdict.add("update_sign_mismatch", check.update_sign_mismatch(
        first["before"], first["after"], ref_grads, opt["lr"],
        opt["weight_decay"]))
    first_loss, first_gnorm = first["loss"], first["gnorm"]
    del first, ref_grads
    # loss and gradient norm are printed, not judged: on the chip the fp8
    # control's errors in them overlap the bf16 program's (PERF.md section 2)
    print(f"[check] first step: loss {first_loss:.6f} (reference "
          f"{ref_loss:.6f}), grad norm {first_gnorm:.6f}", flush=True)

    state_bytes = sum(
        leaf.addressable_shards[0].data.nbytes
        for tree in (engine.params, engine.master_params, engine.opt_state.m,
                     engine.opt_state.v)
        for leaf in jax.tree.leaves(tree))

    tracer = Tracer(trace)
    losses, step_s, prev = [], [], None
    setup_done = setup.mark("warm_up")
    tracer.start()
    t0 = t_last = time.perf_counter()
    while True:
        with tracer.span("train_batch"):
            loss = engine.train_batch(it)
        # the traced run syncs every step, for the per-step host clock; the
        # untraced run keeps one step queued behind the running one
        wait_for = loss if trace else prev
        if wait_for is not None:
            with tracer.span("sync"):
                wait_for.block_until_ready()
        if trace:
            step_s.append(time.perf_counter() - t_last)
            t_last = time.perf_counter()
        losses.append(loss)
        prev = loss
        if time.perf_counter() - t0 >= seconds:
            break
    loss.block_until_ready()
    elapsed = time.perf_counter() - t0
    tracer.stop()
    summary = tracer.summary()

    losses = [float(x) for x in losses]
    bad = sum(not np.isfinite(x) for x in losses)
    tokens = len(losses) * global_batch * seq
    return {
        "correct": verdict.correct and bad == 0,
        "checks": verdict.rows,
        "attempted": len(losses), "failed": bad,
        "setup_done": setup_done, "setup_marks": setup.marks,
        "end_to_end": {"train_tokens_per_s_per_chip": tokens / elapsed / n},
        "counters": {"tokens_per_s_per_chip": tokens / elapsed / n,
                     "seq_len": seq, "global_batch": global_batch,
                     "steps": len(losses), "chips": n,
                     "state_bytes_per_chip": state_bytes,
                     "n_layers": model.config.num_layers,
                     "n_heads": model.config.num_heads,
                     "head_dim": model.config.head_dim,
                     "hidden": model.config.hidden_size,
                     "n_params": model.config.num_parameters},
        "spans": {"train_step": step_s},
        "trace": summary, "window_s": elapsed,
    }
