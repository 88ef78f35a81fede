"""The quickest proof that the system still starts on the chip.

``python chip_smoke.py`` drives the two main paths once, through the entry
points a user calls, at the full width of GPT-2 350M (``gpt2_config("350m")``:
hidden 1024, 24 layers, 16 heads of 64, vocab 50304, nothing cut), bf16, with
random weights made from a seed:

* trainer: ``deepspeed_tpu.initialize`` + ``engine.train_batch`` on the
  ``gpt2-medium.train-seq1024`` job (seq 1024, micro-batch 8, AdamW, clipping
  1.0, ZeRO stage 0), one seeded batch repeated, so the loss has to fall;
* server: paged ``InferenceEngineV2`` under ``ContinuousBatchScheduler``, a
  handful of requests arriving in two waves so prefill chunks and decode rounds
  interleave, one request's greedy tokens checked against a plain full forward.

Both phases read ``tpu_custom_call`` out of the compiled programs themselves:
flash attention in the train step, paged decode in the serving program.

``python chip_smoke.py --chips 4`` runs only the four-chip path and what it is
compared with: the same job under ``"mesh": {"data": 4}`` at ZeRO stage 3
against stage 0 on the same mesh and global batch.

It is one process, needs a TPU (it refuses any other platform with a non-zero
exit), runs every phase unguarded (an exception ends the run with a traceback),
and prints as its LAST line only
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Times on earlier lines are for the builder's orientation; they are not results.
The phase functions take their sizes as arguments so that the CPU rehearsal in
``tests/unit/test_chip_smoke.py`` can hand them a tiny config; the script
itself has no size option.
"""

import argparse
import gc
import json
import os
import sys
import time

import numpy as np

SEED = 0
#: a served token may differ from the reference's argmax only where the
#: reference itself all but ties: its logit for the served token within this
#: much of its best. With random weights the 50304 logits have a spread of
#: ~0.6 and the top two sit ~0.1 apart on average; bf16 keeps 8 mantissa bits,
#: so served logits near 3 are quantised in steps of 2**-6 = 0.016 and 24
#: layers of bf16 residual adds move them by about as much again.
TIE_TOL = 0.06
#: and such near-ties must stay the exception
MIN_EXACT = 0.75
#: stage 3 and stage 0 run the same math on the same global batch in another
#: collective order (reduce-scatter + sharded update against all-reduce +
#: replicated update); bf16 forward/backward makes each loss good to ~3 digits
LOSS_RTOL = 2e-2


def say(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def cache_entries(path: str) -> int:
    """How many compiled programs JAX's cache directory holds."""
    try:
        return sum(name.endswith("-cache") for name in os.listdir(path))
    except FileNotFoundError:
        return 0


def peak_bytes():
    """Per-device peak bytes in use since the process started (None where the
    backend does not report it, as on the CPU)."""
    import jax

    stats = [d.memory_stats() for d in jax.local_devices()]
    if not all(s and "peak_bytes_in_use" in s for s in stats):
        return None
    return [int(s["peak_bytes_in_use"]) for s in stats]


def _bench_job(micro_bs: int, zero_stage: int, mesh=None) -> dict:
    """The training job of the ``gpt2-medium.train-seq1024`` cell."""
    cfg = {
        "train_micro_batch_size_per_gpu": micro_bs,
        "gradient_accumulation_steps": 1,
        "optimizer": {"type": "adamw",
                      "params": {"lr": 1e-4, "weight_decay": 0.01}},
        "zero_optimization": {"stage": zero_stage},
        "bf16": {"enabled": True},
        "gradient_clipping": 1.0,
        "steps_per_print": 0,
    }
    if mesh:
        cfg["mesh"] = dict(mesh)
    return cfg


def train_phase(model_cfg, *, seq: int, micro_bs: int, steps: int,
                zero_stage: int = 0, mesh=None, expect_kernel: bool = True,
                inspect=None) -> dict:
    """A few ``train_batch`` steps on one seeded batch, repeated.

    Checks: every loss finite, the last below the first, and (with
    ``expect_kernel``) a Mosaic kernel in the compiled train step.
    ``inspect(engine, compiled_text)`` runs before the engine is dropped.
    """
    import jax

    import deepspeed_tpu
    from deepspeed_tpu.comm import topology
    from deepspeed_tpu.models import TransformerLM

    topology.reset_topology()
    engine = deepspeed_tpu.initialize(
        model=TransformerLM(model_cfg),
        config=_bench_job(micro_bs, zero_stage, mesh))[0]
    dp = engine.topology.data_parallel_size
    batch = {"input_ids": np.random.default_rng(SEED).integers(
        0, model_cfg.vocab_size, (micro_bs * dp, seq), dtype=np.int32)}

    def same_batch():
        while True:
            yield batch

    t0 = time.perf_counter()
    compiled = engine.lower_train_step(batch).compile()
    text = compiled.as_text()
    compile_s = time.perf_counter() - t0
    has_kernel = "tpu_custom_call" in text
    if expect_kernel:
        assert has_kernel, ("no tpu_custom_call in the compiled train step: "
                            "flash attention gave way to the XLA path")

    it = same_batch()
    losses, step_s = [], []
    for _ in range(steps):
        t0 = time.perf_counter()
        losses.append(float(engine.train_batch(it)))  # float() = device sync
        step_s.append(time.perf_counter() - t0)
    assert all(np.isfinite(losses)), f"non-finite loss: {losses}"
    assert losses[-1] < losses[0], f"loss did not fall: {losses}"

    out = {
        "losses": losses, "first_step_s": step_s[0],
        "steady_step_s": float(np.median(step_s[1:])) if steps > 1 else None,
        "compile_s": compile_s, "tpu_custom_call": has_kernel,
        "peak_bytes": peak_bytes(),
    }
    if inspect is not None:
        out.update(inspect(engine, compiled, text))
    del engine, compiled
    gc.collect()
    jax.clear_caches()
    return out


def reference_tokens(model, params, prompt, served):
    """Teacher-forced plain forward over ``prompt + served``: float32 weights
    (the served bf16 ones, widened), XLA attention, ``highest`` matmul
    precision. Returns (argmax per generated position, how far the reference's
    logit for each served token sits below its best)."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.ops.transformer.attention import (get_default_impl,
                                                         set_default_impl)

    ids = np.asarray(prompt + served[:-1], np.int32)[None]
    f32 = jax.tree.map(lambda a: a.astype(jnp.float32)
                       if jnp.issubdtype(a.dtype, jnp.floating) else a, params)
    before = get_default_impl()
    set_default_impl("xla")
    try:
        with jax.default_matmul_precision("highest"):
            lg = jax.jit(model.logits)(f32, ids)[0, len(prompt) - 1:]
    finally:
        set_default_impl(before)
    lg = np.asarray(lg, np.float32)
    assert lg.shape == (len(served), model.config.vocab_size), lg.shape
    gap = lg.max(axis=-1) - lg[np.arange(len(served)), served]
    return lg.argmax(axis=-1), gap


def serve_phase(model_cfg, *, prompt_lens, new_tokens, max_seqs: int,
                max_seq_len: int, token_budget: int, prefill_chunk: int,
                block_size: int = 64, expect_kernel: bool = True) -> dict:
    """Paged continuous batching: the first half of the requests is submitted
    and stepped until it decodes, then the rest arrives, so prefill chunks and
    decode rounds share dispatches.

    Checks: every request finishes with the tokens asked for; at least one
    step advanced a decode while a prompt was mid-prefill; the first request's
    greedy tokens agree with ``reference_tokens``; and (with
    ``expect_kernel``) the Mosaic paged-decode kernel is in both compiled
    shapes of the serving program.
    """
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.inference.v2 import InferenceEngineV2
    from deepspeed_tpu.models import TransformerLM
    from deepspeed_tpu.serve import ContinuousBatchScheduler
    from deepspeed_tpu.serve.request import RequestState

    model = TransformerLM(model_cfg)
    params = model.init_params(jax.random.PRNGKey(SEED))
    engine = InferenceEngineV2(
        model, params, paged=True, dtype=jnp.bfloat16, max_seqs=max_seqs,
        max_seq_len=max_seq_len, prefill_chunk=prefill_chunk,
        block_size=block_size, token_budget=token_budget)
    del params

    t0 = time.perf_counter()
    has_kernel = {}
    for rows in sorted({token_budget, max_seqs}):
        text = engine.lower_ragged(rows).compile().as_text()
        has_kernel[rows] = "tpu_custom_call" in text
    compile_s = time.perf_counter() - t0
    if expect_kernel:
        # one query token per row, learned positions, no softcap, head size
        # 64, block size 64: every condition of the paged branch holds
        assert all(has_kernel.values()), (
            f"no tpu_custom_call in the serving program at rows {has_kernel}: "
            "paged decode took the XLA gather path")

    rng = np.random.default_rng(SEED)
    prompts = [rng.integers(0, model_cfg.vocab_size, (n,)).tolist()
               for n in prompt_lens]
    first_wave = (len(prompts) + 1) // 2
    round_s, mixed_steps, steps = [], 0, 0
    t_start = time.perf_counter()
    with ContinuousBatchScheduler(engine) as sched:
        reqs = [sched.submit(p, max_new_tokens=n)
                for p, n in zip(prompts[:first_wave], new_tokens)]

        def step():
            nonlocal mixed_steps, steps
            decoding = {r.uid: len(r.tokens) for r in reqs
                        if r.state is RequestState.DECODE}
            prefilling = engine.prefill_backlog() > 0
            t0 = time.perf_counter()
            more = sched.step()
            dt = time.perf_counter() - t0
            steps += 1
            advanced = any(len(r.tokens) > decoding[r.uid] for r in reqs
                           if r.uid in decoding)
            if prefilling and advanced:
                mixed_steps += 1
            elif advanced and not prefilling:
                round_s.append(dt)
            return more

        waiting = (RequestState.QUEUED, RequestState.PREFILL)
        while any(r.state in waiting for r in reqs):
            step()
        reqs += [sched.submit(p, max_new_tokens=n)
                 for p, n in zip(prompts[first_wave:], new_tokens[first_wave:])]
        while step():
            pass
    wall_s = time.perf_counter() - t_start

    for r, n in zip(reqs, new_tokens):
        assert r.state is RequestState.DONE, (r.uid, r.state, r.error)
        assert len(r.tokens) == n, (r.uid, len(r.tokens), n)
        assert all(0 <= t < model_cfg.vocab_size for t in r.tokens), r.uid
    assert mixed_steps >= 1, "no step mixed a prefill chunk with a decode"

    served = list(reqs[0].tokens)
    ref, gap = reference_tokens(model, engine.params, prompts[0], served)
    exact = float(np.mean(ref == np.asarray(served)))
    assert exact >= MIN_EXACT and float(gap.max()) <= TIE_TOL, (
        f"served tokens disagree with the full forward: exact {exact:.3f} "
        f"(need >= {MIN_EXACT}), worst gap {gap.max():.4f} (need <= {TIE_TOL})"
        f"\nserved {served}\nref    {ref.tolist()}")

    return {
        "requests": len(reqs), "tokens_served": sum(len(r.tokens) for r in reqs),
        "prompt_tokens": sum(prompt_lens), "steps": steps,
        "mixed_steps": mixed_steps, "wall_s": wall_s, "compile_s": compile_s,
        "decode_round_s": float(np.median(round_s[1:])) if len(round_s) > 1
        else None,
        "ref_exact": exact, "ref_worst_gap": float(gap.max()),
        "tpu_custom_call": has_kernel, "peak_bytes": peak_bytes(),
    }


def _leaves(engine):
    """(name, array) for every leaf of the engine's bf16 params, fp32 master
    and Adam moments."""
    import jax

    trees = {"params": engine.params, "master": engine.master_params,
             "adam_m": engine.opt_state.m, "adam_v": engine.opt_state.v}
    for kind, tree in trees.items():
        for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
            yield kind + jax.tree_util.keystr(path), leaf


def sharding_report(engine, n_devices: int, large_elems: int) -> dict:
    """Every leaf above ``large_elems`` (ZeRO-3's persistence threshold) must
    sit in ``n_devices`` shards of 1/n of its bytes on distinct devices; the
    ones below it may stay whole on every device and are listed."""
    sharded, replicated = [], []
    for name, leaf in _leaves(engine):
        shards = leaf.addressable_shards
        per_dev = {s.device.id: s.data.nbytes for s in shards}
        if leaf.sharding.is_fully_replicated:
            assert leaf.size <= large_elems, (
                f"{name} {leaf.shape} sits whole on every device")
            replicated.append((name, leaf.nbytes))
            continue
        assert len(per_dev) == n_devices == len(shards), (name, per_dev)
        assert all(b * n_devices == leaf.nbytes for b in per_dev.values()), (
            name, leaf.nbytes, per_dev)
        sharded.append((name, leaf.nbytes))
    large = [n for n, leaf in _leaves(engine) if leaf.size > large_elems]
    assert large and set(large) <= {n for n, _ in sharded}
    return {"sharded": sharded, "replicated": replicated}


def zero3_phase(model_cfg, *, seq: int, micro_bs: int, steps: int,
                n_devices: int, expect_kernel: bool = True,
                expect_peak_drop: bool = True,
                expect_reduce_scatter: bool = True) -> dict:
    """ZeRO stage 3 against stage 0 on the same ``data=n_devices`` mesh and
    global batch. Stage 3 runs first: the backend's peak counter only rises,
    so a stage-0 peak above the stage-3 reading is what shows stage 3 needs
    less."""
    mesh = {"data": n_devices}

    def inspect3(engine, compiled, text):
        large = engine.config.zero_config.param_persistence_threshold
        rep = sharding_report(engine, n_devices, large)
        # parameters gathered for use, gradients reduced into shards. The TPU
        # compiler writes the latter in its fused form, an `all-reduce-scatter`
        # fusion; the CPU compiler leaves all-reduce + dynamic-slice
        ops = {op: text.count(op) for op in
               ("all-gather", "reduce-scatter", "all-reduce", "all-to-all")}
        assert ops["all-gather"] and ops["reduce-scatter" if
                                         expect_reduce_scatter
                                         else "all-reduce"], (
            f"ZeRO-3 step lacks its collectives: {ops}")
        return {**rep, "collectives": ops,
                "memory_analysis": str(compiled.memory_analysis())}

    def inspect0(engine, compiled, text):
        return {"memory_analysis": str(compiled.memory_analysis())}

    kw = dict(seq=seq, micro_bs=micro_bs, steps=steps, mesh=mesh,
              expect_kernel=expect_kernel)
    z3 = train_phase(model_cfg, zero_stage=3, inspect=inspect3, **kw)
    z0 = train_phase(model_cfg, zero_stage=0, inspect=inspect0, **kw)
    np.testing.assert_allclose(z3["losses"], z0["losses"], rtol=LOSS_RTOL)
    if expect_peak_drop:
        assert z3["peak_bytes"] and z0["peak_bytes"], "backend reports no peak"
        assert all(a < b for a, b in zip(z3["peak_bytes"], z0["peak_bytes"])), (
            f"stage 3 peak {z3['peak_bytes']} not below stage 0's "
            f"{z0['peak_bytes']}")
    return {"zero3": z3, "zero0": z0}


def require_tpu(n_chips: int):
    """The device as JAX reports it; exits non-zero unless it is ``n_chips``
    TPU chips."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, JAX found platform "
                 f"'{devices[0].platform}' ({devices[0].device_kind})")
    if len(devices) != n_chips:
        sys.exit(f"chip_smoke: needs {n_chips} chip(s), JAX found "
                 f"{len(devices)}")
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the ZeRO-3 mesh phase, on four chips")
    args = ap.parse_args(argv)

    import jax
    import jaxlib

    from deepspeed_tpu.models import gpt2_config
    from deepspeed_tpu.utils.xla_env import enable_compile_cache

    t_start = time.perf_counter()
    device = require_tpu(args.chips)
    try:
        from importlib.metadata import version
        libtpu = version("libtpu")
    except Exception:  # version is for the log line only
        libtpu = "?"
    say(f"jax {jax.__version__} jaxlib {jaxlib.__version__} libtpu {libtpu}; "
        f"device {device}")
    cache = enable_compile_cache()
    before = cache_entries(cache)
    say(f"compile cache {cache}: {before} entries "
        f"({'warm' if before else 'cold'})")

    train_cfg = gpt2_config("350m", max_seq_len=1024, remat=True,
                            remat_policy="dots", scan_layers=False)
    gib = 2.0 ** 30

    def fmt_peak(p):
        return "n/a" if p is None else [round(b / gib, 2) for b in p]

    if args.chips == 4:
        r = zero3_phase(train_cfg, seq=1024, micro_bs=8, steps=4, n_devices=4)
        z3, z0 = r["zero3"], r["zero0"]
        rep_bytes = sum(b for _, b in z3["replicated"])
        shard_bytes = sum(b for _, b in z3["sharded"])
        say(f"zero3: {len(z3['sharded'])} leaves ({shard_bytes / gib:.2f} GiB) "
            f"in 4 shards on 4 devices, a quarter of the bytes each; "
            f"{len(z3['replicated'])} small leaves ({rep_bytes / 2**20:.2f} MiB)"
            f" stay whole on every device:")
        say("  " + ", ".join(f"{n} {b}B" for n, b in z3["replicated"]))
        say(f"zero3 step: collectives {z3['collectives']}, tpu_custom_call "
            f"{z3['tpu_custom_call']}")
        for name, z in (("zero3", z3), ("zero0", z0)):
            say(f"{name}: losses {[round(x, 4) for x in z['losses']]}; peak "
                f"GiB/device {fmt_peak(z['peak_bytes'])}; compile "
                f"{z['compile_s']:.1f}s, steady step {z['steady_step_s']:.3f}s"
                " (orientation only)")
            say(f"{name} step program: {z['memory_analysis']}")
        say(f"losses agree within rtol {LOSS_RTOL}; stage-3 peak below "
            "stage-0's on every device")
    else:
        t = train_phase(train_cfg, seq=1024, micro_bs=8, steps=6)
        say(f"trainer: GPT-2 350M seq 1024 mb 8, losses "
            f"{[round(x, 4) for x in t['losses']]} (fell), tpu_custom_call in "
            f"the train step: {t['tpu_custom_call']}; compile "
            f"{t['compile_s']:.1f}s, first step {t['first_step_s']:.1f}s, "
            f"steady step {t['steady_step_s']:.3f}s (orientation only); peak "
            f"GiB {fmt_peak(t['peak_bytes'])}")
        s = serve_phase(
            gpt2_config("350m"),
            prompt_lens=(384, 200, 320, 260, 448, 300),
            new_tokens=(48, 32, 24, 40, 32, 36),
            max_seqs=8, max_seq_len=1024, token_budget=256, prefill_chunk=128)
        say(f"server: {s['requests']} requests done, {s['prompt_tokens']} "
            f"prompt + {s['tokens_served']} generated tokens in {s['steps']} "
            f"steps ({s['mixed_steps']} mixed prefill+decode); reference check "
            f"passed: exact {s['ref_exact']:.3f} (>= {MIN_EXACT}), worst gap "
            f"{s['ref_worst_gap']:.4f} (<= {TIE_TOL}); tpu_custom_call in the "
            f"serving program by rows: {s['tpu_custom_call']}; compile "
            f"{s['compile_s']:.1f}s, wall {s['wall_s']:.1f}s, decode round "
            f"{s['decode_round_s']:.4f}s (orientation only); process peak GiB "
            f"{fmt_peak(s['peak_bytes'])}")

    say(f"compile cache: {before} entries before, "
        f"{cache_entries(cache)} after; total "
        f"{time.perf_counter() - t_start:.1f}s (orientation only)")
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
