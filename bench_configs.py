"""Tracked-config benchmarks (BASELINE.json ``configs``) beyond the headline.

``python bench.py`` prints ONE JSON line (the headline GPT-2-350M number — the
driver contract). ``python bench.py --all`` additionally runs the other four
tracked configs as scaled stand-ins sized for the available hardware (one real
chip + the host), emitting one JSON line each and writing ``BENCH_ALL.json``.

Stand-in honesty: every line's ``detail.standin`` says exactly how the config
was scaled, and ``detail.normalization`` documents what its ``vs_baseline``
is measured against (a reference claim, the MFU/0.54 headline basis, or the
config's tracked correctness clause).
"""

import json
import os
import subprocess
import sys
import time

import numpy as np

from bench import peak_bf16_flops


def _cpu_env(n_devices: int = 8) -> dict:
    """Environment of a child that must stay off the chip: the parent
    (``bench.py --all``) holds it, and a chip belongs to one process."""
    from deepspeed_tpu.utils.xla_env import virtual_mesh_flags

    env = dict(os.environ)
    env["XLA_FLAGS"] = virtual_mesh_flags(env.get("XLA_FLAGS", ""), n_devices)
    env["JAX_PLATFORMS"] = "cpu"
    return env


def _run_cpu_subprocess(name: str) -> dict:
    """Run a registered config in a CPU-backend subprocess on the virtual
    mesh and parse its JSON line; a child that fails raises."""
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), name],
        env=_cpu_env(), capture_output=True, text=True,
        cwd=os.path.dirname(os.path.abspath(__file__)))
    if proc.returncode != 0:
        raise RuntimeError(f"bench config {name} failed rc={proc.returncode}:\n"
                           + (proc.stderr or proc.stdout)[-2000:])
    return json.loads(proc.stdout.strip().splitlines()[-1])


def _train_throughput(model_cfg, ds_config, *, seq, micro_bs, steps=10,
                      warmup=3, labels=False):
    import jax
    import jax.numpy as jnp

    import deepspeed_tpu
    from deepspeed_tpu.comm import topology as topo_mod
    from deepspeed_tpu.models import TransformerLM

    topo_mod.reset_topology()
    model = TransformerLM(model_cfg)
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, config=ds_config)
    dp = 1
    topo = topo_mod.get_topology(required=False)
    if topo is not None:
        dp = topo.get_dim("data") * topo.get_dim("hpz")
    B = micro_bs * dp
    rng = np.random.default_rng(0)

    def mk():
        b = {"input_ids": jnp.asarray(
            rng.integers(0, model_cfg.vocab_size, (B, seq), dtype=np.int32))}
        if labels:
            b["labels"] = jnp.asarray(
                rng.integers(0, model_cfg.vocab_size, (B, seq), dtype=np.int32))
        return b

    # one distinct batch per step: repeated batches get one-shot-memorized by
    # large models under AdamW (verified: loss 0.05 on a revisited batch,
    # 11.2 on fresh data), which makes final_loss misleading
    batches = [mk() for _ in range(steps + warmup)]

    def it():
        i = 0
        while True:
            yield batches[i % len(batches)]
            i += 1

    g = it()
    gas = ds_config.get("gradient_accumulation_steps", 1)
    for _ in range(warmup):
        float(engine.train_batch(g))
    t0 = time.perf_counter()
    loss = None
    for _ in range(steps):
        loss = engine.train_batch(g)
    loss = float(loss)
    jax.block_until_ready(engine.params)
    dt = time.perf_counter() - t0
    tokens = B * seq * gas * steps
    return tokens / dt, loss, dt / steps


def _cpu_adam_speedup(n=4_000_000, iters=5):
    """Measured C++ CPUAdam speedup over torch CPU Adam on THIS host. The
    reference claim (5-7×, ``deepspeed/ops/adam/cpu_adam.py:26-32``) predates
    torch's vectorized multi-tensor `foreach` path — its baseline is the
    single-tensor loop, so both torch variants are measured: `foreach=False`
    reproduces the claim's experimental baseline, `foreach=True` is modern
    torch. Returns (speedup_vs_claim_baseline, speedup_vs_modern_torch)."""
    import torch

    from deepspeed_tpu.ops.adam.cpu_adam import DeepSpeedCPUAdam

    rng = np.random.default_rng(0)
    p = rng.standard_normal(n).astype(np.float32)
    g = rng.standard_normal(n).astype(np.float32)

    def bench_torch(foreach):
        tp = torch.nn.Parameter(torch.from_numpy(p.copy()))
        topt = torch.optim.AdamW([tp], lr=1e-4, foreach=foreach)
        tp.grad = torch.from_numpy(g.copy())
        topt.step()  # warmup/state init
        t0 = time.perf_counter()
        for _ in range(iters):
            topt.step()
        return (time.perf_counter() - t0) / iters

    t_single = bench_torch(False)
    t_foreach = bench_torch(True)

    ours = DeepSpeedCPUAdam(lr=1e-4)
    pp, m, v = p.copy(), np.zeros(n, np.float32), np.zeros(n, np.float32)
    ours.step_flat(pp, g, m, v, step=1)
    t0 = time.perf_counter()
    for i in range(iters):
        ours.step_flat(pp, g, m, v, step=2 + i)
    t_ours = (time.perf_counter() - t0) / iters
    return t_single / t_ours, t_foreach / t_ours


def bench_cpu_zero1_125m():
    """Config 1: GPT-2 125M ZeRO-1 fp32, single process, C++ CPUAdam (host)."""
    from deepspeed_tpu.models import gpt2_config

    seq, mb = 128, 1
    cfg = gpt2_config("125m", max_seq_len=seq)
    tok_s, loss, step_s = _train_throughput(cfg, {
        "train_micro_batch_size_per_gpu": mb,
        "gradient_accumulation_steps": 1,
        "optimizer": {"type": "adam", "params": {"lr": 1e-4}},
        "zero_optimization": {"stage": 1,
                              "offload_optimizer": {"device": "cpu"}},
        "gradient_clipping": 0.0,
        "steps_per_print": 0,
    }, seq=seq, micro_bs=mb, steps=2, warmup=1)
    # normalization: the reference's measurable claim for THIS config's hot
    # component is CPUAdam's 5-7× over torch CPU Adam; report our measured
    # speedup against the claim's low end
    sp_claim, sp_modern = _cpu_adam_speedup()
    # normalization: THIS config's tracked claim (BASELINE.md north star) is
    # the bitwise CPU ZeRO-1 loss curve, not a throughput number — run the
    # parity test and score it
    repo = os.path.dirname(os.path.abspath(__file__))
    parity = subprocess.run(
        [sys.executable, "-m", "pytest", "-q",
         os.path.join(repo, "tests", "unit", "test_bitwise_cpu_zero1.py")],
        env=_cpu_env(), capture_output=True, text=True, cwd=repo)
    return {
        "metric": "gpt2_125m_zero1_fp32_cpu_tokens_per_sec",
        "value": round(tok_s, 1), "unit": "tokens/s",
        "vs_baseline": 1.0 if parity.returncode == 0 else 0.0,
        "detail": {"standin": "full 125M dims; seq 128, mb 1, 2 steps, CPU "
                              "backend",
                   "normalization": "vs_baseline = 1.0 iff the config's "
                                    "tracked claim holds: BITWISE loss-curve "
                                    "parity vs a plain CPUAdam loop "
                                    "(BASELINE.md north-star clause; "
                                    "tests/unit/test_bitwise_cpu_zero1.py, "
                                    "re-executed by this bench)",
                   "bitwise_parity_test": "passed" if parity.returncode == 0
                                          else (parity.stdout + parity.stderr)[-300:],
                   "cpu_adam_speedup_vs_torch_singletensor": round(sp_claim, 2),
                   "cpu_adam_speedup_vs_torch_foreach": round(sp_modern, 2),
                   "cpu_adam_note": "the reference 5-7x CPUAdam claim is "
                                    "thread-parallel on many-core hosts; "
                                    "this host exposes 1 vCPU, where the "
                                    "AVX-512 kernel lands at parity with "
                                    "torch",
                   "final_loss": loss, "step_s": round(step_s, 2)},
    }


def bench_zero2_350m():
    """Config 2: GPT-2 350M ZeRO-2 bf16 + FusedAdam (dp over available chips)."""
    import jax

    from deepspeed_tpu.models import gpt2_config

    seq, mb = 1024, 8
    n = len(jax.devices())
    cfg = gpt2_config("350m", max_seq_len=seq, remat=True, remat_policy="dots",
                      scan_layers=False)
    tok_s, loss, step_s = _train_throughput(cfg, {
        "train_micro_batch_size_per_gpu": mb,
        "gradient_accumulation_steps": 1,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-4}},
        "zero_optimization": {"stage": 2},
        "bf16": {"enabled": True},
        "gradient_clipping": 1.0,
        "steps_per_print": 0,
    }, seq=seq, micro_bs=mb, steps=20, warmup=4)
    peak = peak_bf16_flops(jax.devices()[0].device_kind)
    mfu = tok_s / n * cfg.flops_per_token(seq) / peak
    # correctness companion: the SAME ZeRO-2 config at dp=8 on the virtual
    # CPU mesh (scaled dims) — the sharded math, not just the 1-chip perf
    dp8 = _run_cpu_subprocess("zero2_dp8_check")
    return {
        "metric": "gpt2_350m_zero2_bf16_tokens_per_sec_per_chip",
        "value": round(tok_s / n, 1), "unit": "tokens/s/chip",
        "vs_baseline": round(mfu / 0.54, 3),
        "detail": {"standin": f"dp={n} perf (8-chip config on available "
                              "chips); dp8 sharded-math pass on the virtual "
                              "mesh recorded below",
                   "normalization": "vs_baseline = mfu / 0.54 (same Ulysses "
                                    ">54%-of-peak basis as the headline)",
                   "mfu": round(mfu, 4),
                   "dp8_virtual_mesh_check": dp8,
                   "final_loss": loss, "step_ms": round(step_s * 1000, 1)},
    }


def bench_zero2_dp8_check():
    """dp=8 ZeRO-2 correctness pass (scaled dims) on the virtual CPU mesh."""
    from deepspeed_tpu.comm import topology as topo_mod
    from deepspeed_tpu.models import gpt2_config

    topo_mod.reset_topology()
    seq, mb = 128, 2
    cfg = gpt2_config("350m", hidden_size=256, num_layers=4, num_heads=4,
                      vocab_size=2048, max_seq_len=seq)
    tok_s, loss, step_s = _train_throughput(cfg, {
        "train_micro_batch_size_per_gpu": mb,
        "gradient_accumulation_steps": 1,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-4}},
        "zero_optimization": {"stage": 2},
        "bf16": {"enabled": True},
        "gradient_clipping": 1.0,
        "steps_per_print": 0,
        "mesh": {"data": 8},
    }, seq=seq, micro_bs=mb, steps=3, warmup=1)
    return {"dp": 8, "stage": 2, "final_loss": loss,
            "loss_finite": bool(np.isfinite(loss))}


def bench_llama7b_zero3():
    """Config 3: LLaMA-2 7B ZeRO-3 + gradient checkpointing (depth-scaled)."""
    import jax

    from deepspeed_tpu.models import llama_config

    # full 7B hidden/FFN/head geometry, 2 of 32 layers: the per-layer compute
    # and memory behavior (the thing the config tracks) is preserved; depth is
    # cut so master+moments fit one 16 GB chip. mb=2: the round-3 decomposition
    # (tests/perf/breakdown_7b.py) showed the round-2 number (mfu 0.405) was a
    # micro-batch artifact — fwd+bwd mfu is 0.70/0.77/0.83 at mb 1/2/4, and at
    # mb=1 the fixed per-step Adam pass (666M params, HBM-bound) amortizes over
    # only 2048 tokens. mb=4 is fastest but leaves <2 GB HBM headroom with the
    # fp32 master+moments resident; mb=2 is the stable pick.
    L = 2
    seq, mb = 2048, 2
    cfg = llama_config("7b", num_layers=L, max_seq_len=seq, remat=True,
                       remat_policy="dots")
    tok_s, loss, step_s = _train_throughput(cfg, {
        "train_micro_batch_size_per_gpu": mb,
        "gradient_accumulation_steps": 1,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-4}},
        "zero_optimization": {"stage": 3},
        "bf16": {"enabled": True},
        "gradient_clipping": 1.0,
        "steps_per_print": 0,
    }, seq=seq, micro_bs=mb, steps=8, warmup=3)
    peak = peak_bf16_flops(jax.devices()[0].device_kind)
    n = len(jax.devices())
    mfu = tok_s / n * cfg.flops_per_token(seq) / peak
    return {
        "metric": "llama7b_zero3_remat_tokens_per_sec_per_chip",
        "value": round(tok_s / n, 1), "unit": "tokens/s/chip",
        "vs_baseline": round(mfu / 0.54, 3),
        "detail": {"standin": f"full 7B layer geometry, {L}/32 layers, seq "
                              f"{seq}, mb {mb}", "mfu": round(mfu, 4),
                   "normalization": "vs_baseline = mfu / 0.54 (same Ulysses "
                                    ">54%-of-peak basis as the headline)",
                   "decomposition": "tests/perf/breakdown_7b.py: fwd+bwd mfu "
                                    "0.70/0.77/0.83 at mb 1/2/4; Adam on 666M "
                                    "params is the fixed per-step cost",
                   "final_loss": loss, "step_ms": round(step_s * 1000, 1)},
    }


def bench_bert_offloadpp():
    """Config 4: BERT-large ZeRO + Offload++ twin-flow (ratio split host/device)."""
    from deepspeed_tpu.models.transformer import TransformerConfig

    seq, mb = 256, 2
    cfg = TransformerConfig(
        vocab_size=30592, hidden_size=1024, num_layers=24, num_heads=16,
        max_seq_len=seq, causal=False, norm_position="post",
        activation="gelu", name="bert-large",
    )
    def run(extra_zero):
        return _train_throughput(cfg, {
            "train_micro_batch_size_per_gpu": mb,
            "gradient_accumulation_steps": 1,
            "optimizer": {"type": "adam", "params": {"lr": 1e-4}},
            "zero_optimization": {"stage": 2, **extra_zero},
            "bf16": {"enabled": True},
            "gradient_clipping": 1.0,
            "steps_per_print": 0,
        }, seq=seq, micro_bs=mb, steps=2, warmup=1, labels=True)

    # decomposition points: twin-flow at SWEPT ratios (the reference's 3×
    # claim is explicitly "with some tuning on offload ratio",
    # blogs/deepspeed-offloadpp/README.md:37 — smaller ratio = more device
    # work = faster, bounded by HBM headroom), FULL offload (ratio 1.0, the
    # reference's plain ZeRO-Offload baseline), and no offload (pure device)
    sweep = {}
    best_ratio, best = None, None
    for ratio in (0.4, 0.3, 0.2):
        tok_s, loss, step_s = run({"offload_optimizer": {"device": "cpu",
                                                         "ratio": ratio}})
        sweep[str(ratio)] = round(step_s * 1000, 1)
        if best is None or step_s < best[2]:
            best_ratio, best = ratio, (tok_s, loss, step_s)
    tok_s, loss, step_s = best
    _, _, step_full = run({"offload_optimizer": {"device": "cpu",
                                                 "ratio": 1.0}})
    _, _, step_dev = run({})
    speedup = step_full / step_s
    return {
        "metric": "bert_large_offloadpp_tokens_per_sec",
        "value": round(tok_s, 1), "unit": "tokens/s",
        "vs_baseline": round(speedup / 3.0, 3),
        "detail": {"standin": "BERT-large dims, MLM-style random labels, seq "
                              "256 mb 2, 2 steps; twin-flow ratio swept "
                              f"(best {best_ratio}: largest leaves host, "
                              "rest device)",
                   "normalization": "vs_baseline = tuned twin-flow speedup "
                                    "over FULL offload (ratio 1.0) / 3.0 — "
                                    "the reference Offload++ claim on A100, "
                                    "itself ratio-tuned "
                                    "(blogs/deepspeed-offloadpp/README.md:34,37)",
                   "twinflow_speedup_vs_full_offload": round(speedup, 2),
                   "ratio_sweep_step_ms": sweep,
                   "best_ratio": best_ratio,
                   "device_compute_step_ms": round(step_dev * 1000, 1),
                   "host_link_overhead_ms": round(
                       (step_s - step_dev) * 1000, 1),
                   "final_loss": loss, "step_ms": round(step_s * 1000, 1)},
    }


def bench_pipe_zero1():
    """Config 5: GPT-2 1.3B PipelineEngine x ZeRO-1 hybrid — pp4 x dp2 on the
    8-device virtual CPU mesh (functional stand-in; no multi-chip hardware)."""
    import jax
    import jax.numpy as jnp

    import deepspeed_tpu
    from deepspeed_tpu.comm import topology as topo_mod
    from deepspeed_tpu.models import TransformerLM, gpt2_config
    from deepspeed_tpu.runtime.pipe import PipelinedLM

    topo_mod.reset_topology()
    topo = topo_mod.initialize_topology(data=2, model=1, seq=1, pipe=4,
                                        expert=1)
    seq, mb, gas = 256, 2, 4
    cfg = gpt2_config("1.3b", hidden_size=512, num_layers=8, num_heads=8,
                      vocab_size=8192, max_seq_len=seq)
    model = PipelinedLM(TransformerLM(cfg), topology=topo)
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, config={
        "train_micro_batch_size_per_gpu": mb,
        "gradient_accumulation_steps": gas,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-4}},
        "zero_optimization": {"stage": 1},
        "bf16": {"enabled": True},
        "steps_per_print": 0,
        "mesh": {"data": 2, "model": 1, "seq": 1, "pipe": 4, "expert": 1},
    })
    rng = np.random.default_rng(0)

    def it():
        while True:
            yield {"input_ids": rng.integers(0, cfg.vocab_size, (mb * 2, seq),
                                             dtype=np.int32)}

    g = it()
    float(engine.train_batch(g))
    t0 = time.perf_counter()
    steps = 3
    loss = None
    for _ in range(steps):
        loss = engine.train_batch(g)
    loss = float(loss)
    # block on params, not just the loss: the numerator must include the
    # final step's pending optimizer update exactly like the denominator
    jax.block_until_ready(engine.params)
    dt = time.perf_counter() - t0
    tokens = mb * 2 * seq * gas * steps
    pipe_tok_s = tokens / dt

    # normalization (VERDICT r4 weak #4 — the old pure-dp8 denominator mixed
    # different collective/remat programs and produced an incoherent >1.0
    # "of ideal"): the denominator is now THE SAME stage-sharded scan program
    # at pp1 (identical per-layer remat, identical embed/head placement,
    # identical gas) on a pipe=1 x data=2 mesh. The only structural
    # difference is the schedule: pp4 runs M+P-1 ticks where pp1 runs M, so
    # on the serialized host (1 vCPU executes all virtual devices) the
    # time ratio's ideal is exactly the 1F1B bubble M/(M+P-1); vs_baseline =
    # achieved fraction of that ideal (≤ 1.0 up to measurement noise; the
    # gap is ppermute + masked-tick overhead).
    topo_mod.reset_topology()
    topo1 = topo_mod.initialize_topology(data=8, model=1, seq=1, pipe=1,
                                         expert=1)
    model1 = PipelinedLM(TransformerLM(cfg), topology=topo1)
    # gas=1 at dp8 gives the same 16-row global step as pp4×dp2×gas4, so the
    # serialized host executes equal useful FLOPs per step in both runs — the
    # per-token stage program (remat, embed/head, layer math) is identical
    engine1, _, _, _ = deepspeed_tpu.initialize(model=model1, config={
        "train_micro_batch_size_per_gpu": mb,
        "gradient_accumulation_steps": 1,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-4}},
        "zero_optimization": {"stage": 1},
        "bf16": {"enabled": True},
        "steps_per_print": 0,
        "mesh": {"data": 8, "model": 1, "seq": 1, "pipe": 1, "expert": 1},
    })

    def it1():
        while True:
            yield {"input_ids": rng.integers(0, cfg.vocab_size, (mb * 8, seq),
                                             dtype=np.int32)}

    g1 = it1()
    float(engine1.train_batch(g1))
    tokens1 = mb * 8 * seq * steps
    t0 = time.perf_counter()
    for _ in range(steps):
        engine1.train_batch(g1)
    jax.block_until_ready(engine1.params)
    pp1_tok_s = tokens1 / (time.perf_counter() - t0)
    P_, M_ = 4, gas
    bubble = M_ / (M_ + P_ - 1)  # ideal 1F1B efficiency at this depth
    achieved = (pipe_tok_s / pp1_tok_s) / bubble
    return {
        "metric": "gpt2_1.3b_pipe_zero1_tokens_per_sec",
        "value": round(pipe_tok_s, 1), "unit": "tokens/s",
        "vs_baseline": round(achieved, 3),
        "detail": {"standin": "scaled dims (h512 L8 v8k) on the 8-device "
                              "virtual CPU mesh, pp4 x dp2, GAS 4 — relative "
                              "efficiency measurement; not a hardware "
                              "throughput number",
                   "normalization": "vs_baseline = (pp4xdp2 tokens/s ÷ pp1 of "
                                    "the SAME stage-sharded scan program, "
                                    "identical per-layer remat + embed/head "
                                    "placement; pp1 runs gas=1 at dp8 for "
                                    "equal 16-row per-step FLOPs) ÷ ideal "
                                    f"1F1B bubble M/(M+P-1)={bubble:.3f}; on "
                                    "the serialized 1-vCPU host the tick-"
                                    "count ratio's ideal IS the bubble, so "
                                    "1.0 = zero overhead beyond the "
                                    "schedule's own bubble and values stay "
                                    "≤1.0 up to noise",
                   "pp1_tokens_per_sec": round(pp1_tok_s, 1),
                   "final_loss": loss},
    }


BENCH_TRAIN_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "BENCH_TRAIN.json")


def bench_train_stages():
    """ZeRO stage-sweep row (docs/ZERO.md): the SAME dp=8 micro-model trained
    at ``zero_optimization.stage`` 0/1/2/3, all in the cpu-offload family —
    the four runs share ONE compiled fwd/bwd program and one elementwise host
    Adam (stages 2/3 build stage-0 compute specs, docs/ZERO.md "Bitwise by
    construction"), so the partitioning of optimizer state and update work is
    the only variable. Reports per-stage step time and per-replica state
    bytes; ``vs_baseline`` scores the tracked claim: stages 1-3 loss curves
    AND final params BITWISE identical to stage 0. The full sweep table is
    also written to BENCH_TRAIN.json."""
    import jax
    import jax.numpy as jnp

    import deepspeed_tpu
    from deepspeed_tpu.comm import topology as topo_mod
    from deepspeed_tpu.models import TransformerLM, gpt2_config

    mb_total, seq, warmup, steps = 8, 32, 2, 6

    def mk_engine(stage, pin_from=None):
        topo_mod.reset_topology()
        model = TransformerLM(gpt2_config(
            "125m", hidden_size=64, num_layers=2, num_heads=4,
            vocab_size=128, max_seq_len=seq))
        engine, _, _, _ = deepspeed_tpu.initialize(model=model, config={
            "train_batch_size": mb_total,
            "optimizer": {"type": "adamw", "params": {"lr": 1e-3,
                                                      "weight_decay": 0.01}},
            "zero_optimization": {"stage": stage,
                                  "offload_optimizer": {"device": "cpu"}},
            "gradient_clipping": 1.0,
            "steps_per_print": 0,
        })
        if pin_from is not None:  # XLA determinism is per compiled program
            for name in ("_fwd_bwd", "_train_loss", "_acc", "_step_fn",
                         "_fused_step_fn", "_multi_step_fn"):
                if hasattr(pin_from, name):
                    setattr(engine, name, getattr(pin_from, name))
        return engine

    def batch(k):
        rng = np.random.default_rng(1000 + k)
        return {"input_ids": jnp.asarray(
            rng.integers(0, 128, (mb_total, seq), dtype=np.int32))}

    table, curves, finals = {}, {}, {}
    ref_engine = None
    for stage in (0, 1, 2, 3):
        eng = mk_engine(stage, pin_from=ref_engine)
        if ref_engine is None:
            ref_engine = eng
        losses = []
        for k in range(warmup):
            loss = eng(batch(k))
            eng.backward(loss)
            eng.step()
            losses.append(np.asarray(loss))
        jax.block_until_ready(eng.params)
        t0 = time.perf_counter()
        for k in range(warmup, warmup + steps):
            loss = eng(batch(k))
            eng.backward(loss)
            eng.step()
            losses.append(np.asarray(loss))
        jax.block_until_ready(eng.params)
        step_ms = (time.perf_counter() - t0) / steps * 1000
        curves[stage] = np.asarray(losses)
        finals[stage] = [np.asarray(l)
                         for l in jax.tree.leaves(eng.get_fp32_params())]
        param_bytes = sum(int(l.nbytes) for l in jax.tree.leaves(eng.params))
        tier = eng._zero_tier
        if tier is not None:  # per-replica owned slice of master+m+v
            opt_bytes = 3 * tier.plan.shard_bytes(0)
        else:  # flat offload: every replica holds the FULL fp32 state
            opt_bytes = 3 * 4 * sum(m.size for m in
                                    eng._offload_mgr["host"].master)
        table[str(stage)] = {
            "step_ms": round(step_ms, 1),
            "param_bytes_resident": param_bytes,
            "opt_state_bytes_owned_per_replica": int(opt_bytes),
            "zero_counters": eng.zero_metrics() or None,
        }

    bitwise = all(
        curves[s].shape == curves[0].shape
        and bool(np.array_equal(curves[s], curves[0]))
        and all(np.array_equal(a, b)
                for a, b in zip(finals[s], finals[0]))
        for s in (1, 2, 3))
    sweep = {
        "model": "gpt2-125m scaled (h64 L2 v128), seq 32, dp=8 virtual mesh",
        "steps": steps, "warmup": warmup,
        "offload": "cpu (all stages — shared compiled program + host Adam)",
        "bitwise_vs_stage0": bitwise,
        "stages": table,
    }
    with open(BENCH_TRAIN_PATH, "w") as f:
        json.dump(sweep, f, indent=1)
    shard_ratio = (table["0"]["opt_state_bytes_owned_per_replica"]
                   / max(1, table["2"]["opt_state_bytes_owned_per_replica"]))
    return {
        "metric": "train_zero_stage_sweep_step_ms",
        "value": table["2"]["step_ms"], "unit": "ms/step (stage 2)",
        "vs_baseline": 1.0 if bitwise else 0.0,
        "detail": {"standin": "scaled dims (h64 L2 v128), seq 32, dp=8 "
                              "virtual CPU mesh, cpu-offloaded Adam at every "
                              "stage; full table in BENCH_TRAIN.json",
                   "normalization": "vs_baseline = 1.0 iff the tracked claim "
                                    "holds: stage-1/2/3 loss curves AND "
                                    "final params BITWISE identical to "
                                    "stage 0 (docs/ZERO.md; compiled "
                                    "programs shared across stages)",
                   "per_replica_opt_bytes_stage0_over_stage2":
                       round(shard_ratio, 2),
                   "stages": table},
    }


def bench_transfer_overlap_train():
    """Unified-TransferEngine training A/B (docs/TRANSFER.md): the dp=8
    micro-model at ZeRO stage 2 with a cpu-offloaded sharded optimizer,
    swept over ``transfer_overlap`` on/off x NVMe moments tier on/off
    (``offload_optimizer.nvme_path``). Overlap ON submits every leaf's D2H
    gradient up front as open tickets settled per leaf at the host Adam's
    drain boundary; OFF is the synchronous twin. The four runs share ONE
    compiled fwd/bwd program, so ``vs_baseline`` scores the tracked claim:
    all four arms' loss curves AND final params are BITWISE identical.
    Reports per-arm step time, the transfer ledger, and the NVMe store
    counters; the table merges into BENCH_TRAIN.json."""
    import shutil
    import tempfile

    import jax
    import jax.numpy as jnp

    import deepspeed_tpu
    from deepspeed_tpu.comm import topology as topo_mod
    from deepspeed_tpu.models import TransformerLM, gpt2_config

    mb_total, seq, warmup, steps = 8, 32, 2, 6

    def mk_engine(overlap, nvme_path, pin_from=None):
        topo_mod.reset_topology()
        model = TransformerLM(gpt2_config(
            "125m", hidden_size=64, num_layers=2, num_heads=4,
            vocab_size=128, max_seq_len=seq))
        off = {"device": "cpu"}
        if nvme_path:
            off["nvme_path"] = nvme_path
        engine, _, _, _ = deepspeed_tpu.initialize(model=model, config={
            "train_batch_size": mb_total,
            "optimizer": {"type": "adamw", "params": {"lr": 1e-3,
                                                      "weight_decay": 0.01}},
            "zero_optimization": {"stage": 2, "offload_optimizer": off,
                                  "transfer_overlap": overlap},
            "gradient_clipping": 1.0,
            "steps_per_print": 0,
        })
        if pin_from is not None:  # XLA determinism is per compiled program
            for name in ("_fwd_bwd", "_train_loss", "_acc", "_step_fn",
                         "_fused_step_fn", "_multi_step_fn"):
                if hasattr(pin_from, name):
                    setattr(engine, name, getattr(pin_from, name))
        return engine

    def batch(k):
        rng = np.random.default_rng(1000 + k)
        return {"input_ids": jnp.asarray(
            rng.integers(0, 128, (mb_total, seq), dtype=np.int32))}

    arms = (("overlap_on", True, False), ("overlap_off", False, False),
            ("overlap_on_nvme", True, True), ("overlap_off_nvme", False, True))
    table, curves, finals = {}, {}, {}
    ref_engine = None
    for label, overlap, nvme in arms:
        nvme_dir = tempfile.mkdtemp(prefix="dstpu_bench_optnvme_") if nvme \
            else None
        try:
            eng = mk_engine(overlap, nvme_dir, pin_from=ref_engine)
            if ref_engine is None:
                ref_engine = eng
            losses = []
            for k in range(warmup):
                loss = eng(batch(k))
                eng.backward(loss)
                eng.step()
                losses.append(np.asarray(loss))
            jax.block_until_ready(eng.params)
            t0 = time.perf_counter()
            for k in range(warmup, warmup + steps):
                loss = eng(batch(k))
                eng.backward(loss)
                eng.step()
                losses.append(np.asarray(loss))
            jax.block_until_ready(eng.params)
            step_ms = (time.perf_counter() - t0) / steps * 1000
            curves[label] = np.asarray(losses)
            finals[label] = [np.asarray(l) for l in
                             jax.tree.leaves(eng.get_fp32_params())]
            te = eng._transfer
            table[label] = {
                "step_ms": round(step_ms, 1),
                "transfer_ledger": te.ledger(),
                "h2d_bytes_per_s": (round(1.0 / te.s_per_byte("h2d"))
                                    if te.s_per_byte("h2d") > 0 else None),
                "d2h_bytes_per_s": (round(1.0 / te.s_per_byte("d2h"))
                                    if te.s_per_byte("d2h") > 0 else None),
                "nvme_counters": dict(te.nvme.counters) if te.nvme else None,
            }
            if nvme:
                assert te.nvme.counters["saves"] >= 1, te.nvme.counters
                assert te.nvme.counters["loads"] >= 1, te.nvme.counters
        finally:
            if nvme_dir is not None:
                shutil.rmtree(nvme_dir, ignore_errors=True)

    bitwise = all(
        curves[l].shape == curves["overlap_on"].shape
        and bool(np.array_equal(curves[l], curves["overlap_on"]))
        and all(np.array_equal(a, b)
                for a, b in zip(finals[l], finals["overlap_on"]))
        for l, _, _ in arms)
    sweep = {
        "model": "gpt2-125m scaled (h64 L2 v128), seq 32, dp=8 virtual mesh",
        "steps": steps, "warmup": warmup,
        "config": "ZeRO stage 2, cpu-offloaded sharded Adam",
        "bitwise_across_arms": bitwise,
        "arms": table,
    }
    try:  # merge next to the stage sweep (read-modify-write)
        with open(BENCH_TRAIN_PATH) as f:
            existing = json.load(f)
    except (OSError, json.JSONDecodeError):
        existing = {}
    existing["transfer_overlap"] = sweep
    with open(BENCH_TRAIN_PATH, "w") as f:
        json.dump(existing, f, indent=1)
    speedup = (table["overlap_off"]["step_ms"]
               / max(table["overlap_on"]["step_ms"], 1e-9))
    return {
        "metric": "train_transfer_overlap_step_ms",
        "value": table["overlap_on"]["step_ms"],
        "unit": "ms/step (overlap on)",
        "vs_baseline": 1.0 if bitwise else 0.0,
        "detail": {"standin": "scaled dims (h64 L2 v128), seq 32, dp=8 "
                              "virtual CPU mesh, ZeRO-2 sharded cpu Adam; "
                              "full table in BENCH_TRAIN.json "
                              "'transfer_overlap'",
                   "normalization": "vs_baseline = 1.0 iff all four arms "
                                    "(overlap on/off x NVMe moments on/off) "
                                    "have BITWISE identical loss curves and "
                                    "final params (docs/TRANSFER.md; "
                                    "compiled programs shared across arms)",
                   "overlap_off_over_on_step_time": round(speedup, 3),
                   "arms": table},
    }


def bench_training_chaos():
    """Training-chaos row (docs/RESILIENCE.md training section): a seeded
    fault storm — transient bursts, a checkpoint-save fault, one device loss
    mid-run, a faulted restore — driven through the ``TrainingSupervisor``.
    Reports goodput under chaos; ``vs_baseline`` scores the config's tracked
    claim: the chaotic run's loss curve is BITWISE identical to the
    fault-free reference's (recovery replays killed steps, never perturbs
    them)."""
    import tempfile

    import jax
    import jax.numpy as jnp

    import deepspeed_tpu
    from deepspeed_tpu.comm import topology as topo_mod
    from deepspeed_tpu.models import TransformerLM, gpt2_config
    from deepspeed_tpu.resilience import (FaultInjector, FaultSpec,
                                          InjectedTrainEngine, RecoveryPolicy,
                                          RetryPolicy, TrainingSupervisor)

    mb, seq, steps = 2, 32, 12

    def batches_for(k):
        rng = np.random.default_rng(1000 + k)
        return [{"input_ids": jnp.asarray(
            rng.integers(0, 256, (mb, seq), dtype=np.int32))}]

    def mk_engine():
        topo_mod.reset_topology()
        topo_mod.initialize_topology(
            data=1, model=1, seq=1, pipe=1, expert=1,
            devices=np.array(jax.devices()[:1]))
        model = TransformerLM(gpt2_config(
            "125m", hidden_size=64, num_layers=2, num_heads=4,
            vocab_size=256, max_seq_len=seq))
        engine, _, _, _ = deepspeed_tpu.initialize(model=model, config={
            "train_micro_batch_size_per_gpu": mb,
            "gradient_accumulation_steps": 1,
            "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
            # stage-2 sharded tier: chaos recovery now also exercises the
            # per-shard optimizer checkpoints + consolidation (docs/ZERO.md)
            "zero_optimization": {"stage": 2,
                                  "offload_optimizer": {"device": "cpu"}},
            "gradient_clipping": 0.0,
            "steps_per_print": 0,
        })
        return engine

    with tempfile.TemporaryDirectory() as d_ref, \
            tempfile.TemporaryDirectory() as d_chaos:
        ref = mk_engine()
        sup_ref = TrainingSupervisor(ref, batches_for, d_ref,
                                     save_interval=3, sleep=lambda s: None)
        sup_ref.run(steps)
        ref_curve = np.asarray([np.asarray(x) for x in sup_ref.loss_curve()])

        eng = mk_engine()
        # XLA determinism is per compiled program: share the reference's
        # programs so the parity claim is about recovery, not fusion luck
        # (the test_bitwise_cpu_zero1 discipline)
        for name in ("_fwd_bwd", "_train_loss", "_acc", "_step_fn",
                     "_fused_step_fn", "_multi_step_fn"):
            if hasattr(ref, name):
                setattr(eng, name, getattr(ref, name))
        inj = FaultInjector([
            FaultSpec(site="train_batch", kind="transient", nth=3, count=2),
            FaultSpec(site="ckpt_save", kind="transient", nth=3),
            FaultSpec(site="train_batch", kind="device_lost", nth=11),
            FaultSpec(site="load_checkpoint", kind="transient", nth=1),
            FaultSpec(site="train_batch", kind="transient", nth=16),
        ], seed=0, sleep=lambda s: None)
        t0 = time.perf_counter()
        sup = TrainingSupervisor(
            InjectedTrainEngine(eng, inj), batches_for, d_chaos,
            save_interval=3, retry=RetryPolicy(max_attempts=4, base_s=0.0),
            recovery=RecoveryPolicy(max_consecutive_rebuilds=3),
            sleep=lambda s: None)
        sup.run(steps)
        wall_s = time.perf_counter() - t0
        rep = sup.report()
        chaos_curve = np.asarray([np.asarray(x) for x in sup.loss_curve()])
        bitwise = (ref_curve.shape == chaos_curve.shape
                   and bool(np.array_equal(ref_curve, chaos_curve)))
        params_ok = all(
            np.array_equal(np.asarray(a), np.asarray(b))
            for a, b in zip(jax.tree.leaves(ref.params),
                            jax.tree.leaves(eng.params)))
    return {
        "metric": "train_chaos_goodput_ratio",
        "value": round(rep["goodput_ratio"], 3), "unit": "steps/attempt",
        "vs_baseline": 1.0 if (bitwise and params_ok) else 0.0,
        "detail": {"standin": "scaled dims (h64 L2 v256), seq 32, mb 1x2, "
                              f"{steps} steps on the CPU backend, ZeRO-2 "
                              "sharded tier (per-shard optimizer "
                              "checkpoints); seeded storm: 2-burst + 1 "
                              "transient train faults, 1 ckpt-save fault, "
                              "1 device loss mid-run, 1 faulted restore",
                   "normalization": "vs_baseline = 1.0 iff the config's "
                                    "tracked claim holds: the chaotic run's "
                                    "loss curve AND final params are BITWISE "
                                    "identical to the fault-free supervised "
                                    "reference (docs/RESILIENCE.md training "
                                    "section; compiled programs shared, so "
                                    "the claim isolates recovery)",
                   "bitwise_loss_curve": "passed" if bitwise else "FAILED",
                   "bitwise_final_params": "passed" if params_ok else "FAILED",
                   "retries": rep["retries"],
                   "recoveries": rep["recoveries"],
                   "replayed_steps": rep["replayed_steps"],
                   "ckpt_corrupt_fallbacks": rep["ckpt_corrupt_fallbacks"],
                   "faults_fired": rep["faults_fired"],
                   "net_steps": rep["net_steps"],
                   "attempts": rep["attempts"],
                   "wall_s": round(wall_s, 2)},
    }


CPU_CONFIGS = {"cpu_zero1_125m": bench_cpu_zero1_125m,
               "pipe_zero1": bench_pipe_zero1,
               "training_chaos": bench_training_chaos,
               "train_zero_stages": bench_train_stages,
               "train_transfer_overlap": bench_transfer_overlap_train}
TPU_CONFIGS = {"zero2_350m": bench_zero2_350m,
               "llama7b_zero3": bench_llama7b_zero3,
               "bert_offloadpp": bench_bert_offloadpp}
# subprocess-only helpers (not rows of BENCH_ALL)
AUX_CONFIGS = {"zero2_dp8_check": bench_zero2_dp8_check}


def run_one(name):
    """Entry for the CPU-backend subprocess (see run_all)."""
    fn = {**CPU_CONFIGS, **TPU_CONFIGS, **AUX_CONFIGS}[name]
    print(json.dumps(fn()))


def run_all():
    """Every tracked config, one JSON line each, written to BENCH_ALL.json.
    A config that fails ends the run: a missing row is not a result."""
    from deepspeed_tpu.utils.transfer import install_transfer_guard

    install_transfer_guard()  # SIGTERM drains in-flight transfers first
    results = [_run_cpu_subprocess(name) for name in CPU_CONFIGS]
    results += [fn() for fn in TPU_CONFIGS.values()]
    for r in results:
        print(json.dumps(r))
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "BENCH_ALL.json"), "w") as f:
        json.dump(results, f, indent=1)
    return results


if __name__ == "__main__":
    import logging

    logging.getLogger("DeepSpeedTPU").setLevel(logging.WARNING)
    if len(sys.argv) > 1:
        name = sys.argv[1]
        if name in CPU_CONFIGS or name in AUX_CONFIGS:
            # these run on the 8-device virtual CPU mesh whatever the
            # environment says. virtual_mesh_flags (NOT just the device
            # count): without the sequential-thunk stability flags the
            # concurrent scheduler deadlocks the in-process collective
            # rendezvous (SIGABRT)
            from deepspeed_tpu.utils.xla_env import virtual_mesh_flags

            os.environ["XLA_FLAGS"] = virtual_mesh_flags(
                os.environ.get("XLA_FLAGS", ""), 8)
            import jax

            jax.config.update("jax_platforms", "cpu")
        run_one(name)
    else:
        run_all()
