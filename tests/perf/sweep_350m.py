"""One-off perf sweep for the GPT-2-350M bench config on the real chip.

Usage: python tests/perf/sweep_350m.py  (runs each config, prints step_ms / MFU)
"""

import sys
import time

import numpy as np



# transfer discipline: SIGTERM drains in-flight device work instead of dying
# mid-transfer (see deepspeed_tpu/utils/transfer.py)
from deepspeed_tpu.utils.transfer import install_transfer_guard

install_transfer_guard()

def run_config(micro_bs, remat, remat_policy="dots", iters=12, seq=1024,
               scan_layers=True):
    import jax
    import jax.numpy as jnp

    import deepspeed_tpu
    from deepspeed_tpu.comm import topology as topo_mod
    from deepspeed_tpu.models import TransformerLM, gpt2_config

    topo_mod.reset_topology()
    n_chips = len(jax.devices())
    cfg = gpt2_config("350m", max_seq_len=seq, remat=remat,
                      remat_policy=remat_policy, scan_layers=scan_layers)
    model = TransformerLM(cfg)
    ds_config = {
        "train_micro_batch_size_per_gpu": micro_bs,
        "gradient_accumulation_steps": 1,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-4, "weight_decay": 0.01}},
        "zero_optimization": {"stage": 1 if n_chips > 1 else 0},
        "bf16": {"enabled": True},
        "gradient_clipping": 1.0,
        "steps_per_print": 0,
    }
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, config=ds_config)
    B = micro_bs * n_chips
    rng = np.random.default_rng(0)
    batches = [
        {"input_ids": jnp.asarray(rng.integers(0, cfg.vocab_size, (B, seq), dtype=np.int32))}
        for _ in range(4)
    ]

    def it():
        i = 0
        while True:
            yield batches[i % len(batches)]
            i += 1

    g = it()
    for _ in range(3):
        float(engine.train_batch(g))
    t0 = time.perf_counter()
    for _ in range(iters):
        loss = engine.train_batch(g)
    float(loss)
    jax.block_until_ready(engine.params)
    dt = (time.perf_counter() - t0) / iters
    tok_s = B * seq / dt
    peak = 197e12
    mfu = tok_s / n_chips * cfg.flops_per_token(seq) / peak
    print(f"mb={micro_bs:3d} remat={remat!s:5s} policy={remat_policy:5s} "
          f"step={dt*1000:7.2f}ms tok/s/chip={tok_s/n_chips:9.0f} mfu={mfu:.4f} "
          f"vs_baseline={mfu/0.54:.3f}", flush=True)
    del engine
    return dt


if __name__ == "__main__":
    import jax

    print(f"devices: {jax.devices()}", flush=True)
    for arg in sys.argv[1:] or ["8,dots_batch", "16,dots_batch", "16,dots"]:
        mb, rm = arg.split(",")
        remat = rm != "False"
        try:
            run_config(int(mb), remat, remat_policy=rm if remat else "dots")
        except Exception as e:  # OOM etc. — report and continue the sweep
            print(f"mb={mb} remat={rm}: FAILED {type(e).__name__}: {str(e)[:200]}",
                  flush=True)
