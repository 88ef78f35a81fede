"""Train a Residual-MoE (PR-MoE) model with expert parallelism.

Shows the `expert` mesh axis, top-2 routing with the load-balance aux
loss, and the PR-MoE residual branch (use_residual semantics).
"""

import os

if os.environ.get("JAX_PLATFORMS") == "cpu":
    # CPU asked for: demo on an 8-device virtual mesh; unset means "use what
    # JAX finds", and the mesh below then needs that many real devices
    if "host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                                   + " --xla_force_host_platform_device_count=8")

import numpy as np

import deepspeed_tpu
from deepspeed_tpu.models import TransformerLM, gpt2_config
from deepspeed_tpu.utils.xla_env import enable_compile_cache

SEQ = 128

def main():
    enable_compile_cache()
    cfg = gpt2_config("125m", hidden_size=128, num_layers=4, num_heads=4,
                      max_seq_len=SEQ, num_experts=4, moe_top_k=2,
                      moe_use_residual=True)
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=TransformerLM(cfg), config={
            "train_micro_batch_size_per_gpu": 2,
            "gradient_accumulation_steps": 1,
            "optimizer": {"type": "adamw", "params": {"lr": 3e-4}},
            "zero_optimization": {"stage": 2},
            "bf16": {"enabled": True},
            "steps_per_print": 5,
            "mesh": {"data": 2, "expert": 4},
        })
    rng = np.random.default_rng(0)
    for step in range(10):
        ids = rng.integers(0, cfg.vocab_size, (4, SEQ), dtype=np.int32)
        loss = engine({"input_ids": ids})
        engine.backward(loss)
        engine.step()
        if step % 5 == 0:
            print(f"step {step}: loss {float(loss):.3f}")
    print("done — experts sharded over the 'expert' mesh axis")


if __name__ == "__main__":
    main()
