"""Train a GPT-2 model with ZeRO-3 + bf16 on any device mesh.

`python examples/train_gpt2.py` uses whatever device JAX finds (the TPU on
the chip machine); `JAX_PLATFORMS=cpu python examples/train_gpt2.py` is the
scaled-down demo on an 8-device virtual CPU mesh.

Mirrors a reference DeepSpeed script: build a ds_config dict, call
initialize(), loop forward/backward/step, save a checkpoint.
"""

import os

if os.environ.get("JAX_PLATFORMS") == "cpu":
    # CPU asked for: demo on an 8-device virtual mesh
    if "host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                                   + " --xla_force_host_platform_device_count=8")

import jax
import numpy as np

import deepspeed_tpu
from deepspeed_tpu.models import TransformerLM, gpt2_config
from deepspeed_tpu.utils.xla_env import enable_compile_cache

# full 125M on an accelerator; a scaled-down stand-in for the CPU demo
ON_CPU = jax.default_backend() == "cpu"
SEQ = 128 if ON_CPU else 256
STEPS = 8 if ON_CPU else 20
DIMS = dict(hidden_size=256, num_layers=4, num_heads=4) if ON_CPU else {}

ds_config = {
    "train_micro_batch_size_per_gpu": 2,
    "gradient_accumulation_steps": 2,
    "optimizer": {"type": "adamw",
                  "params": {"lr": 3e-4, "weight_decay": 0.01}},
    "scheduler": {"type": "WarmupLR",
                  "params": {"warmup_num_steps": 10}},
    "zero_optimization": {"stage": 3},
    "bf16": {"enabled": True},
    "gradient_clipping": 1.0,
    "steps_per_print": 5,
}


def main():
    enable_compile_cache()
    cfg = gpt2_config("125m", max_seq_len=SEQ, remat=True, **DIMS)
    model = TransformerLM(cfg)
    engine, _, _, lr_sched = deepspeed_tpu.initialize(model=model,
                                                      config=ds_config)
    dp = engine.topology.data_parallel_size
    rng = np.random.default_rng(0)

    def data():
        while True:
            yield {"input_ids": rng.integers(
                0, cfg.vocab_size, (2 * dp, SEQ), dtype=np.int32)}

    it = data()
    for step in range(STEPS):
        loss = engine.train_batch(it)
        if step % 5 == 0:
            print(f"step {step}: loss {float(loss):.3f} "
                  f"lr {engine.get_lr()[0]:.2e}")
    engine.save_checkpoint("ckpt_gpt2", tag="final")
    print("saved checkpoint to ckpt_gpt2/final")


if __name__ == "__main__":
    main()
