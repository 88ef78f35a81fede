"""Serve a model with continuous batching (FastGen-style paged KV).

Demonstrates InferenceEngineV2: staggered arrivals, chunked prefill, and
decode rounds share one compiled ragged program. Uses whatever device JAX
finds; `JAX_PLATFORMS=cpu python examples/serve_paged.py` is the CPU demo.
"""

import jax
import numpy as np

from deepspeed_tpu.inference.v2 import InferenceEngineV2
from deepspeed_tpu.models import build_model
from deepspeed_tpu.utils.xla_env import enable_compile_cache


def main():
    enable_compile_cache()
    model = build_model("llama-tiny", vocab_size=32000, hidden_size=256,
                        num_layers=4, num_heads=8, num_kv_heads=4,
                        intermediate_size=512, max_seq_len=512)
    params = model.init_params(jax.random.PRNGKey(0))
    engine = InferenceEngineV2(model, params, max_seqs=8, max_seq_len=512,
                               prefill_chunk=128, paged=True, block_size=32,
                               token_budget=128)
    rng = np.random.default_rng(0)
    prompts = {uid: rng.integers(0, 32000, (n,)).tolist()
               for uid, n in ((1, 40), (2, 200))}
    out = engine.put(list(prompts), list(prompts.values()))
    sequences = {u: list(p) for u, p in prompts.items()}
    for step in range(16):
        toks = {u: int(np.argmax(v)) for u, v in out.items()}
        for u, t in toks.items():
            sequences[u].append(t)
        if step == 4:  # a request arrives mid-stream
            prompts[3] = rng.integers(0, 32000, (64,)).tolist()
            sequences[3] = list(prompts[3])
            out.update(engine.put([3], [prompts[3]]))
            toks[3] = int(np.argmax(out[3]))
            sequences[3].append(toks[3])
        out = engine.decode_step(toks)
    for u, s in sequences.items():
        print(f"uid {u}: prompt {len(prompts[u])} tokens -> "
              f"generated {len(s) - len(prompts[u])}")
    free, ctx = engine.query()
    print(f"free slots {free}, max context {ctx}")

    # fused multi-token decode (docs/SERVING.md): one compiled K-step
    # dispatch per K tokens, driven through the production scheduler
    from deepspeed_tpu.serve import ContinuousBatchScheduler

    fused = InferenceEngineV2(model, params, max_seqs=8, max_seq_len=512,
                              prefill_chunk=128, paged=True, block_size=32,
                              token_budget=128, decode_horizon=4)
    with ContinuousBatchScheduler(fused) as sched:
        req = sched.submit(rng.integers(0, 32000, (48,)).tolist(),
                           max_new_tokens=24)
        sched.run_until_complete()
    print(f"decode_horizon=4: {len(req.tokens)} tokens in "
          f"{int(sched.metrics.decode['fused_steps'])} fused dispatches "
          f"(+ adaptive single-step tail)")


if __name__ == "__main__":
    main()
