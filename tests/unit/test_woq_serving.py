"""Weight-only-quantized serving through inference v2 (reference FP6/INT4
serving path, ``inference/quantization`` + v2 ``cuda_linear`` WOQ GEMM): a
WOQ-quantized model decodes through ``InferenceEngineV2`` with the quantized
leaves kept in their storage dtype, and the int8 continuation matches the
fp32 oracle."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.comm import topology as topo_mod
from deepspeed_tpu.inference.v2 import InferenceEngineV2
from deepspeed_tpu.models import build_model
from deepspeed_tpu.ops.quantizer.woq import quantize_param_tree


@pytest.fixture
def setup():
    topo_mod.reset_topology()
    m = build_model("llama-tiny", vocab_size=128, hidden_size=64, num_layers=2,
                    num_heads=4, num_kv_heads=2, intermediate_size=128,
                    max_seq_len=128)
    params = m.init_params(jax.random.PRNGKey(0))
    return m, params


def _greedy(eng, uid, prompt, n_gen):
    out = eng.put([uid], [prompt])
    seq = list(prompt)
    for _ in range(n_gen - 1):
        t = int(np.argmax(out[uid]))
        seq.append(t)
        out = eng.decode_step({uid: t})
    seq.append(int(np.argmax(out[uid])))
    return seq


class TestWoqServing:
    @pytest.mark.parametrize("bits", [8, 6, 4])
    def test_quantized_leaves_survive_engine_cast(self, setup, bits):
        """The engine's dtype cast must keep int codes and fp32 group scales
        in their storage dtypes — casting codes to the compute dtype would
        silently destroy the quantization."""
        m, params = setup
        q = quantize_param_tree(params, num_bits=bits)
        eng = InferenceEngineV2(m, q, max_seqs=2, max_seq_len=64,
                                prefill_chunk=16)
        blocks = eng.params["blocks"]
        code_keys = [k for k in blocks if "::q" in k]
        assert code_keys, "no quantized leaves reached the engine"
        for k in code_keys:
            assert jnp.issubdtype(blocks[k].dtype, jnp.integer), k
        for k in (k for k in blocks if k.endswith("::scale")):
            assert blocks[k].dtype == jnp.float32, k

    def test_int8_decode_matches_fp32_oracle(self, setup):
        """int8 WOQ is near-lossless at these scales: the greedy continuation
        through the paged engine must equal the fp32 dense oracle."""
        m, params = setup
        q = quantize_param_tree(params, num_bits=8)
        eng = InferenceEngineV2(m, q, max_seqs=2, max_seq_len=64,
                                prefill_chunk=16, paged=True, block_size=8,
                                token_budget=24)
        prompt = [3, 99, 41, 7, 120]
        got = _greedy(eng, 1, prompt, 4)
        cur = jnp.asarray(np.array(prompt)[None], jnp.int32)
        for _ in range(4):
            nxt = int(jnp.argmax(m.logits(params, cur)[0, -1]))
            cur = jnp.concatenate([cur, jnp.asarray([[nxt]], jnp.int32)],
                                  axis=1)
        assert got == list(np.asarray(cur[0]))

    def test_int4_decode_finite_and_consistent(self, setup):
        """int4 diverges from fp32 numerically but must be self-consistent:
        the engine and the model's full forward over the SAME quantized
        params agree exactly."""
        m, params = setup
        q = quantize_param_tree(params, num_bits=4)
        prompt = [5, 9, 33, 77]
        eng = InferenceEngineV2(m, q, max_seqs=2, max_seq_len=64,
                                prefill_chunk=16, block_size=8,
                                token_budget=24)
        got = _greedy(eng, 1, prompt, 4)
        cur = jnp.asarray(np.array(prompt)[None], jnp.int32)
        for _ in range(4):
            nxt = int(jnp.argmax(m.logits(q, cur)[0, -1]))
            cur = jnp.concatenate([cur, jnp.asarray([[nxt]], jnp.int32)],
                                  axis=1)
        assert got == list(np.asarray(cur[0]))

    def test_woq_moe_decode(self, setup):
        """WOQ composes with routed-FFN serving: a quantized MoE model
        decodes through the paged engine (expert weights stay quantized)."""
        topo_mod.reset_topology()
        m = build_model("llama-tiny", vocab_size=128, hidden_size=64,
                        num_layers=2, num_heads=4, num_kv_heads=2,
                        intermediate_size=128, max_seq_len=128, num_experts=4,
                        moe_top_k=2, moe_drop_tokens=False)
        params = m.init_params(jax.random.PRNGKey(0))
        q = quantize_param_tree(params, num_bits=8)
        assert any("::q8" in k for k in q["blocks"])
        eng = InferenceEngineV2(m, q, max_seqs=2, max_seq_len=64,
                                prefill_chunk=16, paged=True, block_size=8,
                                token_budget=24)
        seq = _greedy(eng, 1, [8, 16, 24], 3)
        assert len(seq) == 6 and all(0 <= t < 128 for t in seq)
        cur = jnp.asarray(np.array([8, 16, 24])[None], jnp.int32)
        for _ in range(3):
            nxt = int(jnp.argmax(m.logits(params, cur)[0, -1]))
            cur = jnp.concatenate([cur, jnp.asarray([[nxt]], jnp.int32)],
                                  axis=1)
        assert seq == list(np.asarray(cur[0]))
