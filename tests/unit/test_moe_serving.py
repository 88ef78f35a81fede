"""MoE serving through inference v2 (reference
``inference/v2/model_implementations/mixtral/`` +
``kernels/ragged_ops/{moe_gather,moe_scatter,top_k_gating}``): a routed-FFN
model decodes through ``InferenceEngineV2`` and matches the dense-recompute
oracle."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.comm import topology as topo_mod
from deepspeed_tpu.inference.v2 import InferenceEngineV2
from deepspeed_tpu.models import build_model
from tests.unit.test_inference_v2 import dense_logits_of


@pytest.fixture
def moe_setup():
    """Mixtral-shaped tiny model: LLaMA skeleton (swiglu) + top-2 routed FFN
    with no token dropping (Mixtral parity, models/hf_converters.py
    from_hf_mixtral)."""
    topo_mod.reset_topology()
    m = build_model("llama-tiny", vocab_size=128, hidden_size=64, num_layers=2,
                    num_heads=4, num_kv_heads=2, intermediate_size=128,
                    max_seq_len=128, num_experts=4, moe_top_k=2,
                    moe_drop_tokens=False)
    assert m.config.num_experts == 4
    params = m.init_params(jax.random.PRNGKey(0))
    return m, params


def _oracle_continuation(m, params, prompt, n_gen):
    """Greedy dense recompute (one jitted program for every length: an eager
    call a length took 27 s of this file's 36)."""
    dense_logits = dense_logits_of(m, params, width=32)
    cur = list(prompt)
    for _ in range(n_gen):
        cur.append(int(jnp.argmax(dense_logits(cur))))
    return cur


class TestMoEServing:
    def test_moe_decodes_paged(self, moe_setup):
        """Routed-FFN decode through the paged (BlockedKVCache) engine —
        the reference's first-class MoE serving path (mixtral policy)."""
        m, params = moe_setup
        eng = InferenceEngineV2(m, params, max_seqs=4, max_seq_len=64,
                                prefill_chunk=16, paged=True, block_size=8,
                                token_budget=32)
        rng = np.random.default_rng(0)
        prompts = {1: rng.integers(0, 128, (5,)).tolist(),
                   2: rng.integers(0, 128, (19,)).tolist()}  # 19 > chunk
        out = eng.put([1, 2], [prompts[1], prompts[2]])
        seqs = {u: list(p) for u, p in prompts.items()}
        n_gen = 5
        for _ in range(n_gen):
            toks = {u: int(np.argmax(out[u])) for u in out}
            for u, t in toks.items():
                seqs[u].append(t)
            out = eng.decode_step(toks)
        for u, t in {u: int(np.argmax(out[u])) for u in out}.items():
            seqs[u].append(t)
        for u in (1, 2):
            expect = _oracle_continuation(m, params, prompts[u], n_gen + 1)
            assert seqs[u] == expect, f"uid {u} diverged from dense oracle"

    def test_moe_residual_decodes_paged(self):
        """PR-MoE (use_residual) also serves: the residual dense branch is
        position-independent math, so paged decode matches the oracle."""
        topo_mod.reset_topology()
        m = build_model("llama-tiny", vocab_size=128, hidden_size=64,
                        num_layers=2, num_heads=4, num_kv_heads=2,
                        intermediate_size=128, max_seq_len=128, num_experts=4,
                        moe_top_k=1, moe_drop_tokens=False,
                        moe_use_residual=True)
        params = m.init_params(jax.random.PRNGKey(0))
        eng = InferenceEngineV2(m, params, max_seqs=2, max_seq_len=64,
                                prefill_chunk=16, paged=True, block_size=8,
                                token_budget=24)
        prompt = [5, 77, 3, 120]
        out = eng.put([1], [prompt])
        seq = list(prompt)
        for _ in range(3):
            tok = int(np.argmax(out[1]))
            seq.append(tok)
            out = eng.decode_step({1: tok})
        seq.append(int(np.argmax(out[1])))
        assert seq == _oracle_continuation(m, params, prompt, 4)

    def test_expert_utilization_during_decode(self, moe_setup):
        """Decode traffic actually routes to multiple experts (the gating is
        live, not collapsed to one expert by the eval path)."""
        m, params = moe_setup
        rng = np.random.default_rng(2)
        ids = jnp.asarray(rng.integers(0, 128, (1, 32), dtype=np.int32))
        x = m._embed(params, ids,
                     jnp.broadcast_to(jnp.arange(32, dtype=jnp.int32), (1, 32)),
                     jnp.float32)
        blk0 = jax.tree.map(lambda a: a[0], params["blocks"])
        logits = x.astype(jnp.float32) @ blk0["moe_wg"].astype(jnp.float32)
        top1 = np.asarray(jnp.argmax(logits[0], axis=-1))
        assert len(set(top1.tolist())) >= 2, "router collapsed to one expert"
