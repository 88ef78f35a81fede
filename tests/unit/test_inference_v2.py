"""Inference v2 continuous-batching tests (reference
``tests/unit/inference/v2/``: ragged batching, KV management, scheduling)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.comm import topology as topo_mod
from deepspeed_tpu.inference.v2 import DSStateManager, InferenceEngineV2
from deepspeed_tpu.models import build_model


@pytest.fixture(scope="module")
def setup():
    topo_mod.reset_topology()
    m = build_model("llama-tiny", vocab_size=128, hidden_size=64, num_layers=2,
                    num_heads=4, num_kv_heads=2, intermediate_size=128, max_seq_len=128)
    params = m.init_params(jax.random.PRNGKey(0))
    return m, params


def dense_logits_of(m, params, width):
    """``f(tokens)``: the full forward's logits behind ``tokens``. The model
    is causal (and an expert layer that drops no token routes each alone), so
    the padding behind the last token moves nothing at it, and every length
    runs the one jitted program of ``width`` tokens (eagerly each length
    compiled every operation anew: most of this file's time)."""
    logits = jax.jit(m.logits)

    def at_the_end(tokens):
        ids = np.zeros((1, width), np.int32)
        ids[0, :len(tokens)] = tokens
        return logits(params, jnp.asarray(ids))[0, len(tokens) - 1]

    return at_the_end


@pytest.fixture(scope="module")
def dense_logits(setup):
    return dense_logits_of(*setup, width=64)    # the engines' max_seq_len


class TestStateManager:
    def test_slot_lifecycle(self):
        sm = DSStateManager(max_seqs=2, max_seq_len=32)
        a = sm.get_or_create_sequence(10)
        b = sm.get_or_create_sequence(11)
        assert {a.slot, b.slot} == {0, 1}
        assert not sm.can_allocate()
        with pytest.raises(RuntimeError):
            sm.get_or_create_sequence(12)
        sm.flush_sequence(10)
        c = sm.get_or_create_sequence(13)
        assert c.slot == a.slot  # slot reused


class TestContinuousBatching:
    def test_default_engine_is_the_pool(self, setup):
        m, params = setup
        eng = InferenceEngineV2(m, params)
        assert eng.max_seqs == 32
        assert eng.block_mgr.block_size == 64
        # one context's worth of blocks a sequence, plus the trash block
        assert eng.block_mgr.num_blocks == 1 + 32 * 2

    def test_slot_mode_is_refused(self, setup):
        m, params = setup
        with pytest.raises(ValueError, match="paged=False"):
            InferenceEngineV2(m, params, paged=False)

    def test_staggered_requests_match_oracle(self, setup, dense_logits):
        m, params = setup
        eng = InferenceEngineV2(m, params, max_seqs=4, max_seq_len=64, prefill_chunk=16)
        rng = np.random.default_rng(0)
        prompts = {1: rng.integers(0, 128, (5,)).tolist(),
                   2: rng.integers(0, 128, (23,)).tolist()}  # 23 > chunk → split-fuse
        out = eng.put([1, 2], [prompts[1], prompts[2]])
        assert set(out) == {1, 2}
        seqs = {u: list(p) for u, p in prompts.items()}
        for step in range(6):
            toks = {u: int(np.argmax(out[u])) for u in out}
            for u, t in toks.items():
                seqs[u].append(t)
            if step == 2:  # uid 3 joins mid-stream
                prompts[3] = rng.integers(0, 128, (9,)).tolist()
                seqs[3] = list(prompts[3])
                out3 = eng.put([3], [prompts[3]])
                seqs[3].append(int(np.argmax(out3[3])))
                toks[3] = seqs[3][-1]
                out.update(out3)
            out = eng.decode_step(toks)
        for u in (1, 2, 3):
            cur = list(prompts[u])
            for _ in range(len(seqs[u]) - len(prompts[u])):
                cur.append(int(jnp.argmax(dense_logits(cur))))
            assert cur == seqs[u]

    def test_flush_frees_capacity(self, setup):
        m, params = setup
        eng = InferenceEngineV2(m, params, max_seqs=2, max_seq_len=32,
                                block_size=16)
        eng.put([1, 2], [[3, 4, 5], [6, 7]])
        assert not eng.can_schedule(1)
        eng.flush(1)
        assert eng.can_schedule(1)
        free, cap = eng.query()
        # uid 2 still holds one 16-token block of the pool's four
        assert free == 1 and cap == 32
        assert eng.block_mgr.free_blocks == 3
        eng.flush(2)
        assert eng.query() == (2, 32) and eng.block_mgr.free_blocks == 4

    def test_context_overflow_raises(self, setup):
        m, params = setup
        eng = InferenceEngineV2(m, params, max_seqs=1, max_seq_len=16, prefill_chunk=16)
        with pytest.raises(RuntimeError):
            eng.put([1], [list(range(40))])


class TestPagedKV:
    def test_block_allocator_lifecycle(self):
        from deepspeed_tpu.inference.v2.ragged_manager import (BlockedKVCache,
                                                               SequenceDescriptor)

        mgr = BlockedKVCache(num_blocks=9, block_size=16, max_blocks_per_seq=4)
        assert mgr.free_blocks == 8  # block 0 reserved
        d = SequenceDescriptor(uid=1, slot=0)
        mgr.ensure(d, 17)  # 2 blocks
        assert len(d.blocks) == 2 and 0 not in d.blocks
        row = mgr.table_row(d)
        assert row.shape == (4,) and list(row[:2]) == d.blocks
        mgr.ensure(d, 30)  # still 2 blocks
        assert len(d.blocks) == 2
        mgr.free(d)
        assert mgr.free_blocks == 8 and d.blocks == []
        with pytest.raises(RuntimeError, match="max"):
            mgr.ensure(SequenceDescriptor(uid=2, slot=1), 16 * 5)
        big = SequenceDescriptor(uid=3, slot=2)
        with pytest.raises(RuntimeError, match="exhausted"):
            for _ in range(3):  # 3*4 blocks > 8 free
                s = SequenceDescriptor(uid=3, slot=2)
                mgr.ensure(s, 64)

    def test_paged_matches_full_forward(self, setup, dense_logits):
        """A staggered prefill+decode workload through the pool produces the
        full forward's logits at every step (paged gather/scatter is
        exact)."""
        m, params = setup
        rng = np.random.default_rng(1)
        seqs = {1: rng.integers(0, 128, (5,)).tolist(),
                2: rng.integers(0, 128, (23,)).tolist()}
        eng = InferenceEngineV2(m, params, max_seqs=4, max_seq_len=64,
                                prefill_chunk=16, block_size=16)
        out = eng.put([1, 2], [seqs[1], seqs[2]])
        for _ in range(6):
            assert set(out) == {1, 2}
            for u in out:
                np.testing.assert_allclose(
                    out[u], np.asarray(dense_logits(seqs[u])), atol=2e-4)
            toks = {u: int(np.argmax(out[u])) for u in out}
            for u, t in toks.items():
                seqs[u].append(t)
            out = eng.decode_step(toks)

    def test_paged_block_reuse_after_flush(self, setup):
        m, params = setup
        eng = InferenceEngineV2(m, params, max_seqs=2, max_seq_len=64,
                                prefill_chunk=16, paged=True, block_size=16,
                                num_blocks=6)  # 5 usable blocks
        rng = np.random.default_rng(2)
        eng.put([1], [rng.integers(0, 128, (40,)).tolist()])  # 3 blocks
        assert eng.block_mgr.free_blocks == 2
        eng.flush(1)
        assert eng.block_mgr.free_blocks == 5
        out = eng.put([2], [rng.integers(0, 128, (60,)).tolist()])  # 4 blocks, fits
        assert 2 in out

    def test_paged_pool_exhaustion_is_loud(self, setup):
        m, params = setup
        eng = InferenceEngineV2(m, params, max_seqs=2, max_seq_len=64,
                                prefill_chunk=16, paged=True, block_size=16,
                                num_blocks=4)  # 3 usable
        rng = np.random.default_rng(3)
        eng.put([1], [rng.integers(0, 128, (40,)).tolist()])  # takes 3 blocks
        with pytest.raises(RuntimeError, match="exhausted"):
            eng.put([2], [rng.integers(0, 128, (20,)).tolist()])

    def test_exhaustion_leaves_state_consistent(self, setup):
        """Pool exhaustion must not corrupt in-flight sequences: after freeing
        room, the failed request retries cleanly and decoding seq 1 still
        matches an unconstrained engine."""
        m, params = setup
        rng = np.random.default_rng(4)
        p1 = rng.integers(0, 128, (20,)).tolist()
        p2 = rng.integers(0, 128, (20,)).tolist()
        eng = InferenceEngineV2(m, params, max_seqs=2, max_seq_len=64,
                                prefill_chunk=32, paged=True, block_size=16,
                                num_blocks=4)  # 3 usable: p1 takes 2
        out1 = eng.put([1], [p1])
        with pytest.raises(RuntimeError, match="exhausted"):
            eng.put([2], [p2])
        # seq 2's tokens are still pending (nothing consumed) and seq 1 intact
        assert eng.state.seqs[2].seen_tokens == 0
        assert eng.state.seqs[2].in_flight == len(p2)
        eng.flush(2)
        out = dict(out1)
        ref_eng = InferenceEngineV2(m, params, max_seqs=2, max_seq_len=64,
                                    prefill_chunk=32, paged=True, block_size=16)
        ref = ref_eng.put([1], [p1])
        for _ in range(3):
            tok = {1: int(np.argmax(out[1]))}
            rtok = {1: int(np.argmax(ref[1]))}
            assert tok == rtok
            out = eng.decode_step(tok)
            ref = ref_eng.decode_step(rtok)
            np.testing.assert_allclose(np.asarray(out[1]), np.asarray(ref[1]),
                                       atol=2e-4)

    def test_ragged_one_program_mixed_arrivals_and_decodes(self, setup,
                                                           dense_logits):
        """The FastGen core property: arrivals + decodes every step run through
        ONE compiled fixed-shape ragged program (no per-(n_seq, S) retraces),
        and the generated trajectories match the unbatched oracle."""
        m, params = setup
        eng = InferenceEngineV2(m, params, max_seqs=4, max_seq_len=64,
                                prefill_chunk=16, paged=True, block_size=16,
                                token_budget=16)
        rng = np.random.default_rng(5)
        prompts = {1: rng.integers(0, 128, (7,)).tolist(),
                   2: rng.integers(0, 128, (21,)).tolist()}  # 21 > budget-decodes
        out = eng.put([1, 2], [prompts[1], prompts[2]])
        seqs = {u: list(p) for u, p in prompts.items()}
        hist = {u: [np.asarray(v)] for u, v in out.items()}
        for step in range(5):
            toks = {u: int(np.argmax(out[u])) for u in out}
            for u, t in toks.items():
                seqs[u].append(t)
            uids, tok_lists = list(toks), [[toks[u]] for u in toks]
            if step == 1:  # uid 3 arrives in the SAME put as live decodes
                prompts[3] = rng.integers(0, 128, (11,)).tolist()
                seqs[3] = list(prompts[3])
                uids.append(3)
                tok_lists.append(prompts[3])
            out = eng.put(uids, tok_lists)
            for u, v in out.items():
                hist.setdefault(u, []).append(np.asarray(v))
        # at most two compiled traces of the ragged program despite varied
        # step compositions (the jit trace-cache, not a hand-kept counter):
        # the mixed-budget shape + the decode-round shape
        assert 1 <= eng.ragged_cache_size <= 2
        # every step's logits match a full unbatched recompute of the engine's
        # own token trajectory (argmax equality is too brittle: near-ties)
        for u in (1, 2, 3):
            n_prompt = len(prompts[u])
            for i, lg in enumerate(hist[u]):
                ref = np.asarray(dense_logits(seqs[u][: n_prompt + i]))
                np.testing.assert_allclose(lg, ref, atol=2e-4)

    def test_can_schedule_consults_block_pool(self, setup):
        m, params = setup
        eng = InferenceEngineV2(m, params, max_seqs=4, max_seq_len=64,
                                prefill_chunk=32, paged=True, block_size=16,
                                num_blocks=4)  # 3 usable = one 32-token chunk + 1
        assert eng.can_schedule(1)
        assert not eng.can_schedule(2)  # needs 2 chunks' worth of blocks
        _, cap = eng.query()
        assert cap == 3 * 16


def test_greedy_on_device_sampling():
    """greedy=True returns on-device argmax tokens identical to host-side
    argmax over the logits path."""
    from deepspeed_tpu.models import TransformerConfig, TransformerLM

    cfg = TransformerConfig(vocab_size=128, hidden_size=32, num_layers=2,
                            num_heads=2, intermediate_size=64, max_seq_len=64)
    m = TransformerLM(cfg)
    params = m.init_params(jax.random.PRNGKey(0))
    rng = np.random.default_rng(9)
    prompts = {1: rng.integers(0, 128, (9,)).tolist(),
               2: rng.integers(0, 128, (5,)).tolist()}
    e_lg, e_gr = (InferenceEngineV2(m, params, max_seqs=4, max_seq_len=64,
                                    prefill_chunk=16, block_size=16,
                                    token_budget=16) for _ in range(2))
    out_lg = e_lg.put([1, 2], [prompts[1], prompts[2]])
    out_gr = e_gr.put([1, 2], [prompts[1], prompts[2]], greedy=True)
    for step in range(3):
        toks = {u: int(np.argmax(v)) for u, v in out_lg.items()}
        assert all(np.ndim(v) == 0 for v in out_gr.values())
        toks_gr = {u: int(v) for u, v in out_gr.items()}
        assert toks == toks_gr, (step, toks, toks_gr)
        out_lg = e_lg.decode_step(toks)
        out_gr = e_gr.decode_step(toks, greedy=True)
