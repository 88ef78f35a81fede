"""Negative-path coverage (VERDICT r4 next #9): each test kills a real
failure mode — fp16 overflow under the qgZ quantized-gradient path, elastic
resume across a changed hpZ axis, paged-KV block churn at pool capacity, and
a launcher rendezvous that must time out loudly instead of hanging."""

import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.comm import topology as topo_mod
from tests.unit.simple_model import make_simple_model

HIDDEN = 16
REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


class TestOverflowUnderQgZ:
    def test_fp16_overflow_skips_step_and_shrinks_scale(self):
        """The qgZ shard_map fwd/bwd path (quantized two-hop gradient
        reduce) must still honor dynamic loss scaling: an overflowed micro
        step skips the update and halves the scale, bit-identical params."""
        topo_mod.reset_topology()
        engine, *_ = deepspeed_tpu.initialize(
            model=make_simple_model(HIDDEN), config={
                "train_batch_size": 8,
                "optimizer": {"type": "adamw", "params": {"lr": 1e-2}},
                "zero_optimization": {"stage": 3,
                                      "zero_quantized_gradients": True,
                                      "stage3_param_persistence_threshold": 0},
                "fp16": {"enabled": True, "initial_scale_power": 4,
                         "hysteresis": 1},
                "mesh": {"data": 8},
            })
        assert engine._qgz_active()
        params_before = np.asarray(jax.device_get(
            jax.tree.leaves(engine.params)[0]))
        x = jnp.full((8, HIDDEN), 1e30, jnp.float32)
        y = jnp.zeros((8, HIDDEN), jnp.float32)
        loss = engine((x, y))
        engine.backward(loss)
        engine.step()
        assert engine.skipped_steps == 1
        assert engine.loss_scale() == 2 ** 3  # halved
        params_after = np.asarray(jax.device_get(
            jax.tree.leaves(engine.params)[0]))
        np.testing.assert_array_equal(params_before, params_after)
        # and a CLEAN batch afterwards still trains (the skip did not poison
        # optimizer state or the compiled program)
        rng = np.random.default_rng(0)
        xc = jnp.asarray(rng.standard_normal((8, HIDDEN)), jnp.float32)
        loss2 = engine((xc, jnp.zeros((8, HIDDEN), jnp.float32)))
        engine.backward(loss2)
        engine.step()
        assert engine.skipped_steps == 1  # no new skip
        assert np.isfinite(float(loss2))


class TestElasticHpzChange:
    def test_universal_reload_across_hpz_axis(self, tmp_path):
        """Elastic restart where the secondary (hpZ) partition axis changes:
        dp4 x hpz2 -> dp8 (hpz retired). The universal checkpoint must land
        the exact fp32 state and the loss must continue (reference universal
        checkpoint + zero_hpz_partition_size interplay)."""
        topo_mod.reset_topology()
        cfg = {"train_batch_size": 8,
               "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
               "zero_optimization": {"stage": 3,
                                     "zero_hpz_partition_size": 2,
                                     "stage3_param_persistence_threshold": 0},
               "bf16": {"enabled": True},
               "mesh": {"data": 4, "hpz": 2}}
        engine, *_ = deepspeed_tpu.initialize(model=make_simple_model(HIDDEN),
                                              config=cfg)
        rng = np.random.default_rng(1)
        b = (jnp.asarray(rng.standard_normal((8, HIDDEN)), jnp.float32),
             jnp.asarray(rng.standard_normal((8, HIDDEN)), jnp.float32))
        for _ in range(3):
            loss = engine(b)
            engine.backward(loss)
            engine.step()
        ck, uni = tmp_path / "ck", tmp_path / "uni"
        engine.save_checkpoint(str(ck), tag="t")
        from deepspeed_tpu.checkpoint import ds_to_universal

        ds_to_universal(str(ck), str(uni), tag="t")
        ref = np.asarray(jax.tree.leaves(engine.get_fp32_params())[0])
        ref_steps = engine.global_steps

        topo_mod.reset_topology()
        cfg2 = {"train_batch_size": 8,
                "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
                "zero_optimization": {"stage": 3,
                                      "stage3_param_persistence_threshold": 0},
                "bf16": {"enabled": True},
                "checkpoint": {"load_universal": True},
                "mesh": {"data": 8}}
        engine2, *_ = deepspeed_tpu.initialize(model=make_simple_model(HIDDEN),
                                               config=cfg2)
        engine2.load_checkpoint(str(uni))
        after = np.asarray(jax.tree.leaves(engine2.get_fp32_params())[0])
        np.testing.assert_allclose(ref, after, atol=1e-6)
        assert engine2.global_steps == ref_steps
        loss2 = engine2(b)
        engine2.backward(loss2)
        engine2.step()
        assert np.isfinite(float(loss2))


class TestPagedKVChurn:
    def test_block_pool_recycles_under_sustained_churn(self):
        """Serve more sequence-lifetimes than the pool could ever hold at
        once: every flush's blocks must recycle, decode must stay exact vs
        the dense oracle after heavy reuse, and the pool must drain back to
        its initial free count (reference BlockedKVCache lifecycle)."""
        from deepspeed_tpu.inference.v2 import InferenceEngineV2
        from deepspeed_tpu.models import build_model

        topo_mod.reset_topology()
        m = build_model("llama-tiny", vocab_size=128, hidden_size=32,
                        num_layers=2, num_heads=2, num_kv_heads=2,
                        intermediate_size=64, max_seq_len=64)
        params = m.init_params(jax.random.PRNGKey(0))
        eng = InferenceEngineV2(m, params, max_seqs=2, max_seq_len=32,
                                prefill_chunk=16, paged=True, block_size=8,
                                num_blocks=9, token_budget=20)
        free0 = eng.block_mgr.free_blocks
        rng = np.random.default_rng(2)
        for round_i in range(10):  # 10 lifetimes >> 8 usable blocks
            uid = 100 + round_i
            prompt = rng.integers(0, 128, (5 + (round_i % 7),)).tolist()
            out = eng.put([uid], [prompt])
            seq = list(prompt)
            for _ in range(2):
                t = int(np.argmax(out[uid]))
                seq.append(t)
                out = eng.decode_step({uid: t})
            seq.append(int(np.argmax(out[uid])))
            cur = jnp.asarray(np.array(prompt)[None], jnp.int32)
            for _ in range(3):
                nxt = int(jnp.argmax(m.logits(params, cur)[0, -1]))
                cur = jnp.concatenate(
                    [cur, jnp.asarray([[nxt]], jnp.int32)], axis=1)
            assert seq == list(np.asarray(cur[0])), f"round {round_i} diverged"
            eng.flush(uid)
            assert eng.block_mgr.free_blocks == free0, f"leak at round {round_i}"

    def test_exhaustion_then_flush_recovers(self):
        """After a loud pool-exhaustion failure, flushing a sequence must
        return the engine to a servable state (no stranded blocks)."""
        from deepspeed_tpu.inference.v2 import InferenceEngineV2
        from deepspeed_tpu.models import build_model

        topo_mod.reset_topology()
        m = build_model("llama-tiny", vocab_size=128, hidden_size=32,
                        num_layers=2, num_heads=2, num_kv_heads=2,
                        intermediate_size=64, max_seq_len=64)
        params = m.init_params(jax.random.PRNGKey(0))
        eng = InferenceEngineV2(m, params, max_seqs=4, max_seq_len=32,
                                prefill_chunk=16, paged=True, block_size=8,
                                num_blocks=5, token_budget=20)  # 4 usable
        eng.put([1], [list(range(16))])  # 2 blocks
        eng.put([2], [list(range(16, 30))])  # 2 blocks → pool full
        with pytest.raises(RuntimeError, match="exhausted"):
            eng.put([3], [list(range(30, 46))])
        # contract: the failed request stays PENDING (retried on the next
        # step); its partial block allocation is owned by the descriptor,
        # so flushing it returns every block — no leak
        eng.flush(3)
        eng.flush(1)
        assert eng.block_mgr.free_blocks == 2  # uid2 still holds 2 of 4
        out = eng.put([4], [[7, 8, 9]])  # recovered capacity serves again
        assert 4 in out and np.isfinite(np.asarray(out[4])).all()


WORKER_TIMEOUT = textwrap.dedent("""
    import os, sys, time
    sys.path.insert(0, {repo!r})
    os.environ["DSTPU_NUM_PROCESSES"] = "2"
    os.environ["DSTPU_PROCESS_ID"] = "1"  # non-coordinator: dials and waits
    os.environ["COORDINATOR_ADDRESS"] = "127.0.0.1:{port}"
    os.environ["JAX_PLATFORMS"] = "cpu"
    import deepspeed_tpu.comm as dist

    t0 = time.time()
    try:
        dist.init_distributed(timeout={timeout})
    except Exception as e:
        print(f"RENDEZVOUS_FAILED after {{time.time()-t0:.1f}}s: "
              f"{{type(e).__name__}}", flush=True)
        sys.exit(3)
    print("UNEXPECTED_SUCCESS", flush=True)
    sys.exit(0)
""")


class TestLauncherRendezvousTimeout:
    def test_missing_peer_fails_within_budget(self, tmp_path):
        """A worker whose peers never arrive must FAIL with a clear error
        inside the configured timeout — not hang the job (reference
        tests/unit/common.py:180 hard-exit contract; the r4 postmortem is
        what silent hangs cost)."""
        import socket

        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        s.close()  # nothing listens here afterwards
        import time

        worker = tmp_path / "w.py"
        worker.write_text(WORKER_TIMEOUT.format(repo=REPO, port=port,
                                                timeout=15))
        env = dict(os.environ)
        t0 = time.monotonic()
        proc = subprocess.run([sys.executable, str(worker)], env=env,
                              timeout=120, capture_output=True, text=True)
        elapsed = time.monotonic() - t0
        # jax's distributed client hard-terminates the process on rendezvous
        # deadline (its own fail-fast contract) OR our wrapper catches it —
        # either way: nonzero exit, DEADLINE diagnostic, within budget
        assert proc.returncode != 0, "rendezvous unexpectedly succeeded"
        blob = proc.stdout + proc.stderr
        assert "DEADLINE_EXCEEDED" in blob or "RENDEZVOUS_FAILED" in blob, \
            blob[-800:]
        assert elapsed < 90, f"took {elapsed:.0f}s — timeout not honored"


class TestMemoryPreflight:
    def test_warns_when_static_state_exceeds_capacity(self, monkeypatch):
        """The init-time OOM guard: an over-capacity config warns with the
        estimate instead of leaving the user to a cryptic allocator abort."""
        from deepspeed_tpu.accelerator import get_accelerator
        from deepspeed_tpu.runtime import engine as engine_mod

        acc = get_accelerator()
        monkeypatch.setattr(type(acc), "total_memory",
                            lambda self, device_index=0: 10_000)  # tiny cap
        seen = []
        monkeypatch.setattr(engine_mod.logger, "warning",
                            lambda msg, *a, **k: seen.append(str(msg)))
        topo_mod.reset_topology()
        deepspeed_tpu.initialize(model=make_simple_model(64), config={
            "train_batch_size": 8,
            "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
            "zero_optimization": {"stage": 0},
            "steps_per_print": 0,
            "mesh": {"data": 8},
        })
        assert any("memory preflight" in m for m in seen), seen

    def test_silent_when_capacity_sufficient(self, monkeypatch):
        from deepspeed_tpu.runtime import engine as engine_mod

        seen = []
        monkeypatch.setattr(engine_mod.logger, "warning",
                            lambda msg, *a, **k: seen.append(str(msg)))
        topo_mod.reset_topology()
        deepspeed_tpu.initialize(model=make_simple_model(16), config={
            "train_batch_size": 8,
            "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
            "zero_optimization": {"stage": 1},
            "steps_per_print": 0,
            "mesh": {"data": 8},
        })
        assert not any("memory preflight" in m for m in seen), seen
