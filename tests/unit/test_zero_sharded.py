"""ZeRO-2/3 sharded training (docs/ZERO.md).

The signature guarantee under test: stage-2/3 training — gradients
reduce-scattered, each replica optimizer-stepping a disjoint shard of the
fp32 master + Adam moments in the host tier, updated parameters
all-gathered back — produces the SAME loss curve and final parameters
bitwise as the unsharded stage-0 loop, on a real 8-device mesh.

Four layers:

- ``PartitionPlan``: balanced contiguous bounds, disjoint + covering
  (``check_shard_conservation`` planted-violation cases live in
  test_train_resilience.py next to the other sanitizer checks);
- bitwise parity: stage-2 and stage-3 vs the stage-0 baseline (all in the
  cpu-offload family — the stages share one compiled fwd/bwd program and
  one elementwise host Adam, so stage only changes who updates what);
- sharded checkpoints: ``optim_states.shard<r>.ckpt`` per rank under the
  manifest-last protocol, consolidation on load (into a sharded engine, a
  flat-offload engine, a device engine, and the universal layout), corrupt
  shard files falling back through the durable-tag ring;
- stage-3 residency: with the ``stage3_*`` window knobs tightened, params
  are actually released/prefetched between steps — and training is STILL
  bitwise, because residency only moves bytes, never changes programs.

Runs under ``DSTPU_SANITIZE=1`` (conftest): partition build, sharded save,
and consolidation all run ``check_shard_conservation`` in anger here.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.comm import topology as topo_mod
from deepspeed_tpu.models import TransformerLM, gpt2_config
from deepspeed_tpu.resilience import CheckpointCorruptError
from deepspeed_tpu.runtime.zero.partition import PartitionPlan

MB_TOTAL, SEQ, STEPS = 8, 32, 4

#: compiled programs shared between compared engines — XLA determinism is
#: per compiled program (test_train_resilience.py PIN discipline)
PIN = ("_fwd_bwd", "_train_loss", "_acc", "_step_fn", "_fused_step_fn",
       "_multi_step_fn")


def _model():
    return TransformerLM(gpt2_config(
        "125m", vocab_size=128, hidden_size=64, num_layers=2, num_heads=4,
        max_seq_len=SEQ))


def _mk_engine(stage, offload=True, bf16=False, extra_zero=None,
               pin_from=None):
    topo_mod.reset_topology()
    zero = {"stage": stage}
    if offload:
        zero["offload_optimizer"] = {"device": "cpu"}
    zero.update(extra_zero or {})
    cfg = {
        "train_batch_size": MB_TOTAL,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-3,
                                                  "weight_decay": 0.01}},
        "zero_optimization": zero,
        "gradient_clipping": 1.0,
        "steps_per_print": 0,
    }
    if bf16:
        cfg["bf16"] = {"enabled": True}
    engine, _, _, _ = deepspeed_tpu.initialize(model=_model(), config=cfg)
    if pin_from is not None:
        for name in PIN:
            if hasattr(pin_from, name):
                setattr(engine, name, getattr(pin_from, name))
    return engine


def _batch(k=0):
    rng = np.random.default_rng(1000 + k)
    return {"input_ids": jnp.asarray(
        rng.integers(0, 128, (MB_TOTAL, SEQ), dtype=np.int32))}


def _train(engine, n=STEPS, start=0):
    out = []
    for k in range(start, start + n):
        loss = engine(_batch(k))
        engine.backward(loss)
        engine.step()
        out.append(np.asarray(loss))
    return np.asarray(out)


def _final_params(engine):
    return [np.asarray(l) for l in jax.tree.leaves(engine.get_fp32_params())]


def _assert_params_equal(a, b):
    assert len(a) == len(b)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)


# ---------------------------------------------------------------------------
# PartitionPlan
# ---------------------------------------------------------------------------

class TestPartitionPlan:
    def test_bounds_partition_every_leaf(self):
        plan = PartitionPlan([np.zeros((3, 5)), np.zeros((7,)),
                              np.zeros(())], 4, sanitize=True)
        assert plan.num_shards == 4
        assert plan.leaf_sizes == [15, 7, 1]
        for j, size in enumerate(plan.leaf_sizes):
            bs = plan.bounds[j]
            assert bs[0] == 0 and bs[-1] == size
            assert all(bs[r] <= bs[r + 1] for r in range(4))
        # every element owned exactly once across ranks
        assert sum(plan.shard_sizes(r)[0] for r in range(4)) == 15

    def test_shards_balanced_within_one(self):
        plan = PartitionPlan([np.zeros((1001,))], 8)
        sizes = [plan.shard_sizes(r)[0] for r in range(8)]
        assert max(sizes) - min(sizes) <= 1
        assert sum(sizes) == 1001

    def test_small_leaf_leaves_late_ranks_empty(self):
        plan = PartitionPlan([np.zeros((3,))], 8)
        sizes = [plan.shard_sizes(r)[0] for r in range(8)]
        assert sum(sizes) == 3 and sizes.count(0) == 5

    def test_describe_round_trips_to_json(self):
        import json

        plan = PartitionPlan([np.zeros((4, 4)), np.zeros((9,))], 4)
        d = json.loads(json.dumps(plan.describe()))
        assert d["num_shards"] == 4
        assert d["leaf_sizes"] == [16, 9]
        assert d["bounds"][0][-1] == 16

    def test_shard_bytes(self):
        plan = PartitionPlan([np.zeros((16,))], 4)
        assert plan.shard_bytes(0) == 4 * 4  # 4 fp32 elements


# ---------------------------------------------------------------------------
# bitwise parity across stages (all in the cpu-offload family)
# ---------------------------------------------------------------------------

class TestBitwiseParity:
    def test_stage2_and_stage3_match_stage0_bitwise(self):
        e0 = _mk_engine(0)
        assert e0._zero_tier is None
        l0 = _train(e0)
        p0 = _final_params(e0)

        e2 = _mk_engine(2, pin_from=e0)
        assert e2._zero_tier is not None
        assert e2._zero_tier.plan.num_shards == \
            e2.topology.data_parallel_size == 8
        l2 = _train(e2)
        np.testing.assert_array_equal(l0, l2)
        _assert_params_equal(p0, _final_params(e2))

        e3 = _mk_engine(3, pin_from=e0)
        assert e3._zero_tier is not None and e3._z3_residency
        l3 = _train(e3)
        np.testing.assert_array_equal(l0, l3)
        _assert_params_equal(p0, _final_params(e3))

    @pytest.mark.parametrize("arm", ["stage1", "overlap_off",
                                     "overlap_on_nvme", "overlap_off_nvme"])
    def test_offload_arm_matches_stage0_bitwise(self, arm, tmp_path):
        """The arms of the cpu-offload family the sweep above leaves out:
        stage 1, and stage 2 with the TransferEngine's overlap off (the
        synchronous twin) and with the Adam moments on the NVMe tier — each
        against stage 0, loss curve and final params bitwise."""
        e0 = _mk_engine(0)
        l0, p0 = _train(e0), _final_params(e0)
        if arm == "stage1":
            e = _mk_engine(1, pin_from=e0)
        else:
            off = {"device": "cpu"}
            if arm.endswith("nvme"):
                off["nvme_path"] = str(tmp_path)
            e = _mk_engine(2, pin_from=e0, extra_zero={
                "offload_optimizer": off,
                "transfer_overlap": not arm.startswith("overlap_off")})
            assert e._zero_tier is not None
        np.testing.assert_array_equal(l0, _train(e))
        _assert_params_equal(p0, _final_params(e))
        if arm.endswith("nvme"):
            c = e._transfer.nvme.counters
            assert c["saves"] >= 1 and c["loads"] >= 1, c

    def test_bf16_stage2_matches_bf16_stage0_bitwise(self):
        e0 = _mk_engine(0, bf16=True)
        e2 = _mk_engine(2, bf16=True, pin_from=e0)
        np.testing.assert_array_equal(_train(e0), _train(e2))
        _assert_params_equal(_final_params(e0), _final_params(e2))

    def test_ratio_below_one_falls_back_to_flat_offload(self):
        # partial offload can't shard the host tier (some leaves are
        # device-stepped): declarative GSPMD sharding takes over instead
        eng = _mk_engine(2, extra_zero={
            "offload_optimizer": {"device": "cpu", "ratio": 0.5}})
        assert eng._zero_tier is None
        assert eng._offload_mgr is not None
        assert eng._offload_mgr["dev_idx"]  # genuinely a twin-flow split
        # must agree with the all-device stage-2 path (same declarative
        # sharding, different update placement)
        ref = _train(_mk_engine(2, offload=False))
        np.testing.assert_allclose(_train(eng), ref, rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# observability
# ---------------------------------------------------------------------------

class TestZeroMetrics:
    def test_counters_advance_with_traffic(self):
        eng = _mk_engine(2)
        assert eng.zero_metrics()["reduce_scatters"] == 0
        _train(eng, 2)
        m = eng.zero_metrics()
        n_leaves = len(eng._zero_tier.master)
        assert m["reduce_scatters"] == 2 * n_leaves
        assert m["gathers"] == 2 * n_leaves  # every update gathered back
        assert m["offload_bytes_in"] > 0 and m["offload_bytes_out"] > 0
        assert m["shard_bytes"] == eng._zero_tier.shard_bytes(0)

    def test_untierd_engine_reports_empty(self):
        assert _mk_engine(0, offload=False).zero_metrics() == {}

    def test_telemetry_emits_train_zero_events(self):
        eng = _mk_engine(2)
        _train(eng, 1)
        captured = []

        class _Mon:
            enabled = True

            def write_events(self, events):
                captured.extend(events)

        eng.monitor = _Mon()
        eng._step_telemetry(None, force=True)
        names = {e[0] for e in captured}
        assert "Train/ZeRO/reduce_scatters" in names
        assert "Train/ZeRO/shard_bytes" in names

    def test_supervisor_report_carries_zero_metrics(self):
        from deepspeed_tpu.resilience import TrainingSupervisor

        eng = _mk_engine(2)
        sup = TrainingSupervisor(eng, lambda k: iter([_batch(k)]),
                                 "/tmp/unused", sleep=lambda s: None)
        sup.run(2)
        rep = sup.report()
        assert rep["zero"]["reduce_scatters"] > 0


# ---------------------------------------------------------------------------
# sharded checkpoints: per-shard files, consolidation, elastic reload
# ---------------------------------------------------------------------------

class TestShardedCheckpoint:
    def _save(self, tmp_path, stage=2):
        eng = _mk_engine(stage)
        _train(eng, 2)
        d = str(tmp_path)
        eng.save_checkpoint(d, tag="t0")
        return eng, d

    def test_save_writes_one_shard_file_per_rank(self, tmp_path):
        eng, d = self._save(tmp_path)
        names = sorted(os.listdir(os.path.join(d, "t0")))
        shards = [n for n in names if n.startswith("optim_states.shard")
                  and n.endswith(".ckpt")]
        assert len(shards) == 8
        # each shard file rides the manifest-last durability protocol
        for s in shards:
            assert f"{s}.manifest.json" in names
        # and the meta file still exists for the consolidator
        assert "optim_states.ckpt" in names
        assert "model_states.ckpt" in names  # layout unchanged at any stage

    def test_resume_into_sharded_engine_is_bitwise(self, tmp_path):
        eng, d = self._save(tmp_path)
        ref = _train(eng, 2, start=2)
        res = _mk_engine(2, pin_from=eng)
        res.load_checkpoint(d, tag="t0")
        assert res._zero_tier.step_count == 2  # Adam t at save time
        np.testing.assert_array_equal(ref, _train(res, 2, start=2))
        _assert_params_equal(_final_params(eng), _final_params(res))

    def test_elastic_load_into_flat_offload_engine_is_bitwise(self, tmp_path):
        eng, d = self._save(tmp_path)
        ref = _train(eng, 2, start=2)
        res = _mk_engine(0, pin_from=eng)  # stage-0 flat offload
        res.load_checkpoint(d, tag="t0")
        np.testing.assert_array_equal(ref, _train(res, 2, start=2))

    def test_elastic_load_into_device_engine(self, tmp_path):
        # consolidated moments land in the jitted device Adam: same math,
        # different (compiled) arithmetic order — close, not bitwise
        eng, d = self._save(tmp_path)
        ref = _train(eng, 2, start=2)
        res = _mk_engine(0, offload=False)
        res.load_checkpoint(d, tag="t0")
        np.testing.assert_allclose(_train(res, 2, start=2), ref,
                                   rtol=1e-4, atol=1e-4)

    def test_stage3_sharded_resume_is_bitwise(self, tmp_path):
        eng, d = self._save(tmp_path, stage=3)
        ref = _train(eng, 2, start=2)
        res = _mk_engine(3, pin_from=eng)
        res.load_checkpoint(d, tag="t0")
        np.testing.assert_array_equal(ref, _train(res, 2, start=2))

    def test_device_stage2_saves_sharded_and_restores(self, tmp_path):
        # no offload: moments live on device, but the checkpoint is still
        # written per-shard (the at-rest layout is stage-owned, not
        # tier-owned)
        eng = _mk_engine(2, offload=False)
        assert eng._zero_tier is None
        _train(eng, 2)
        d = str(tmp_path)
        eng.save_checkpoint(d, tag="t0")
        names = os.listdir(os.path.join(d, "t0"))
        assert any(n.startswith("optim_states.shard") for n in names)
        ref = _train(eng, 2, start=2)
        res = _mk_engine(2, offload=False, pin_from=eng)
        res.load_checkpoint(d, tag="t0")
        np.testing.assert_allclose(_train(res, 2, start=2), ref,
                                   rtol=1e-5, atol=1e-5)

    def test_corrupt_shard_explicit_tag_raises(self, tmp_path):
        eng, d = self._save(tmp_path)
        path = os.path.join(d, "t0", "optim_states.shard03.ckpt")
        with open(path, "r+b") as f:
            f.truncate(os.path.getsize(path) // 2)
        res = _mk_engine(2)
        with pytest.raises(CheckpointCorruptError):
            res.load_checkpoint(d, tag="t0")

    def test_corrupt_shard_falls_back_through_ring(self, tmp_path):
        d = str(tmp_path)
        eng = _mk_engine(2)
        _train(eng, 1)
        eng.save_checkpoint(d)  # global_step1
        _train(eng, 1, start=1)
        eng.save_checkpoint(d)  # global_step2
        path = os.path.join(d, "global_step2", "optim_states.shard00.ckpt")
        os.remove(path)  # a rank's shard vanished after the newest save
        res = _mk_engine(2)
        res.load_checkpoint(d)
        assert res.global_steps == 1  # newest fully-verifiable tag won
        assert res.ckpt_corrupt_fallbacks == 1

    def test_universal_conversion_consolidates_shards(self, tmp_path):
        from deepspeed_tpu.checkpoint.universal import (
            ds_to_universal, load_universal_into_engine)

        eng, d = self._save(tmp_path / "ckpt")
        ref = _train(eng, 2, start=2)
        udir = str(tmp_path / "universal")
        ds_to_universal(d, udir, tag="t0")
        # per-parameter moment files exist (the consolidator ran)
        zdir = os.path.join(udir, "zero")
        pdirs = os.listdir(zdir)
        assert pdirs
        assert all(os.path.exists(os.path.join(zdir, p, "exp_avg.npy"))
                   for p in pdirs)
        res = _mk_engine(2, pin_from=eng)
        load_universal_into_engine(res, udir)
        np.testing.assert_array_equal(ref, _train(res, 2, start=2))


# ---------------------------------------------------------------------------
# stage-3 parameter residency
# ---------------------------------------------------------------------------

class TestStage3Residency:
    KNOBS = {"stage3_max_live_parameters": 1,
             "stage3_param_persistence_threshold": 64,
             "stage3_prefetch_bucket_size": 1 << 16}

    def test_release_and_prefetch_fire_and_stay_bitwise(self):
        e0 = _mk_engine(0)
        l0 = _train(e0)
        eng = _mk_engine(3, extra_zero=dict(self.KNOBS), pin_from=e0)
        losses = _train(eng)
        np.testing.assert_array_equal(l0, losses)
        _assert_params_equal(_final_params(e0), _final_params(eng))
        m = eng.zero_metrics()
        # residency traffic happened: re-gathers beyond the per-step update
        # gather, and at least one prefetched leaf was consumed by forward
        assert m["gathers"] > m["reduce_scatters"]
        assert m["prefetch_hits"] > 0

    def test_params_actually_leave_device_between_steps(self):
        eng = _mk_engine(3, extra_zero=dict(self.KNOBS))
        _train(eng, 1)
        released = eng._z3_released
        assert released  # big leaves were dropped from HBM after the step
        leaves = jax.tree.leaves(eng.params)
        assert any(leaves[j].is_deleted() for j in released
                   if j not in eng._z3_prefetched)
        # forward() re-gathers everything it needs — next step still works
        _train(eng, 1, start=1)

    def test_default_window_keeps_params_resident(self):
        eng = _mk_engine(3)  # default knobs: max_live = 1e9 params
        _train(eng, 2)
        assert not eng._z3_released
        assert all(not l.is_deleted() for l in jax.tree.leaves(eng.params))
