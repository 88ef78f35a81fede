"""Training engine.

Parity with reference ``runtime/engine.py`` (``DeepSpeedEngine:180``): the object
returned by ``initialize()`` with ``forward / backward / step`` semantics, config
plumbing, checkpoint save/load, and gradient-accumulation bookkeeping — re-designed
around a functional core:

- ``forward(batch)`` runs ONE fused jitted value-and-grad over the global (sharded)
  micro-batch and caches the gradients; it returns the loss, so the reference's
  imperative ``loss = engine(batch); engine.backward(loss); engine.step()`` sequence
  works unchanged but costs a single compiled program per micro-step (the autograd
  hook machinery of ``stage_1_and_2.py:887``/``stage3.py:1249`` has no analogue —
  XLA schedules the DP collectives chosen by the ZeRO sharding rules in
  ``zero/partition.py``).
- ``step()`` applies the jitted optimizer update at gradient-accumulation
  boundaries: unscale → overflow check → global-norm clip → update (skipped on
  overflow) → lp-param cast, with optimizer state sharded per ZeRO stage
  (reference call stack §3.2 of SURVEY.md).
- Mixed precision: bf16/fp16 compute params with fp32 master weights inside the
  engine (reference ``bf16_optimizer.py`` / ``fp16/fused_optimizer.py``), dynamic
  loss scaling from ``fp16/loss_scaler.py``.
"""

import collections
import os
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from .. import comm as dist
from ..analysis.program_audit import audited_jit
from ..comm.topology import MeshTopology
from ..resilience.errors import CheckpointCorruptError, EngineUsageError
from ..ops.optimizers import Optimizer, build_optimizer
from ..utils import tracing
from ..utils.logging import log_dist, logger
from ..utils.timer import (
    BACKWARD_GLOBAL_TIMER,
    BACKWARD_MICRO_TIMER,
    FORWARD_GLOBAL_TIMER,
    FORWARD_MICRO_TIMER,
    STEP_GLOBAL_TIMER,
    STEP_MICRO_TIMER,
    NoopTimer,
    SynchronizedWallClockTimer,
    ThroughputTimer,
)
from .checkpoint_engine.native_checkpoint_engine import NativeCheckpointEngine
from .config import DeepSpeedConfig
from .dataloader import DeepSpeedDataLoader, RepeatingLoader
from .fp16.loss_scaler import CreateLossScaler, LossScalerState, has_overflow
from .lr_schedules import build_lr_scheduler
from .zero.partition import (
    batch_spec,
    stage_grad_specs,
    stage_opt_specs,
    stage_param_specs,
    to_named,
)


def _gather_to_host(tree):
    """Materialize every jax.Array as a host numpy array, collectively gathering
    shards that are not fully addressable from this process (multi-host save).

    Device→host pulls go through ``chunked_device_get`` so checkpoint gathers
    never queue more than ~32 MB per flight (utils/transfer.py)."""
    from ..utils.transfer import chunked_device_get

    def to_np(x):
        if isinstance(x, jax.Array):
            if not x.is_fully_addressable:
                from jax.experimental import multihost_utils

                # tiled=True: reassemble the GLOBAL value from the per-process
                # shards (required for non-fully-addressable global arrays)
                return np.asarray(multihost_utils.process_allgather(x, tiled=True))
            return chunked_device_get(x)
        return x

    out = jax.tree.map(to_np, tree)
    from ..analysis.sanitizer import sanitize_enabled

    if sanitize_enabled():
        from ..analysis.sanitizer import check_gather_conservation

        check_gather_conservation(tree, out)
    return out


def _batch_key(batch):
    """What tells two compiled variants of a step program apart."""
    return tuple(getattr(x, "shape", None) for x in jax.tree.leaves(batch))


def _tree_select(pred, on_true, on_false):
    return jax.tree.map(lambda t, f: jnp.where(pred, t, f), on_true, on_false)


def _global_norm(grads):
    leaves = [jnp.sum(jnp.square(g.astype(jnp.float32))) for g in jax.tree.leaves(grads)]
    if not leaves:
        return jnp.asarray(0.0, jnp.float32)
    return jnp.sqrt(jnp.sum(jnp.stack(leaves)))


_WARNED_FORCE_THEN_BACKWARD = False


class LazyLoss:
    """Loss placeholder returned by a training-mode ``forward()``.

    Nothing is dispatched at forward time. The fused fwd+bwd program launches
    when ``backward()`` consumes this — the training fast path keeps exactly
    one program per micro-step, same as eager dispatch. Reading the value
    without ever calling ``backward()`` (``float(loss)``, any jnp op) instead
    launches a loss-only program, so a validation-style forward never pays a
    backward. This mirrors the reference's torch semantics, where ``forward``
    only builds the autograd graph and the backward cost lands in
    ``loss.backward()`` (reference runtime/engine.py forward/backward split).

    After ``backward()`` the forced value is the fused program's loss (no
    extra compute). Interops with python/numpy via ``float()``/``__array__``;
    for jnp ops use ``.value`` (jax 0.9 removed the ``__jax_array__``
    abstractification hook, so jnp cannot consume the wrapper directly).

    ``__eq__``/``__hash__`` are both VALUE-based (hash forces the device
    value) so the hash/eq contract holds for dict/set membership; every
    comparison or hash on the wrapper synchronizes with the device — code
    that wants the raw jnp scalar without wrapper semantics should read
    ``.value`` once and use that (see docs/MIGRATING.md).
    """

    __slots__ = ("_fused_fn", "_loss_fn", "_args", "_loss", "_forced_early")

    def __init__(self, fused_fn, loss_fn, args):
        self._fused_fn = fused_fn
        self._loss_fn = loss_fn
        self._args = args
        self._loss = None
        self._forced_early = False

    def _run_fused(self):
        """Launch the fused fwd+bwd (called by ``engine.backward`` once)."""
        global _WARNED_FORCE_THEN_BACKWARD
        if self._forced_early and not _WARNED_FORCE_THEN_BACKWARD:
            _WARNED_FORCE_THEN_BACKWARD = True
            logger.warning(
                "loss value was read BEFORE backward(): that read ran a "
                "loss-only forward, and backward() now recomputes the fused "
                "fwd+bwd — ~2x forward cost this micro-step. Read losses "
                "after backward() (or use engine.eval() for validation). "
                "[warned once]")
        with tracing.span("engine.enqueue", program="fwd_bwd") as sp:
            if sp.recording:
                tracing.note_program("engine.fwd_bwd", self._fused_fn,
                                     self._args, key=_batch_key(self._args[1]))
            loss, grads = self._fused_fn(*self._args)
        self._loss = loss
        self._args = None
        return loss, grads

    def _force(self):
        if self._loss is None:
            params, batch, _scale, step_idx = self._args
            self._forced_early = True
            self._loss = self._loss_fn(params, batch, step_idx)
        return self._loss

    # -- jax / python interop ------------------------------------------------
    @property
    def value(self):
        """The concrete replicated loss array (forces if still pending)."""
        return self._force()

    def __array__(self, dtype=None, copy=None):
        arr = np.asarray(self._force())
        return arr.astype(dtype) if dtype is not None else arr

    def __float__(self):
        return float(self._force())

    def __bool__(self):
        return bool(self._force())

    def item(self):
        return self._force().item()

    def block_until_ready(self):
        jax.block_until_ready(self._force())
        return self

    @property
    def dtype(self):
        return self._force().dtype

    @property
    def shape(self):
        return self._force().shape

    def astype(self, dtype):
        return self._force().astype(dtype)

    def __repr__(self):
        # never forces: repr must stay side-effect-free (debuggers, logging of
        # containers); str()/format() DO force and show the value
        if self._loss is None:
            return "LazyLoss(<pending>)"
        return f"LazyLoss({self._loss!r})"

    def __str__(self):
        return str(self._force())

    def __format__(self, spec):
        return format(self._force(), spec)

    def __add__(self, o):
        return self._force() + o

    __radd__ = __add__

    def __mul__(self, o):
        return self._force() * o

    __rmul__ = __mul__

    def __sub__(self, o):
        return self._force() - o

    def __rsub__(self, o):
        return o - self._force()

    def __truediv__(self, o):
        return self._force() / o

    def __rtruediv__(self, o):
        return o / self._force()

    def __lt__(self, o):
        return self._force() < o

    def __le__(self, o):
        return self._force() <= o

    def __gt__(self, o):
        return self._force() > o

    def __ge__(self, o):
        return self._force() >= o

    def __eq__(self, o):
        if o is self:
            return True
        return self._force() == o

    def __ne__(self, o):
        if o is self:
            return False
        return self._force() != o

    def __hash__(self):
        # value-based, matching __eq__ (hash/eq contract): two losses that
        # compare equal must hash equal for dict/set membership to behave.
        # Forces the device value — same cost class as any comparison on the
        # wrapper; use `.value` where a jnp array (no host sync) is wanted.
        return hash(float(self._force()))


class DeepSpeedEngine:
    def __init__(
        self,
        model,
        config: DeepSpeedConfig,
        optimizer: Optional[Optimizer] = None,
        lr_scheduler=None,
        training_data=None,
        collate_fn=None,
        topology: Optional[MeshTopology] = None,
        model_params=None,
        dont_change_device: bool = False,
    ):
        # the constructor's own account (docs/TRACING.md "Set-up and
        # recompiles"): one always-on `engine.init` record, five phases
        init = tracing.Phases("engine.init", engine="train")
        self.config = config
        self.module = model
        self.topology = topology or dist.get_topology()
        self.global_steps = 0
        self.global_samples = 0
        self.micro_steps = 0
        self.skipped_steps = 0
        # batches consumed from the engine-owned training iterator — persisted
        # so a resume continues at the same dataset position (bitwise resume)
        self._data_position = 0
        # durable-tag ring fallbacks taken because `latest` pointed at a
        # checkpoint that failed integrity verification (CheckpointCorruptError)
        self.ckpt_corrupt_fallbacks = 0
        self._cached = None  # (loss, grads) from the last forward
        if config.checkpoint_config.async_save:
            from .checkpoint_engine.async_checkpoint_engine import (
                AsyncCheckpointEngine,
            )

            self.checkpoint_engine = AsyncCheckpointEngine()
        else:
            self.checkpoint_engine = NativeCheckpointEngine()
        self.loaded_checkpoint_tag = None

        # ---- precision ----
        if config.fp16_enabled:
            self.compute_dtype = jnp.float16
        elif config.bfloat16_enabled or config.amp_enabled:
            self.compute_dtype = jnp.bfloat16
        else:
            self.compute_dtype = jnp.float32
        self._mixed = self.compute_dtype != jnp.float32

        # ---- monitor (reference engine.py:252 MonitorMaster) ----
        from ..monitor.monitor import MonitorMaster

        self.monitor = MonitorMaster(config.monitor_config)

        # ---- timers ----
        self.wall_clock_breakdown = config.wall_clock_breakdown
        self.timers = SynchronizedWallClockTimer() if self.wall_clock_breakdown else NoopTimer()
        self.tput_timer = ThroughputTimer(
            batch_size=config.train_batch_size,
            steps_per_output=config.steps_per_print or 50,
        )

        # ---- model params + apply fn ----
        self._rng = jax.random.PRNGKey(config.seed)
        # zero.Init path: initialize INSIDE jit with sharded outputs so large
        # models never materialize unsharded (reference zero.Init,
        # partition_parameters.py:783); shapes come from eval_shape
        sharded_init = (
            model_params is None and not isinstance(model, tuple)
            and not hasattr(model, "params") and hasattr(model, "init_params")
        )
        if sharded_init:
            init_rng = jax.random.PRNGKey(0)
            params = jax.eval_shape(model.init_params, init_rng)  # abstract
            apply_fn = model.apply
            tp_specs = getattr(model, "tp_specs", None)
        else:
            params, apply_fn, tp_specs = self._extract_model(model, model_params)
        self._apply_fn = self._under_mesh(apply_fn)
        self._tp_specs = tp_specs

        # ---- compression (QAT): schedule-keyed jit variants so the schedule
        # anneals rather than baking the trace-time state (compression/compress.py)
        self._compression = getattr(model, "_compression_scheduler", None)
        if self._compression is not None and hasattr(model, "_uncompressed_apply"):
            self._apply_fn = self._under_mesh(model._uncompressed_apply)
        if self._compression is not None and config.optimizer_name in (
                "onebitadam", "zerooneadam", "onebitlamb"):
            raise ValueError(
                "compression (QAT) and 1-bit optimizers cannot be combined: the "
                "compressed-gradient path bypasses the QAT forward"
            )

        # PLD needs BOTH the engine schedule and the model flag — catch the
        # half-configured case instead of silently training without drop
        pld_cfg = config.progressive_layer_drop
        if pld_cfg and pld_cfg.get("enabled"):
            mc = getattr(model, "config", None)
            if (mc is not None and hasattr(mc, "progressive_layer_drop")
                    and not mc.progressive_layer_drop):
                raise ValueError(
                    "progressive_layer_drop is enabled in the ds_config but the "
                    "model was built without TransformerConfig("
                    "progressive_layer_drop=True) — the injected theta would be "
                    "silently ignored"
                )

        # ---- random-LTD (reference data_pipeline/data_routing: middle layers
        # process a scheduled-size random token subset; the kept count is a
        # STATIC int, so each quantized schedule value gets its own jit variant
        # like the compression schedule) ----
        self._ltd_scheduler = None
        routing = (config.data_efficiency_config or {}).get("data_routing", {})
        ltd_cfg = routing.get("random_ltd", {})
        if routing.get("enabled") and ltd_cfg.get("enabled"):
            from .data_pipeline.data_routing import RandomLTDScheduler

            mc = getattr(model, "config", None)
            if (mc is not None and hasattr(mc, "random_ltd")
                    and not mc.random_ltd):
                raise ValueError(
                    "random_ltd is enabled in the ds_config but the model was "
                    "built without TransformerConfig(random_ltd=True) — the "
                    "injected ltd_keep would be silently ignored"
                )
            pld_cfg_ = config.progressive_layer_drop
            if pld_cfg_ and pld_cfg_.get("enabled"):
                raise ValueError(
                    "random_ltd and progressive_layer_drop cannot be combined: "
                    "the LTD trunk has no stochastic-depth path, so PLD would "
                    "be silently ignored"
                )
            if config.optimizer_name in ("onebitadam", "zerooneadam", "onebitlamb") \
                    or config.zero_config.zero_quantized_gradients:
                raise ValueError(
                    "random_ltd uses schedule-keyed jit variants of the standard "
                    "fwd/bwd; the 1-bit / zero_quantized_gradients shard_map "
                    "paths bypass them, so LTD would be silently ignored"
                )
            sched = ltd_cfg.get("random_ltd_schedule", {})
            sc = sched.get("schedule_config", {})
            seq_len = int(sched.get("max_value")
                          or getattr(mc, "max_seq_len", 0) or 0)
            if seq_len <= 0:
                raise ValueError("random_ltd needs random_ltd_schedule."
                                 "max_value or a model config max_seq_len")
            self._ltd_scheduler = RandomLTDScheduler(
                total_layers=int(ltd_cfg.get("total_layer_num")
                                 or getattr(mc, "num_layers", 0) or 0),
                start_length=int(sched.get("min_value", 128)),
                seq_length=seq_len,
                schedule_steps=int(sc.get("require_steps", 1000)),
                increment=int(sc.get("seq_per_step", 16)),
            )

        # ---- legacy curriculum learning (reference engine.py:1824-1837 +
        # top-level `curriculum_learning` block): seqlen-difficulty truncation
        # of each training batch. The difficulty is a host int quantized by
        # difficulty_step, so each schedule phase is one static shape → one
        # jit variant (the LTD pattern), not a per-step retrace ----
        self._curriculum = None
        from .constants import CURRICULUM_LEARNING_LEGACY

        cl = config._param_dict.get(CURRICULUM_LEARNING_LEGACY, {}) or {}
        if cl.get("enabled"):
            from .data_pipeline.curriculum_scheduler import CurriculumScheduler

            ctype = cl.get("curriculum_type", "seqlen")
            if ctype != "seqlen":
                raise ValueError(
                    f"legacy curriculum_learning supports curriculum_type "
                    f"'seqlen' (got {ctype!r}); metric-based curricula use "
                    "data_efficiency.data_sampling (DeepSpeedDataSampler)")
            self._curriculum = CurriculumScheduler(cl)

        init.mark("model")
        # ---- sharding rules per ZeRO stage ----
        stage = config.zero_config.stage
        self.zero_stage = stage
        topo = self.topology
        off = config.zero_config.offload_optimizer
        self._offload_enabled = bool(
            off is not None and off.device in ("cpu", "nvme")
        )
        # Cross-replica weight-update sharding (docs/ZERO.md): at stage >= 2
        # with the FULL optimizer state host-resident (cpu offload, ratio 1),
        # gradient/optimizer partitioning moves to the host tier's per-rank
        # update loop (ZeroShardedTier) — params and grads keep stage-0 specs
        # so the compiled fwd/bwd program is identical to the unsharded loop,
        # which is what makes stage-2/3 bitwise-comparable to stage 0. Partial
        # (ratio < 1) or NVMe offload at stage >= 2 falls back to the flat
        # offload path with the declarative GSPMD specs.
        self._zero_sharded_planned = bool(
            stage >= 2 and off is not None and off.device == "cpu"
            and off.ratio == 1.0
        )
        spec_stage = 0 if self._zero_sharded_planned else stage
        self._param_specs = stage_param_specs(
            params, spec_stage, topo, tp_specs,
            persistence_threshold=config.zero_config.param_persistence_threshold if spec_stage >= 3 else 0,
        )
        self._grad_specs = stage_grad_specs(params, spec_stage, topo, tp_specs)
        self._opt_specs = stage_opt_specs(params, spec_stage, topo, tp_specs)
        self._param_shardings = to_named(self._param_specs, topo)
        self._grad_shardings = to_named(self._grad_specs, topo)
        self._opt_shardings = to_named(self._opt_specs, topo)
        self._batch_sharding = NamedSharding(topo.mesh, batch_spec(topo))
        self._replicated = NamedSharding(topo.mesh, PartitionSpec())

        # place lp params (compute dtype) and fp32 master
        if sharded_init:
            from ..zero import sharded_dual_init

            want_master = self._mixed or self._offload_enabled
            self.params, master = sharded_dual_init(
                model, init_rng, self.compute_dtype, self._param_shardings,
                self._opt_shardings if want_master else None,
            )
            if self._mixed and not self._offload_enabled:
                self.master_params = master
            else:
                self.master_params = None
            if self._offload_enabled:
                # offload manager needs concrete fp32 leaves on host — taken
                # from the TRUE fp32 init, not a bf16 round trip
                src = master if master is not None else self.params
                params = jax.tree.map(lambda p: np.asarray(p, np.float32), src)
                del master
        else:
            lp = jax.tree.map(lambda p: jnp.asarray(p, self.compute_dtype), params)
            self.params = jax.device_put(lp, self._param_shardings)
            if self._mixed and not self._offload_enabled:
                master = jax.tree.map(lambda p: jnp.asarray(p, jnp.float32), params)
                self.master_params = jax.device_put(master, self._opt_shardings)
            else:
                self.master_params = None

        init.mark("params")
        # ---- optimizer ----
        self.client_optimizer = optimizer
        if optimizer is not None:
            self.optimizer = optimizer
        elif config.optimizer_name is not None:
            self.optimizer = build_optimizer(config.optimizer_name, config.optimizer_params)
        else:
            self.optimizer = None
        self._offload_mgr = None
        # unified TransferEngine owning all offload host<->device byte
        # movement (docs/TRANSFER.md; set by _setup_offload)
        self._transfer = None
        # ZeRO-2/3 sharded host tier state (set by _setup_offload when planned)
        self._zero_tier = None
        self._z3_residency = False
        self._z3_released = {}
        self._z3_prefetched = set()
        # per-leaf access schedule (writeback order of the first completed
        # step = the order forward consumes leaves) driving stage-3
        # release/prefetch ordering once recorded
        self._z3_schedule = []
        if self.optimizer is not None and self._offload_enabled:
            self.opt_state = None
            self._setup_offload(off, params)
        elif self.optimizer is not None:
            master_like = self.master_params if self._mixed else self.params
            opt_state = self.optimizer.init(master_like)
            # moments shard like the master/opt specs; step counter replicated
            # (placed as the step returns it: a counter left uncommitted made
            # the second step a second program, traced, lowered and compiled)
            self.opt_state = opt_state._replace(
                step=jax.device_put(opt_state.step, self._replicated),
                m=None if opt_state.m is None else jax.device_put(opt_state.m, self._opt_shardings),
                v=None if opt_state.v is None else jax.device_put(opt_state.v, self._opt_shardings),
            )
        else:
            self.opt_state = None

        # ---- loss scaling ----
        self.loss_scaler = CreateLossScaler(config.fp16_config, config.fp16_enabled)
        self.scaler_state: LossScalerState = jax.device_put(
            self.loss_scaler.init_state(), self._replicated
        )

        # ---- lr scheduler ----
        self.client_lr_scheduler = lr_scheduler
        if lr_scheduler is not None:
            self.lr_scheduler = lr_scheduler
        elif config.scheduler_name is not None:
            self.lr_scheduler = build_lr_scheduler(
                config.scheduler_name, self.optimizer, config.scheduler_params
            )
        else:
            self.lr_scheduler = None

        # ---- gradient accumulation buffer ----
        self._acc_grads = None

        # ---- dataloader ----
        self.training_dataloader = None
        if training_data is not None:
            self.training_dataloader = self.deepspeed_io(training_data, collate_fn=collate_fn)

        # ---- ZeRO++ qgZ validation (zero/zeropp.py) ----
        self._qgz_enabled = bool(config.zero_config.zero_quantized_gradients)
        if self._qgz_enabled:
            if topo.get_dim("pipe") > 1:
                raise ValueError(
                    "zero_quantized_gradients is not supported with pipeline "
                    "parallelism (the pipeline engine owns its own gradient "
                    "reduction schedule)"
                )
            if topo.get_dim("expert") > 1:
                raise ValueError(
                    "zero_quantized_gradients is not supported with expert "
                    "parallelism: expert-sharded weights are never gathered "
                    "and expert grads reduce in their own groups"
                )
            if config.optimizer_name in ("onebitadam", "zerooneadam", "onebitlamb"):
                raise ValueError(
                    "zero_quantized_gradients and 1-bit optimizers both own the "
                    "gradient reduction — enable one or the other"
                )
            if self._compression is not None:
                raise ValueError(
                    "zero_quantized_gradients and compression (QAT) cannot be "
                    "combined: the qgZ fwd/bwd path bypasses the compression "
                    "schedule's fake-quant forward"
                )

        init.mark("optimizer")
        # ---- compiled fns ----
        self._build_compiled_fns()
        init.mark("functions")

        # reference compile() / is_compiled surface (runtime/compiler.py):
        # the step IS whole-program compiled; this records/validates the block
        from .compiler import CompiledSurface

        self._compile_surface = CompiledSurface(config.compile_config)

        self._memory_preflight()

        log_dist(
            f"DeepSpeedEngine: zero_stage={stage} dtype={self.compute_dtype.__name__} "
            f"mesh={topo.axis_sizes} batch=({config.train_batch_size},"
            f"{config.train_micro_batch_size_per_gpu},{config.gradient_accumulation_steps})",
            ranks=[0],
        )
        init.mark("preflight")
        init.close()

    def _memory_preflight(self) -> None:
        """OOM guard (reference analogue: the autotuner's memory model,
        ``autotuner.py:278`` — here applied at engine init): estimate the
        per-chip STATIC state (weights + grads + optimizer) from the actual
        param tree and the ZeRO/mesh sharding, and warn loudly when it
        exceeds the device's capacity — a hint hours cheaper than the OOM.
        Activations are excluded (batch/remat-dependent), so this
        under-estimates; crossing it is near-certain failure."""
        try:
            from ..autotuning.autotuner import estimate_static_state_per_chip
            from ..comm.topology import ZERO_AXES

            topo = self.topology
            n_params = sum(int(np.prod(a.shape))
                           for a in jax.tree.leaves(self.params))
            stage = self.config.zero_config.stage
            # grads/opt shard over the full ZeRO degree; stage-3 WEIGHTS over
            # hpz only when hpz>1 (zero/partition.py stage_param_specs)
            zero_degree = max(1, int(np.prod([topo.get_dim(a)
                                              for a in ZERO_AXES])))
            hpz = topo.get_dim("hpz")
            weight_shards = hpz if hpz > 1 else zero_degree
            mp = max(1, topo.get_dim("model"))
            offload = self.config.zero_config.offload_optimizer
            off_frac = 0.0
            if offload is not None and offload.device in ("cpu", "nvme"):
                # ratio = fraction OFFLOADED (split_by_ratio semantics)
                off_frac = max(0.0, min(1.0, getattr(offload, "ratio", 1.0)))
            off_param = self.config.zero_config.offload_param
            est = estimate_static_state_per_chip(
                n_params, stage, zero_degree=zero_degree, mp=mp,
                dtype_bytes=2 if self._mixed else 4,
                offload_opt_fraction=off_frac,
                weight_shard_degree=weight_shards,
                # pure-fp32 runs keep no separate master copy
                has_master=self._mixed)
            if off_param is not None and getattr(off_param, "device", None) \
                    in ("cpu", "nvme"):
                # param-offloaded configs stream weights from the host tier;
                # HBM holds O(2 layers), not the model (swap_tensor/streamed)
                est -= (n_params / max(1, mp)) \
                    * (2 if self._mixed else 4) / (weight_shards
                                                   if stage >= 3 else 1)
            from ..accelerator import get_accelerator

            cap = float(get_accelerator().total_memory(0))
            if cap > 0 and est > 0.92 * cap:
                logger.warning(
                    f"memory preflight: static state needs ~{est / 2**30:.1f} "
                    f"GiB/chip (params {n_params / 1e6:.0f}M, stage {stage}, "
                    f"zero_degree {zero_degree}, mp {mp}) vs "
                    f"~{cap / 2**30:.1f} GiB capacity — activations come on "
                    "top; expect OOM. Raise the ZeRO stage, shard further, "
                    "or enable offload.")
        except Exception:  # the guard must never break init
            pass

    # ------------------------------------------------------------------
    def curriculum_enabled_legacy(self) -> bool:
        """Reference ``engine.curriculum_enabled_legacy`` parity."""
        return self._curriculum is not None

    def curriculum_seqlen(self) -> int:
        """The current legacy-curriculum difficulty (training seqlen)."""
        if self._curriculum is None:
            raise RuntimeError("legacy curriculum_learning is not enabled")
        return int(self._curriculum.get_difficulty(self.global_steps))

    # ------------------------------------------------------------------
    def compile(self, backend="xla", compile_kwargs=None) -> None:
        """Reference ``engine.compile`` parity (runtime/compiler.py): the XLA
        training step is already one compiled program; validates/logs."""
        self._compile_surface.compile(backend, compile_kwargs)

    @property
    def is_compiled(self) -> bool:
        return self._compile_surface.is_compiled

    # ------------------------------------------------------------------
    @staticmethod
    def _extract_model(model, model_params=None):
        """Accept (params, apply_fn) tuples, flax-style modules with
        ``.init``/``.apply``, or objects exposing ``.params``/``.apply``."""
        tp_specs = getattr(model, "tp_specs", None)
        if isinstance(model, tuple) and len(model) == 2:
            params, apply_fn = model
            return params, apply_fn, tp_specs
        if model_params is not None:
            return model_params, model.apply, tp_specs
        if hasattr(model, "params") and hasattr(model, "apply"):
            return model.params, model.apply, tp_specs
        if hasattr(model, "init_params") and hasattr(model, "apply"):
            params = model.init_params(jax.random.PRNGKey(0))
            return params, model.apply, tp_specs
        raise TypeError(
            "model must be (params, apply_fn), or expose .params/.apply or .init_params/.apply"
        )

    # ------------------------------------------------------------------
    def _loss_of(self, out):
        if isinstance(out, tuple):
            return out[0]
        return out

    def _build_compiled_fns(self):
        cfg = self.config
        # pipeline engines consume all microbatches in ONE apply → no loss division
        gas = getattr(self, "_gas_divisor", cfg.gradient_accumulation_steps)
        apply_fn = self._apply_fn

        # ZeRO++ qwZ: stage-3 parameter gathers move int8 codes instead of
        # bf16/fp32 (zero/zeropp.py; reference zero_quantized_weights)
        self._qwz = None
        if self.zero_stage >= 3 and cfg.zero_config.zero_quantized_weights:
            from .zero.zeropp import make_qwz_transform

            self._qwz = make_qwz_transform(self._param_specs, self.topology)
        qwz = self._qwz
        # prescale_gradients / gradient_predivide_factor order pre- vs post-divide
        # around the reference's allreduce; here the DP average is a single mean
        # over the global batch inside one compiled program, so both orderings are
        # the same operation — the flags are accepted as no-ops.

        base_rng = self._rng

        def make_fwd_bwd(comp_key, ltd_keep=None):
            """comp_key: None, or (active, bits) compression schedule state;
            ltd_keep: None, or the static random-LTD kept-token count — a new
            jit variant per state keeps the schedules effective under jit."""

            def fwd_bwd(lp_params, batch, scale, step_idx):
                # per-micro-step rng derived on device (no host-side split dispatch)
                rng = jax.random.fold_in(base_rng, step_idx)

                def loss_fn(p):
                    if qwz is not None:
                        p = qwz(p)
                    if comp_key is not None and comp_key[0]:
                        from ..compression.compress import compress_params

                        p = compress_params(p, self._compression,
                                            num_bits=comp_key[1],
                                            tp_specs=self._param_specs,
                                            topo=self.topology)
                    b = batch
                    if ltd_keep is not None and isinstance(batch, dict):
                        b = dict(batch, ltd_keep=ltd_keep)
                    out = apply_fn(p, b, train=True, rng=rng)
                    loss = self._loss_of(out)
                    scaled = loss.astype(jnp.float32) * scale / gas
                    return scaled, loss

                (_, loss), grads = jax.value_and_grad(loss_fn, has_aux=True)(lp_params)
                return loss, grads

            return audited_jit(
                "engine.fwd_bwd", fwd_bwd, max_traces=4,
                out_shardings=(self._replicated, self._grad_shardings),
            )

        self._make_fwd_bwd = make_fwd_bwd
        self._fwd_bwd_variants = {}
        self._fwd_bwd = make_fwd_bwd(None)

        def make_train_loss(comp_key, ltd_keep=None):
            """Loss-ONLY train-mode program (dropout on, no gradients): what a
            LazyLoss runs when its value is read without a backward()."""

            def train_loss(lp_params, batch, step_idx):
                rng = jax.random.fold_in(base_rng, step_idx)
                p = lp_params
                if qwz is not None:
                    p = qwz(p)
                if comp_key is not None and comp_key[0]:
                    from ..compression.compress import compress_params

                    p = compress_params(p, self._compression,
                                        num_bits=comp_key[1],
                                        tp_specs=self._param_specs,
                                        topo=self.topology)
                b = batch
                if ltd_keep is not None and isinstance(batch, dict):
                    b = dict(batch, ltd_keep=ltd_keep)
                out = apply_fn(p, b, train=True, rng=rng)
                return self._loss_of(out).astype(jnp.float32)

            return jax.jit(train_loss, out_shardings=self._replicated)

        self._make_train_loss = make_train_loss
        self._train_loss_variants = {}
        self._train_loss = make_train_loss(None)

        def eval_loss(lp_params, batch):
            out = apply_fn(lp_params, batch, train=False, rng=None)
            return self._loss_of(out).astype(jnp.float32)

        self._eval_fn = jax.jit(eval_loss, out_shardings=self._replicated)

        def acc(acc_grads, grads):
            return jax.tree.map(lambda a, g: a + g.astype(a.dtype), acc_grads, grads)

        self._acc = jax.jit(acc, donate_argnums=(0,),
                            out_shardings=self._grad_shardings)

        opt = self.optimizer
        scaler = self.loss_scaler
        clip = cfg.gradient_clipping
        mixed = self._mixed
        check_overflow = cfg.fp16_enabled
        compute_dtype = self.compute_dtype

        # everything after the gradients: unscale, norm, clip, update, the
        # cast back to the compute dtype (device time by scope: "optimizer")
        @jax.named_scope("optimizer")
        def step_fn(lp_params, master, opt_state, acc_grads, scaler_state, lr):
            inv = 1.0 / scaler_state.cur_scale
            grads = jax.tree.map(lambda g: g.astype(jnp.float32) * inv, acc_grads)
            overflow = has_overflow(grads) if check_overflow else jnp.asarray(False)
            gnorm = _global_norm(grads)
            if clip > 0:
                coef = jnp.minimum(1.0, clip / (gnorm + 1e-6))
                grads = jax.tree.map(lambda g: g * coef, grads)
            target = master if mixed else lp_params
            new_master, new_opt = opt.update(grads, opt_state, target, lr)
            # skip the update entirely on overflow
            new_master = _tree_select(overflow, target, new_master)
            new_opt = _tree_select(overflow, opt_state, new_opt)
            new_lp = jax.tree.map(lambda p: p.astype(compute_dtype), new_master)
            new_scaler_state = scaler.update(scaler_state, overflow)
            if mixed:
                return new_lp, new_master, new_opt, new_scaler_state, gnorm, overflow
            return new_lp, None, new_opt, new_scaler_state, gnorm, overflow

        if opt is not None:
            self._step_fn = jax.jit(
                step_fn,
                donate_argnums=(0, 1, 2, 3),
                out_shardings=(
                    self._param_shardings,
                    self._opt_shardings if mixed else None,
                    None,  # opt state: inferred (moments sharded via inputs)
                    None,
                    self._replicated,
                    self._replicated,
                ),
            )
        else:
            self._step_fn = None

        # fused micro-step (fwd+bwd+optimizer in ONE program): used by
        # train_batch() when GAS == 1 — halves the per-step dispatch count and
        # keeps the gradients out of the dispatch boundary entirely
        def fused_step(lp_params, master, opt_state, scaler_state, batch, step_idx, lr):
            rng = jax.random.fold_in(base_rng, step_idx)

            def loss_fn(p):
                if qwz is not None:
                    p = qwz(p)
                out = apply_fn(p, batch, train=True, rng=rng)
                loss = self._loss_of(out)
                return loss.astype(jnp.float32) * scaler_state.cur_scale, loss

            (_, loss), grads = jax.value_and_grad(loss_fn, has_aux=True)(lp_params)
            new_lp, new_master, new_opt, new_scaler, gnorm, overflow = step_fn(
                lp_params, master, opt_state, grads, scaler_state, lr
            )
            return new_lp, new_master, new_opt, new_scaler, loss, gnorm, overflow

        if opt is not None:
            self._fused_step_fn = jax.jit(
                fused_step,
                donate_argnums=(0, 1, 2),
                out_shardings=(
                    self._param_shardings,
                    self._opt_shardings if mixed else None,
                    None, None,
                    self._replicated, self._replicated, self._replicated,
                ),
            )
        else:
            self._fused_step_fn = None

        # multi-step dispatch (`steps_per_execution`, Keras precedent): K
        # optimizer steps as ONE compiled program — a lax.scan over the fused
        # micro-step with the K batches stacked on a leading axis. Amortizes
        # per-dispatch host/runtime overhead across K steps. bf16/fp32 only: the fp16
        # overflow-skip bookkeeping needs a host sync per step.
        n_exec = cfg.steps_per_execution
        if n_exec > 1 and cfg.fp16_enabled:
            raise ValueError(
                "steps_per_execution > 1 requires bf16/fp32: the fp16 "
                "overflow-skip bookkeeping syncs the host every step")
        if n_exec > 1 and cfg.gradient_accumulation_steps != 1:
            raise ValueError(
                "steps_per_execution > 1 requires gradient_accumulation_steps"
                " == 1 (each scanned step is a full optimizer step)")
        if opt is not None and n_exec > 1 and not cfg.fp16_enabled:
            def multi_step(lp_params, master, opt_state, scaler_state,
                           batches, step0, lrs):
                def body(carry, xs):
                    lp, mst, ost, scs = carry
                    batch, i, lr = xs
                    lp, mst, ost, scs, loss, gnorm, _ = fused_step(
                        lp, mst, ost, scs, batch, step0 + i, lr)
                    return (lp, mst, ost, scs), (loss, gnorm)

                (lp, mst, ost, scs), (losses, gnorms) = jax.lax.scan(
                    body, (lp_params, master, opt_state, scaler_state),
                    (batches, jnp.arange(n_exec, dtype=jnp.int32), lrs))
                return lp, mst, ost, scs, losses, gnorms

            self._multi_step_fn = jax.jit(
                multi_step,
                donate_argnums=(0, 1, 2),
                out_shardings=(
                    self._param_shardings,
                    self._opt_shardings if mixed else None,
                    None, None,
                    self._replicated, self._replicated,
                ),
            )
        else:
            self._multi_step_fn = None

    # ------------------------------------------------------------------
    # explicit-collective (shard_map) gradient paths: 1-bit EF and ZeRO++ qgZ
    # ------------------------------------------------------------------
    def _dp_shardmap_batch_specs(self, batch, axes):
        """Mirror ``_shard_batch``: leaves whose dim 0 divides the DP degree
        are split over the axes; scalars / non-divisible leaves replicate
        (e.g. the injected ``pld_theta`` scalar)."""
        from jax.sharding import PartitionSpec as P

        dpn = int(np.prod([self.topology.get_dim(a) for a in axes]))
        return jax.tree.map(
            lambda x: P(axes) if (getattr(x, "ndim", 0) >= 1
                                  and x.shape[0] % dpn == 0) else P(),
            batch)

    # ------------------------------------------------------------------
    # 1-bit optimizers: error-feedback sign-compressed gradient allreduce
    # (reference runtime/comm/nccl.py:52 + fp16/onebit/*; comm/compressed.py)
    # ------------------------------------------------------------------
    def _onebit_active(self) -> bool:
        from ..comm.topology import ZERO_AXES
        from ..ops.adam.onebit_adam import OnebitAdam

        if not isinstance(self.optimizer, OnebitAdam):
            return False
        axes = tuple(a for a in ZERO_AXES if self.topology.get_dim(a) > 1)
        if not axes or self.zero_stage > 1:
            return False
        # warmup phase communicates full-precision (reference freeze_step).
        # Only APPLIED steps warm the Adam variance — overflow-skipped steps
        # must not advance the freeze counter, or compression starts against
        # v ~= 0 and the first real update explodes (the reference's state
        # step likewise only counts real updates)
        return (self.global_steps - self.skipped_steps) >= self.optimizer.freeze_step

    def _onebit_fwd_bwd(self, batch):
        """Local grads under shard_map over the DP axes + EF 1-bit allreduce."""
        from jax.sharding import PartitionSpec as P

        from ..comm.topology import ZERO_AXES
        from .comm.compressed import compressed_allreduce_tree

        topo = self.topology
        axes = tuple(a for a in ZERO_AXES if topo.get_dim(a) > 1)
        dpn = int(np.prod([topo.get_dim(a) for a in axes]))

        if getattr(self, "_onebit_fn", None) is None:
            apply_fn = self._apply_fn
            base_rng = self._rng
            gas = getattr(self, "_gas_divisor", self.config.gradient_accumulation_steps)

            def body(lp, batch_local, err_local, scale, step_idx):
                rng = jax.random.fold_in(base_rng, step_idx)
                err = jax.tree.map(lambda e: e[0], err_local)

                def loss_fn(p):
                    out = apply_fn(p, batch_local, train=True, rng=rng)
                    loss = self._loss_of(out)
                    return loss.astype(jnp.float32) * scale / gas, loss

                (_, loss), grads = jax.value_and_grad(loss_fn, has_aux=True)(lp)
                # EF state must live in UNSCALED units: a loss-scale change
                # between steps would otherwise re-inject the residual at the
                # wrong magnitude. Unscale → compress → rescale for step_fn.
                inv = 1.0 / scale
                g_unscaled = jax.tree.map(lambda g: g.astype(jnp.float32) * inv, grads)
                red, new_err = compressed_allreduce_tree(g_unscaled, err, axes)
                red = jax.tree.map(lambda g: g * scale, red)
                # an fp16 overflow would poison the residual with NaN/Inf
                # forever (the step is skipped, the buffer is not) — sanitize
                new_err = jax.tree.map(
                    lambda e: jnp.where(jnp.isfinite(e), e, 0.0), new_err
                )
                new_err = jax.tree.map(lambda e: e[None], new_err)
                return jax.lax.pmean(loss, axes), red, new_err

            param_specs = jax.tree.map(lambda _: P(), self.params)
            batch_spec_ = self._dp_shardmap_batch_specs(batch, axes)
            err_spec = jax.tree.map(lambda _: P(axes), self.params)
            # check_vma off: the packed-wire reduce ends in an all_gather +
            # local decompress whose replication the static checker cannot
            # infer (same situation as the qgZ path)
            self._onebit_fn = jax.jit(jax.shard_map(
                body, mesh=topo.mesh,
                in_specs=(param_specs, batch_spec_, err_spec, P(), P()),
                out_specs=(P(), jax.tree.map(lambda _: P(), self.params), err_spec),
                axis_names=set(axes), check_vma=False,
            ))
        if getattr(self, "_ef_errors", None) is None:
            self._ef_errors = jax.tree.map(
                lambda p: jax.device_put(
                    jnp.zeros((dpn,) + p.shape, jnp.float32),
                    NamedSharding(topo.mesh, P(axes)),
                ),
                self.params,
            )
        loss, grads, self._ef_errors = self._onebit_fn(
            self.params, batch, self._ef_errors, self.scaler_state.cur_scale,
            jnp.asarray(self.micro_steps, jnp.int32),
        )
        return loss, grads

    # ------------------------------------------------------------------
    # ZeRO++ qgZ: int8 block-quantized gradient reduction over the DP axes
    # (reference runtime/comm/coalesced_collectives.py all_to_all_quant_reduce;
    # zero/zeropp.py quantized_grad_reduce_tree)
    # ------------------------------------------------------------------
    def _qgz_active(self) -> bool:
        if not getattr(self, "_qgz_enabled", False):
            return False
        from ..comm.topology import ZERO_AXES

        return any(self.topology.get_dim(a) > 1 for a in ZERO_AXES)

    def _qgz_fwd_bwd(self, batch):
        """Local grads under shard_map over the DP axes + quantized reduce."""
        self._build_qgz_fn(batch)
        return self._qgz_fn(
            self.params, batch, self.scaler_state.cur_scale,
            jnp.asarray(self.micro_steps, jnp.int32),
        )

    def _build_qgz_fn(self, batch):
        """Build (once) the qgZ shard_map program WITHOUT executing it — the
        wire-byte tests lower it directly from this seam."""
        from jax.sharding import PartitionSpec as P

        from ..comm.topology import ZERO_AXES
        from .zero.zeropp import quantized_grad_reduce_tree

        topo = self.topology
        axes = tuple(a for a in ZERO_AXES if topo.get_dim(a) > 1)
        dpn = int(np.prod([topo.get_dim(a) for a in axes]))

        if getattr(self, "_qgz_fn", None) is None:
            from .zero.zeropp import gather_params_tree, manual_axis_specs

            apply_fn = self._apply_fn
            base_rng = self._rng
            gas = getattr(self, "_gas_divisor", self.config.gradient_accumulation_steps)
            full_specs = self._param_specs
            qwz_wire = bool(self.config.zero_config.zero_quantized_weights)

            def body(lp, batch_local, scale, step_idx):
                rng = jax.random.fold_in(base_rng, step_idx)
                # stage-3: inside the manual ZeRO axes GSPMD no longer inserts
                # the param gather — do it explicitly (int8 wire when qwZ is
                # also on), OUTSIDE the grad so qgZ owns the reduction
                p_full = gather_params_tree(lp, full_specs, axes,
                                            quantized=qwz_wire)

                def loss_fn(p):
                    out = apply_fn(p, batch_local, train=True, rng=rng)
                    loss = self._loss_of(out)
                    return loss.astype(jnp.float32) * scale / gas, loss

                (_, loss), grads = jax.value_and_grad(loss_fn, has_aux=True)(p_full)
                red = quantized_grad_reduce_tree(grads, axes, dpn)
                return jax.lax.pmean(loss, axes), red

            # manual in_specs: the params' real sharding over the ZeRO axes
            # (replicated at stage<=2, sharded at stage 3); TP axes stay auto
            param_specs = manual_axis_specs(full_specs, axes)
            batch_spec_ = self._dp_shardmap_batch_specs(batch, axes)
            # check_vma off: the quantized reduce ends in an all_gather whose
            # replication the static checker cannot infer
            self._qgz_fn = jax.jit(jax.shard_map(
                body, mesh=topo.mesh,
                in_specs=(param_specs, batch_spec_, P(), P()),
                out_specs=(P(), jax.tree.map(lambda _: P(), self.params)),
                axis_names=set(axes), check_vma=False,
            ))

    # ------------------------------------------------------------------
    # ZeRO-Offload / Offload++ / ZeRO-Infinity (reference stage_1_and_2.py
    # cpu_offload + swap_tensor NVMe tier; see zero/offload.py)
    # ------------------------------------------------------------------
    def _setup_offload(self, off, fp32_params):
        from ..ops.adam.cpu_adam import DeepSpeedCPUAdam
        from ..ops.optimizers import FusedAdam
        from .zero.offload import OffloadedAdamState, split_by_ratio

        if not isinstance(self.optimizer, FusedAdam):
            raise ValueError(
                "offload_optimizer requires an Adam-family optimizer "
                "(reference forces DeepSpeedCPUAdam)"
            )
        leaves, treedef = jax.tree.flatten(fp32_params)
        host_idx, dev_idx = split_by_ratio(leaves, off.ratio)
        from ..analysis.sanitizer import sanitize_enabled

        if sanitize_enabled():
            from ..analysis.sanitizer import check_offload_split

            check_offload_split(host_idx, dev_idx, len(leaves))
        opt = self.optimizer
        cpu_opt = DeepSpeedCPUAdam(
            lr=opt.lr, betas=opt.betas, eps=opt.eps, weight_decay=opt.weight_decay,
            bias_correction=opt.bias_correction, adamw_mode=opt.adam_w_mode,
        )
        # one TransferEngine per engine: every offload D2H/H2D byte rides its
        # ledger; overlap=False is the synchronous bitwise twin (A/B arm).
        # nvme_path on the SHARDED (cpu) tier selects the NVMe third tier for
        # the Adam moments — the legacy device="nvme" AIO path is untouched.
        from .transfer_engine import TransferEngine

        zc = self.config.zero_config
        nvme_dir = off.nvme_path if (self._zero_sharded_planned
                                     and off.nvme_path) else None
        self._transfer = TransferEngine(
            overlap=bool(getattr(zc, "transfer_overlap", True)),
            nvme_dir=nvme_dir,
        )
        dev_state = None
        if self._zero_sharded_planned:
            # stage >= 2: the host tier shards the optimizer state per DP rank
            # (ratio == 1 guaranteed by the predicate, so host_idx is every
            # leaf and there is no device twin-flow subset)
            from .zero.partition import PartitionPlan
            from .zero.sharded import ZeroShardedTier

            plan = PartitionPlan(
                [leaves[i] for i in host_idx],
                self.topology.data_parallel_size,
                sanitize=sanitize_enabled(),
            )
            host_state = ZeroShardedTier(
                [np.asarray(leaves[i], np.float32) for i in host_idx],
                plan, stage=self.zero_stage,
                nvme_store=self._transfer.nvme if nvme_dir else None,
            )
            self._zero_tier = host_state
            self._z3_residency = self.zero_stage >= 3
            log_dist(
                f"ZeRO-{self.zero_stage} sharded tier: {len(host_idx)} leaves "
                f"-> cpu, optimizer state in {plan.num_shards} shards "
                f"(~{plan.shard_bytes(0) // 1024} KiB/shard)"
                + (f", moments on NVMe ({nvme_dir})" if nvme_dir else ""),
                ranks=[0],
            )
        else:
            host_state = OffloadedAdamState(
                [np.asarray(leaves[i], np.float32) for i in host_idx],
                device=off.device, nvme_path=off.nvme_path,
            )
            opt_shardings_flat = jax.tree.leaves(self._opt_shardings)
            if dev_idx:
                dev_master = [jax.device_put(jnp.asarray(leaves[i], jnp.float32),
                                             opt_shardings_flat[i]) for i in dev_idx]
                dev_state = {
                    "master": dev_master,
                    "m": [jnp.zeros_like(m) for m in dev_master],
                    "v": [jnp.zeros_like(m) for m in dev_master],
                }
            log_dist(
                f"ZeRO-Offload: {len(host_idx)} leaves -> {off.device} "
                f"(ratio={off.ratio}), {len(dev_idx)} stay on device", ranks=[0],
            )
        # both tiers settle their gradient tickets through THIS ledger
        host_state.transfer = self._transfer
        self._offload_mgr = {
            "treedef": treedef, "host_idx": host_idx, "dev_idx": dev_idx,
            "host": host_state, "dev": dev_state, "cpu_opt": cpu_opt,
        }

    def _step_offload(self, lr: float):
        """Optimizer step with offloaded states. Host leaves run the C++ CPU
        Adam (twin-flow: concurrently with the device subset's jitted update)."""
        mgr = self._offload_mgr
        grads_flat = jax.tree.leaves(self._acc_grads)
        cfg = self.config
        if not hasattr(self, "_norm_fn"):
            self._norm_fn = jax.jit(_global_norm)
        inv_scale = 1.0 / float(self.scaler_state.cur_scale)
        # overflow must cover ALL gradients (host and device leaves) and must be
        # decided BEFORE the donating device sub-step runs
        overflow = False
        if cfg.fp16_enabled:
            if not hasattr(self, "_overflow_fn"):
                self._overflow_fn = jax.jit(has_overflow)
            overflow = bool(self._overflow_fn(self._acc_grads))
        gnorm = None
        clip_coef = 1.0
        if cfg.gradient_clipping > 0:
            # norm of the UNSCALED gradients (norm is homogeneous: scale after)
            gnorm = float(self._norm_fn(self._acc_grads)) * inv_scale
            clip_coef = min(1.0, cfg.gradient_clipping / (gnorm + 1e-6))
        if overflow:
            mgr["host"].step_count += 1  # keep Adam step parity with skipped steps
            self._last_global_norm = gnorm
            self.scaler_state = self.loss_scaler.update(
                self.scaler_state, jnp.asarray(True)
            )
            return True, gnorm

        # kick off the device subset first so it overlaps the host work
        dev_out = None
        if mgr["dev"] is not None:
            if not hasattr(self, "_sub_step_fn"):
                opt = self.optimizer

                def sub_step(master, m, v, grads, lr, coef, inv, step):
                    from ..ops.optimizers import OptState

                    g = [gg.astype(jnp.float32) * inv * coef for gg in grads]
                    state = OptState(step=step, m=m, v=v)
                    new_master, new_state = opt.update(g, state, master, lr)
                    return new_master, new_state.m, new_state.v

                self._sub_step_fn = jax.jit(
                    sub_step, donate_argnums=(0, 1, 2))
            d = mgr["dev"]
            dev_out = self._sub_step_fn(
                d["master"], d["m"], d["v"],
                [grads_flat[i] for i in mgr["dev_idx"]],
                jnp.asarray(lr, jnp.float32), jnp.asarray(clip_coef, jnp.float32),
                jnp.asarray(inv_scale, jnp.float32),
                # opt.update increments internally: pass the pre-step count
                jnp.asarray(mgr["host"].step_count, jnp.int32),
            )

        # twin-flow overlap (reference Offload++ blog): submit EVERY host
        # leaf's D2H gradient transfer now through the TransferEngine (native
        # dtype — half the wire bytes under bf16); the per-leaf Adam loop
        # settles each ticket at its drain_before boundary while later leaves
        # are still in flight. overlap=False makes each submit a synchronous
        # bitwise twin.
        host_idx = mgr["host_idx"]
        te = self._transfer
        host_grads_dev = [
            te.submit_d2h(grads_flat[i])
            if hasattr(grads_flat[i], "copy_to_host_async") else grads_flat[i]
            for i in host_idx
        ]

        params_flat = list(jax.tree.leaves(self.params))
        shard_flat = jax.tree.leaves(self._param_shardings)
        np_compute = np.dtype(self.compute_dtype)
        tier = self._zero_tier
        sched = self._z3_schedule
        record = tier is not None and len(sched) < len(host_idx)
        if record:
            del sched[:]  # re-record from scratch if a prior step aborted

        def _writeback(j, master_np):
            # per-leaf H2D upload, dispatched while the NEXT leaf's host Adam
            # runs; cast on host so the link moves compute-dtype bytes (2
            # instead of 4 per element under bf16/fp16)
            i = host_idx[j]
            lp_np = master_np if np_compute == master_np.dtype else \
                master_np.astype(np_compute)
            params_flat[i] = te.submit_h2d(lp_np, shard_flat[i]).value
            if tier is not None:
                # the updated-weights all-gather of the sharded tier
                tier.counters["gathers"] += 1
                tier.counters["offload_bytes_out"] += lp_np.nbytes
            if record:
                # first completed step records the leaf schedule (writeback
                # order == tree-leaf order == the order forward consumes) for
                # stage-3 release/prefetch ordering
                sched.append(j)

        mgr["host"].adam_step(
            mgr["cpu_opt"], host_grads_dev, lr, grad_scale=inv_scale,
            clip_coef=clip_coef, on_leaf=_writeback,
        )
        if dev_out is not None:
            d = mgr["dev"]
            d["master"], d["m"], d["v"] = dev_out
            for j, i in enumerate(mgr["dev_idx"]):
                params_flat[i] = jax.device_put(
                    d["master"][j].astype(self.compute_dtype), shard_flat[i]
                )
        self.params = jax.tree.unflatten(mgr["treedef"], params_flat)
        self._last_global_norm = gnorm
        if cfg.fp16_enabled:
            self.scaler_state = self.loss_scaler.update(
                self.scaler_state, jnp.asarray(False)
            )
        from ..analysis.sanitizer import sanitize_enabled

        if sanitize_enabled():
            # step boundary: every gradient ticket drained, every H2D settled
            # -> submitted == completed + cancelled, nothing in flight
            from ..analysis.sanitizer import check_transfer_ledger

            check_transfer_ledger(te)
        return False, gnorm

    # ------------------------------------------------------------------
    # ZeRO-3 parameter residency (docs/ZERO.md "Stage-3 residency window")
    # ------------------------------------------------------------------
    def _z3_release_and_prefetch(self):
        """After the step's writeback: demote non-persistent lp leaves to the
        tier's host cache until the live-element count fits
        ``max_live_parameters`` (the params-sharded-at-rest half of stage 3),
        then re-upload up to ``prefetch_bucket_size`` bytes so the next
        forward starts with its window warm. The cached host array is the
        SAME compute-dtype cast the writeback uploaded, so a release/upload
        round trip is byte-exact — residency never changes the math.

        Ordering comes from the recorded access schedule (``_z3_schedule``,
        first completed step's writeback order == the order forward consumes
        leaves): release farthest-next-use first (reverse schedule), prefetch
        earliest-needed first. Until a schedule exists (e.g. step 1 hit a
        loss-scale overflow) the old largest-first heuristic stands in."""
        tier = self._zero_tier
        zc = self.config.zero_config
        sizes = tier.plan.leaf_sizes
        released = self._z3_released
        sched = self._z3_schedule if len(self._z3_schedule) == len(sizes) \
            else None
        live = sum(sizes) - sum(sizes[j] for j in released)
        if live > zc.max_live_parameters:
            params_flat = list(jax.tree.leaves(self.params))
            np_compute = np.dtype(jnp.dtype(self.compute_dtype).name)
            release_order = list(reversed(sched)) if sched is not None else \
                sorted(range(len(sizes)), key=lambda j: -sizes[j])
            for j in release_order:
                if live <= zc.max_live_parameters:
                    break
                if j in released or sizes[j] <= zc.param_persistence_threshold:
                    continue
                released[j] = tier.master[j].astype(np_compute)
                leaf = params_flat[j]
                if hasattr(leaf, "delete"):
                    leaf.delete()  # the device shard is actually freed
                live -= sizes[j]
        if not released:
            return
        # prefetch window, in schedule order (earliest-needed first)
        budget = int(zc.prefetch_bucket_size)
        params_flat = list(jax.tree.leaves(self.params))
        shard_flat = jax.tree.leaves(self._param_shardings)
        te = self._transfer
        changed = False
        prefetch_order = [j for j in sched if j in released] \
            if sched is not None else sorted(released)
        for j in prefetch_order:
            lp = released[j]
            if lp.nbytes > budget:
                break
            budget -= lp.nbytes
            params_flat[j] = te.submit_h2d(lp, shard_flat[j]).value
            del released[j]
            self._z3_prefetched.add(j)
            tier.counters["gathers"] += 1
            tier.counters["offload_bytes_out"] += lp.nbytes
            changed = True
        if changed:
            self.params = jax.tree.unflatten(
                self._offload_mgr["treedef"], params_flat)

    def _ensure_zero3_params(self):
        """On-demand all-gather before a forward: upload every leaf the
        residency window released and the prefetcher did not restore. Leaves
        the window DID restore count as prefetch hits — the knob's figure of
        merit."""
        tier = self._zero_tier
        released = self._z3_released
        pre = self._z3_prefetched
        if pre:
            tier.counters["prefetch_hits"] += sum(
                1 for j in pre if j not in released)
            pre.clear()
        if not released:
            return
        params_flat = list(jax.tree.leaves(self.params))
        shard_flat = jax.tree.leaves(self._param_shardings)
        te = self._transfer
        for j in sorted(released):
            lp = released.pop(j)
            params_flat[j] = te.submit_h2d(lp, shard_flat[j]).value
            tier.counters["gathers"] += 1
            tier.counters["offload_bytes_out"] += lp.nbytes
        self.params = jax.tree.unflatten(
            self._offload_mgr["treedef"], params_flat)

    def zero_metrics(self):
        """``train/zero/*`` counter snapshot (empty when no sharded tier)."""
        tier = self._zero_tier
        if tier is None:
            return {}
        out = dict(tier.counters)
        out["shard_bytes"] = tier.shard_bytes(0)
        return out

    def transfer_metrics(self):
        """TransferEngine ledger snapshot (empty when no offload tier)."""
        te = self._transfer
        if te is None:
            return {}
        led = te.ledger()
        out = {f"{d}_{k}": v for k, dd in led.items()
               if isinstance(dd, dict) for d, v in dd.items()}
        return out

    # ------------------------------------------------------------------
    # reference API surface
    # ------------------------------------------------------------------
    def train(self, mode: bool = True):
        self._training = mode
        return self

    def eval(self):
        return self.train(False)

    def __call__(self, batch, **kwargs):
        return self.forward(batch, **kwargs)

    def _inject_train_kwargs(self, batch):
        """Curriculum/PLD injection (reference engine.py:1824-1837): adds the
        per-step progressive-layer-drop theta to dict batches and applies the
        legacy curriculum's seqlen truncation."""
        if self._curriculum is not None and getattr(self, "_training", True):
            seqlen = int(self._curriculum.get_difficulty(self.global_steps))
            # host-side static slice: one jit variant per quantized
            # difficulty value (difficulty_step bounds the variant count)
            if isinstance(batch, dict):
                ids = batch.get("input_ids")
                if ids is not None and ids.shape[-1] > seqlen:
                    batch = dict(batch)
                    for k in ("input_ids", "labels", "positions",
                              "attention_mask", "token_type_ids"):
                        if k in batch and hasattr(batch[k], "shape") \
                                and batch[k].shape[-1] == ids.shape[-1]:
                            batch[k] = batch[k][..., :seqlen]
            elif isinstance(batch, (tuple, list)):
                full = max((a.shape[-1] for a in batch
                            if hasattr(a, "shape") and a.ndim >= 1),
                           default=0)
                if full > seqlen:
                    elems = [a[..., :seqlen] if hasattr(a, "shape")
                             and a.ndim >= 1 and a.shape[-1] == full else a
                             for a in batch]
                    if isinstance(batch, tuple) and hasattr(batch, "_fields"):
                        # NamedTuple constructors take positional fields, not
                        # an iterable — type(batch)(generator) would stuff the
                        # whole generator into the first field (or raise)
                        batch = type(batch)(*elems)
                    else:
                        try:
                            batch = type(batch)(elems)
                        except TypeError:  # exotic sequence subclass
                            batch = tuple(elems)
            elif hasattr(batch, "shape") and batch.ndim >= 1 \
                    and batch.shape[-1] > seqlen:
                batch = batch[..., :seqlen]
        pld = self.config.progressive_layer_drop
        if (pld and pld.get("enabled") and isinstance(batch, dict)
                and getattr(self, "_training", True)):
            import math

            theta = float(pld.get("theta", 0.5))
            gamma = float(pld.get("gamma", 0.001))
            theta_t = (1.0 - theta) * math.exp(-gamma * self.global_steps) + theta
            batch = dict(batch)
            batch["pld_theta"] = jnp.asarray(theta_t, jnp.float32)
        return batch

    def forward(self, batch, **kwargs):
        """Return the micro-step loss and arm the pending ``backward`` (see
        module docstring). After ``eval()``, runs loss-only with
        ``train=False`` (no dropout, no gradients) and returns a concrete
        replicated jax scalar.

        In training mode this returns a :class:`LazyLoss`: the fused fwd+bwd
        program launches when ``backward()`` consumes it (one program per
        micro-step — the fast path is unchanged), while reading the value
        without a backward launches a loss-only program, so a training-mode
        validation forward never silently pays a backward."""
        if kwargs:
            raise TypeError(
                f"forward() got unexpected kwargs {sorted(kwargs)}: pass model inputs "
                "inside `batch` (the apply_fn receives it whole)"
            )
        self.timers(FORWARD_MICRO_TIMER).start()
        if self._z3_residency:
            # stage-3 on-demand all-gather: any leaf the residency window
            # released since the last step must be device-resident before the
            # compiled program below captures self.params
            self._ensure_zero3_params()
        batch = self._shard_batch(self._inject_train_kwargs(batch))
        if not getattr(self, "_training", True):
            loss = self._eval_fn(self.params, batch)
            self.timers(FORWARD_MICRO_TIMER).stop()
            return loss
        fwd_bwd = self._fwd_bwd
        train_loss = self._train_loss
        comp_key = None
        if self._compression is not None:
            # full schedule state (weight bits, prune phases, act-quant mode/
            # frozen range) — one compiled variant per distinct value
            comp_key = self._compression.jit_key()
        ltd_keep = self._ltd_keep_now()
        if ltd_keep is not None and not isinstance(batch, dict):
            raise ValueError(
                "random_ltd needs dict batches (the kept-token count is "
                f"injected as batch['ltd_keep']); got {type(batch).__name__}")
        if comp_key is not None or ltd_keep is not None:
            vkey = (comp_key, ltd_keep)
            fwd_bwd = self._fwd_bwd_variants.get(vkey)
            if fwd_bwd is None:
                fwd_bwd = self._fwd_bwd_variants[vkey] = self._make_fwd_bwd(
                    comp_key, ltd_keep)
            train_loss = self._train_loss_variants.get(vkey)
            if train_loss is None:
                train_loss = self._train_loss_variants[vkey] = \
                    self._make_train_loss(comp_key, ltd_keep)
        if self._onebit_active():
            loss, grads = self._onebit_fwd_bwd(batch)
            self._cached = (loss, grads)
            self.timers(FORWARD_MICRO_TIMER).stop()
            return loss
        if self._qgz_active():
            loss, grads = self._qgz_fwd_bwd(batch)
            self._cached = (loss, grads)
            self.timers(FORWARD_MICRO_TIMER).stop()
            return loss
        lazy = LazyLoss(fwd_bwd, train_loss, (
            self.params, batch, self.scaler_state.cur_scale,
            jnp.asarray(self.micro_steps, jnp.int32),
        ))
        self._cached = lazy
        self.timers(FORWARD_MICRO_TIMER).stop()
        return lazy

    def _ltd_keep_now(self):
        """Current random-LTD kept-token count (None = full sequence)."""
        s = self._ltd_scheduler
        if s is None or not getattr(self, "_training", True):
            return None
        keep = s.update(self.global_steps)
        return None if keep >= s.full else int(keep)

    def backward(self, loss=None, retain_graph: bool = False):
        """Fold the cached gradients into the accumulation buffer. With
        gradient_accumulation_steps == 1 the buffer is the gradients themselves
        (no extra full-tree read/write — matters at 2×model-size fp32)."""
        if self._cached is None:
            raise EngineUsageError("backward() called without a preceding forward()")
        self.timers(BACKWARD_MICRO_TIMER).start()
        if isinstance(self._cached, LazyLoss):
            # the fused fwd+bwd launches HERE — forward() deferred it so a
            # never-backwarded forward doesn't pay gradient compute
            _, grads = self._cached._run_fused()
        else:
            _, grads = self._cached
        self._cached = None
        if self.config.gradient_accumulation_steps == 1:
            self._acc_grads = grads
        elif self._acc_grads is None:
            # first micro-step: take the gradients as the buffer (cast if the
            # accumulation dtype differs) — no zeros tree, no extra add
            acc_dtype = self._grad_acc_dtype()
            if all(g.dtype == acc_dtype for g in jax.tree.leaves(grads)):
                self._acc_grads = grads
            else:
                if not hasattr(self, "_cast_acc"):
                    self._cast_acc = jax.jit(
                        lambda g: jax.tree.map(lambda x: x.astype(acc_dtype), g),
                        out_shardings=self._grad_shardings,
                    )
                self._acc_grads = self._cast_acc(grads)
        else:
            self._acc_grads = self._acc(self._acc_grads, grads)
        self.micro_steps += 1
        self.timers(BACKWARD_MICRO_TIMER).stop()
        return loss

    def _grad_acc_dtype(self):
        name = self.config.gradient_accumulation_dtype
        if name is None:
            return jnp.float32 if self._mixed else self.compute_dtype
        return {"fp32": jnp.float32, "fp16": jnp.float16, "bf16": jnp.bfloat16}[name]

    def is_gradient_accumulation_boundary(self) -> bool:
        return self.micro_steps % self.config.gradient_accumulation_steps == 0

    def block_until_ready(self):
        """Wait for every in-flight device program touching the engine's state.

        JAX dispatch is asynchronous: ``step()`` returns as soon as the update
        program is enqueued. On real hardware that is the point (overlap), but
        the in-process CPU communicator used by the virtual-mesh gate can
        deadlock its collective rendezvous when two programs' collectives
        overlap on an oversubscribed host, so correctness harnesses serialize
        program boundaries through this method. Plays the role of
        ``torch.cuda.synchronize()`` in the reference's distributed test
        harness (reference tests/unit/common.py:113).
        """
        leaves = jax.tree.leaves((
            self.params,
            getattr(self, "master_params", None),
            getattr(self, "opt_state", None),
            getattr(self, "scaler_state", None),
            getattr(self, "_acc_grads", None),
        ))
        # stage-3 residency may have released (deleted) lp leaves between a
        # step and the next forward — there is nothing in flight to wait on
        jax.block_until_ready([
            l for l in leaves
            if not (hasattr(l, "is_deleted") and l.is_deleted())])
        return self

    def get_lr(self):
        if self.lr_scheduler is not None:
            return self.lr_scheduler.get_last_lr()
        return [self.optimizer.lr if self.optimizer else 0.0]

    def step(self):
        """Optimizer step at gradient-accumulation boundaries (no-op otherwise)."""
        if self.micro_steps == 0 or not self.is_gradient_accumulation_boundary():
            return
        if self._offload_mgr is not None:
            self.timers(STEP_MICRO_TIMER).start()
            overflow, gnorm = self._step_offload(float(self.get_lr()[0]))
            self._acc_grads = None
            self.global_steps += 1
            self.global_samples += self.config.train_batch_size
            if self._compression is not None:
                self._compression.step()
            if overflow:
                self.skipped_steps += 1
            elif self.lr_scheduler is not None:
                self.lr_scheduler.step()
            if self._z3_residency and not overflow:
                self._z3_release_and_prefetch()
            self._step_telemetry(gnorm)
            self.timers(STEP_MICRO_TIMER).stop()
            return
        if self._step_fn is None:
            raise EngineUsageError("no optimizer configured")
        self.timers(STEP_MICRO_TIMER).start()
        lr = jnp.asarray(self.get_lr()[0], jnp.float32)
        args = (
            self.params,
            self.master_params if self._mixed else None,
            self.opt_state,
            self._acc_grads,
            self.scaler_state,
            lr,
        )
        with tracing.span("engine.enqueue", program="step") as sp:
            if sp.recording:
                tracing.note_program("engine.step", self._step_fn, args)
            (new_lp, new_master, new_opt, new_scaler, gnorm, overflow) = \
                self._step_fn(*args)
        self.params = new_lp
        if self._mixed:
            self.master_params = new_master
        self.opt_state = new_opt
        self.scaler_state = new_scaler
        self._acc_grads = None
        self._last_global_norm = gnorm
        self.global_steps += 1
        self.global_samples += self.config.train_batch_size
        if self._compression is not None:
            self._compression.step()
        # only fp16 can overflow; bool(overflow) is a host sync — never pay it
        # on the bf16/fp32 paths (keeps the step loop free of round trips)
        if self.config.fp16_enabled and bool(overflow):
            self.skipped_steps += 1
            log_dist(
                f"[step {self.global_steps}] overflow: skipping step, "
                f"loss scale -> {float(self.scaler_state.cur_scale)}",
                ranks=[0],
            )
        elif self.lr_scheduler is not None:
            self.lr_scheduler.step()
        self._step_telemetry(gnorm)
        self.timers(STEP_MICRO_TIMER).stop()
        if self.wall_clock_breakdown and self.config.steps_per_print and \
                self.global_steps % self.config.steps_per_print == 0:
            self.timers.log(
                [FORWARD_MICRO_TIMER, BACKWARD_MICRO_TIMER, STEP_MICRO_TIMER]
            )

    def train_batch(self, data_iter=None):
        """One full global batch = GAS micro-steps + optimizer step. Returns the
        mean micro-loss (reference ``PipelineEngine.train_batch`` surface on the
        plain engine). Under a profiler session the call is one step of
        xprof's step view and an ``engine.train_batch`` span
        (docs/TRACING.md)."""
        with tracing.step_span("engine.train_batch", self.global_steps):
            return self._train_batch(data_iter)

    def _train_batch(self, data_iter):
        if data_iter is None and self.training_dataloader is None:
            raise ValueError("train_batch needs a data_iter or training_data at init")
        if data_iter is not None:
            it = data_iter
        else:
            # persistent repeating iterator: successive calls advance through the
            # dataset instead of restarting at batch 0
            if getattr(self, "_train_iter", None) is None:
                inner = iter(RepeatingLoader(self.training_dataloader))
                # resume: fast-forward to the persisted dataset position so a
                # restored run sees the same batch sequence it would have seen
                # uninterrupted (RepeatingLoader repeats the epoch order, so
                # position modulo epoch length is the in-epoch offset)
                if self._data_position:
                    try:
                        epoch_len = len(self.training_dataloader)
                    except TypeError:
                        epoch_len = 0
                    for _ in range(self._data_position % epoch_len
                                   if epoch_len else 0):
                        next(inner)
                self._train_iter = self._count_batches(inner)
            it = self._train_iter
        self.tput_timer.start()
        if (self.config.gradient_accumulation_steps == 1
                and self._fused_step_fn is not None
                and self._offload_mgr is None and self._compression is None
                and self._ltd_keep_now() is None
                and not self._onebit_active() and not self._qgz_active()
                and getattr(self, "_training", True)):
            pld = self.config.progressive_layer_drop
            if self._multi_step_fn is not None and not (
                    pld and pld.get("enabled")):
                # (PLD excluded: its per-step theta is computed host-side from
                # global_steps, which would be stale for steps 2..K of a window)
                loss = self._multi_exec_step(it)
            else:
                with tracing.span("engine.next_batch"):
                    batch = next(it)
                loss = self._fused_micro_step(batch)
            self.tput_timer.stop(global_step=True)
            return loss
        if self._multi_step_fn is not None and not getattr(self, "_warned_spe", False):
            self._warned_spe = True
            logger.warning(
                "steps_per_execution > 1 is inactive this step: the engine is "
                "on the unfused path (offload/compression/1-bit/qgZ/random-LTD "
                "take per-step dispatches)")
        losses = []
        for _ in range(self.config.gradient_accumulation_steps):
            with tracing.span("engine.next_batch"):
                batch = next(it)
            loss = self.forward(batch)
            self.backward(loss)
            losses.append(loss.value if isinstance(loss, LazyLoss) else loss)
        self.step()
        self.tput_timer.stop(global_step=True)
        return jnp.mean(jnp.stack(losses))

    def _count_batches(self, inner):
        """Wrap the engine-owned training iterator so every batch pulled bumps
        ``_data_position`` — whatever step path consumes it (fused, multi-exec
        window refill, unfused GAS loop). The counter is checkpointed; resume
        fast-forwards to it. External ``data_iter`` positions are the
        caller's to track."""
        for batch in inner:
            self._data_position += 1
            yield batch

    def _multi_exec_step(self, it):
        """steps_per_execution path: every K-th call pulls K batches, stacks
        them on a leading axis and dispatches ONE compiled program running K
        full optimizer steps; the K per-step losses are queued and returned
        one per call (device arrays — no host sync, so dispatch stays
        pipelined). Counters/lr-scheduler advance K at dispatch time, so
        ``global_steps``/``get_lr()`` move in K-sized jumps between
        executions (documented `steps_per_execution` semantics)."""
        queue = getattr(self, "_exec_queue", None)
        if queue is None:
            queue = self._exec_queue = collections.deque()
        if not queue:
            K = self.config.steps_per_execution
            batches = []
            for _ in range(K):
                try:
                    batches.append(self._inject_train_kwargs(next(it)))
                except StopIteration:
                    break
            if not batches:
                raise StopIteration
            if len(batches) < K:
                # iterator exhausted mid-window: run the tail as plain
                # single-step dispatches instead of crashing after some
                # optimizer steps already applied
                for b in batches:
                    queue.append(self._fused_micro_step(b))
                return queue.popleft()
            stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *batches)
            stacked = self._shard_stacked_batch(stacked)
            lrs = []
            for _ in range(K):
                lrs.append(self.get_lr()[0])
                if self.lr_scheduler is not None:
                    self.lr_scheduler.step()
            step0 = jnp.asarray(self.micro_steps, jnp.int32)
            (new_lp, new_master, new_opt, new_scaler, losses, gnorms) = \
                self._multi_step_fn(
                    self.params,
                    self.master_params if self._mixed else None,
                    self.opt_state, self.scaler_state, stacked, step0,
                    jnp.asarray(lrs, jnp.float32),
                )
            self.params = new_lp
            if self._mixed:
                self.master_params = new_master
            self.opt_state = new_opt
            self.scaler_state = new_scaler
            old_steps = self.global_steps
            self.micro_steps += K
            self.global_steps += K
            self.global_samples += K * self.config.train_batch_size
            self._last_global_norm = gnorms[-1]
            # counters jump by K: emit telemetry when the print cadence was
            # crossed ANYWHERE inside the window, not only on exact multiples
            every = self.config.steps_per_print
            self._step_telemetry(
                gnorms[-1],
                force=bool(every) and (old_steps // every != self.global_steps // every))
            for i in range(K):
                queue.append(losses[i])
        return queue.popleft()

    def _shard_stacked_batch(self, stacked):
        """Place a K-stacked batch: batch leaves shard over DP on dim 1 (dim 0
        is the steps axis), everything else replicates."""
        spec = batch_spec(self.topology)
        stacked_sh = NamedSharding(
            self.topology.mesh, PartitionSpec(None, *spec))

        def put(x):
            x = jnp.asarray(x)
            if x.ndim >= 2 and x.shape[1] % self.topology.data_parallel_size == 0:
                return jax.device_put(x, stacked_sh)
            return jax.device_put(x, self._replicated)

        return jax.tree.map(put, stacked)

    def _under_mesh(self, apply_fn):
        """``apply_fn`` traced under ``kernel_mesh``: Pallas kernels in the
        model map themselves over the engine's mesh (ops/pallas_utils.py).
        What the model says of the path a shape took (``head_loss``) lands in
        ``_program_attrs`` and from there on the ``engine.enqueue`` spans."""
        from ..ops.pallas_utils import kernel_mesh

        mesh = self.topology.mesh
        self._program_attrs = {}

        def apply(*args, **kwargs):
            with kernel_mesh(mesh), tracing.program_attrs(self._program_attrs):
                return apply_fn(*args, **kwargs)

        return apply

    def _fused_step_args(self, batch):
        return (
            self.params,
            self.master_params if self._mixed else None,
            self.opt_state, self.scaler_state,
            self._shard_batch(self._inject_train_kwargs(batch)),
            jnp.asarray(self.micro_steps, jnp.int32),
            jnp.asarray(self.get_lr()[0], jnp.float32),
        )

    def lower_train_step(self, batch):
        """The fused GAS=1 train step (forward, backward, optimizer) lowered
        for ``batch`` against the engine's current state, as a
        ``jax.stages.Lowered``. ``.compile()`` is the program ``train_batch``
        dispatches: its ``as_text()`` shows the kernels and collectives in
        it, its ``memory_analysis()`` what it needs. Nothing runs and nothing
        is donated."""
        return self._fused_step_fn.lower(*self._fused_step_args(batch))

    def _fused_micro_step(self, batch):
        """One fwd+bwd+optimizer step as a single compiled program (GAS=1 path)."""
        self.timers(STEP_MICRO_TIMER).start()
        args = self._fused_step_args(batch)
        with tracing.span("engine.enqueue", program="fused_step") as sp:
            if sp.recording:
                tracing.note_program("engine.fused_step", self._fused_step_fn,
                                     args, key=_batch_key(args[4]))
            (new_lp, new_master, new_opt, new_scaler, loss, gnorm, overflow) = \
                self._fused_step_fn(*args)
            sp.set(**self._program_attrs)     # known once the step is traced
        self.params = new_lp
        if self._mixed:
            self.master_params = new_master
        self.opt_state = new_opt
        self.scaler_state = new_scaler
        self._last_global_norm = gnorm
        self.micro_steps += 1
        self.global_steps += 1
        self.global_samples += self.config.train_batch_size
        if self.config.fp16_enabled and bool(overflow):
            self.skipped_steps += 1
            log_dist(
                f"[step {self.global_steps}] overflow: skipping step, "
                f"loss scale -> {float(self.scaler_state.cur_scale)}", ranks=[0],
            )
        elif self.lr_scheduler is not None:
            self.lr_scheduler.step()
        self._step_telemetry(gnorm)
        self.timers(STEP_MICRO_TIMER).stop()
        return loss

    def _step_telemetry(self, gnorm, force=False):
        """Print-cadence logging + monitor events (shared by all step paths).
        ``force`` fires the cadence actions regardless of the modulo — used by
        the multi-step path whose counters advance in K-jumps."""
        every = self.config.steps_per_print

        def gn():
            # float() is a device sync: read the norm only at the print
            # cadence, so that dispatching a step never waits for the step.
            # The offload/sharded step paths skip the norm when clipping is
            # off — telemetry must not crash on the absent value
            return float("nan") if gnorm is None else float(gnorm)

        if every and (force or self.global_steps % every == 0):
            log_dist(
                f"step={self.global_steps} lr={self.get_lr()} "
                f"grad_norm={gn():.4f} skipped={self.skipped_steps}",
                ranks=[0],
            )
        if self.monitor.enabled and jax.process_index() == 0:
            if force or self.global_steps % max(1, every or 1) == 0:
                events = [
                    ("Train/Samples/lr", float(self.get_lr()[0]), self.global_samples),
                    ("Train/Samples/loss_scale", float(self.scaler_state.cur_scale),
                     self.global_samples),
                    ("Train/Samples/grad_norm", gn(), self.global_samples),
                ]
                # train/zero/* counter group (docs/ZERO.md "Observability")
                events += [(f"Train/ZeRO/{k}", float(v), self.global_samples)
                           for k, v in self.zero_metrics().items()]
                # transfer-engine bandwidth EMAs + ledger (docs/TRANSFER.md)
                if self._transfer is not None:
                    events += self._transfer.monitor_events(
                        "Train/Transfer", self.global_samples)
                self.monitor.write_events(events)

    # ------------------------------------------------------------------
    def _shard_batch(self, batch):
        def put(x):
            if isinstance(x, jax.Array) and hasattr(x, "sharding"):
                try:
                    if not x.sharding.is_fully_addressable or x.sharding.mesh == self.topology.mesh:
                        return x
                except Exception:
                    pass
            x = jnp.asarray(x)
            if x.ndim >= 1 and x.shape[0] % self.topology.data_parallel_size == 0:
                return jax.device_put(x, self._batch_sharding)
            return jax.device_put(x, self._replicated)

        return jax.tree.map(put, batch)

    def deepspeed_io(self, dataset, batch_size=None, collate_fn=None, shuffle=True):
        """Build the data loader (reference ``engine.py:1697 deepspeed_io``)."""
        global_micro = (
            batch_size
            if batch_size is not None
            else self.config.train_micro_batch_size_per_gpu * self.topology.data_parallel_size
        )
        return DeepSpeedDataLoader(
            dataset,
            batch_size=global_micro,
            topology=self.topology,
            collate_fn=collate_fn,
            shuffle=shuffle,
            seed=self.config.seed,
            drop_last=self.config.dataloader_drop_last,
        )

    # ------------------------------------------------------------------
    # checkpointing (reference engine.py:3054 save_checkpoint / :2710 load_checkpoint)
    # ------------------------------------------------------------------
    def _ckpt_paths(self, save_dir, tag):
        d = os.path.join(save_dir, str(tag))
        return d, os.path.join(d, "model_states.ckpt"), os.path.join(d, "optim_states.ckpt")

    @staticmethod
    def _durable_tags_before(load_dir, tag):
        """The durable-tag ring behind ``tag``: every other ``global_step<N>``
        directory under ``load_dir`` that has a model file, newest first.
        These are the fallback candidates when the tag ``latest`` points at
        fails integrity verification — sorted descending so the fallback
        loses the fewest steps."""
        def step_of(name):
            try:
                return int(name[len("global_step"):])
            except ValueError:
                return -1

        try:
            names = os.listdir(load_dir)
        except OSError:
            return []
        ring = [n for n in names
                if n != tag and n.startswith("global_step") and step_of(n) >= 0
                and os.path.exists(os.path.join(load_dir, n, "model_states.ckpt"))]
        return sorted(ring, key=step_of, reverse=True)

    def _save_sharded_optim(self, tag_dir, optim_path, plan, m_leaves,
                            v_leaves, step):
        """Stage>=2 optimizer save (docs/ZERO.md "Sharded checkpoints"):
        ``optim_states.ckpt`` becomes a small metadata record (partition plan
        + step + scaler) and the Adam moments go to one file per rank, each
        independently durable under the manifest-last protocol. The fp32
        master is NOT written here — the checkpoint's module tree already
        carries it. Slices are snapshot copies: with an async checkpoint
        engine the write happens later, while the live buffers keep
        mutating."""
        from .checkpoint_engine.consolidate import shard_path

        optim_sd = {
            "zero_sharded": plan.describe(),
            "step": int(step),
            "scaler": _gather_to_host(self.scaler_state._asdict()),
        }
        m_flat = [np.asarray(m, np.float32).reshape(-1) for m in m_leaves]
        v_flat = [np.asarray(v, np.float32).reshape(-1) for v in v_leaves]
        shard_sds = []
        for r in range(plan.num_shards):
            sl = plan.slices(r)
            shard_sds.append({
                "rank": r, "num_shards": plan.num_shards,
                "m": [np.array(m_flat[j][lo:hi], copy=True)
                      for j, (lo, hi) in enumerate(sl)],
                "v": [np.array(v_flat[j][lo:hi], copy=True)
                      for j, (lo, hi) in enumerate(sl)],
            })
        from ..analysis.sanitizer import sanitize_enabled

        if sanitize_enabled():
            from ..analysis.sanitizer import check_shard_conservation

            # the slices about to hit disk must still partition the state —
            # a buggy plan or aliasing slip would save silently wrong
            check_shard_conservation(plan.leaf_sizes, plan.bounds,
                                     [s["m"] for s in shard_sds],
                                     dtype=np.float32)
        if jax.process_index() == 0:
            self.checkpoint_engine.save(optim_sd, optim_path)
            for r, sd in enumerate(shard_sds):
                self.checkpoint_engine.save(sd, shard_path(tag_dir, r))

    def save_checkpoint(self, save_dir, tag=None, client_state=None, save_latest=True,
                        exclude_frozen_parameters=False):
        if tag is None:
            tag = f"global_step{self.global_steps}"
        d, model_path, optim_path = self._ckpt_paths(save_dir, tag)
        self.checkpoint_engine.makedirs(d, exist_ok=True)
        self.checkpoint_engine.create(tag)

        if self._offload_mgr is not None:
            module_state = self._offload_master_tree()
        else:
            module_state = self.master_params if self._mixed else self.params
        model_sd = {
            "module": module_state,
            "dtype": str(self.compute_dtype.__name__),
            "global_steps": self.global_steps,
            "global_samples": self.global_samples,
            "skipped_steps": self.skipped_steps,
            # bitwise-resume completeness (docs/RESILIENCE.md): the training
            # PRNGKey the compiled fns fold per-micro-step, the micro-step
            # counter they fold it WITH, and the dataset position — without
            # all three a resumed run diverges from the uninterrupted one
            "rng": self._rng,
            "micro_steps": self.micro_steps,
            "data_position": self._data_position,
            "ds_config_batch": [
                self.config.train_batch_size,
                self.config.train_micro_batch_size_per_gpu,
                self.config.gradient_accumulation_steps,
            ],
            "client_state": client_state or {},
        }
        if self.lr_scheduler is not None:
            model_sd["lr_scheduler"] = self.lr_scheduler.state_dict()
        # every process participates in gathering global arrays to host; only the
        # lead process touches shared storage (multi-host safe)
        model_sd = _gather_to_host(model_sd)
        if jax.process_index() == 0:
            self.checkpoint_engine.save(model_sd, model_path)

        if self._zero_tier is not None:
            self._save_sharded_optim(d, optim_path, self._zero_tier.plan,
                                     [m for m in self._zero_tier.m],
                                     [v for v in self._zero_tier.v],
                                     self._zero_tier.step_count)
        elif self._offload_mgr is not None:
            mgr = self._offload_mgr
            optim_sd = {
                "offload_host": mgr["host"].state_dict(),
                "offload_dev": None if mgr["dev"] is None else _gather_to_host(
                    {"master": mgr["dev"]["master"], "m": mgr["dev"]["m"],
                     "v": mgr["dev"]["v"]}
                ),
                # the ratio split at save time — lets a load with a DIFFERENT
                # offload ratio reshard (reference elastic ckpt reload,
                # stage_1_and_2.py:2173)
                "host_idx": list(mgr["host_idx"]),
                "dev_idx": list(mgr["dev_idx"]),
                "scaler": _gather_to_host(self.scaler_state._asdict()),
            }
            if jax.process_index() == 0:
                self.checkpoint_engine.save(optim_sd, optim_path)
        elif self.opt_state is not None and self.zero_stage >= 2 \
                and self.opt_state.m is not None:
            # device-resident stage-2/3 moments save per-shard too: gather the
            # global arrays once, then slice under a fresh partition plan
            from .zero.partition import PartitionPlan

            host_mv = _gather_to_host({"m": self.opt_state.m,
                                       "v": self.opt_state.v})
            m_leaves = jax.tree.leaves(host_mv["m"])
            v_leaves = jax.tree.leaves(host_mv["v"])
            plan = PartitionPlan(m_leaves, self.topology.data_parallel_size)
            self._save_sharded_optim(
                d, optim_path, plan, m_leaves, v_leaves,
                int(np.asarray(jax.device_get(self.opt_state.step))))
        elif self.opt_state is not None:
            optim_sd = {
                "step": self.opt_state.step,
                "m": self.opt_state.m,
                "v": self.opt_state.v,
                "scaler": self.scaler_state._asdict(),
            }
            optim_sd = _gather_to_host(optim_sd)
            if jax.process_index() == 0:
                self.checkpoint_engine.save(optim_sd, optim_path)

        self.checkpoint_engine.commit(tag)
        if save_latest and jax.process_index() == 0:
            def _write_latest():
                # tmp → os.replace: a crash mid-write must never leave a
                # truncated `latest` shadowing the previous complete pointer
                final = os.path.join(save_dir, "latest")
                tmp = final + ".tmp"
                with open(tmp, "w") as f:
                    f.write(str(tag))
                os.replace(tmp, final)

            if hasattr(self.checkpoint_engine, "enqueue_task"):
                # async engine: the pointer write rides the FIFO queue, so
                # `latest` moves only after every file of this tag is on disk
                # (a crash mid-save resumes from the previous complete tag)
                self.checkpoint_engine.enqueue_task(_write_latest)
            else:
                _write_latest()
        log_dist(f"saved checkpoint {tag} to {save_dir}", ranks=[0])
        return True

    def load_checkpoint(self, load_dir, tag=None, load_module_strict=True,
                        load_optimizer_states=True, load_lr_scheduler_states=True,
                        load_module_only=False):
        if hasattr(self.checkpoint_engine, "wait"):
            # async engine: completion barrier — `latest` and all tag files
            # must be on disk before we read them back. Errors from earlier
            # unrelated saves are logged, not raised: they must not fail a
            # load of a checkpoint that IS complete on disk.
            self.checkpoint_engine.wait(raise_errors=False)
        if self.config.load_universal_checkpoint and os.path.exists(
                os.path.join(load_dir, "universal_meta.pkl")):
            from ..checkpoint.universal import load_universal_into_engine

            load_universal_into_engine(self, load_dir)
            self.loaded_checkpoint_tag = "universal"
            return load_dir, {}
        from_latest = tag is None
        if tag is None:
            latest = os.path.join(load_dir, "latest")
            if not os.path.isfile(latest):
                logger.warning(f"no 'latest' file at {load_dir}; nothing loaded")
                return None, {}
            with open(latest) as f:
                tag = f.read().strip()
        # both state dicts are read (and integrity-verified) to host BEFORE
        # any engine state is mutated: a corrupt optim file discovered after
        # the params were already overwritten would leave the engine
        # half-restored with no way back
        want_optim = load_optimizer_states and not load_module_only
        tags = [tag] + (self._durable_tags_before(load_dir, tag)
                        if from_latest else [])
        model_sd = optim_sd = None
        last_err = None
        for t in tags:
            d, model_path, optim_path = self._ckpt_paths(load_dir, t)
            try:
                m_sd = self.checkpoint_engine.load(model_path)
                o_sd = (self.checkpoint_engine.load(optim_path)
                        if want_optim and os.path.exists(optim_path)
                        else None)
                if o_sd is not None and "zero_sharded" in o_sd:
                    # stage>=2 sharded save: rebuild full-leaf moments from
                    # the per-rank files INSIDE the ring loop, so a torn or
                    # missing shard falls back to the previous durable tag
                    # like any other corrupt file
                    from .checkpoint_engine.consolidate import (
                        consolidate_sharded_optim,
                    )

                    o_sd = consolidate_sharded_optim(
                        self.checkpoint_engine, d, o_sd)
            except CheckpointCorruptError as e:
                e.tag = e.tag or t
                last_err = e
                if from_latest:
                    # one fallback hop per corrupt tag skipped over
                    self.ckpt_corrupt_fallbacks += 1
                    logger.warning(
                        f"checkpoint tag '{t}' failed integrity verification "
                        f"({e}); falling back to the previous durable tag")
                    continue
                raise
            model_sd, optim_sd, tag = m_sd, o_sd, t
            break
        if model_sd is None:
            raise CheckpointCorruptError(
                f"no loadable checkpoint under {load_dir}: 'latest' tag and "
                f"every earlier durable tag failed verification "
                f"(last: {last_err})", tag=tag) from last_err

        module = model_sd["module"]
        # chunked host→device pushes: a checkpoint's full param tree can be
        # GBs; each flight is bounded at ~32 MB (utils/transfer.py).
        # Casts happen host-side so the link moves target-dtype bytes.
        from ..utils.transfer import chunked_device_put

        np_f32 = np.dtype(np.float32)
        # ml_dtypes (a jax dependency) registers bfloat16 with numpy
        np_compute = np.dtype(jnp.dtype(self.compute_dtype).name)
        if self._mixed and self._offload_mgr is None:
            self.master_params = chunked_device_put(
                jax.tree.map(lambda p: np.asarray(p).astype(np_f32), module),
                self._opt_shardings,
            )
        # under offload the fp32 master lives host/NVMe-side (restored below);
        # materializing a device copy would defeat the offload
        self.params = chunked_device_put(
            jax.tree.map(lambda p: np.asarray(p).astype(np_compute), module),
            self._param_shardings,
        )
        self.global_steps = int(model_sd.get("global_steps", 0))
        self.global_samples = int(model_sd.get("global_samples", 0))
        self.skipped_steps = int(model_sd.get("skipped_steps", 0))
        # pre-completeness checkpoints (no "micro_steps") can only have been
        # taken at a GAS boundary, where micro_steps == steps * GAS exactly
        self.micro_steps = int(model_sd.get(
            "micro_steps",
            self.global_steps * self.config.gradient_accumulation_steps))
        self._data_position = int(model_sd.get("data_position", 0))
        saved_rng = model_sd.get("rng")
        if saved_rng is not None:
            saved_rng = np.asarray(saved_rng)
            cur = np.asarray(self._rng)
            if cur.shape != saved_rng.shape or not np.array_equal(cur, saved_rng):
                # the compiled step fns close over the OLD key — rebuild them.
                # Same-key resume (the common case: same config.seed) skips
                # this, keeping compiled programs — and therefore bitwise
                # trajectories — shared between the saver and the resumer.
                self._rng = jnp.asarray(saved_rng)
                self._build_compiled_fns()
        # in-flight micro-step state is meaningless across a restore: the
        # resumed run re-pulls its batches and re-runs the window
        self._cached = None
        self._acc_grads = None
        self._train_iter = None
        if getattr(self, "_exec_queue", None):
            self._exec_queue.clear()
        if self._z3_residency:
            # params were just fully re-materialized — the residency window
            # restarts empty
            self._z3_released.clear()
            self._z3_prefetched.clear()

        if load_lr_scheduler_states and self.lr_scheduler is not None and "lr_scheduler" in model_sd:
            self.lr_scheduler.load_state_dict(model_sd["lr_scheduler"])

        if optim_sd is not None and optim_sd.get("_consolidated"):
            # sharded save, consolidated above — normalize into the format
            # THIS engine's restore branch expects (elastic across stage,
            # precision, offload mode, and rank count)
            optim_sd = self._adapt_consolidated_optim(optim_sd, module)
        if self._offload_mgr is not None and optim_sd is not None \
                and "offload_host" not in optim_sd:
            # legacy device-format checkpoint restoring into an offloaded/
            # sharded engine: synthesize the flat-offload format (master
            # comes from the module tree either way)
            if optim_sd.get("m") is None:
                optim_sd = None
            else:
                optim_sd = self._adapt_consolidated_optim({
                    "step": int(np.asarray(optim_sd["step"])),
                    "scaler": optim_sd.get("scaler"),
                    "m": [np.asarray(l, np.float32)
                          for l in jax.tree.leaves(optim_sd["m"])],
                    "v": [np.asarray(l, np.float32)
                          for l in jax.tree.leaves(optim_sd["v"])],
                }, module)

        if self._offload_mgr is not None and optim_sd is not None:
            mgr = self._offload_mgr
            saved_h = optim_sd.get("host_idx")
            saved_d = optim_sd.get("dev_idx") or []
            from ..analysis.sanitizer import sanitize_enabled

            if saved_h is not None and sanitize_enabled():
                from ..analysis.sanitizer import check_offload_split

                # a checkpoint with overlapping or gappy index lists would
                # silently double- or un-restore optimizer shards
                check_offload_split(saved_h, saved_d,
                                    len(jax.tree.leaves(self._opt_shardings)))
            same_split = saved_h is None or (
                list(saved_h) == list(mgr["host_idx"])
                and list(saved_d) == list(mgr["dev_idx"]))
            if same_split:
                mgr["host"].load_state_dict(optim_sd["offload_host"])
                if mgr["dev"] is not None and optim_sd.get("offload_dev"):
                    od = optim_sd["offload_dev"]
                    shard_flat = jax.tree.leaves(self._opt_shardings)
                    for j, i in enumerate(mgr["dev_idx"]):
                        mgr["dev"]["master"][j] = jax.device_put(
                            jnp.asarray(od["master"][j], jnp.float32), shard_flat[i])
                        mgr["dev"]["m"][j] = jax.device_put(
                            jnp.asarray(od["m"][j], jnp.float32), shard_flat[i])
                        mgr["dev"]["v"][j] = jax.device_put(
                            jnp.asarray(od["v"][j], jnp.float32), shard_flat[i])
            else:
                self._reshard_offload_load(optim_sd, saved_h, saved_d)
            # module weights ARE the master copies under offload
            master = model_sd["module"]
            flat = jax.tree.leaves(master)
            for j, i in enumerate(mgr["host_idx"]):
                mgr["host"].master[j][...] = np.asarray(flat[i], np.float32)
            if mgr["dev"] is not None and same_split \
                    and not optim_sd.get("offload_dev"):
                shard_flat = jax.tree.leaves(self._opt_shardings)
                for j, i in enumerate(mgr["dev_idx"]):
                    mgr["dev"]["master"][j] = jax.device_put(
                        jnp.asarray(flat[i], jnp.float32), shard_flat[i])
            sc = optim_sd.get("scaler")
            if sc is not None:
                self.scaler_state = LossScalerState(
                    cur_scale=jnp.asarray(sc["cur_scale"], jnp.float32),
                    cur_hysteresis=jnp.asarray(sc["cur_hysteresis"], jnp.int32),
                    last_overflow_iter=jnp.asarray(sc["last_overflow_iter"], jnp.int32),
                    iter_=jnp.asarray(sc["iter_"], jnp.int32),
                )
        elif optim_sd is not None and self.opt_state is not None:
            self.opt_state = self.opt_state._replace(
                step=jnp.asarray(optim_sd["step"], jnp.int32),
                m=None if optim_sd["m"] is None else jax.device_put(optim_sd["m"], self._opt_shardings),
                v=None if optim_sd["v"] is None else jax.device_put(optim_sd["v"], self._opt_shardings),
            )
            sc = optim_sd.get("scaler")
            if sc is not None:
                self.scaler_state = LossScalerState(
                    cur_scale=jnp.asarray(sc["cur_scale"], jnp.float32),
                    cur_hysteresis=jnp.asarray(sc["cur_hysteresis"], jnp.int32),
                    last_overflow_iter=jnp.asarray(sc["last_overflow_iter"], jnp.int32),
                    iter_=jnp.asarray(sc["iter_"], jnp.int32),
                )
        self.loaded_checkpoint_tag = tag
        log_dist(f"loaded checkpoint {tag} from {load_dir}", ranks=[0])
        client_state = model_sd.get("client_state", {})
        return model_path, client_state

    # ------------------------------------------------------------------
    # introspection / parity helpers
    # ------------------------------------------------------------------
    def get_global_grad_norm(self):
        return getattr(self, "_last_global_norm", None)

    def zero_optimization(self) -> bool:
        return self.zero_stage > 0

    def zero_optimization_stage(self) -> int:
        return self.zero_stage

    def _adapt_consolidated_optim(self, optim_sd, module):
        """Normalize consolidated full-leaf moments (from a sharded save —
        or a legacy device-format dict pre-flattened by the caller) into the
        restore format THIS engine uses. The fp32 master always comes from
        the module tree: module weights ARE the master copies, so shard files
        never duplicate them (docs/ZERO.md "Sharded checkpoints")."""
        step, sc = int(optim_sd["step"]), optim_sd.get("scaler")
        m_list, v_list = optim_sd["m"], optim_sd["v"]
        if self._offload_mgr is not None:
            flat = jax.tree.leaves(module)
            return {
                "offload_host": {
                    "step": step,
                    "master": [np.asarray(l, np.float32) for l in flat],
                    "m": [np.asarray(m, np.float32).reshape(-1)
                          for m in m_list],
                    "v": [np.asarray(v, np.float32).reshape(-1)
                          for v in v_list],
                },
                "offload_dev": None,
                # full-range split: the offload branch reshards under the
                # engine's own ratio split / partition plan as needed
                "host_idx": list(range(len(flat))),
                "dev_idx": [],
                "scaler": sc,
            }
        if self.opt_state is not None:
            treedef = jax.tree.structure(self.params)
            shapes = [tuple(p.shape) for p in jax.tree.leaves(self.params)]
            m_tree = jax.tree.unflatten(treedef, [
                np.asarray(m, np.float32).reshape(s)
                for m, s in zip(m_list, shapes)])
            v_tree = jax.tree.unflatten(treedef, [
                np.asarray(v, np.float32).reshape(s)
                for v, s in zip(v_list, shapes)])
            return {"step": step, "m": m_tree, "v": v_tree, "scaler": sc}
        return None

    def _reshard_offload_load(self, optim_sd, saved_h, saved_d):
        """Restore offloaded optimizer state saved under a DIFFERENT ratio
        split: rebuild the global per-leaf (master, m, v) map from the saved
        host+device shards, then redistribute into this engine's split
        (reference elastic checkpoint re-partitioning,
        ``stage_1_and_2.py:2173``)."""
        mgr = self._offload_mgr
        oh = optim_sd["offload_host"]
        n = len(mgr["host_idx"]) + len(mgr["dev_idx"])
        gmaster, gm, gv = [None] * n, [None] * n, [None] * n
        for j, i in enumerate(saved_h):
            gmaster[i] = np.asarray(oh["master"][j], np.float32)
            if "mv" in oh:  # nvme-format state: [m; v] stacked
                gm[i] = np.asarray(oh["mv"][j][0], np.float32)
                gv[i] = np.asarray(oh["mv"][j][1], np.float32)
            else:
                gm[i] = np.asarray(oh["m"][j], np.float32)
                gv[i] = np.asarray(oh["v"][j], np.float32)
        od = optim_sd.get("offload_dev")
        for j, i in enumerate(saved_d):
            gmaster[i] = np.asarray(od["master"][j], np.float32)
            gm[i] = np.asarray(od["m"][j], np.float32).reshape(-1)
            gv[i] = np.asarray(od["v"][j], np.float32).reshape(-1)
        step = int(oh["step"])
        host_sd = {"step": step,
                   "master": [gmaster[i] for i in mgr["host_idx"]]}
        if mgr["host"]._aio is None:
            host_sd["m"] = [gm[i].reshape(-1) for i in mgr["host_idx"]]
            host_sd["v"] = [gv[i].reshape(-1) for i in mgr["host_idx"]]
        else:
            host_sd["mv"] = [np.stack([gm[i].reshape(-1), gv[i].reshape(-1)])
                             for i in mgr["host_idx"]]
        mgr["host"].load_state_dict(host_sd)
        if mgr["dev"] is not None:
            shard_flat = jax.tree.leaves(self._opt_shardings)
            shapes = [m.shape for m in mgr["dev"]["master"]]
            for j, i in enumerate(mgr["dev_idx"]):
                mgr["dev"]["master"][j] = jax.device_put(
                    jnp.asarray(gmaster[i], jnp.float32).reshape(shapes[j]),
                    shard_flat[i])
                mgr["dev"]["m"][j] = jax.device_put(
                    jnp.asarray(gm[i], jnp.float32).reshape(shapes[j]),
                    shard_flat[i])
                mgr["dev"]["v"][j] = jax.device_put(
                    jnp.asarray(gv[i], jnp.float32).reshape(shapes[j]),
                    shard_flat[i])

    def _offload_master_tree(self):
        """Full fp32 master pytree assembled from host + device offload shards."""
        mgr = self._offload_mgr
        flat = [None] * (len(mgr["host_idx"]) + len(mgr["dev_idx"]))
        for j, i in enumerate(mgr["host_idx"]):
            flat[i] = mgr["host"].master[j]
        if mgr["dev"] is not None:
            for j, i in enumerate(mgr["dev_idx"]):
                flat[i] = mgr["dev"]["master"][j]
        return jax.tree.unflatten(mgr["treedef"], flat)

    def get_fp32_params(self):
        """Full-precision view of the module weights (``zero_to_fp32`` surface)."""
        if self._offload_mgr is not None:
            src = self._offload_master_tree()
        else:
            src = self.master_params if self._mixed else self.params
        return jax.tree.map(
            lambda p: np.asarray(jax.device_get(p) if isinstance(p, jax.Array) else p,
                                 np.float32), src)

    @property
    def train_batch_size(self):
        return self.config.train_batch_size

    @property
    def train_micro_batch_size_per_gpu(self):
        return self.config.train_micro_batch_size_per_gpu

    @property
    def gradient_accumulation_steps(self):
        return self.config.gradient_accumulation_steps

    def loss_scale(self):
        return float(self.scaler_state.cur_scale)
