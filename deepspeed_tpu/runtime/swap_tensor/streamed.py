"""ZeRO-Infinity parameter-tier training: layer-streamed execution.

Reference: ``deepspeed/runtime/swap_tensor/partitioned_param_swapper.py`` +
``runtime/zero/stage3.py`` NVMe/CPU ``offload_param`` — partitioned parameters
live off-device and are fetched just-in-time per submodule during fwd/bwd.

TPU re-design: the reference hooks per-module fetch/release into torch's
module system; under XLA a single fused jit holds ALL params in HBM for the
program's lifetime, so the parameter tier instead changes the EXECUTION SHAPE:
one compiled program per layer (all layers share it — the block is uniform),
driven by a host loop that streams each layer's weights from the
``StreamedParamStore`` (host RAM or NVMe with read-ahead) and retires them
immediately after use. Device-resident parameter footprint is O(stem + 2
layers) regardless of depth; the backward recomputes each layer's forward
(remat is implied by streaming). The fp32 master and Adam moments stay host-
resident and are updated by the C++ CPUAdam sweep (``OffloadedAdamState``),
i.e. the parameter tier composes with — and subsumes — the optimizer tier.

Scope: ``TransformerLM`` dense models (no MoE/PLD/LTD), bf16 or fp32 compute,
fp16 loss scaling unsupported. GAS > 1 accumulates gradients host-side
(resident-engine mean semantics); dropout runs with a streamed-engine rng
stream (fold_in(seed, micro_step, layer) — a valid dropout pattern, but a
DIFFERENT stream than the resident engine's, so dropout trajectories are not
bit-comparable across engines); data-parallel meshes shard the batch over
'data' with GSPMD psum-ing the parameter grads. Checkpointing via
``state_dict``/``load_state_dict`` on the host masters.
"""

from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ...utils.logging import log_dist
from ..zero.offload import OffloadedAdamState
from .param_swapper import StreamedParamStore


class StreamedZeroEngine:
    """Training engine whose parameters never fully reside in HBM."""

    def __init__(self, model, config, lr_scheduler=None):
        from ...models.transformer import TransformerLM

        if not isinstance(model, TransformerLM):
            raise ValueError(
                "offload_param streaming requires a TransformerLM model")
        mcfg = model.config
        if mcfg.num_experts > 0 or mcfg.progressive_layer_drop or mcfg.random_ltd:
            raise ValueError(
                "offload_param streaming supports dense models only "
                "(no MoE / PLD / random-LTD)")
        if mcfg.looped:
            raise NotImplementedError(
                f"loop_steps {mcfg.loop_steps}: offload_param streaming walks "
                "the layers once")
        if config.fp16_enabled:
            raise ValueError("offload_param streaming: use bf16 or fp32, not fp16")
        self.model = model
        self.config = config
        self.lr_scheduler = lr_scheduler
        self.optimizer = None  # reference surface: engine owns the optimizer
        self.training_dataloader = None
        self.compute_dtype = jnp.bfloat16 if config.bfloat16_enabled else jnp.float32
        self.global_steps = 0
        self.global_samples = 0
        self.micro_steps = 0

        # mesh composition: with a data axis > 1, the per-layer programs run
        # under GSPMD — batch sharded over 'data', weights replicated; the
        # parameter-gradient outputs are marked replicated so GSPMD inserts
        # the psum (the distributed ZeRO-3 grad reduction of the reference's
        # swapped tier). Host masters/moments stay whole per controller.
        from jax.sharding import NamedSharding, PartitionSpec
        from ...comm.topology import get_topology

        topo = get_topology(required=False)
        # only an EXPLICIT mesh request turns on the dp path (the default
        # topology spreads over every local device, which a single-controller
        # param-tier run on a laptop/test mesh should not silently shard over)
        self._dp = (topo.data_parallel_size
                    if topo is not None and config.mesh_config.data > 0 else 1)
        if self._dp > 1:
            self._bsh = NamedSharding(topo.mesh, PartitionSpec("data"))
            self._repl = NamedSharding(topo.mesh, PartitionSpec())
        else:
            self._bsh = self._repl = None

        off = config.zero_config.offload_param
        opt_off = config.zero_config.offload_optimizer
        # init on the host CPU backend: the whole point is that the full
        # parameter set never materializes in HBM
        cpu = jax.local_devices(backend="cpu")[0]
        with jax.default_device(cpu):
            # PRNGKey(0): the same init stream the resident engine uses
            params = model.init_params(jax.random.PRNGKey(0))
        params = jax.tree.map(lambda p: np.asarray(p, np.float32), params)

        L = mcfg.num_layers
        self.L = L
        blocks = params.pop("blocks")
        self.stem_keys = sorted(params)
        self.block_keys = sorted(blocks)
        stem_group = {k: params[k] for k in self.stem_keys}
        layer_groups = [
            {k: np.ascontiguousarray(blocks[k][i]) for k in self.block_keys}
            for i in range(L)
        ]
        self._groups = [stem_group] + layer_groups  # group 0 = stem

        # host optimizer state over every leaf, flattened in group order
        from ...ops.adam.cpu_adam import DeepSpeedCPUAdam
        from ...ops.optimizers import build_optimizer

        opt = build_optimizer(config.optimizer_name or "adamw",
                              config.optimizer_params or {})
        self._lr = float(getattr(opt, "lr", 1e-3))
        if self.lr_scheduler is None and config.scheduler_name is not None:
            from ..lr_schedules import build_lr_scheduler

            self.lr_scheduler = build_lr_scheduler(
                config.scheduler_name, opt, config.scheduler_params)
        self.cpu_opt = DeepSpeedCPUAdam(
            lr=self._lr, betas=getattr(opt, "betas", (0.9, 0.999)),
            eps=getattr(opt, "eps", 1e-8),
            weight_decay=getattr(opt, "weight_decay", 0.0),
            adamw_mode=getattr(opt, "adam_w_mode", True),
        )
        self._flat_masters = [g[k] for g in self._groups for k in sorted(g)]
        self.adam_state = OffloadedAdamState(
            self._flat_masters, device=(opt_off.device if opt_off else "cpu"),
            nvme_path=(opt_off.nvme_path if opt_off else None),
        )
        # OffloadedAdamState copies; keep its buffers as THE masters so the
        # param store and optimizer share storage
        self._flat_masters = self.adam_state.master
        it = iter(self._flat_masters)
        for g in self._groups:
            for k in sorted(g):
                g[k] = next(it)

        self.store = StreamedParamStore(
            self._groups, device=off.device, nvme_path=off.nvme_path,
            compute_dtype=self.compute_dtype,
        )
        self._jit_cache: Dict[Any, Any] = {}
        log_dist(
            f"StreamedZeroEngine: L={L} param tier={off.device} "
            f"opt tier={(opt_off.device if opt_off else 'cpu')} "
            f"dtype={self.compute_dtype.__name__}", ranks=[0])

    # ------------------------------------------------------------------
    # per-shape compiled programs (one each; layers share the block program)
    # ------------------------------------------------------------------
    def _programs(self, B: int, S: int):
        key = (B, S)
        if key in self._jit_cache:
            return self._jit_cache[key]
        model = self.model
        stem_keys = self.stem_keys

        def pos(B, S):
            return jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))

        use_rng = model.config.dropout > 0

        def embed(stem, ids):
            return model._embed(stem, ids, pos(*ids.shape), self.compute_dtype)

        def block(blk, x, rng):
            y, _, _ = model._block(x, blk, positions=pos(x.shape[0], x.shape[1]),
                                   rng=rng if use_rng else None, train=True)
            return y

        def block_vjp(blk, x, dy, rng):
            _, pull = jax.vjp(lambda b, h: block(b, h, rng), blk, x)
            dblk, dx = pull(dy)
            return dx, dblk

        def head_loss(stem, xL, ids):
            lg = model._head(stem, xL).astype(jnp.float32)
            labels = jnp.concatenate(
                [ids[:, 1:], jnp.full_like(ids[:, :1], -100)], axis=1)
            mask = labels != -100
            safe = jnp.where(mask, labels, 0)
            logz = jax.scipy.special.logsumexp(lg, axis=-1)
            gold = jnp.take_along_axis(lg, safe[..., None], axis=-1)[..., 0]
            return jnp.sum((logz - gold) * mask) / jnp.maximum(jnp.sum(mask), 1)

        def head_grad(stem, xL, ids):
            (loss), pull = jax.vjp(lambda s, x: head_loss(s, x, ids), stem, xL)
            dstem, dxL = pull(jnp.ones((), jnp.float32))
            return loss, dxL, dstem

        def embed_vjp(stem, ids, dx0):
            _, pull = jax.vjp(lambda s: embed(s, ids), stem)
            (dstem,) = pull(dx0)
            return dstem

        if self._bsh is None:
            progs = {
                "embed": jax.jit(embed),
                "block": jax.jit(block),
                "block_vjp": jax.jit(block_vjp),
                "head_grad": jax.jit(head_grad),
                "embed_vjp": jax.jit(embed_vjp),
            }
        else:
            # dp composition: batch/activations shard over 'data'; weights
            # replicate; replicated grad outputs make GSPMD psum them
            b, r = self._bsh, self._repl
            progs = {
                "embed": jax.jit(embed, in_shardings=(r, b), out_shardings=b),
                "block": jax.jit(block, in_shardings=(r, b, r), out_shardings=b),
                "block_vjp": jax.jit(block_vjp, in_shardings=(r, b, b, r),
                                     out_shardings=(b, r)),
                "head_grad": jax.jit(head_grad, in_shardings=(r, b, b),
                                     out_shardings=(r, b, r)),
                "embed_vjp": jax.jit(embed_vjp, in_shardings=(r, b, b),
                                     out_shardings=r),
            }
        self._jit_cache[key] = progs
        return progs

    # ------------------------------------------------------------------
    def _micro_fwd_bwd(self, ids, rng_base):
        """One streamed fwd+bwd; returns (loss, flat grad list np.float32)."""
        B, S = ids.shape
        progs = self._programs(B, S)
        L = self.L
        if self._bsh is not None:
            ids = jax.device_put(ids, self._bsh)

        def layer_rng(i):
            return jax.random.fold_in(rng_base, i)

        stem = self.store.get(0)
        x = progs["embed"](stem, ids)
        xs = [x]
        self.store.prefetch(1)
        for i in range(L):
            w = self.store.get(1 + i)
            self.store.prefetch(2 + i)
            x = progs["block"](w, x, layer_rng(i))
            xs.append(x)
            self.store.release()  # layer weights retire after the fwd
        loss, dx, dstem_h = progs["head_grad"](stem, xs[L], ids)

        grads: List[Optional[Dict]] = [None] * (L + 1)
        for i in reversed(range(L)):
            w = self.store.get(1 + i)
            if i > 0:
                self.store.prefetch(i)  # read-ahead: layer i-1's weights
            dx, dblk = progs["block_vjp"](w, xs[i], dx, layer_rng(i))
            grads[1 + i] = {k: np.asarray(v, np.float32)
                            for k, v in dblk.items()}
            xs[i + 1] = None  # retire the activation stash as we go
            self.store.release()
        dstem_e = progs["embed_vjp"](stem, ids, dx)
        dstem = jax.tree.map(lambda a, b: a.astype(jnp.float32)
                             + b.astype(jnp.float32), dstem_h, dstem_e)
        grads[0] = {k: np.asarray(v, np.float32) for k, v in dstem.items()}
        self.store.release()  # stem
        return loss, [g[k] for g in grads for k in sorted(g)]

    def train_batch(self, data_iter=None):
        """GAS micro-steps (grads accumulated host-side, matching the
        resident engine's mean-of-micro-losses semantics) + one host Adam
        sweep + async NVMe writeback (overlaps the next step's compute; a
        group's next read drains its pending write first)."""
        gas = self.config.gradient_accumulation_steps
        flat_grads = None
        losses = []
        B = 0
        for m in range(gas):
            batch = next(data_iter) if data_iter is not None else None
            ids = batch["input_ids"] if isinstance(batch, dict) else batch
            ids = jnp.asarray(ids, jnp.int32)
            B = ids.shape[0]
            rng_base = jax.random.fold_in(
                jax.random.fold_in(jax.random.PRNGKey(self.config.seed or 0),
                                   self.micro_steps), m)
            loss, g = self._micro_fwd_bwd(ids, rng_base)
            losses.append(loss)
            if flat_grads is None:
                # writable copies only when accumulating (np.asarray views of
                # device arrays are read-only); one copy per GLOBAL step is
                # the accumulation buffer itself, not a per-dispatch leak
                flat_grads = g if gas == 1 else [
                    np.array(a) for a in g]  # dstpu-lint: ignore[DSTPU002]
            else:
                for a, b in zip(flat_grads, g):
                    a += b
            self.micro_steps += 1
        if gas > 1:
            inv = 1.0 / gas
            for a in flat_grads:
                a *= inv
        clip = self.config.gradient_clipping
        clip_coef = 1.0
        gnorm = None
        if clip and clip > 0:
            sq = sum(self.cpu_opt.sq_norm(a.reshape(-1)) for a in flat_grads)
            gnorm = float(np.sqrt(sq))
            clip_coef = min(1.0, clip / (gnorm + 1e-6))
        lr = self._current_lr()
        self.adam_state.adam_step(self.cpu_opt, flat_grads, lr,
                                  clip_coef=clip_coef)
        if self.store.device == "nvme":
            # async double-buffered writeback (reference
            # pipelined_optimizer_swapper): queue all groups; reads drain
            for gi in range(len(self._groups)):
                self.store.writeback(gi, wait=False)
        self.global_steps += 1
        self.global_samples += B * gas
        self._last_global_norm = gnorm
        if self.lr_scheduler is not None:
            self.lr_scheduler.step()
        return jnp.mean(jnp.stack([jnp.asarray(l) for l in losses]))

    def _current_lr(self) -> float:
        if self.lr_scheduler is not None and hasattr(self.lr_scheduler, "get_lr"):
            lr = self.lr_scheduler.get_lr()
            return float(lr[0] if isinstance(lr, (list, tuple)) else lr)
        return self._lr

    def get_lr(self):
        return [self._current_lr()]

    # ------------------------------------------------------------------
    def state_dict(self) -> Dict:
        return {"adam": self.adam_state.state_dict(),
                "global_steps": self.global_steps}

    def load_state_dict(self, sd: Dict):
        self.adam_state.load_state_dict(sd["adam"])
        self.global_steps = int(sd.get("global_steps", 0))
        if self.store.device == "nvme":
            for gi in range(len(self._groups)):
                self.store.writeback(gi, wait=True)
