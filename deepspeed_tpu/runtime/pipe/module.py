"""Pipeline model descriptions.

Reference: ``runtime/pipe/module.py`` — ``LayerSpec:30`` (deferred layer
construction), ``PipelineModule:86`` (layer list → stage partitioning,
``_partition_layers:370`` with ``uniform|parameters`` methods), tied layers.

Two constructs here:

- ``LayerSpec`` / ``PipelineModule``: reference-parity surface for a list of
  homogeneous functional layers, partitioned uniformly over ``pipe`` stages.
- ``PipelinedLM``: pipelines a ``TransformerLM`` — blocks are re-stacked from
  (L, ...) to (P, L/P, ...) with the leading dim sharded over the ``pipe`` axis;
  embedding/head replicated across stages (their grads psum over the pipe axis
  in the shard_map transpose — the analogue of the reference's tied-weight
  all-reduce, ``runtime/pipe/engine.py:259 ReduceTiedGrads``).
"""

from typing import Any, Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ...comm.topology import get_topology
from ...utils.logging import log_dist
from .spmd import spmd_pipeline


class LayerSpec:
    """Deferred layer build (reference ``LayerSpec``): ``typename(*args)`` must
    yield an object with ``init_params(rng)`` and ``apply(params, x)``."""

    def __init__(self, typename: Callable, *module_args, **module_kwargs):
        self.typename = typename
        self.module_args = module_args
        self.module_kwargs = module_kwargs

    def build(self):
        return self.typename(*self.module_args, **self.module_kwargs)


class TiedLayerSpec(LayerSpec):
    """A layer sharing its parameters with every other ``TiedLayerSpec`` of the
    same ``name`` (reference ``pipe/module.py:77 TiedLayerSpec`` — e.g. the
    embedding reused as the LM head). Parameters are initialized by the first
    occurrence and live replicated across the pipe axis; the shard_map
    transpose psums their cotangents from every using stage — the analogue of
    the reference's tied-weight all-reduce (``pipe/engine.py:259
    ReduceTiedGrads``)."""

    def __init__(self, name: str, typename: Callable, *module_args,
                 **module_kwargs):
        super().__init__(typename, *module_args, **module_kwargs)
        self.name = name


class PipelineModule:
    """Uniform pipeline over a list of identical-structure layers.

    Layers must share one parameter structure (the reference's ``uniform``
    partitioning over a homogeneous stack — e.g. its ``LinearStackPipe`` test
    fixture). Loss is computed by ``loss_fn(final_state, labels)`` on the last
    stage. Engine model protocol: ``init_params`` / ``apply`` / ``tp_specs``.
    """

    def __init__(self, layers: Sequence, num_stages: Optional[int] = None,
                 loss_fn: Optional[Callable] = None, topology=None,
                 partition_method: str = "uniform",
                 activation_checkpoint_interval: int = 0,
                 example_input=None):
        self.specs = list(layers)
        topo = topology or get_topology()
        self.topology = topo
        self.num_stages = num_stages or topo.pipe_parallel_size
        self.partition_method = partition_method
        self.loss_fn = loss_fn or (lambda out, labels: jnp.mean((out - labels) ** 2))
        self._built = [s.build() if isinstance(s, LayerSpec) else s for s in self.specs]
        self.num_micro = 1  # set by the engine (= gradient_accumulation_steps)
        # heterogeneous mode: tied layers, weight-balanced partitioning, or
        # per-layer parameter structures that differ (reference
        # ``_partition_layers:370`` handles arbitrary LayerSpec lists)
        self._tied_idx = {i: s.name for i, s in enumerate(self.specs)
                          if isinstance(s, TiedLayerSpec)}
        self._heterogeneous = bool(self._tied_idx) or partition_method != "uniform"
        self._plan = None
        if not self._heterogeneous:
            try:
                shapes = [jax.eval_shape(lyr.init_params, jax.random.PRNGKey(0))
                          for lyr in self._built]
                sigs = {
                    (str(jax.tree.structure(s)),
                     tuple((l.shape, str(l.dtype)) for l in jax.tree.leaves(s)))
                    for s in shapes
                }
                self._heterogeneous = len(sigs) > 1
            except Exception as e:
                from ...utils.logging import logger

                logger.warning(
                    "PipelineModule: could not shape-trace layer init_params "
                    f"({type(e).__name__}: {e}); falling back to the "
                    "heterogeneous (fully-replicated) pipeline path")
                self._heterogeneous = True
        if not self._heterogeneous and len(self.specs) % self.num_stages:
            raise ValueError(
                f"{len(self.specs)} layers not divisible by {self.num_stages} "
                "stages (use partition_method='parameters' for unequal stages)"
            )
        if self._heterogeneous and example_input is not None:
            # stage assignment needs the activation shape chain; with an
            # example input available at construction, middle-layer params can
            # be flat-packed per stage and SHARDED over the pipe axis (each
            # stage holds ≈ its own share instead of the full model —
            # reference _partition_layers memory behavior). Without it, the
            # fully-replicated functional mode is used.
            self._plan = self._make_plan(example_input)

    # ------------------------------------------------------------------
    # stage-sharded heterogeneous packing
    # ------------------------------------------------------------------
    def _shape_params(self, i):
        return jax.eval_shape(self._built[i].init_params, jax.random.PRNGKey(0))

    def _make_plan(self, example_input):
        """Static packing plan: per-stage flat rows (one buffer per dtype);
        every untied MIDDLE layer's leaves get (dtype, start, shape) slots in
        its owner stage's row. Prefix/suffix/tied layers stay replicated (the
        SPMD body computes them on every stage, gated)."""
        if not isinstance(example_input, (jax.ShapeDtypeStruct,)):
            example_input = jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(jnp.shape(a), jnp.result_type(a)),
                example_input)
        p_end, q_start, ranges = self._analyze_shapes(example_input)
        stage_of = {}
        for k, (lo, hi) in enumerate(ranges):
            for i in range(lo, hi):
                stage_of[i] = k
        cursor = [dict() for _ in range(self.num_stages)]  # dtype -> next elem
        offsets: Dict[int, list] = {}
        treedefs: Dict[int, Any] = {}
        for i in range(p_end, q_start):
            if i in self._tied_idx:
                continue
            leaves, treedef = jax.tree.flatten(self._shape_params(i))
            k = stage_of[i]
            slots = []
            for leaf in leaves:
                dt = str(jnp.dtype(leaf.dtype))
                start = cursor[k].get(dt, 0)
                size = int(np.prod(leaf.shape)) if leaf.shape else 1
                cursor[k][dt] = start + size
                slots.append((dt, start, tuple(leaf.shape)))
            offsets[i] = slots
            treedefs[i] = treedef
        max_elems = {}
        for c in cursor:
            for dt, n in c.items():
                max_elems[dt] = max(max_elems.get(dt, 0), n)
        return {"p_end": p_end, "q_start": q_start, "ranges": ranges,
                "stage_of": stage_of, "offsets": offsets,
                "treedefs": treedefs, "max_elems": max_elems}

    def _unpack_layer(self, flat_local, i):
        """Rebuild layer ``i``'s param tree from a stage's local flat row(s).
        On non-owner stages the slices read other layers' values — harmless:
        the per-layer ownership gate zeroes their outputs AND cotangents."""
        plan = self._plan
        leaves = [flat_local[dt][start:start + int(np.prod(shape) or 1)].reshape(shape)
                  for dt, start, shape in plan["offsets"][i]]
        return jax.tree.unflatten(plan["treedefs"][i], leaves)

    # ------------------------------------------------------------------
    def init_params(self, rng):
        L = len(self._built)
        keys = jax.random.split(rng, L)
        if self._heterogeneous:
            params = {"layers": {}, "tied": {}}
            packed = set(self._plan["offsets"]) if self._plan else set()
            rows = {}
            if self._plan:
                rows = {dt: np.zeros((self.num_stages, n), dtype=dt)
                        for dt, n in self._plan["max_elems"].items()}
            for i, (lyr, k) in enumerate(zip(self._built, keys)):
                name = self._tied_idx.get(i)
                if name is not None:
                    if name not in params["tied"]:
                        params["tied"][name] = lyr.init_params(k)
                elif i in packed:
                    sk = self._plan["stage_of"][i]
                    leaves = jax.tree.leaves(lyr.init_params(k))
                    for leaf, (dt, start, shape) in zip(
                            leaves, self._plan["offsets"][i]):
                        size = int(np.prod(shape) or 1)
                        rows[dt][sk, start:start + size] = np.asarray(
                            leaf, dtype=dt).ravel()
                else:
                    params["layers"][f"l{i}"] = lyr.init_params(k)
            if self._plan:
                params["stages"] = {dt: jnp.asarray(a) for dt, a in rows.items()}
            return params
        per_layer = [lyr.init_params(k) for lyr, k in zip(self._built, keys)]
        stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *per_layer)
        Pn = self.num_stages
        stages = jax.tree.map(
            lambda a: a.reshape((Pn, L // Pn) + a.shape[1:]), stacked
        )
        return {"stages": stages}

    @property
    def tp_specs(self):
        dummy = jax.eval_shape(lambda: self.init_params(jax.random.PRNGKey(0)))
        if self._heterogeneous:
            # tied/prefix/suffix leaves replicate (every stage computes them,
            # gated; the transpose-psum realizes ReduceTiedGrads); the packed
            # middle rows — when an example_input enabled the plan — shard
            # over the pipe axis so each stage holds ≈ its own share
            specs = jax.tree.map(lambda a: P(*([None] * a.ndim)), dummy)
            if self._plan:
                specs["stages"] = jax.tree.map(
                    lambda a: P("pipe", None), dummy["stages"])
            return specs

        def spec_of(a):
            return P("pipe", *([None] * (a.ndim - 1)))

        return jax.tree.map(spec_of, dummy)

    # ------------------------------------------------------------------
    def _layer_params(self, params, i):
        name = self._tied_idx.get(i)
        return params["tied"][name] if name is not None else params["layers"][f"l{i}"]

    def _analyze(self, params, inputs_mb):
        """Shape-chain the layer list: the state handed between stages must
        have ONE shape (the ppermute ring), so a leading shape-changing prefix
        (embedding) runs in first_fn and a trailing one (LM head) in last_fn.
        Returns ``(prefix_end, suffix_start, stage_ranges)`` — layers
        [0, prefix_end) are the ingest prefix, [suffix_start, n) the head
        suffix, and stage_ranges partitions [prefix_end, suffix_start)."""
        return self._analyze_shapes(
            inputs_mb, get_params=lambda i: self._layer_params(params, i))

    def _analyze_shapes(self, inputs_mb, get_params=None):
        if get_params is None:
            tied_first = {}
            for i, name in self._tied_idx.items():
                tied_first.setdefault(name, i)

            def get_params(i):
                name = self._tied_idx.get(i)
                j = i if name is None else tied_first[name]
                return self._shape_params(j)
        n = len(self._built)
        cur = jax.eval_shape(lambda x: x, inputs_mb)
        chain = [cur]
        for i, lyr in enumerate(self._built):
            cur = jax.eval_shape(lyr.apply, get_params(i), cur)
            chain.append(cur)

        def sig(s):
            return (s.shape, str(s.dtype))

        sigs = [sig(s) for s in chain]  # len n+1; sigs[i] = input of layer i
        # boundary signature: the most common inter-layer state
        from collections import Counter

        boundary = Counter(sigs).most_common(1)[0][0]
        p = next(i for i in range(n + 1) if sigs[i] == boundary)
        q = max(i for i in range(n + 1) if sigs[i] == boundary)
        middle = list(range(p, q))  # layers whose input AND output are boundary
        for i in middle:
            if sigs[i] != boundary or sigs[i + 1] != boundary:
                raise ValueError(
                    f"pipeline stage boundary shape changes at layer {i} "
                    f"({sigs[i]} -> {sigs[i + 1]}): mid-pipeline shape changes "
                    "cannot cross stage boundaries")
        if not middle:
            raise ValueError("no uniform-shape middle segment to partition")
        Pn = self.num_stages
        m = len(middle)  # middle is the contiguous layer range [p, q)
        if m < Pn:
            raise ValueError(
                f"{m} partitionable middle layers < {Pn} pipeline stages")
        if self.partition_method == "parameters":
            # balance by parameter count (reference 'parameters' method):
            # place cut k at the prefix-sum closest to k/Pn of the total,
            # clamped so every stage gets >= 1 layer (no empty/inverted ranges)
            counts = []
            for i in middle:
                leaves = jax.tree.leaves(jax.eval_shape(
                    lambda i=i: get_params(i)))
                counts.append(sum(int(np.prod(l.shape)) for l in leaves))
            total = float(sum(counts)) or 1.0
            prefix = np.cumsum([0] + counts)  # len m+1
            cuts = [0]
            for k in range(1, Pn):
                target = total * k / Pn
                j = int(np.argmin(np.abs(prefix - target)))
                j = max(cuts[-1] + 1, min(j, m - (Pn - k)))
                cuts.append(j)
            cuts.append(m)
            ranges = [(p + cuts[k], p + cuts[k + 1]) for k in range(Pn)]
        else:
            base, rem = divmod(m, Pn)
            ranges, s = [], 0
            for k in range(Pn):
                cnt = base + (1 if k < rem else 0)
                ranges.append((p + s, p + s + cnt))
                s += cnt
        return p, q, ranges

    # ------------------------------------------------------------------
    def apply(self, params, batch, train: bool = True, rng=None):
        """batch: flat (inputs, labels) with global batch dim B — always split
        into ``self.num_micro`` microbatches (pre-microbatched input is NOT
        inferred: a flat B that happens to equal num_micro is ambiguous)."""
        params = PipelinedLM._cpu_safe(params)
        inputs, labels = batch
        M = self.num_micro
        if inputs.shape[0] % M:
            raise ValueError(f"batch {inputs.shape[0]} not divisible by {M} microbatches")
        inputs = inputs.reshape((M, inputs.shape[0] // M) + inputs.shape[1:])
        labels = labels.reshape((M, labels.shape[0] // M) + labels.shape[1:])
        if self._heterogeneous:
            return self._apply_heterogeneous(params, inputs, labels)
        layer = self._built[0]

        def first_fn(p, feed_t):
            return feed_t[0].astype(jax.tree.leaves(p["stages"])[0].dtype)

        def stage_fn(stage_params, state, feed_t, rng_t):
            def body(h, lp):
                return layer.apply(lp, h), None

            out, _ = jax.lax.scan(body, state, stage_params)
            return out, jnp.zeros((), jnp.float32)

        def last_fn(p, state, feed_t):
            loss = self.loss_fn(state, feed_t[1])
            return loss.astype(jnp.float32), jnp.asarray(1.0, jnp.float32)

        loss, _ = spmd_pipeline(
            first_fn, stage_fn, last_fn, params, (inputs, labels),
            mesh=self.topology.mesh, num_micro=self.num_micro,
        )
        return loss

    def _apply_heterogeneous(self, params, inputs, labels):
        """Arbitrary LayerSpec lists (+ TiedLayerSpec), two storage modes:

        - plan (constructed with ``example_input``): untied middle layers'
          params live flat-packed in per-stage rows SHARDED over the pipe axis
          (each stage holds ≈ its share — reference ``_partition_layers``
          memory behavior); tied/prefix/suffix replicate and their cotangents
          psum across the pipe axis (ReduceTiedGrads).
        - no plan: everything replicated — the always-available functional
          fallback.

        Compute uses per-layer ownership gating either way (every stage traces
        all middle layers; non-owned outputs AND their cotangents are gated to
        zero) — the homogeneous stacked path remains the performance mode."""
        mb0 = jax.eval_shape(lambda a: a[0], inputs)
        if self._plan:
            p_end, q_start = self._plan["p_end"], self._plan["q_start"]
            ranges = self._plan["ranges"]
        else:
            p_end, q_start, ranges = self._analyze(params, mb0)

        def run_range(pp, h, lo, hi):
            for i in range(lo, hi):
                h = self._built[i].apply(self._layer_params(pp, i), h)
            return h

        def first_fn(pp, feed_t):
            return run_range(pp, feed_t[0], 0, p_end)

        stage_of = {}
        for k, (lo, hi) in enumerate(ranges):
            for i in range(lo, hi):
                stage_of[i] = k

        plan = self._plan

        def middle_params(seg, pp, i):
            if plan and i in plan["offsets"]:
                return self._unpack_layer(seg, i)
            return self._layer_params(pp, i)

        def stage_fn(seg_pp, state, feed_t, rng_t):
            # per-layer gating instead of lax.switch (switch inside the
            # pipeline scan transpose crashes XLA's CPU backend): every stage
            # applies only its own layers, passing the state through
            # elsewhere.
            if plan:
                # (local flat rows already unwrapped to (E,), replicated rest)
                seg, pp = seg_pp
            else:
                seg, pp = None, seg_pp
            sid = jax.lax.axis_index("pipe")
            h = state
            for i in range(p_end, q_start):
                y = self._built[i].apply(middle_params(seg, pp, i), h)
                own = (sid == stage_of[i])
                h = jax.tree.map(
                    lambda a, b: jnp.where(own, a, b), y, h)
            return h, jnp.zeros((), jnp.float32)

        def last_fn(pp, state, feed_t):
            out = run_range(pp, state, q_start, len(self._built))
            loss = self.loss_fn(out, feed_t[1])
            return loss.astype(jnp.float32), jnp.asarray(1.0, jnp.float32)

        # remat=False: jax.checkpoint of a lax.switch body segfaults XLA's CPU
        # backend in the transpose (the het path targets functionality; the
        # homogeneous stacked path keeps tick-level remat)
        loss, _ = spmd_pipeline(
            first_fn, stage_fn, last_fn, params, (inputs, labels),
            mesh=self.topology.mesh, num_micro=self.num_micro, remat=False,
            pass_full_params=bool(plan), hetero=True,
        )
        return loss


class PipelinedLM:
    """Pipeline-parallel wrapper of a ``TransformerLM``.

    Presents the engine model protocol; ``apply`` consumes the FULL global batch
    (all microbatches) and returns the mean LM loss — the pipeline schedule is
    one compiled program (see ``spmd.py``).
    """

    _remat_note_logged = False

    def __init__(self, model, num_stages: Optional[int] = None, topology=None):
        from ...models.transformer import TransformerLM

        assert isinstance(model, TransformerLM), "PipelinedLM wraps a TransformerLM"
        self.model = model
        self.config = model.config
        topo = topology or get_topology()
        self.topology = topo
        self.num_stages = num_stages or topo.pipe_parallel_size
        if model.config.num_layers % self.num_stages:
            raise ValueError(
                f"{model.config.num_layers} layers not divisible by "
                f"{self.num_stages} pipeline stages"
            )
        if model.config.looped:
            raise NotImplementedError(
                f"loop_steps {model.config.loop_steps}: the pipeline's stages "
                "walk the layers once")
        self.num_micro = 1  # set by the engine

    # ------------------------------------------------------------------
    def init_params(self, rng):
        params = self.model.init_params(rng)
        L, Pn = self.config.num_layers, self.num_stages
        params["blocks"] = jax.tree.map(
            lambda a: a.reshape((Pn, L // Pn) + a.shape[1:]), params["blocks"]
        )
        return params

    @property
    def tp_specs(self):
        specs = self.model.tp_specs
        # blocks keep their TP entries shifted right by the new pipe dim
        specs["blocks"] = jax.tree.map(
            lambda s: P("pipe", *tuple(s)),
            specs["blocks"],
            is_leaf=lambda s: isinstance(s, P),
        )
        # vocab-parallel embedding gathers inside the (partial-manual) pipeline
        # shard_map crash XLA's SPMD partitioner (PartitionGather check);
        # embeddings are replicated across TP here — like across stages
        if "wte" in specs:
            specs["wte"] = P(*([None] * len(specs["wte"])))
        if "lm_head" in specs:
            specs["lm_head"] = P(*([None] * len(specs["lm_head"])))
        return specs

    # ------------------------------------------------------------------
    @staticmethod
    def _cpu_safe(params):
        """XLA's CPU backend crashes ('Invalid binary instruction opcode copy')
        when transposing bf16 matmuls inside the scan+ppermute pipeline body;
        compute in fp32 on CPU (tests/dryrun), bf16 stays bf16 on TPU. The
        astype is differentiable, so cotangents come back in the lp dtype."""
        if jax.default_backend() != "cpu":
            return params
        return jax.tree.map(
            lambda a: a.astype(jnp.float32) if a.dtype == jnp.bfloat16 else a, params
        )

    def apply(self, params, batch, train: bool = True, rng=None):
        cfg = self.config
        m = self.model
        params = self._cpu_safe(params)
        positions = None
        if isinstance(batch, dict):
            input_ids = batch["input_ids"]
            labels = batch.get("labels")
            positions = batch.get("positions")
        elif isinstance(batch, (tuple, list)):
            input_ids, labels = batch
        else:
            input_ids, labels = batch, None
        if labels is None:
            labels = jnp.concatenate(
                [input_ids[:, 1:], jnp.full_like(input_ids[:, :1], -100)], axis=1
            )
        M = self.num_micro
        B = input_ids.shape[0]
        S = input_ids.shape[1]
        if B % M:
            raise ValueError(f"global batch {B} not divisible by {M} microbatches")
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        ids_mb = input_ids.reshape(M, B // M, S)
        lbl_mb = labels.reshape(M, B // M, S)
        pos_mb = positions.reshape(M, B // M, S)

        pipeline_params = {
            "stages": params["blocks"],
            "rest": {k: v for k, v in params.items() if k != "blocks"},
        }

        def first_fn(p, feed_t):
            ids, pos = feed_t[0], feed_t[2]
            x = m._embed(p["rest"], ids, pos, p["rest"]["wte"].dtype)
            return m._constraint(x, m._act_spec(True))

        def stage_fn(stage_params, state, feed_t, rng_t):
            pos = feed_t[2]
            n_local = jax.tree.leaves(stage_params)[0].shape[0]
            rngs = None if rng_t is None else jax.random.split(rng_t, n_local)

            def body(carry, layer):
                h, aux = carry
                blk, r = (layer, None) if rngs is None else layer
                y, _, a = m._block(h, blk, positions=pos, rng=r, train=train)
                return (y, aux + a), None

            body_fn = jax.checkpoint(body) if cfg.remat else body
            xs = stage_params if rngs is None else (stage_params, rngs)
            (out, aux), _ = jax.lax.scan(
                body_fn, (state, jnp.zeros((), jnp.float32)), xs
            )
            return out, aux

        def last_fn(p, state, feed_t):
            lbl = feed_t[1]
            lg = m._head(p["rest"], state).astype(jnp.float32)
            mask = lbl != -100
            safe = jnp.where(mask, lbl, 0)
            logz = jax.scipy.special.logsumexp(lg, axis=-1)
            gold = jnp.take_along_axis(lg, safe[..., None], axis=-1)[..., 0]
            nll = (logz - gold) * mask
            return jnp.sum(nll), jnp.sum(mask).astype(jnp.float32)

        use_rng = rng is not None and cfg.dropout > 0 and train
        # remat=False here: stage_fn already checkpoints PER LAYER (body_fn
        # above); wrapping the tick as well nests remats, and the backward
        # then recomputes every forward twice — measured bwd/fwd 4.8 vs the
        # per-layer scheme's 4.0, the whole gap to ideal 1F1B efficiency
        # (r3 pipe row 0.75 → ~0.97 without the double wrap). cfg.remat on
        # the pipe path therefore means PER-LAYER checkpointing only;
        # tick-level remat is intentionally unavailable (logged once below).
        if cfg.remat and not PipelinedLM._remat_note_logged:
            PipelinedLM._remat_note_logged = True
            log_dist(
                "PipelinedLM: remat applies per-layer inside each stage "
                "(tick-level remat would nest and double backward recompute); "
                "activation memory per stage is O(microbatches) — see "
                "runtime/pipe/spmd.py docstring for the tradeoff", ranks=[0])
        loss, aux = spmd_pipeline(
            first_fn, stage_fn, last_fn, pipeline_params, (ids_mb, lbl_mb, pos_mb),
            mesh=self.topology.mesh, num_micro=M, remat=False,
            rng=rng if use_rng else None,
        )
        if cfg.num_experts > 0:
            loss = loss + cfg.moe_aux_loss_coef * aux
        return loss
