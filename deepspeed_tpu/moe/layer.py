"""MoE layer — experts sharded over the ``expert`` mesh axis.

Reference: ``deepspeed/moe/layer.py`` (``MoE:17``, ``set_deepspeed_parallelism``),
``experts.py:13 Experts``, dispatch via ``_AllToAll`` (``sharded_moe.py:95``).

GShard-style **group-wise dense dispatch**: tokens keep a leading group dim
(one group per sequence) sharded over the data axes, experts are sharded over
the ``expert`` axis, and capacity is per-group — so the one-hot combine/dispatch
tensors are O(S²·k·cf/E) per group instead of O((B·S)²) global, and the expert
FFN is *not* replicated across data shards. XLA lowers the group→expert
resharding between the dispatch einsum and the expert matmuls to the same token
all-to-all the reference issues explicitly over its EP process group.
"""

from functools import partial
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..utils import tracing
from .sharded_moe import (group_limited_gating, softmax_topk_gating,
                          topk_gating)

# the held experts' parts, told apart on the device: routing, the grouped
# products, the shared expert, the identity experts
tracing.layer_scopes("moe_route", "moe_experts", "moe_shared", "moe_zero")


def _constraint(x, spec):
    try:
        return jax.lax.with_sharding_constraint(x, spec)
    except (ValueError, RuntimeError):
        return x


def routed_ffn(x, wg, wi, wo, wgate=None, *, k: int = 1,
               capacity_factor: float = 1.25, min_capacity: int = 4,
               drop_tokens: bool = True, activation: str = "gelu",
               expert_axis: str = "expert", data_axes=("data", "hpz"),
               rng: Optional[jax.Array] = None, noise_eps: float = 0.0):
    """Shared routed-FFN core (used by ``MoE`` and ``TransformerLM``).

    x: (G, S, H) tokens grouped by leading dim (typically one group per
    sequence). wg: (H, E); wi/wgate: (E, H, I); wo: (E, I, H).
    Returns (y (G,S,H), l_aux scalar).
    """
    G, S, H = x.shape
    logits = x.astype(jnp.float32) @ wg.astype(jnp.float32)  # (G, S, E)
    gate = partial(topk_gating, k=k, capacity_factor=capacity_factor,
                   min_capacity=min_capacity, drop_tokens=drop_tokens,
                   noise_eps=noise_eps)
    if noise_eps > 0.0 and rng is not None:
        rngs = jax.random.split(rng, G)
        combine, dispatch, l_aux, _ = jax.vmap(lambda l, r: gate(l, rng=r))(logits, rngs)
    else:
        combine, dispatch, l_aux, _ = jax.vmap(lambda l: gate(l, rng=None))(logits)
    # combine/dispatch: (G, S, E, C); group dim rides the data axes, expert dim
    # the expert axis — XLA inserts the token all-to-all at this boundary
    expert_in = jnp.einsum("gsh,gsec->gech", x.astype(jnp.float32),
                           dispatch.astype(jnp.float32)).astype(x.dtype)
    expert_in = _constraint(expert_in, P(data_axes, expert_axis, None, None))
    h = jnp.einsum("gech,ehi->geci", expert_in, wi.astype(x.dtype))
    if activation == "swiglu":
        g = jnp.einsum("gech,ehi->geci", expert_in, wgate.astype(x.dtype))
        h = jax.nn.silu(g) * h
    elif activation == "silu":
        h = jax.nn.silu(h)
    else:
        h = jax.nn.gelu(h, approximate=True)
    expert_out = jnp.einsum("geci,eih->gech", h, wo.astype(x.dtype))
    expert_out = _constraint(expert_out, P(data_axes, expert_axis, None, None))
    y = jnp.einsum("gech,gsec->gsh", expert_out.astype(jnp.float32), combine)
    return y.astype(x.dtype), jnp.mean(l_aux).astype(jnp.float32)


def _gated_mlp(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate.astype(x.dtype))
            * (x @ w_up.astype(x.dtype))) @ w_down.astype(x.dtype)


#: The most rows one tile of the grouped product holds; a step of no more
#: rows than this IS one tile (:func:`grouped_experts`).
EXPERT_TILE_ROWS = 128


def _expert_tile(pairs: int, experts: int) -> int:
    """Rows of one tile of the grouped product: about an expert's even share
    of ``pairs``, a power of two in [8, ``EXPERT_TILE_ROWS``]."""
    share = max(1, pairs // max(experts, 1))
    return min(EXPERT_TILE_ROWS, max(8, 1 << (share - 1).bit_length()))


def _expert_matrices(ws, e, layer):
    """Expert ``e``'s (up, gate, down) of ``ws`` = (wi, w_gate, w_down);
    stacked leaves are indexed by (``layer``, e) where they lie."""
    if layer is None:
        return tuple(a[e] for a in ws)
    return tuple(
        jax.lax.dynamic_slice(a, (layer, e, 0, 0), (1, 1) + a.shape[2:])
        .reshape(a.shape[2:]) for a in ws)


def _touched_sum(live_trips_only, x, c, on, touched, n_live, ws, layer):
    """float32 (T, H): the sum over the first ``n_live`` experts ``e`` of
    ``touched`` of ``c[:, e] * expert_e(x)``, every row through every such
    expert. The loop runs the live trips alone, or (differentiable) is a
    scan over all of ``touched`` whose trips past ``n_live`` hand ``y`` on."""
    def trip(i, y):
        e = jax.lax.dynamic_index_in_dim(touched, i, keepdims=False)
        up, gate, down = _expert_matrices(ws, e, layer)
        out = _gated_mlp(x, gate, up, down).astype(jnp.float32)
        # a row that did not choose e adds nothing, whatever came out of e's
        # product for it (0 * inf is not 0)
        return y + jnp.where(jax.lax.dynamic_slice_in_dim(on, e, 1, axis=1),
                             jax.lax.dynamic_slice_in_dim(c, e, 1, axis=1)
                             * out, 0.0)

    y = jnp.zeros(x.shape, jnp.float32)
    if live_trips_only:
        return jax.lax.fori_loop(0, n_live, trip, y)
    return jax.lax.scan(
        lambda y, i: (jax.lax.cond(i < n_live, partial(trip, i),
                                   lambda y: y, y), None),
        y, jnp.arange(touched.shape[0], dtype=jnp.int32))[0]


# forward: a loop of n_live trips (on the chip a dead trip of the scan costs
# 1.4-2.3 us and its cond 5-13 us a LIVE trip, the carry copied in and out);
# backward: the scan's, which reverse mode can differentiate
_touched_experts = jax.custom_vjp(partial(_touched_sum, True))
_touched_experts.defvjp(
    lambda *args: (_touched_sum(True, *args), args),
    lambda args, g: jax.vjp(partial(_touched_sum, False), *args)[1](g))


def grouped_experts(x, chosen, weights, wi, w_gate, w_down, *, first: int = 0,
                    layer=None):
    """The held experts' part of a routed gated-SiLU FFN, by grouped matrix
    products: no one-hot dispatch, no capacity, no dropped token.

    x (T, H); ``chosen`` / ``weights`` (T, k): each token's expert ids over
    the ROUTER's outputs and its combine weights. ``wi`` / ``w_gate`` /
    ``w_down`` hold the experts ``first .. first + E - 1``: (E, H, I) /
    (E, I, H), or with ``layer`` (an int32 scalar) the stacked
    (L, E, ...) leaves, indexed by (layer, expert) where they lie. Returns
    (y (T, H) in x's dtype, (rows, rows_max) int32: the (token, choice) pairs
    that landed on held experts, and the busiest expert's).

    One algorithm: each expert's matrices times the rows that chose it, a
    tile of rows at a time, the weighted results summed per token in float32.
    The pairs that land here are sorted by expert and laid into a row buffer
    in which every expert's rows start on a tile boundary, so that each tile
    of ``tile`` rows belongs to one expert: one pass over the tiles multiplies
    each by its expert's matrices (a tile no pair reached is skipped, so the
    cost follows the rows that landed, and an expert no token chose is never
    read). The weighted rows are gathered back per (token, choice) and
    summed in float32.

    Where one tile holds the whole step (``T <= EXPERT_TILE_ROWS``: a decode
    round) the tiling degenerates: the step's rows ARE each touched expert's
    tile, so nothing is sorted over the pairs, laid into a buffer or
    gathered back. One loop over the touched experts multiplies each one's
    matrices by all ``T`` rows (the weights' bytes set what such a product
    costs, not its rows) and adds the result at the (T, E) combine weight,
    masked where a row did not choose the expert. The shape alone decides.

    Differentiable either way, so a model of held experts trains through
    it; the GShard models' training still goes through :func:`routed_ffn`'s
    one-hot dispatch."""
    T, H = x.shape
    ws = (wi, w_gate, w_down)
    E = wi.shape[0] if layer is None else wi.shape[1]
    if T <= EXPERT_TILE_ROWS:
        with jax.named_scope("moe_route"):
            hit = (chosen.astype(jnp.int32)[:, :, None] - first
                   == jnp.arange(E))                               # (T, k, E)
            # a token that picks one expert twice adds both weights
            c = jnp.sum(jnp.where(hit, weights.astype(jnp.float32)[:, :, None],
                                  0.0), axis=1)                    # (T, E)
            counts = jnp.sum(hit, axis=(0, 1), dtype=jnp.int32)    # (E,)
            touched = jnp.argsort(counts == 0, stable=True).astype(jnp.int32)
        with jax.named_scope("moe_experts"):
            y = _touched_experts(x, c, jnp.any(hit, axis=1), touched,
                                 jnp.sum(counts > 0, dtype=jnp.int32), ws,
                                 layer)
        return y.astype(x.dtype), (jnp.sum(counts), jnp.max(counts))

    k = chosen.shape[1]
    pairs = T * k
    k_here = min(k, E)
    tile = _expert_tile(T * k_here, E)
    # rows the buffer must hold: every landed pair, plus each expert's
    # padding to a tile boundary
    R = -(-T * k_here // tile) * tile + E * tile
    with jax.named_scope("moe_route"):
        local = chosen.astype(jnp.int32) - first
        here = (local >= 0) & (local < E)
        key = jnp.where(here, local, E).reshape(pairs)
        order = jnp.argsort(key, stable=True).astype(jnp.int32)
        sorted_key = key[order]
        counts = jnp.sum(key[:, None] == jnp.arange(E)[None, :], axis=0,
                         dtype=jnp.int32)                       # (E,)
        padded = -(-counts // tile) * tile
        row0 = jnp.cumsum(padded) - padded      # first row of each expert
        rank0 = jnp.cumsum(counts) - counts     # first sorted pair of each
        e_c = jnp.minimum(sorted_key, E - 1)
        dest = row0[e_c] + jnp.arange(pairs, dtype=jnp.int32) - rank0[e_c]
        dest = jnp.where(sorted_key < E, dest, R)     # absent: past the end
        row_token = jnp.full((R,), T, jnp.int32).at[dest].set(
            order // k, mode="drop", unique_indices=True)
        pair_row = jnp.zeros((pairs,), jnp.int32).at[order].set(
            dest, unique_indices=True).reshape(T, k)
        xs = jnp.concatenate([x, jnp.zeros((1, H), x.dtype)])[row_token]
        tile_row0 = jnp.arange(R // tile, dtype=jnp.int32) * tile
        tile_e = jnp.searchsorted(row0 + padded, tile_row0,
                                  side="right").astype(jnp.int32)
        tile_ec = jnp.minimum(tile_e, E - 1)
        live = (tile_e < E) & (tile_row0 < (row0 + counts)[tile_ec])

    def one_tile(_, t):
        e, is_live, xt = t

        def run(xt):
            up, gate, down = _expert_matrices(ws, e, layer)
            return _gated_mlp(xt, gate, up, down)

        return None, jax.lax.cond(is_live, run, jnp.zeros_like, xt)

    with jax.named_scope("moe_experts"):
        _, ys = jax.lax.scan(one_tile, None,
                             (tile_ec, live, xs.reshape(R // tile, tile, H)))
    with jax.named_scope("moe_route"):
        # a pair that landed nowhere adds nothing: it reads row 0 at weight
        # zero (a zero row appended for it to read was a copy of the whole
        # (R, H) buffer, 88 MB a layer in a 512-row step)
        landed = pair_row < R
        rows = ys.reshape(R, H)[jnp.where(landed, pair_row, 0)]
        y = jnp.einsum("tkh,tk->th", rows.astype(jnp.float32),
                       jnp.where(landed, weights.astype(jnp.float32), 0.0))
    return y.astype(x.dtype), (jnp.sum(counts), jnp.max(counts))


def held_experts_ffn(x, wg, bias, wi, w_gate, w_down, shared=None, *, k: int,
                     n_group: int = 1, topk_group: int = 1,
                     normalize: bool = True, scale: float = 1.0,
                     first: int = 0, layer=None, token_mask=None,
                     router: str = "group_limited", zero_experts: int = 0):
    """One routed layer that is told which experts it holds: the router
    (``wg`` (H, E_all), selection ``bias`` (E_all,)) runs over ALL its
    outputs in float32 (``router``: :func:`group_limited_gating` or
    :func:`softmax_topk_gating`), the layer computes its
    own experts' part for the tokens routed to them
    (:func:`grouped_experts`, whose form follows from T: a decode round's
    rows are one tile; ``first`` is the router output of the first
    held expert) and adds the ``shared`` expert ((w_gate, w_up, w_down),
    which every chip of the deployment computes alike). What the absent
    experts would have added is left out: there is no exchange here and
    nothing stands in for one. x (T, H); ``token_mask`` (T,) bool: rows that
    are padding route nowhere. Returns (y, (rows, rows_max)).

    The router's last ``zero_experts`` outputs are identity (zero-compute)
    experts: a token's choices among them add ``(sum of their weights) * x``,
    computed where the token lives (no matrix, no row of the grouped
    product, no exchange in a deployment). The counts are then (rows,
    rows_max, zero_picks): the live rows' choices that went to them."""
    with jax.named_scope("moe_route"):
        logits = jnp.dot(x.astype(jnp.float32), wg.astype(jnp.float32),
                         precision=jax.lax.Precision.HIGHEST)
        if router == "softmax_topk":
            chosen, weights = softmax_topk_gating(
                logits, bias, k=k, normalize=normalize, scale=scale)
        elif router == "group_limited":
            chosen, weights = group_limited_gating(
                logits, bias, k=k, n_group=n_group, topk_group=topk_group,
                normalize=normalize, scale=scale)
        else:
            raise ValueError(f"held_experts_ffn: unknown router {router!r}")
        if token_mask is not None:
            chosen = jnp.where(token_mask[:, None], chosen, -1)
    y, stats = grouped_experts(x, chosen, weights, wi, w_gate, w_down,
                               first=first, layer=layer)
    if zero_experts:
        with jax.named_scope("moe_zero"):
            to_zero = chosen >= wg.shape[1] - zero_experts
            w0 = jnp.sum(jnp.where(to_zero, weights, 0.0), axis=1)
            y = (y.astype(jnp.float32)
                 + w0[:, None] * x.astype(jnp.float32)).astype(x.dtype)
            stats = (*stats, jnp.sum(to_zero, dtype=jnp.int32))
    if shared is not None:
        with jax.named_scope("moe_shared"):
            y = y + _gated_mlp(x, *shared)
    return y, stats


def residual_mix(x, moe_out, mlp_wi, mlp_wo, coef_w, coef_b, *,
                 activation: str = "gelu", mlp_wgate=None):
    """Residual-MoE combine (PR-MoE, arXiv:2201.05596; reference
    ``moe/layer.py:125-132``): run a dense MLP on the same input and blend
    ``coef[...,0]·moe_out + coef[...,1]·mlp_out`` with
    ``coef = softmax(x @ coef_w + coef_b)`` learned per token."""
    h = x @ mlp_wi.astype(x.dtype)
    if activation == "swiglu" and mlp_wgate is not None:
        h = jax.nn.silu(x @ mlp_wgate.astype(x.dtype)) * h
    elif activation == "silu":
        h = jax.nn.silu(h)
    else:
        h = jax.nn.gelu(h, approximate=True)
    mlp_out = h @ mlp_wo.astype(x.dtype)
    coef = jax.nn.softmax(
        x.astype(jnp.float32) @ coef_w.astype(jnp.float32)
        + coef_b.astype(jnp.float32), axis=-1).astype(x.dtype)
    return moe_out * coef[..., 0:1] + mlp_out * coef[..., 1:2]


class MoE:
    """Functional MoE FFN: router + E experts (2-layer MLP, gelu/silu/swiglu).

    Engine/model protocol: ``init_params(rng) -> params``, ``apply(params, x,
    train, rng) -> (y, l_aux)``, ``tp_specs`` property.
    """

    def __init__(self, hidden_size: int, num_experts: int, expert_intermediate_size: int,
                 k: int = 1, capacity_factor: float = 1.25,
                 eval_capacity_factor: float = 1.0, min_capacity: int = 4,
                 drop_tokens: bool = True, activation: str = "gelu",
                 noisy_gate_policy: Optional[str] = None,
                 use_residual: bool = False,
                 expert_axis: str = "expert", model_axis: str = "model",
                 data_axes=("data", "hpz")):
        self.use_residual = use_residual
        self.hidden_size = hidden_size
        self.num_experts = num_experts
        self.inter = expert_intermediate_size
        self.k = k
        self.capacity_factor = capacity_factor
        self.eval_capacity_factor = eval_capacity_factor
        self.min_capacity = min_capacity
        self.drop_tokens = drop_tokens
        self.activation = activation
        self.noisy_gate_policy = noisy_gate_policy
        self.expert_axis = expert_axis
        self.model_axis = model_axis
        self.data_axes = data_axes

    # ------------------------------------------------------------------
    def init_params(self, rng) -> Dict[str, Any]:
        H, E, I = self.hidden_size, self.num_experts, self.inter
        # split stays at 4 — widening it would silently shift k1-k4 and change
        # every existing seeded MoE init; residual keys derive via fold_in
        k1, k2, k3, k4 = jax.random.split(rng, 4)
        k5, k6, k7 = (jax.random.fold_in(k4, i) for i in (1, 2, 3))
        init = jax.nn.initializers.normal(0.02)
        p = {
            "wg": init(k1, (H, E), jnp.float32),  # router
            "wi": init(k2, (E, H, I), jnp.float32),
            "wo": init(k3, (E, I, H), jnp.float32),
        }
        if self.activation == "swiglu":
            p["wgate"] = init(k4, (E, H, I), jnp.float32)
        if self.use_residual:
            # Residual/PR-MoE (arXiv:2201.05596; reference moe/layer.py:80-84):
            # a dense MLP runs alongside the routed experts and a learned
            # 2-way coefficient (Linear(H,2) + softmax) mixes the two outputs
            p["mlp_wi"] = init(k5, (H, I), jnp.float32)
            p["mlp_wo"] = init(k6, (I, H), jnp.float32)
            p["coef_w"] = init(k7, (H, 2), jnp.float32)
            p["coef_b"] = jnp.zeros((2,), jnp.float32)
            if self.activation == "swiglu":
                p["mlp_wgate"] = init(
                    jax.random.fold_in(k5, 1), (H, I), jnp.float32)
        return p

    @property
    def tp_specs(self) -> Dict[str, Any]:
        e, m = self.expert_axis, self.model_axis
        specs = {
            "wg": P(None, None),
            "wi": P(e, None, m),
            "wo": P(e, m, None),
        }
        if self.activation == "swiglu":
            specs["wgate"] = P(e, None, m)
        if self.use_residual:
            specs["mlp_wi"] = P(None, m)
            specs["mlp_wo"] = P(m, None)
            specs["coef_w"] = P(None, None)
            specs["coef_b"] = P(None)
            if self.activation == "swiglu":
                specs["mlp_wgate"] = P(None, m)
        return specs

    # ------------------------------------------------------------------
    def apply(self, params, x, train: bool = True, rng=None):
        """x: (..., H) → (y (..., H), l_aux scalar). Leading dim is the dispatch
        group; a 2-D input becomes a single group."""
        orig_shape = x.shape
        H = orig_shape[-1]
        x3 = x.reshape((orig_shape[0], -1, H) if x.ndim >= 3 else (1, -1, H))
        y, l_aux = routed_ffn(
            x3, params["wg"], params["wi"], params["wo"], params.get("wgate"),
            k=self.k,
            capacity_factor=self.capacity_factor if train else self.eval_capacity_factor,
            min_capacity=self.min_capacity, drop_tokens=self.drop_tokens,
            activation=self.activation, expert_axis=self.expert_axis,
            data_axes=self.data_axes,
            rng=rng if (train and self.noisy_gate_policy) else None,
            noise_eps=1e-2 if self.noisy_gate_policy else 0.0,
        )
        y = y.reshape(orig_shape)
        if self.use_residual:
            y = residual_mix(
                x, y, params["mlp_wi"], params["mlp_wo"],
                params["coef_w"], params["coef_b"],
                activation=self.activation,
                mlp_wgate=params.get("mlp_wgate"))
        return y, l_aux

    def __call__(self, params, x, train=True, rng=None):
        return self.apply(params, x, train=train, rng=rng)
