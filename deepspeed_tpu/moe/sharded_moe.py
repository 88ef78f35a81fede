"""Top-k gating + expert dispatch (reference ``deepspeed/moe/sharded_moe.py``:
``top1gating:249``, ``top2gating:367 TopKGate``, ``_AllToAll:95``, ``MOELayer:444``).

TPU-native design: GShard-style *dense dispatch*. Instead of the reference's
boolean-index + all-to-all of token buffers, tokens are routed with one-hot
combine/dispatch einsum tensors of static shape (tokens, experts, capacity) —
XLA lowers the expert-axis resharding to the same all-to-all over ICI, but the
whole layer stays static-shaped and fusible. Capacity overflow drops tokens
exactly like the reference's capacity mechanism.
"""

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax


def _one_hot(x, n):
    return jax.nn.one_hot(x, n, dtype=jnp.float32)


def compute_capacity(num_tokens: int, num_experts: int, capacity_factor: float,
                     min_capacity: int = 4, k: int = 1) -> int:
    """Static per-expert buffer size (reference ``_capacity``, sharded_moe.py:90)."""
    cap = int(math.ceil(k * num_tokens / num_experts * capacity_factor))
    return max(cap, min_capacity)


def topk_gating(
    logits,
    k: int = 1,
    capacity_factor: float = 1.0,
    min_capacity: int = 4,
    drop_tokens: bool = True,
    rng: Optional[jax.Array] = None,
    noise_eps: float = 0.0,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, dict]:
    """Route each token to its top-k experts under a capacity limit.

    logits: (T, E) router scores. Returns (combine (T,E,C) fp32, dispatch (T,E,C)
    bool, l_aux scalar, metadata). Math follows the reference's top1/top2 gating:
    softmax gates, per-expert position by arrival order with earlier-choice
    priority, load-balancing aux loss ``E · Σ_e mean(gates_e) · mean(dispatch_e)``.
    """
    T, E = logits.shape
    logits = logits.astype(jnp.float32)
    if noise_eps > 0.0 and rng is not None:
        logits = logits + jax.random.normal(rng, logits.shape) * noise_eps
    gates = jax.nn.softmax(logits, axis=-1)

    C = compute_capacity(T, E, capacity_factor, min_capacity, k) if drop_tokens else T

    topv, topi = lax.top_k(gates, k)  # (T, k)
    if k > 1:
        denom = jnp.sum(topv, axis=-1, keepdims=True)
        topv = topv / jnp.maximum(denom, 1e-9)

    # choice-priority positions: all 1st choices claim slots before 2nd choices
    masks = [_one_hot(topi[:, j], E) for j in range(k)]  # each (T, E)
    prior = jnp.zeros((E,), jnp.float32)
    combine = jnp.zeros((T, E, C), jnp.float32)
    dispatch = jnp.zeros((T, E, C), bool)
    for j in range(k):
        m = masks[j]
        pos = jnp.cumsum(m, axis=0) - 1.0 + prior[None, :]  # slot per (token, expert)
        prior = prior + jnp.sum(m, axis=0)
        keep = m * (pos < C)
        slot = jnp.clip(pos, 0, C - 1).astype(jnp.int32)
        oh = _one_hot(slot, C) * keep[..., None]  # (T, E, C)
        combine = combine + oh * topv[:, j][:, None, None]
        dispatch = dispatch | (oh > 0)

    # load-balancing loss on first-choice routing (reference top1/2 l_aux)
    me = jnp.mean(gates, axis=0)
    ce = jnp.mean(masks[0], axis=0)
    l_aux = jnp.sum(me * ce) * E

    meta = {
        "tokens_per_expert": prior,
        "dropped_fraction": 1.0 - jnp.sum(dispatch.astype(jnp.float32)) / (T * k),
        "capacity": C,
    }
    return combine, dispatch, l_aux, meta


def group_limited_gating(logits, bias=None, *, k: int, n_group: int = 1,
                         topk_group: int = 1, normalize: bool = True,
                         scale: float = 1.0):
    """DeepSeek-V3 routing (arXiv:2412.19437 section 2.1.2, ``noaux_tc``):
    every token picks ``k`` of the router's ``E`` outputs, no capacity and no
    drop. Returns (chosen (T, k) int32 expert ids, weights (T, k) float32).

    Scores ``s`` are the sigmoid of the float32 ``logits`` (T, E). ``bias`` (E,) is added for SELECTION only. The outputs form
    ``n_group`` contiguous groups; a group scores the sum of its two largest
    biased scores, the ``topk_group`` best groups are kept and the ``k``
    largest biased scores are taken among their experts (a tie goes to the
    lower index, among groups as among experts). The weights are the
    unbiased ``s`` of the chosen, divided by their sum over all ``k`` (held
    on this chip or not) and multiplied by ``scale``."""
    T, E = logits.shape
    logits = logits.astype(jnp.float32)
    s = jax.nn.sigmoid(logits)
    biased = s if bias is None else s + bias.astype(jnp.float32)
    if n_group > 1:
        # nothing is sorted to choose the groups (on the chip a ``top_k``
        # is a sort of every score): a group's two largest are its maximum
        # and the maximum with ONE occurrence of it taken out
        by_group = biased.reshape(T, n_group, E // n_group)
        m1 = jnp.max(by_group, axis=-1)
        top = by_group == m1[:, :, None]
        m2 = jnp.where(jnp.sum(top, axis=-1) > 1, m1,
                       jnp.max(jnp.where(top, -jnp.inf, by_group), axis=-1))
        group_score = m1 + m2                                   # (T, n_group)
        # a group is kept iff fewer than ``topk_group`` stand ahead of it:
        # a larger score, or an equal one at a lower index (``top_k``'s order)
        mine, other = group_score[:, :, None], group_score[:, None, :]
        g = jnp.arange(n_group)
        ahead = (other > mine) | ((other == mine) & (g[None, :] < g[:, None]))
        group_ok = jnp.sum(ahead, axis=-1) < topk_group
        biased = jnp.where(jnp.repeat(group_ok, E // n_group, axis=1),
                           biased, -jnp.inf)
    chosen = lax.top_k(biased, k)[1].astype(jnp.int32)
    w = jnp.take_along_axis(s, chosen, axis=1)
    if normalize:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return chosen, w * scale


def _first_k(scores, k: int):
    """(T, k) int32: each row's ``k`` largest of ``scores`` (T, E) in
    ``lax.top_k``'s order, a tie to the lower index, as ``k`` rounds of the
    first ``argmax`` and a mask. Every row must hold ``k`` scores above
    ``-inf``. On the chip a ``top_k`` is a sort of the whole array: at 12 of
    768 the rounds take 14 us where it takes 37; at 8 of 256 or fewer the
    sort wins (``examples/kernels/route_alone.py``)."""
    cols = jnp.arange(scores.shape[1], dtype=jnp.int32)[None, :]
    picks = []
    for _ in range(k):
        i = jnp.argmax(scores, axis=-1).astype(jnp.int32)
        picks.append(i)
        scores = jnp.where(cols == i[:, None], -jnp.inf, scores)
    return jnp.stack(picks, axis=1)


def softmax_topk_gating(logits, bias=None, *, k: int, normalize: bool = False,
                        scale: float = 1.0):
    """LongCat-Flash routing: every token picks ``k`` of the router's ``E``
    outputs (real and identity experts alike), no groups, no capacity, no
    drop. Returns (chosen (T, k) int32 output ids, weights (T, k) float32).

    Scores ``p`` are the softmax of the float32 ``logits`` (T, E). ``bias``
    (E,) is added for SELECTION only and is held in units of the uniform
    score ``1 / E`` (the published bias is of the scores' own size: a loader
    multiplies the checkpoint's by ``E``). The weights are the unbiased ``p``
    of the chosen times ``scale``, divided by their sum only if
    ``normalize``."""
    E = logits.shape[1]
    p = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    biased = p if bias is None else p + bias.astype(jnp.float32) / E
    chosen = _first_k(biased, k)
    w = jnp.take_along_axis(p, chosen, axis=1)
    if normalize:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return chosen, w * scale
