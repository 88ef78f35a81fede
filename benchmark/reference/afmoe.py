"""AFMoE (Arcee Trinity, ``model_type`` ``afmoe``; here ``arcee-ai/Trinity-Mini``):
grouped-query attention with an RMSNorm on every q and k head and a sigmoid
output gate, window layers (rotary, a query sees the last ``sliding_window``
tokens) beside full ones (no positional term, the whole context), four
RMSNorms a layer (one before and one after each sublayer), leading dense
gated-SiLU layers, then layers of routed experts (sigmoid scores, a
selection-only bias, top-k, weights normalised and times ``route_scale``)
beside one shared expert, the embedding times ``sqrt(hidden_size)`` under
``mup_enabled``, an untied output head::

    a = rms(x; g1);  q, k, v, z = a Wq, a Wk, a Wv, a Wz
    q, k = rms_d(q; gq), rms_d(k; gk)            a head; rotary on a window layer
    o = softmax(q k^T / sqrt(d) + mask) v;  o = o * sigmoid(z)
    x = x + rms(o Wo; g2)
    b = rms(x; g3);  x = x + rms(F(b); g4)

Attention is computed by blocks of queries and by kv heads, every score with
its mask (a window layer's mask is ``0 <= i - j < W`` over the whole row: no
banded shortcut). Every held expert is applied to every token and weighted by
the routing (zero where the token did not choose it).

The chip's share, as ``deepseek_v3``'s: ``wi`` / ``w_gate`` / ``w_down`` hold
the experts ``expert_offset .. expert_offset + E_held - 1`` of the router's
``moe_wg.shape[1]`` outputs; the router, the top-k and the normalisation run
over all outputs, only the held experts' parts are added.

**Which layer is a window layer.** The parts are handed one layer's leaves
and no index (``harness/check.py``), and a window layer's leaves are a full
layer's. So the hidden states carry the layer's index with them: ``embed``
returns (S, H + 1), the last column the index of the layer that comes next
(0), ``feed`` adds one to it, ``final`` drops it, and ``attend`` looks the
type up in ``cfg["layer_types"]`` by it.
"""

import math

import jax
import jax.numpy as jnp

from . import scan_layers
# one group of router outputs (``n_group`` 1) makes ``deepseek_v3``'s
# group-limited choice this model's plain top-k, and its fit of a balanced
# selection bias this model's
from .deepseek_v3 import balanced_bias, gated_mlp, picks, rms_norm, scores
from .gptneox import rotary


def is_window(cfg):
    """Per kept layer: is it a window layer?"""
    return [t == "sliding_attention" for t in cfg["layer_types"]]


def groups(cfg):
    """The stacked layer groups in forward order: a run of layers of one
    type and one feed-forward (the leading dense layers apart) is a group."""
    runs, last = [], None
    for i, window in enumerate(is_window(cfg)):
        kind = (window, i < cfg["num_dense_layers"])
        if kind == last:
            runs[-1][1] += 1
        else:
            runs.append([f"blocks_{len(runs)}", 1])
        last = kind
    return [tuple(r) for r in runs]


def masked_attention(q, k, v, window, bound, ein, q_block=None):
    """q (S, heads, d), k, v (S, kv heads, d) of one sequence; ``window`` a
    traced bool: a query at ``i`` sees ``j <= i``, and of a window layer only
    ``i - j < bound``. One kv head's group of query heads and ``q_block``
    queries at a time: scores of (group, q_block, S)."""
    s, heads, d = q.shape
    kv_heads = k.shape[1]
    g = heads // kv_heads
    cols = jnp.arange(s)[None]

    def rows(q, k, v, first):
        """q (n, g, d) of one kv head ``k``, ``v`` (S, d), from row ``first``."""
        at = (first + jnp.arange(q.shape[0]))[:, None]
        seen = (cols <= at) & (~window | (at - cols < bound))
        scores = ein("qgd,kd->gqk", q, k) / jnp.sqrt(jnp.float32(d))
        probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), axis=-1)
        return ein("gqk,kd->qgd", probs, v)

    def head(args):
        q, k, v = args                                      # (S, g, d), (S, d)
        if q_block is None or q_block >= s:
            return rows(q, k, v, 0)
        out = jax.lax.map(lambda a: rows(a[0], k, v, a[1]), (
            q.reshape(s // q_block, q_block, g, d),
            jnp.arange(0, s, q_block)))
        return out.reshape(s, g, d)

    if q_block is not None and q_block < s and s % q_block:
        raise ValueError(f"q_block {q_block} does not divide the length {s}")
    out = jax.lax.map(head, (
        q.reshape(s, kv_heads, g, d).transpose(1, 0, 2, 3),
        k.transpose(1, 0, 2), v.transpose(1, 0, 2)))        # (kvh, S, g, d)
    return out.transpose(1, 0, 2, 3).reshape(s, heads, d)


def attention(h, b, cfg, ein, window):
    heads, kv_heads = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d, eps = cfg["head_dim"], cfg["rms_norm_eps"]
    s = h.shape[0]
    q = rms_norm(ein("sh,hd->sd", h, b["wq"]).reshape(s, heads, d),
                 b["q_norm_scale"], eps)
    k = rms_norm(ein("sh,hd->sd", h, b["wk"]).reshape(s, kv_heads, d),
                 b["k_norm_scale"], eps)
    v = ein("sh,hd->sd", h, b["wv"]).reshape(s, kv_heads, d)
    # rotate-half pairs (i, i + d/2) over the whole head, a window layer's
    q = jnp.where(window, rotary(q, d, cfg["rope_theta"]), q)
    k = jnp.where(window, rotary(k, d, cfg["rope_theta"]), k)
    o = masked_attention(q, k, v, window, cfg["sliding_window"], ein,
                         q_block=128 if s > 128 and s % 128 == 0 else None)
    o = o.reshape(s, heads * d) * jax.nn.sigmoid(
        ein("sh,hd->sd", h, b["w_ogate"]))
    return ein("sd,dh->sh", o, b["wo"])


def route(h, b, cfg, ein):
    """(S, E_all) float32 combine weights: zero where an expert was not
    chosen. Selection by ``s + bias``, weights from ``s``."""
    s = scores(h, b, ein)
    n, e_all = s.shape
    chosen = picks(s + b["moe_bias"], cfg)
    picked = jnp.zeros((n, e_all), bool).at[
        jnp.arange(n)[:, None], chosen].set(True)
    w = jnp.where(picked, s, 0.0)
    if cfg.get("route_norm", True):
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return w * cfg["route_scale"]


def experts(h, b, cfg, ein):
    """The held experts' part of the routed result plus the shared expert."""
    first = cfg.get("expert_offset", 0)
    held = b["wi"].shape[0]
    w = route(h, b, cfg, ein)[:, first:first + held]                # (S, held)

    def one(y, e):
        w_gate, w_up, w_down, w_e = e
        return y + w_e[:, None] * gated_mlp(h, w_gate, w_up, w_down, ein), None

    y = jax.lax.scan(one, jnp.zeros_like(h),
                     (b["w_gate"], b["wi"], b["w_down"], w.T))[0]
    return y + gated_mlp(h, b["shared_w_gate"], b["shared_w_up"],
                         b["shared_w_down"], ein)


def embed(w, ids, cfg):
    """(S, H + 1) float32 input of the first layer for one sequence ``ids``
    (S,): the scaled embedding, and the index of the next layer (0) as the
    last column."""
    x = w["wte"][ids].astype(jnp.float32)
    if cfg.get("mup_enabled"):
        x = x * math.sqrt(cfg["hidden_size"])
    return jnp.concatenate([x, jnp.zeros((x.shape[0], 1), jnp.float32)], -1)


def attend(x, b, cfg, ein):
    """The first half of a layer: ``x`` plus its normed attention; the
    layer's type by the index ``x`` carries."""
    x, at = x[:, :-1], x[:, -1:]
    window = jnp.asarray(is_window(cfg))[at[0, 0].astype(jnp.int32)]
    eps = cfg["rms_norm_eps"]
    a = attention(rms_norm(x, b["ln1_scale"], eps), b, cfg, ein, window)
    return jnp.concatenate(
        [x + rms_norm(a, b["post_attn_scale"], eps), at], axis=-1)


def router_scores(x, b, cfg, ein):
    """(S, E_all) scores of an expert layer's router on ``x`` as ``attend``
    returned it: what ``balanced_bias`` is fitted to."""
    return scores(rms_norm(x[:, :-1], b["ln2_scale"], cfg["rms_norm_eps"]), b,
                  ein)


def feed(x, b, cfg, ein):
    """The second half: ``x`` plus its normed experts where ``b`` holds a
    router, its normed dense MLP otherwise; the index moves on."""
    x, at = x[:, :-1], x[:, -1:]
    eps = cfg["rms_norm_eps"]
    h = rms_norm(x, b["ln2_scale"], eps)
    f = experts(h, b, cfg, ein) if "moe_wg" in b else gated_mlp(
        h, b["w_gate"], b["w_up"], b["w_down"], ein)
    return jnp.concatenate(
        [x + rms_norm(f, b["post_mlp_scale"], eps), at + 1.0], axis=-1)


def layer(x, b, cfg, ein):
    """One layer over ``b``, its leaves: ``feed`` after ``attend``."""
    return feed(attend(x, b, cfg, ein), b, cfg, ein)


def final(w, x, cfg):
    return rms_norm(x[..., :-1], w["lnf_scale"], cfg["rms_norm_eps"])


def logits(w, h, ein):
    return ein("sh,hv->sv", h, w["lm_head"])


def hidden(w, ids, cfg, ein):
    """Final-norm hidden states (S, H) of one sequence ``ids`` (S,): the
    parts above over a whole tree."""
    x = embed(w, ids, cfg)
    for group, _ in groups(cfg):
        x = scan_layers(lambda x, b: layer(x, b, cfg, ein), x, w[group])
    return final(w, x, cfg)
