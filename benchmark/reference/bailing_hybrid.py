"""Bailing hybrid (inclusionAI Ling-3.0, ``model_type`` ``bailing_hybrid``;
here ``inclusionAI/Ling-3.0-flash``): pre-norm residual blocks whose mixer is
Kimi Delta Attention (KDA: Kimi Linear, arXiv:2510.26692 section 3, and the
public ``fla/layers/kda.py``) in five layers of six and multi-head latent attention
in the sixth (published layer ``i`` is latent when ``(i + 1) %
layer_group_size == 0``), leading dense gated-SiLU layers, then layers of
routed experts (sigmoid scores, a selection-only bias, group-limited top-k,
weights normalised and scaled) beside one shared expert, RMSNorm throughout,
an untied output head. With ``N`` an RMSNorm, ``x`` the stream::

    x = x + Mixer(N(x));   x = x + F(N(x))

**KDA layer** (``h`` = N(x), ``H`` heads of ``d`` = ``head_dim`` keys and
values; ``num_kv_heads_for_linear_attn`` 0: keys have the queries' heads)::

    [q~ | k~ | v~]_t = silu(sum_i w_i (h [W_q | W_k | W_v])_{t - K + 1 + i})
                       a depthwise causal convolution of K =
                       short_conv_kernel_size taps, no bias, then SiLU
    q_t = q~ / sqrt(|q~|^2 + eps) * d^-1/2;  k_t = k~ / sqrt(|k~|^2 + eps)
    a_t = L * sigmoid(exp(A_log_h) * (h_t W_f + dt_bias))    (H, d) in (L, 0),
                                                      L = kda_lower_bound
    b_t = sigmoid(h_t W_b)                                   (H,)
    S'  = Diag(exp(a_t)) S_{t-1}
    S_t = S' + b_t k_t (v_t - S'^T k_t)^T;   o_t = S_t^T q_t
    Mixer = W_o [ N_d(o_t) * sigmoid(h_t W_g) ]      the gate a scalar a head

computed as the plain recurrence, one ``lax.scan`` step a position, the state
(heads, d, d) in float32: no chunk form, no cache.

**Latent layer**: ``q = h W_q`` (no low-rank step: ``q_lora_rank`` null) in
heads of ``qk_nope_head_dim + qk_rope_head_dim``; ``kv_a = h W_kv_a``,
``c_kv = N(kv_a[:rank])``, one rope head ``kv_a[rank:]``; interleaved rotary
(``rope_theta``, no scaling) on it and on each query's last
``qk_rope_head_dim``; ``[k_nope | v] = c_kv W_kv_b``; causal softmax attention
at scale ``(nope + rope)^-1/2``, un-absorbed, by blocks of queries; the same
head-wise sigmoid gate before ``W_o``.

The chip's share, as ``deepseek_v3``'s: ``wi`` / ``w_gate`` / ``w_down`` hold
the experts ``expert_offset .. expert_offset + E_held - 1`` of the router's
``moe_wg.shape[1]`` outputs; the router, the group choice, the top-k and the
normalisation run over all outputs, only the held experts' parts are added.

**Which layer is which.** The parts are handed one layer's leaves and no
index: a latent layer is the one whose leaves hold ``wkv_a``, a dense layer
the one whose leaves hold no router. ``groups`` lays the kept layers
(``layers_kept``: their published indices) out as runs of one mixer and one
feed-forward, as the served tree's groups are.

``ablate`` in ``cfg`` (the controls of the tests and of the builder's scratch
runs, never set by a benchmark run) names one mechanism to leave out:
``gate`` (no output gate, either mixer), ``erase`` (the write is ``b k v^T``:
no ``S'^T k``), ``head_decay`` (each head's channels all decay by their mean),
``conv`` (the current tap alone), ``forget:<n>`` (every KDA state zeroed each
``n`` tokens: how much of the result is older state).
"""

import jax
import jax.numpy as jnp

from . import causal_attention, scan_layers
from .deepseek_v3 import (balanced_bias, experts, gated_mlp,  # noqa: F401
                          rms_norm, rotary, scores)
# the depthwise causal convolution (here without a bias) and the largest
# divisor under a bound are that module's
from .falcon_h1 import _divisor, causal_conv

#: what is left out of, or differs from, the published forward
departures = (
    "the multi-token-prediction module (num_nextn_predict_layers) is left "
    "out: it is not part of the next-token forward",
    "expert_swiglu_limit_list / share_expert_swiglu_limit_list are not "
    "applied: they are 0 (no clamp) for every published layer below 34, and "
    "the layers kept are 1 and 6-11",
    "the absent experts' parts of a layer's result are left out (the chip's "
    "share of an expert-parallel deployment)",
)


def kept_layers(cfg):
    """Published indices of the layers held here, in forward order."""
    return list(cfg.get("layers_kept", range(cfg["num_hidden_layers"])))


def is_latent(cfg):
    """Per kept layer: is its mixer latent attention?"""
    period = cfg["layer_group_size"]
    return [(i + 1) % period == 0 for i in kept_layers(cfg)]


def groups(cfg):
    """The stacked layer groups in forward order: a run of layers of one
    mixer and one feed-forward (the leading dense layers apart) is a group."""
    runs, last = [], None
    for at, latent in enumerate(is_latent(cfg)):
        kind = (latent, at < cfg["first_k_dense_replace"])
        if kind == last:
            runs[-1][1] += 1
        else:
            runs.append([f"blocks_{len(runs)}", 1])
        last = kind
    return [tuple(r) for r in runs]


def unit(x, eps):
    """Each head's row over its L2 norm."""
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True)
                             + eps)


def delta_rule(q, k, v, a, beta, ein, ablate=None):
    """The recurrence over positions. q, k (S, heads, d_k), v (S, heads,
    d_v), a (S, heads, d_k) log-decays, beta (S, heads); returns o (S, heads,
    d_v)."""
    s, heads, d_k = q.shape
    forget = int(ablate.split(":")[1]) if (ablate or "").startswith(
        "forget:") else 0

    def step(state, args):
        q, k, v, a, beta, t = args
        if forget:
            state = jnp.where(t % forget == 0, 0.0, state)
        state = jnp.exp(a)[..., None] * state
        seen = 0.0 if ablate == "erase" else ein("hk,hkv->hv", k, state)
        state = state + k[..., None] * (beta[:, None] * (v - seen))[:, None, :]
        return state, ein("hk,hkv->hv", q, state)

    zero = jnp.zeros((heads, d_k, v.shape[-1]), jnp.float32)
    return jax.lax.scan(step, zero, (q, k, v, a, beta, jnp.arange(s)))[1]


def head_gate(o, h, b, cfg, ein):
    """``o`` (S, heads, d) times the head-wise sigmoid gate of ``h``."""
    if cfg.get("ablate") == "gate":
        return o
    return o * jax.nn.sigmoid(ein("sh,hg->sg", h, b["w_ogate"]))[..., None]


def kda(h, b, cfg, ein):
    """The KDA mixer of the normed ``h`` (S, hidden)."""
    heads, d = cfg["num_attention_heads"], cfg["head_dim"]
    eps = cfg["rms_norm_eps"]
    s, ablate = h.shape[0], cfg.get("ablate")
    qkv = jnp.concatenate([ein("sh,hd->sd", h, b[w])
                           for w in ("wq", "wk", "wv")], axis=-1)
    qkv = jax.nn.silu(causal_conv(qkv, b["kda_conv_scale"], 0.0, ablate))
    q, k, v = (t.reshape(s, heads, d) for t in jnp.split(qkv, 3, axis=-1))
    q, k = unit(q, eps) * d ** -0.5, unit(k, eps)
    a = cfg["kda_lower_bound"] * jax.nn.sigmoid(
        jnp.exp(b["a_log"])[None, :, None]
        * (ein("sh,hd->sd", h, b["w_decay"]) + b["dt_bias"]).reshape(
            s, heads, d))
    if ablate == "head_decay":
        a = jnp.broadcast_to(jnp.mean(a, axis=-1, keepdims=True), a.shape)
    beta = jax.nn.sigmoid(ein("sh,hg->sg", h, b["w_beta"]))
    o = delta_rule(q, k, v, a, beta, ein, ablate)
    o = head_gate(rms_norm(o, b["o_norm_scale"], eps), h, b, cfg, ein)
    return ein("sd,dh->sh", o.reshape(s, heads * d), b["wo"])


def latent(h, b, cfg, ein):
    """The latent-attention mixer of the normed ``h`` (S, hidden)."""
    heads, eps = cfg["num_attention_heads"], cfg["rms_norm_eps"]
    nope, rope, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    rank, theta = cfg["kv_lora_rank"], float(cfg["rope_theta"])
    s = h.shape[0]
    pos = jnp.arange(s)
    q = ein("sh,hd->sd", h, b["wq"]).reshape(s, heads, nope + rope)
    kv_a = ein("sh,hr->sr", h, b["wkv_a"])
    c_kv = rms_norm(kv_a[:, :rank], b["kv_a_scale"], eps)
    k_rope = rotary(kv_a[:, None, rank:], pos, theta, None)      # one head
    q_rope = rotary(q[..., nope:], pos, theta, None)
    kv = ein("sr,rd->sd", c_kv, b["wkv_b"]).reshape(s, heads, nope + vd)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_rope, (s, heads, rope))], axis=-1)
    q = jnp.concatenate([q[..., :nope], q_rope], axis=-1)
    o = causal_attention(q, k, kv[..., nope:], ein,
                         q_block=_divisor(s, 128))
    return ein("sd,dh->sh", head_gate(o, h, b, cfg, ein).reshape(
        s, heads * vd), b["wo"])


def embed(w, ids, cfg):
    """(S, H) float32 input of the first layer for one sequence ``ids`` (S,)."""
    return w["wte"][ids].astype(jnp.float32)


def attend(x, b, cfg, ein):
    """The first half of a layer: ``x`` plus its mixer, latent attention
    where ``b`` holds the latent's down-projection, KDA otherwise."""
    h = rms_norm(x, b["ln1_scale"], cfg["rms_norm_eps"])
    return x + (latent if "wkv_a" in b else kda)(h, b, cfg, ein)


def router_scores(x, b, cfg, ein):
    """(S, E_all) scores of an expert layer's router on ``x`` as ``attend``
    returned it: what ``balanced_bias`` is fitted to."""
    return scores(rms_norm(x, b["ln2_scale"], cfg["rms_norm_eps"]), b, ein)


def feed(x, b, cfg, ein):
    """The second half: ``x`` plus its experts where ``b`` holds a router,
    plus its dense MLP otherwise."""
    h2 = rms_norm(x, b["ln2_scale"], cfg["rms_norm_eps"])
    if "moe_wg" in b:
        return x + experts(h2, b, cfg, ein)
    return x + gated_mlp(h2, b["w_gate"], b["w_up"], b["w_down"], ein)


def layer(x, b, cfg, ein):
    """One layer over ``b``, its leaves: ``feed`` after ``attend``."""
    return feed(attend(x, b, cfg, ein), b, cfg, ein)


def final(w, x, cfg):
    return rms_norm(x, w["lnf_scale"], cfg["rms_norm_eps"])


def logits(w, h, ein):
    return ein("sh,hv->sv", h, w["lm_head"])


def hidden(w, ids, cfg, ein):
    """Final-norm hidden states (S, H) of one sequence ``ids`` (S,): the
    parts above over a whole tree."""
    x = embed(w, ids, cfg)
    for group, _ in groups(cfg):
        x = scan_layers(lambda x, b: layer(x, b, cfg, ein), x, w[group])
    return final(w, x, cfg)
