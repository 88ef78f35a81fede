"""LongCat-Flash (Meituan 2025, ``meituan-longcat/LongCat-Flash-Chat``): a
stack of shortcut-connected double layers (ScMoE). One double layer is two
multi-head latent attentions, two dense gated-SiLU feed-forwards, and one
expert layer that branches off after the first attention and rejoins the
residual stream after the second feed-forward. With ``N`` an RMSNorm::

    x1 = x  + A_0(N_a0(x))
    h  = N_f0(x1)
    m  = E(h)
    x2 = x1 + F_0(h)
    x3 = x2 + A_1(N_a1(x2))
    y  = x3 + F_1(N_f1(x3)) + m

Latent attention is ``deepseek_v3``'s, un-absorbed, with plain rotary (no
YaRN) on interleaved pairs and the two latent norms' outputs scaled:
``c_q = sqrt(hidden / q_lora_rank) * N(h W_qa)`` and ``c_kv = sqrt(hidden /
kv_lora_rank) * N(c_kv_raw)`` (``mla_scale_q_lora``, ``mla_scale_kv_lora``);
the rope head ``k_r`` is not scaled.

The expert layer: ``p = softmax(h W_r)`` over all the router's outputs, real
experts first and ``zero_expert_num`` identity experts behind them; chosen =
the ``moe_topk`` largest of ``p + b / E_all`` (the leaf ``moe_bias`` holds the
selection bias ``b`` in units of the uniform score ``1 / E_all``); the weight
of a chosen output is ``routed_scaling_factor * p``, not divided by the sum
over the chosen. A real expert is a gated-SiLU feed-forward; an identity
expert adds ``weight * h``. Every held expert is applied to every token and
weighted by the routing (zero where the token did not choose it).

The chip's share: ``wi`` / ``w_gate`` / ``w_down`` hold the real experts
``expert_offset .. expert_offset + E_held - 1``. The router and the top-k run
over all outputs; only the held experts' parts and the identity part (which a
token's own chip computes in the deployment) are added. What the absent
experts would have added is left out.

A double layer's leaves: sublayer ``i``'s attention, norms and dense
feed-forward under the prefix ``s<i>_`` with a dense ``deepseek_v3`` layer's
names, the expert layer's under its own.
"""

import jax
import jax.numpy as jnp

from . import causal_attention, scan_layers
from .deepseek_v3 import gated_mlp, rms_norm, rotary


def sublayer(b, i):
    """Sublayer ``i``'s leaves of the double layer ``b``, prefix removed."""
    prefix = f"s{i}_"
    return {k[len(prefix):]: v for k, v in b.items() if k.startswith(prefix)}


def attention(h, b, cfg, ein):
    """Latent attention of one sublayer on its normed input ``h`` (S, H)."""
    heads, eps = cfg["num_attention_heads"], cfg["rms_norm_eps"]
    nope, rope, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    q_rank, rank, hidden = cfg["q_lora_rank"], cfg["kv_lora_rank"], h.shape[1]
    s_q = (hidden / q_rank) ** 0.5 if cfg.get("mla_scale_q_lora") else 1.0
    s_kv = (hidden / rank) ** 0.5 if cfg.get("mla_scale_kv_lora") else 1.0
    s, theta = h.shape[0], cfg["rope_theta"]
    pos = jnp.arange(s)
    c_q = s_q * rms_norm(ein("sh,hr->sr", h, b["wq_a"]), b["q_a_scale"], eps)
    q = ein("sr,rd->sd", c_q, b["wq_b"]).reshape(s, heads, nope + rope)
    kv_a = ein("sh,hr->sr", h, b["wkv_a"])
    c_kv = s_kv * rms_norm(kv_a[:, :rank], b["kv_a_scale"], eps)
    k_rope = rotary(kv_a[:, None, rank:], pos, theta, None)      # one head
    q_rope = rotary(q[..., nope:], pos, theta, None)
    kv = ein("sr,rd->sd", c_kv, b["wkv_b"]).reshape(s, heads, nope + vd)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_rope, (s, heads, rope))], axis=-1)
    q = jnp.concatenate([q[..., :nope], q_rope], axis=-1)
    a = causal_attention(q, k, kv[..., nope:], ein,
                         q_block=128 if s > 128 and s % 128 == 0 else None)
    return ein("sd,dh->sh", a.reshape(s, heads * vd), b["wo"])


def route(h, b, cfg, ein):
    """(S, E_all) float32 combine weights over all the router's outputs:
    zero where an output was not chosen. Selection by ``p + b / E_all``,
    weights from ``p``."""
    p = jax.nn.softmax(ein("sh,he->se", h, b["moe_wg"]), axis=-1)
    n, e_all = p.shape
    chosen = jax.lax.top_k(p + b["moe_bias"] / e_all, cfg["moe_topk"])[1]
    picked = jnp.zeros((n, e_all), bool).at[
        jnp.arange(n)[:, None], chosen].set(True)
    return jnp.where(picked, p, 0.0) * cfg["routed_scaling_factor"]


def experts(h, b, cfg, ein, identity=True):
    """The held experts' part of the routed result plus (``identity``) the
    identity experts' part."""
    first = cfg.get("expert_offset", 0)
    held = b["wi"].shape[0]
    w_all = route(h, b, cfg, ein)
    w = w_all[:, first:first + held]                                # (S, held)

    def one(y, e):
        w_gate, w_up, w_down, w_e = e
        return y + w_e[:, None] * gated_mlp(h, w_gate, w_up, w_down, ein), None

    y = jax.lax.scan(one, jnp.zeros_like(h),
                     (b["w_gate"], b["wi"], b["w_down"], w.T))[0]
    if identity:
        zero_from = w_all.shape[1] - cfg["zero_expert_num"]
        y = y + jnp.sum(w_all[:, zero_from:], axis=1, keepdims=True) * h
    return y


def groups(cfg):
    """The stacked layer groups in the order the forward walks them."""
    return [("blocks", cfg["num_layers"])]


def embed(w, ids, cfg):
    """(S, H) float32 input of the first layer for one sequence ``ids`` (S,)."""
    return w["wte"][ids].astype(jnp.float32)


def layer(x, b, cfg, ein):
    """One double layer over ``b``, its leaves."""
    eps = cfg["rms_norm_eps"]
    m = None
    for i in range(2):
        s = sublayer(b, i)
        x = x + attention(rms_norm(x, s["ln1_scale"], eps), s, cfg, ein)
        h = rms_norm(x, s["ln2_scale"], eps)
        if i == 0:
            m = experts(h, b, cfg, ein)
        x = x + gated_mlp(h, s["w_gate"], s["w_up"], s["w_down"], ein)
    return x + m


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
