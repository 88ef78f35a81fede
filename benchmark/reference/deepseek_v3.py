"""DeepSeek-V3 family (DeepSeek-AI 2024, arXiv:2412.19437; here
``ai-sage/GigaChat3.1-702B-A36B``, ``model_type`` ``deepseek_v3``): multi-head
latent attention with YaRN-scaled rotary on a shared rope head, leading dense
gated-SiLU layers, then layers of routed experts (sigmoid scores, a
selection-only bias, group-limited top-k, normalised and scaled weights)
beside one shared expert, RMSNorm throughout, untied output head.

Un-absorbed attention: the latent ``c_kv`` is up-projected to per-head keys
and values and attended to plainly. Every held expert is applied to every
token and weighted by the routing (zero where the token did not choose it).

The chip's share: ``wi`` / ``w_gate`` / ``w_down`` of an expert layer hold the
experts ``expert_offset .. expert_offset + E_held - 1`` of the router's
``moe_wg.shape[1]`` outputs. The router, the group choice, the top-k and the
normalisation run over all outputs; only the held experts' parts are added.
What the absent experts would have added is left out.

Departures from the published forward: none in the layers kept. The
multi-token prediction module (``num_nextn_predict_layers``) is not part of
the published ``deepseek_v3`` forward either and is left out.
"""

import math

import jax
import jax.numpy as jnp

from . import causal_attention, scan_layers


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                             + eps) * scale


def yarn_mscale(factor, m):
    return 1.0 if factor <= 1 or m <= 0 else 0.1 * m * math.log(factor) + 1.0


def yarn_inv_freq(dim, theta, scaling):
    """(dim/2,) rotary frequencies under YaRN: interpolated (divided by
    ``factor``) below the ``beta_slow`` correction dimension, kept above the
    ``beta_fast`` one, a linear ramp between."""
    f = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    if not scaling:
        return f
    orig = scaling["original_max_position_embeddings"]

    def correction(rotations):
        return dim * math.log(orig / (rotations * 2 * math.pi)) / (
            2 * math.log(theta))

    lo = max(math.floor(correction(scaling["beta_fast"])), 0)
    hi = min(math.ceil(correction(scaling["beta_slow"])), dim // 2 - 1)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - lo)
                    / max(hi - lo, 1e-3), 0.0, 1.0)
    return f / scaling["factor"] * ramp + f * (1.0 - ramp)


def rotary(x, positions, theta, scaling):
    """x (S, heads, d): rotate the interleaved pairs ``(x[2i], x[2i+1])``
    (the ``deepseek_v3`` pairing) by ``positions * inv_freq_i``. cos and sin
    carry ``mscale(factor, mscale) / mscale(factor, mscale_all_dim)``."""
    d = x.shape[-1]
    ang = positions.astype(jnp.float32)[:, None] * yarn_inv_freq(
        d, theta, scaling)[None]
    amp = 1.0
    if scaling:
        amp = (yarn_mscale(scaling["factor"], scaling.get("mscale", 1))
               / yarn_mscale(scaling["factor"],
                             scaling.get("mscale_all_dim", 0)))
    cos, sin = (jnp.cos(ang) * amp)[:, None], (jnp.sin(ang) * amp)[:, None]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape)


def softmax_mscale(cfg):
    """The factor on the softmax scale: ``mscale(factor, mscale_all_dim)``
    squared."""
    sc = cfg.get("rope_scaling")
    if not sc or not sc.get("mscale_all_dim"):
        return 1.0
    return yarn_mscale(sc["factor"], sc["mscale_all_dim"]) ** 2


def attention(h, b, cfg, ein):
    heads, eps = cfg["num_attention_heads"], cfg["rms_norm_eps"]
    nope, rope, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    rank = cfg["kv_lora_rank"]
    theta, scaling = cfg["rope_theta"], cfg.get("rope_scaling")
    s = h.shape[0]
    pos = jnp.arange(s)
    c_q = rms_norm(ein("sh,hr->sr", h, b["wq_a"]), b["q_a_scale"], eps)
    q = ein("sr,rd->sd", c_q, b["wq_b"]).reshape(s, heads, nope + rope)
    kv_a = ein("sh,hr->sr", h, b["wkv_a"])
    c_kv = rms_norm(kv_a[:, :rank], b["kv_a_scale"], eps)
    k_rope = rotary(kv_a[:, None, rank:], pos, theta, scaling)   # one head
    q_rope = rotary(q[..., nope:], pos, theta, scaling)
    kv = ein("sr,rd->sd", c_kv, b["wkv_b"]).reshape(s, heads, nope + vd)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_rope, (s, heads, rope))], axis=-1)
    q = jnp.concatenate([q[..., :nope], q_rope], axis=-1) * softmax_mscale(cfg)
    a = causal_attention(q, k, kv[..., nope:], ein,
                         q_block=128 if s > 128 and s % 128 == 0 else None)
    return ein("sd,dh->sh", a.reshape(s, heads * vd), b["wo"])


def gated_mlp(h, w_gate, w_up, w_down, ein):
    return ein("si,ih->sh", jax.nn.silu(ein("sh,hi->si", h, w_gate))
               * ein("sh,hi->si", h, w_up), w_down)


def picks(biased, cfg):
    """(S, k) chosen outputs of the (S, E_all) selection scores ``biased``:
    a group scores the sum of its two largest, the ``topk_group`` best groups
    are kept and the ``num_experts_per_tok`` largest are taken among them."""
    groups, keep, k = cfg["n_group"], cfg["topk_group"], cfg["num_experts_per_tok"]
    n, e_all = biased.shape
    by_group = biased.reshape(n, groups, e_all // groups)
    group_score = jnp.sum(jax.lax.top_k(by_group, 2)[0], axis=-1)   # (S, groups)
    kept = jax.lax.top_k(group_score, keep)[1]
    group_ok = jnp.zeros((n, groups), bool).at[
        jnp.arange(n)[:, None], kept].set(True)
    masked = jnp.where(jnp.repeat(group_ok, e_all // groups, axis=1), biased,
                       -jnp.inf)
    return jax.lax.top_k(masked, k)[1]


def scores(h, b, ein):
    """(S, E_all) float32 router scores of the normed ``h``."""
    return jax.nn.sigmoid(ein("sh,he->se", h, b["moe_wg"]))


def route(h, b, cfg, ein):
    """(S, E_all) float32 combine weights: zero where an expert was not
    chosen. Selection by ``s + bias``, weights from ``s``."""
    s = scores(h, b, ein)
    n, e_all = s.shape
    chosen = picks(s + b["moe_bias"], cfg)                          # (S, k)
    picked = jnp.zeros((n, e_all), bool).at[
        jnp.arange(n)[:, None], chosen].set(True)
    w = jnp.where(picked, s, 0.0)
    if cfg.get("norm_topk_prob", True):
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return w * cfg["routed_scaling_factor"]


#: the fit of ``balanced_bias``: steps, and the first and last step size in
#: units of the scores (which lie in (0, 1))
BALANCE_STEPS, BALANCE_FIRST, BALANCE_LAST = 40, 0.1, 0.002


def balanced_bias(s, valid, cfg):
    """The selection bias (E_all,) under which the tokens ``valid`` (N,) of
    the scores ``s`` (N, E_all) choose every router output equally often: what
    the published model's ``e_score_correction_bias`` is trained to do
    (arXiv:2412.19437 section 2.1.2: after each step an overloaded expert's
    bias goes down by a step and an underloaded one's up), run here on the
    seed's own tokens with a step that shrinks geometrically. The result has
    mean zero and is rounded to bfloat16, so that the served tree holds the
    reference's values exactly. Deterministic: sums of 0/1 counts."""
    e_all = s.shape[1]
    valid = valid.astype(jnp.float32)
    target = jnp.sum(valid) * cfg["num_experts_per_tok"] / e_all

    def step(t, bias):
        size = BALANCE_FIRST * (BALANCE_LAST / BALANCE_FIRST) ** (
            t / (BALANCE_STEPS - 1))
        hit = picks(s + bias, cfg)[:, :, None] == jnp.arange(e_all)
        load = jnp.sum(jnp.any(hit, axis=1) * valid[:, None], axis=0)
        bias = bias - size * jnp.clip(load / target - 1.0, -1.0, 1.0)
        return bias - jnp.mean(bias)

    bias = jax.lax.fori_loop(0, BALANCE_STEPS, step,
                             jnp.zeros((e_all,), jnp.float32))
    return bias.astype(jnp.bfloat16).astype(jnp.float32)


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


def groups(cfg):
    """The stacked layer groups in the order the forward walks them."""
    dense = cfg["first_k_dense_replace"]
    return [("dense_blocks", dense),
            ("blocks", cfg["num_hidden_layers"] - dense)]


def embed(w, ids, cfg):
    """(S, H) float32 input of the first layer for one sequence ``ids`` (S,)."""
    return w["wte"][ids].astype(jnp.float32)


def attend(x, b, cfg, ein):
    """The first half of a layer: ``x`` plus its attention."""
    return x + attention(rms_norm(x, b["ln1_scale"], cfg["rms_norm_eps"]), b,
                         cfg, ein)


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
