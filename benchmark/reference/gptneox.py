"""GPT-NeoX / Pythia (Black et al. 2022; Biderman et al. 2023;
``EleutherAI/pythia-1.4b``): rotary positions on the first ``rotary_pct`` of
each head (rotate-half convention), parallel residual with two LayerNorms,
exact (erf) GELU, untied output head.

Departure from the HF checkpoint layout: q, k and v arrive as three (H, H)
matrices, not the per-head interleaved fused one; the arithmetic is the same.
"""

import jax
import jax.numpy as jnp

from . import causal_attention, layer_norm, scan_layers


def rotary(x, dim, base):
    """x (S, heads, head_dim): rotate the leading ``dim`` of every head."""
    s = x.shape[0]
    inv = 1.0 / (base ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None]     # (S, dim/2)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2, rest = x[..., :dim // 2], x[..., dim // 2:dim], x[..., dim:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1)


def groups(cfg):
    """The stacked layer groups in the order the forward walks them."""
    return [("blocks", cfg["num_hidden_layers"])]


def embed(w, ids, cfg):
    """(S, H) float32 input of the first layer for one sequence ``ids`` (S,)."""
    return w["wte"][ids].astype(jnp.float32)


def layer(x, b, cfg, ein):
    """One block: ``b`` holds that layer's leaves."""
    heads, eps = cfg["num_attention_heads"], cfg["layer_norm_eps"]
    rot = int(cfg["hidden_size"] // heads * cfg["rotary_pct"])
    base = cfg["rotary_emb_base"]
    s = x.shape[0]
    h = layer_norm(x, b["ln1_scale"], b["ln1_bias"], eps)
    q, k, v = (
        (ein("sh,hd->sd", h, b["w" + n]) + b[f"w{n}_bias"]).reshape(s, heads, -1)
        for n in "qkv")
    a = causal_attention(rotary(q, rot, base), rotary(k, rot, base), v,
                         ein).reshape(s, -1)
    attn = ein("sd,dh->sh", a, b["wo"]) + b["attn_bias"]
    h2 = layer_norm(x, b["ln2_scale"], b["ln2_bias"], eps)
    up = ein("sh,hi->si", h2, b["w_up"]) + b["mlp_up_bias"]
    mlp = ein("si,ih->sh", jax.nn.gelu(up, approximate=False),
              b["w_down"]) + b["mlp_bias"]
    return x + attn + mlp          # use_parallel_residual


def final(w, x, cfg):
    return layer_norm(x, w["lnf_scale"], w["lnf_bias"], cfg["layer_norm_eps"])


def logits(w, h, ein):
    return ein("sh,hv->sv", h, w["lm_head"])


def hidden(w, ids, cfg, ein):
    """Final-LayerNorm hidden states (S, H) of one sequence ``ids`` (S,):
    the parts above over a whole tree."""
    x = embed(w, ids, cfg)
    for group, _ in groups(cfg):
        x = scan_layers(lambda x, b: layer(x, b, cfg, ein), x, w[group])
    return final(w, x, cfg)
