"""GPT-2 (Radford et al. 2019; ``openai-community/gpt2-medium``): learned
positions, pre-LayerNorm blocks, fused qkv with biases, tanh GELU, tied
embedding and output head."""

import jax
import jax.numpy as jnp

from . import causal_attention, layer_norm, scan_layers


def groups(cfg):
    """The stacked layer groups in the order the forward walks them."""
    return [("blocks", cfg["n_layer"])]


def embed(w, ids, cfg):
    """(S, H) float32 input of the first layer for one sequence ``ids`` (S,)."""
    return (w["wte"][ids] + w["wpe"][:ids.shape[0]]).astype(jnp.float32)


def layer(x, b, cfg, ein):
    """One block: ``b`` holds that layer's leaves."""
    heads, eps = cfg["n_head"], cfg["layer_norm_epsilon"]
    s = x.shape[0]
    h = layer_norm(x, b["ln1_scale"], b["ln1_bias"], eps)
    q, k, v = (
        (ein("sh,hd->sd", h, b["w" + n]) + b[f"w{n}_bias"]).reshape(s, heads, -1)
        for n in "qkv")
    a = causal_attention(q, k, v, ein).reshape(s, -1)
    x = x + ein("sd,dh->sh", a, b["wo"]) + b["attn_bias"]
    h = layer_norm(x, b["ln2_scale"], b["ln2_bias"], eps)
    up = ein("sh,hi->si", h, b["w_up"]) + b["mlp_up_bias"]
    return x + ein("si,ih->sh", jax.nn.gelu(up, approximate=True),
                   b["w_down"]) + b["mlp_bias"]


def final(w, x, cfg):
    return layer_norm(x, w["lnf_scale"], w["lnf_bias"],
                      cfg["layer_norm_epsilon"])


def logits(w, h, ein):
    return ein("sh,vh->sv", h, w["wte"])


def hidden(w, ids, cfg, ein):
    """Final-LayerNorm hidden states (S, H) of one sequence ``ids`` (S,):
    the parts above over a whole tree."""
    x = embed(w, ids, cfg)
    for group, _ in groups(cfg):
        x = scan_layers(lambda x, b: layer(x, b, cfg, ein), x, w[group])
    return final(w, x, cfg)
