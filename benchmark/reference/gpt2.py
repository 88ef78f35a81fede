"""GPT-2 (Radford et al. 2019; ``openai-community/gpt2-medium``): learned
positions, pre-LayerNorm blocks, fused qkv with biases, tanh GELU, tied
embedding and output head."""

import jax
import jax.numpy as jnp

from . import causal_attention, layer_norm, scan_layers


def hidden(w, ids, cfg, ein):
    """Final-LayerNorm hidden states (S, H) of one sequence ``ids`` (S,)."""
    heads, eps = cfg["n_head"], cfg["layer_norm_epsilon"]
    s = ids.shape[0]
    x = w["wte"][ids] + w["wpe"][:s]

    def layer(x, b):
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

    x = scan_layers(layer, x.astype(jnp.float32), w["blocks"])
    return layer_norm(x, w["lnf_scale"], w["lnf_bias"], eps)


def logits(w, h, ein):
    return ein("sh,vh->sv", h, w["wte"])
