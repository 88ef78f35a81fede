"""HuggingFace checkpoint converters.

Reference analogue: ``deepspeed/module_inject`` policy system +
``inference/v2/model_implementations`` parameter containers — the machinery
that lets DeepSpeed users point the engine at an HF model and get sharded
weights. Here the conversion is explicit and total: an HF ``GPT2LMHeadModel``
or ``LlamaForCausalLM`` (module or state_dict) becomes a ``TransformerLM``
config + stacked parameter pytree; sharding then comes for free from
``tp_specs`` (the AutoTP analogue).

Conventions handled: torch ``nn.Linear`` stores (out, in) → transposed;
GPT-2 ``Conv1D`` stores (in, out) → copied; per-layer tensors are stacked on a
leading layer axis for the scan; vocab is zero-padded to the MXU-friendly size.
"""

from typing import Any, Dict, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from ..utils.logging import log_dist
from .transformer import TransformerConfig, TransformerLM


def _np(t):
    return np.asarray(t.detach().cpu().numpy() if hasattr(t, "detach") else t,
                      np.float32)


def _pad_vocab(w: np.ndarray, vocab: int) -> np.ndarray:
    if w.shape[0] == vocab:
        return w
    out = np.zeros((vocab,) + w.shape[1:], w.dtype)
    out[: w.shape[0]] = w
    return out


def _round_vocab(v: int, multiple: int = 128) -> int:
    return ((v + multiple - 1) // multiple) * multiple


def from_hf_gpt2(model_or_state_dict, pad_vocab_to: Optional[int] = None
                 ) -> Tuple[TransformerLM, Dict[str, Any]]:
    """Convert an HF GPT-2 LM (``GPT2LMHeadModel`` or its state_dict)."""
    if hasattr(model_or_state_dict, "state_dict"):
        sd = model_or_state_dict.state_dict()
        hf_cfg = model_or_state_dict.config
        H, L = hf_cfg.n_embd, hf_cfg.n_layer
        nh, S, V = hf_cfg.n_head, hf_cfg.n_positions, hf_cfg.vocab_size
    else:
        sd = model_or_state_dict
        wte = _np(sd["transformer.wte.weight"])
        V, H = wte.shape
        S = _np(sd["transformer.wpe.weight"]).shape[0]
        L = max(int(k.split(".")[2]) for k in sd if k.startswith("transformer.h.")) + 1
        nh = None  # must be provided via config for bare state dicts
        raise ValueError("pass the HF module (config needed for head count)")
    sd = {k: _np(v) for k, v in sd.items()}
    Vp = pad_vocab_to or _round_vocab(V)
    cfg = TransformerConfig(
        vocab_size=Vp, hidden_size=H, num_layers=L, num_heads=nh, max_seq_len=S,
        pos_embedding="learned", norm="layernorm", activation="gelu",
        tie_embeddings=True, qkv_bias=True, name="gpt2-hf",
    )

    def stack(fmt):
        return jnp.asarray(np.stack([sd[fmt.format(i)] for i in range(L)]))

    # GPT-2 Conv1D weights are already (in, out)
    c_attn_w = np.stack([sd[f"transformer.h.{i}.attn.c_attn.weight"] for i in range(L)])
    c_attn_b = np.stack([sd[f"transformer.h.{i}.attn.c_attn.bias"] for i in range(L)])
    wq, wk, wv = np.split(c_attn_w, 3, axis=2)
    bq, bk, bv = np.split(c_attn_b, 3, axis=1)

    params = {
        "wte": jnp.asarray(_pad_vocab(sd["transformer.wte.weight"], Vp)),
        "wpe": jnp.asarray(sd["transformer.wpe.weight"]),
        "blocks": {
            "ln1_scale": stack("transformer.h.{}.ln_1.weight"),
            "ln1_bias": stack("transformer.h.{}.ln_1.bias"),
            "wq": jnp.asarray(wq), "wk": jnp.asarray(wk), "wv": jnp.asarray(wv),
            "wq_bias": jnp.asarray(bq), "wk_bias": jnp.asarray(bk),
            "wv_bias": jnp.asarray(bv),
            "wo": stack("transformer.h.{}.attn.c_proj.weight"),
            "attn_bias": stack("transformer.h.{}.attn.c_proj.bias"),
            "ln2_scale": stack("transformer.h.{}.ln_2.weight"),
            "ln2_bias": stack("transformer.h.{}.ln_2.bias"),
            "w_up": stack("transformer.h.{}.mlp.c_fc.weight"),
            "mlp_up_bias": stack("transformer.h.{}.mlp.c_fc.bias"),
            "w_down": stack("transformer.h.{}.mlp.c_proj.weight"),
            "mlp_bias": stack("transformer.h.{}.mlp.c_proj.bias"),
        },
        "lnf_scale": jnp.asarray(sd["transformer.ln_f.weight"]),
        "lnf_bias": jnp.asarray(sd["transformer.ln_f.bias"]),
    }
    model = TransformerLM(cfg)
    log_dist(f"converted HF GPT-2: H={H} L={L} heads={nh} vocab {V}->{Vp}", ranks=[0])
    return model, params


def _stack(sd, fmt, L):
    return jnp.asarray(np.stack([sd[fmt.format(i)] for i in range(L)]))


def _stackT(sd, fmt, L):
    # torch Linear (out, in) → ours (in, out)
    return jnp.asarray(np.stack([sd[fmt.format(i)].T for i in range(L)]))


def _act(hf_name: str) -> str:
    """HF activation name → TransformerConfig.activation. HF 'gelu' is the exact
    erf form; 'gelu_new'/'gelu_fast'/'gelu_pytorch_tanh' are the tanh approx."""
    table = {"relu": "relu", "gelu": "gelu_exact", "gelu_new": "gelu",
             "gelu_fast": "gelu", "gelu_pytorch_tanh": "gelu"}
    if hf_name not in table:
        raise ValueError(f"unsupported HF activation '{hf_name}'")
    return table[hf_name]


def _rotary_perm(rotary_dim: int, head_dim: int) -> np.ndarray:
    """Column permutation turning interleaved-pair rotary weights (GPT-J
    'rotate every two') into rotate-half layout: the q·k inner product is
    invariant under a shared permutation of head dims, and pair (2i, 2i+1)
    maps to pair (i, i + r/2) with the same frequency."""
    r = rotary_dim
    return np.concatenate([np.arange(0, r, 2), np.arange(1, r, 2),
                           np.arange(r, head_dim)])


def _permute_heads(w, perm, num_heads, head_dim):
    """Apply a per-head column permutation to (L, in, num_heads*head_dim)."""
    Lw, I, _ = w.shape
    return np.ascontiguousarray(
        w.reshape(Lw, I, num_heads, head_dim)[..., perm].reshape(Lw, I, -1))


def _split_fused_qkv(sd, key, nh, hd):
    """Split a per-head-interleaved fused [q;k;v] projection (GPT-NeoX/BLOOM/
    classic-Falcon layout: out dim = nh·3·hd grouped per head) into our
    (in, out) q/k/v weights and their biases (None when the checkpoint has no
    bias)."""
    w, b = sd[key + ".weight"], sd.get(key + ".bias")
    H_in = w.shape[1]
    wh = w.reshape(nh, 3, hd, H_in)
    ws = [wh[:, j].reshape(nh * hd, H_in).T for j in range(3)]
    if b is None:
        return ws, None
    bh = b.reshape(nh, 3, hd)
    return ws, [bh[:, j].reshape(nh * hd) for j in range(3)]


def from_hf_llama(model) -> Tuple[TransformerLM, Dict[str, Any]]:
    """Convert an HF LLaMA/Mistral/Qwen2-family causal LM (``LlamaForCausalLM``,
    ``Qwen2ForCausalLM`` — Qwen2 is LLaMA plus q/k/v biases). Reference
    containers: ``module_inject/containers/llama.py``, v2 model_implementations
    ``{llama_v2,mistral,qwen_v2}``."""
    hf_cfg = model.config
    sd = {k: _np(v) for k, v in model.state_dict().items()}
    H, L = hf_cfg.hidden_size, hf_cfg.num_hidden_layers
    nh = hf_cfg.num_attention_heads
    kvh = getattr(hf_cfg, "num_key_value_heads", nh)
    V = hf_cfg.vocab_size
    tie = bool(getattr(hf_cfg, "tie_word_embeddings", False))
    qkv_bias = "model.layers.0.self_attn.q_proj.bias" in sd
    o_bias = "model.layers.0.self_attn.o_proj.bias" in sd  # InternLM bias=True
    cfg = TransformerConfig(
        vocab_size=V, hidden_size=H, num_layers=L, num_heads=nh, num_kv_heads=kvh,
        intermediate_size=hf_cfg.intermediate_size,
        max_seq_len=getattr(hf_cfg, "max_position_embeddings", 4096),
        pos_embedding="rope", norm="rmsnorm", activation="swiglu",
        tie_embeddings=tie, norm_eps=getattr(hf_cfg, "rms_norm_eps", 1e-5),
        qkv_bias=qkv_bias, attn_out_bias=o_bias,
        rope_theta=float(getattr(hf_cfg, "rope_theta", 10000.0)), name="llama-hf",
    )
    pre = "model.layers.{}"
    params = {
        "wte": jnp.asarray(sd["model.embed_tokens.weight"]),
        "blocks": {
            "ln1_scale": _stack(sd, pre + ".input_layernorm.weight", L),
            "wq": _stackT(sd, pre + ".self_attn.q_proj.weight", L),
            "wk": _stackT(sd, pre + ".self_attn.k_proj.weight", L),
            "wv": _stackT(sd, pre + ".self_attn.v_proj.weight", L),
            "wo": _stackT(sd, pre + ".self_attn.o_proj.weight", L),
            "ln2_scale": _stack(sd, pre + ".post_attention_layernorm.weight", L),
            "w_gate": _stackT(sd, pre + ".mlp.gate_proj.weight", L),
            "w_up": _stackT(sd, pre + ".mlp.up_proj.weight", L),
            "w_down": _stackT(sd, pre + ".mlp.down_proj.weight", L),
        },
        "lnf_scale": jnp.asarray(sd["model.norm.weight"]),
    }
    if qkv_bias:
        blocks = params["blocks"]
        blocks["wq_bias"] = _stack(sd, pre + ".self_attn.q_proj.bias", L)
        blocks["wk_bias"] = _stack(sd, pre + ".self_attn.k_proj.bias", L)
        blocks["wv_bias"] = _stack(sd, pre + ".self_attn.v_proj.bias", L)
    if o_bias:
        params["blocks"]["attn_bias"] = _stack(sd, pre + ".self_attn.o_proj.bias", L)
    if not tie:
        params["lm_head"] = jnp.asarray(sd["lm_head.weight"].T)
    model_out = TransformerLM(cfg)
    log_dist(f"converted HF LLaMA-family: H={H} L={L} heads={nh}/{kvh} vocab={V}",
             ranks=[0])
    return model_out, params


def from_hf_ouro(model) -> Tuple[TransformerLM, Dict[str, Any]]:
    """Convert an Ouro looped causal LM (``OuroForCausalLM``, ``model_type``
    ``ouro``): ``num_hidden_layers`` layers run ``total_ut_steps`` times a
    token, four RMSNorms a layer, the model's one norm closing every step and
    an exit gate beside it (``TransformerConfig.loop_steps``). ``model`` is
    the HF module or anything with its ``config`` and ``state_dict()``.

    The name map is recalled from the public ``modeling_ouro.py``, not read
    (there is no checkpoint in the repository): ``input_layernorm`` and
    ``input_layernorm_2`` norm the attention's input and output,
    ``post_attention_layernorm`` and ``post_attention_layernorm_2`` the
    feed-forward's; ``model.norm`` is the closing norm, ``model.
    early_exit_gate`` a ``Linear(hidden_size, 1)`` with a bias."""
    hf_cfg = model.config
    sd = {k: _np(v) for k, v in model.state_dict().items()}
    H, L = hf_cfg.hidden_size, hf_cfg.num_hidden_layers
    nh = hf_cfg.num_attention_heads
    V = hf_cfg.vocab_size
    tie = bool(getattr(hf_cfg, "tie_word_embeddings", False))
    cfg = TransformerConfig(
        vocab_size=V, hidden_size=H, num_layers=L, num_heads=nh,
        num_kv_heads=getattr(hf_cfg, "num_key_value_heads", nh),
        head_dim_override=getattr(hf_cfg, "head_dim", None),
        intermediate_size=hf_cfg.intermediate_size,
        max_seq_len=getattr(hf_cfg, "max_position_embeddings", 4096),
        pos_embedding="rope", norm="rmsnorm", activation="swiglu",
        tie_embeddings=tie, norm_eps=getattr(hf_cfg, "rms_norm_eps", 1e-6),
        rope_theta=float(getattr(hf_cfg, "rope_theta", 10000.0)),
        post_norms=True, loop_steps=int(hf_cfg.total_ut_steps),
        early_exit_threshold=float(getattr(hf_cfg, "early_exit_threshold", 1.0)),
        name="ouro-hf",
    )
    if not cfg.looped:
        raise ValueError("from_hf_ouro: total_ut_steps 1 is no looped model "
                         "(its tree has no `loop` group)")
    pre = "model.layers.{}"
    params = {
        "wte": jnp.asarray(sd["model.embed_tokens.weight"]),
        "blocks": {
            "ln1_scale": _stack(sd, pre + ".input_layernorm.weight", L),
            "wq": _stackT(sd, pre + ".self_attn.q_proj.weight", L),
            "wk": _stackT(sd, pre + ".self_attn.k_proj.weight", L),
            "wv": _stackT(sd, pre + ".self_attn.v_proj.weight", L),
            "wo": _stackT(sd, pre + ".self_attn.o_proj.weight", L),
            "post_attn_scale": _stack(sd, pre + ".input_layernorm_2.weight", L),
            "ln2_scale": _stack(sd, pre + ".post_attention_layernorm.weight", L),
            "w_gate": _stackT(sd, pre + ".mlp.gate_proj.weight", L),
            "w_up": _stackT(sd, pre + ".mlp.up_proj.weight", L),
            "w_down": _stackT(sd, pre + ".mlp.down_proj.weight", L),
            "post_mlp_scale": _stack(
                sd, pre + ".post_attention_layernorm_2.weight", L),
        },
        # the closing norm and the gate: a stacked group of one layer
        "loop": {
            "norm_scale": jnp.asarray(sd["model.norm.weight"])[None],
            "exit_w": jnp.asarray(sd["model.early_exit_gate.weight"]
                                  ).reshape(1, H),
            "exit_b": jnp.asarray(sd["model.early_exit_gate.bias"]
                                  ).reshape(1),
        },
    }
    if not tie:
        params["lm_head"] = jnp.asarray(sd["lm_head.weight"].T)
    log_dist(f"converted HF Ouro: H={H} L={L} x {cfg.loop_steps} steps "
             f"heads={nh} vocab={V}", ranks=[0])
    return TransformerLM(cfg), params


def from_hf_opt(model) -> Tuple[TransformerLM, Dict[str, Any]]:
    """Convert an HF OPT causal LM (reference ``module_inject/containers/opt.py``,
    v2 ``model_implementations/opt``). Learned positions carry a +2 offset in the
    HF weight table; we bake it out by dropping the first two rows."""
    hf_cfg = model.config
    sd = {k: _np(v) for k, v in model.state_dict().items()}
    H, L, nh = hf_cfg.hidden_size, hf_cfg.num_hidden_layers, hf_cfg.num_attention_heads
    V = hf_cfg.vocab_size
    if getattr(hf_cfg, "word_embed_proj_dim", H) != H:
        raise ValueError("OPT word_embed_proj_dim != hidden_size (350m variant) unsupported")
    if not getattr(hf_cfg, "do_layer_norm_before", True):
        raise ValueError("OPT do_layer_norm_before=False unsupported")
    tie = bool(getattr(hf_cfg, "tie_word_embeddings", True))
    cfg = TransformerConfig(
        vocab_size=V, hidden_size=H, num_layers=L, num_heads=nh,
        intermediate_size=hf_cfg.ffn_dim, max_seq_len=hf_cfg.max_position_embeddings,
        pos_embedding="learned", norm="layernorm",
        activation=_act(hf_cfg.activation_function),
        tie_embeddings=tie, qkv_bias=True, name="opt-hf",
    )
    pre = "model.decoder.layers.{}"
    params = {
        "wte": jnp.asarray(sd["model.decoder.embed_tokens.weight"]),
        "wpe": jnp.asarray(sd["model.decoder.embed_positions.weight"][2:]),
        "blocks": {
            "ln1_scale": _stack(sd, pre + ".self_attn_layer_norm.weight", L),
            "ln1_bias": _stack(sd, pre + ".self_attn_layer_norm.bias", L),
            "wq": _stackT(sd, pre + ".self_attn.q_proj.weight", L),
            "wk": _stackT(sd, pre + ".self_attn.k_proj.weight", L),
            "wv": _stackT(sd, pre + ".self_attn.v_proj.weight", L),
            "wq_bias": _stack(sd, pre + ".self_attn.q_proj.bias", L),
            "wk_bias": _stack(sd, pre + ".self_attn.k_proj.bias", L),
            "wv_bias": _stack(sd, pre + ".self_attn.v_proj.bias", L),
            "wo": _stackT(sd, pre + ".self_attn.out_proj.weight", L),
            "attn_bias": _stack(sd, pre + ".self_attn.out_proj.bias", L),
            "ln2_scale": _stack(sd, pre + ".final_layer_norm.weight", L),
            "ln2_bias": _stack(sd, pre + ".final_layer_norm.bias", L),
            "w_up": _stackT(sd, pre + ".fc1.weight", L),
            "mlp_up_bias": _stack(sd, pre + ".fc1.bias", L),
            "w_down": _stackT(sd, pre + ".fc2.weight", L),
            "mlp_bias": _stack(sd, pre + ".fc2.bias", L),
        },
        "lnf_scale": jnp.asarray(sd["model.decoder.final_layer_norm.weight"]),
        "lnf_bias": jnp.asarray(sd["model.decoder.final_layer_norm.bias"]),
    }
    if not tie:
        params["lm_head"] = jnp.asarray(sd["lm_head.weight"].T)
    log_dist(f"converted HF OPT: H={H} L={L} heads={nh} vocab={V}", ranks=[0])
    return TransformerLM(cfg), params


def from_hf_gptj(model) -> Tuple[TransformerLM, Dict[str, Any]]:
    """Convert an HF GPT-J causal LM (reference ``module_inject/containers/gptj.py``).
    Parallel attention+MLP off one shared LayerNorm; partial interleaved rotary
    (converted to rotate-half via ``_rotary_perm``); untied LM head with bias."""
    hf_cfg = model.config
    sd = {k: _np(v) for k, v in model.state_dict().items()}
    H, L, nh = hf_cfg.n_embd, hf_cfg.n_layer, hf_cfg.n_head
    hd = H // nh
    r = hf_cfg.rotary_dim or hd
    V = hf_cfg.vocab_size
    cfg = TransformerConfig(
        vocab_size=V, hidden_size=H, num_layers=L, num_heads=nh,
        max_seq_len=hf_cfg.n_positions, pos_embedding="rope", rotary_dim=r,
        norm="layernorm", activation=_act(hf_cfg.activation_function),
        tie_embeddings=False, lm_head_bias=True,
        parallel_block=True, parallel_shared_ln=True, name="gptj-hf",
    )
    pre = "transformer.h.{}"
    perm = _rotary_perm(r, hd)
    wq = _permute_heads(np.stack([sd[pre.format(i) + ".attn.q_proj.weight"].T
                                  for i in range(L)]), perm, nh, hd)
    wk = _permute_heads(np.stack([sd[pre.format(i) + ".attn.k_proj.weight"].T
                                  for i in range(L)]), perm, nh, hd)
    zeros_h = jnp.zeros((L, H), jnp.float32)
    params = {
        "wte": jnp.asarray(sd["transformer.wte.weight"]),
        "blocks": {
            "ln1_scale": _stack(sd, pre + ".ln_1.weight", L),
            "ln1_bias": _stack(sd, pre + ".ln_1.bias", L),
            "wq": jnp.asarray(wq), "wk": jnp.asarray(wk),
            "wv": _stackT(sd, pre + ".attn.v_proj.weight", L),
            "wo": _stackT(sd, pre + ".attn.out_proj.weight", L),
            "attn_bias": zeros_h,  # GPT-J out_proj has no bias
            "w_up": _stackT(sd, pre + ".mlp.fc_in.weight", L),
            "mlp_up_bias": _stack(sd, pre + ".mlp.fc_in.bias", L),
            "w_down": _stackT(sd, pre + ".mlp.fc_out.weight", L),
            "mlp_bias": _stack(sd, pre + ".mlp.fc_out.bias", L),
        },
        "lnf_scale": jnp.asarray(sd["transformer.ln_f.weight"]),
        "lnf_bias": jnp.asarray(sd["transformer.ln_f.bias"]),
        "lm_head": jnp.asarray(sd["lm_head.weight"].T),
        "lm_head_bias": jnp.asarray(sd["lm_head.bias"]),
    }
    log_dist(f"converted HF GPT-J: H={H} L={L} heads={nh} rotary={r}", ranks=[0])
    return TransformerLM(cfg), params


def from_hf_gptneox(model) -> Tuple[TransformerLM, Dict[str, Any]]:
    """Convert an HF GPT-NeoX/Pythia causal LM (reference
    ``module_inject/containers/gptneox.py``). Fused per-head [q;k;v] projection,
    partial rotate-half rotary, parallel residual with two LayerNorms."""
    hf_cfg = model.config
    sd = {k: _np(v) for k, v in model.state_dict().items()}
    H, L, nh = hf_cfg.hidden_size, hf_cfg.num_hidden_layers, hf_cfg.num_attention_heads
    hd = H // nh
    r = int(hd * hf_cfg.rotary_pct)
    V = hf_cfg.vocab_size
    attn_bias = bool(getattr(hf_cfg, "attention_bias", True))
    cfg = TransformerConfig(
        vocab_size=V, hidden_size=H, num_layers=L, num_heads=nh,
        intermediate_size=hf_cfg.intermediate_size,
        max_seq_len=hf_cfg.max_position_embeddings,
        pos_embedding="rope", rotary_dim=r,
        rope_theta=float(getattr(hf_cfg, "rotary_emb_base", 10000.0)),
        norm="layernorm", norm_eps=hf_cfg.layer_norm_eps,
        activation=_act(hf_cfg.hidden_act), tie_embeddings=False,
        qkv_bias=attn_bias,
        parallel_block=bool(hf_cfg.use_parallel_residual),
        parallel_shared_ln=False, name="gptneox-hf",
    )
    pre = "gpt_neox.layers.{}"
    qkv = [_split_fused_qkv(sd, pre.format(i) + ".attention.query_key_value",
                            nh, hd) for i in range(L)]
    params = {
        "wte": jnp.asarray(sd["gpt_neox.embed_in.weight"]),
        "blocks": {
            "ln1_scale": _stack(sd, pre + ".input_layernorm.weight", L),
            "ln1_bias": _stack(sd, pre + ".input_layernorm.bias", L),
            "ln2_scale": _stack(sd, pre + ".post_attention_layernorm.weight", L),
            "ln2_bias": _stack(sd, pre + ".post_attention_layernorm.bias", L),
            "wq": jnp.asarray(np.stack([w[0] for w, _ in qkv])),
            "wk": jnp.asarray(np.stack([w[1] for w, _ in qkv])),
            "wv": jnp.asarray(np.stack([w[2] for w, _ in qkv])),
            "wo": _stackT(sd, pre + ".attention.dense.weight", L),
            "attn_bias": (_stack(sd, pre + ".attention.dense.bias", L)
                          if attn_bias else jnp.zeros((L, H), jnp.float32)),
            "w_up": _stackT(sd, pre + ".mlp.dense_h_to_4h.weight", L),
            "mlp_up_bias": _stack(sd, pre + ".mlp.dense_h_to_4h.bias", L),
            "w_down": _stackT(sd, pre + ".mlp.dense_4h_to_h.weight", L),
            "mlp_bias": _stack(sd, pre + ".mlp.dense_4h_to_h.bias", L),
        },
        "lnf_scale": jnp.asarray(sd["gpt_neox.final_layer_norm.weight"]),
        "lnf_bias": jnp.asarray(sd["gpt_neox.final_layer_norm.bias"]),
        "lm_head": jnp.asarray(sd["embed_out.weight"].T),
    }
    if attn_bias:
        blocks = params["blocks"]
        blocks["wq_bias"] = jnp.asarray(np.stack([b[0] for _, b in qkv]))
        blocks["wk_bias"] = jnp.asarray(np.stack([b[1] for _, b in qkv]))
        blocks["wv_bias"] = jnp.asarray(np.stack([b[2] for _, b in qkv]))
    log_dist(f"converted HF GPT-NeoX: H={H} L={L} heads={nh} rotary={r} "
             f"parallel={cfg.parallel_block}", ranks=[0])
    return TransformerLM(cfg), params


def from_hf_bloom(model) -> Tuple[TransformerLM, Dict[str, Any]]:
    """Convert an HF BLOOM causal LM (reference
    ``module_inject/containers/bloom.py``). ALiBi positions, embedding
    LayerNorm, fused per-head [q;k;v] projection."""
    hf_cfg = model.config
    sd = {k: _np(v) for k, v in model.state_dict().items()}
    H, L, nh = hf_cfg.hidden_size, hf_cfg.n_layer, hf_cfg.n_head
    hd = H // nh
    V = hf_cfg.vocab_size
    if getattr(hf_cfg, "apply_residual_connection_post_layernorm", False):
        raise ValueError("BLOOM apply_residual_connection_post_layernorm unsupported")
    cfg = TransformerConfig(
        vocab_size=V, hidden_size=H, num_layers=L, num_heads=nh,
        max_seq_len=2048, pos_embedding="alibi", embed_layernorm=True,
        norm="layernorm", norm_eps=hf_cfg.layer_norm_epsilon,
        activation="gelu",  # BloomGelu = tanh approximation
        tie_embeddings=True, qkv_bias=True, name="bloom-hf",
    )
    pre = "transformer.h.{}"
    qkv = [_split_fused_qkv(sd, pre.format(i) + ".self_attention.query_key_value",
                            nh, hd) for i in range(L)]
    params = {
        "wte": jnp.asarray(sd["transformer.word_embeddings.weight"]),
        "ln_emb_scale": jnp.asarray(sd["transformer.word_embeddings_layernorm.weight"]),
        "ln_emb_bias": jnp.asarray(sd["transformer.word_embeddings_layernorm.bias"]),
        "blocks": {
            "ln1_scale": _stack(sd, pre + ".input_layernorm.weight", L),
            "ln1_bias": _stack(sd, pre + ".input_layernorm.bias", L),
            "ln2_scale": _stack(sd, pre + ".post_attention_layernorm.weight", L),
            "ln2_bias": _stack(sd, pre + ".post_attention_layernorm.bias", L),
            "wq": jnp.asarray(np.stack([w[0] for w, _ in qkv])),
            "wk": jnp.asarray(np.stack([w[1] for w, _ in qkv])),
            "wv": jnp.asarray(np.stack([w[2] for w, _ in qkv])),
            "wq_bias": jnp.asarray(np.stack([b[0] for _, b in qkv])),
            "wk_bias": jnp.asarray(np.stack([b[1] for _, b in qkv])),
            "wv_bias": jnp.asarray(np.stack([b[2] for _, b in qkv])),
            "wo": _stackT(sd, pre + ".self_attention.dense.weight", L),
            "attn_bias": _stack(sd, pre + ".self_attention.dense.bias", L),
            "w_up": _stackT(sd, pre + ".mlp.dense_h_to_4h.weight", L),
            "mlp_up_bias": _stack(sd, pre + ".mlp.dense_h_to_4h.bias", L),
            "w_down": _stackT(sd, pre + ".mlp.dense_4h_to_h.weight", L),
            "mlp_bias": _stack(sd, pre + ".mlp.dense_4h_to_h.bias", L),
        },
        "lnf_scale": jnp.asarray(sd["transformer.ln_f.weight"]),
        "lnf_bias": jnp.asarray(sd["transformer.ln_f.bias"]),
    }
    log_dist(f"converted HF BLOOM: H={H} L={L} heads={nh} vocab={V}", ranks=[0])
    return TransformerLM(cfg), params


def from_hf_falcon(model) -> Tuple[TransformerLM, Dict[str, Any]]:
    """Convert an HF Falcon causal LM (reference v2
    ``model_implementations/falcon``). Handles all three fused-QKV layouts:
    new-decoder grouped (kv, ratio+2, hd), multi-query flat [q…,k,v], and
    classic per-head [q;k;v]; rotary or ALiBi positions; optional parallel
    attention with one or two LayerNorms."""
    hf_cfg = model.config
    sd = {k: _np(v) for k, v in model.state_dict().items()}
    H, L, nh = hf_cfg.hidden_size, hf_cfg.num_hidden_layers, hf_cfg.num_attention_heads
    hd = H // nh
    V = hf_cfg.vocab_size
    new_arch = bool(getattr(hf_cfg, "new_decoder_architecture", False))
    multi_query = bool(getattr(hf_cfg, "multi_query", True))
    # HF FalconDecoderLayer runs the parallel residual whenever either flag is set
    parallel = new_arch or bool(getattr(hf_cfg, "parallel_attn", True))
    use_alibi = bool(getattr(hf_cfg, "alibi", False))
    has_bias = bool(getattr(hf_cfg, "bias", False))
    if new_arch:
        kvh = getattr(hf_cfg, "num_kv_heads", nh) or nh
    else:
        kvh = 1 if multi_query else nh
    tie = bool(getattr(hf_cfg, "tie_word_embeddings", True))
    two_ln = new_arch and getattr(hf_cfg, "num_ln_in_parallel_attn", 2) != 1
    cfg = TransformerConfig(
        vocab_size=V, hidden_size=H, num_layers=L, num_heads=nh, num_kv_heads=kvh,
        max_seq_len=getattr(hf_cfg, "max_position_embeddings", 2048),
        pos_embedding="alibi" if use_alibi else "rope",
        alibi_slope_scale=hd ** -0.5,
        rope_theta=float(getattr(hf_cfg, "rope_theta", 10000.0)),
        norm="layernorm", norm_eps=hf_cfg.layer_norm_epsilon,
        activation="gelu_exact", tie_embeddings=tie, qkv_bias=has_bias,
        parallel_block=parallel, parallel_shared_ln=not two_ln, name="falcon-hf",
    )
    pre = "transformer.h.{}"
    ratio = nh // kvh

    def split_qkv(i):
        """→ ((wq, wk, wv), biases-or-None) for one layer."""
        if not (new_arch or multi_query):  # classic per-head [q;k;v]
            return _split_fused_qkv(
                sd, pre.format(i) + ".self_attention.query_key_value", nh, hd)
        # grouped: (kvh, ratio+2, hd, H) — q rows kv-major, matching our GQA order
        w = sd[pre.format(i) + ".self_attention.query_key_value.weight"]
        wh = w.reshape(kvh, ratio + 2, hd, H)
        ws = (wh[:, :ratio].reshape(nh * hd, H).T,
              wh[:, ratio].reshape(kvh * hd, H).T,
              wh[:, ratio + 1].reshape(kvh * hd, H).T)
        b = sd.get(pre.format(i) + ".self_attention.query_key_value.bias")
        if b is None:
            return ws, None
        bh = b.reshape(kvh, ratio + 2, hd)
        return ws, (bh[:, :ratio].reshape(-1), bh[:, ratio].reshape(-1),
                    bh[:, ratio + 1].reshape(-1))

    qkv = [split_qkv(i) for i in range(L)]
    blocks = {
        "wq": jnp.asarray(np.stack([w[0] for w, _ in qkv])),
        "wk": jnp.asarray(np.stack([w[1] for w, _ in qkv])),
        "wv": jnp.asarray(np.stack([w[2] for w, _ in qkv])),
        "wo": _stackT(sd, pre + ".self_attention.dense.weight", L),
        "w_up": _stackT(sd, pre + ".mlp.dense_h_to_4h.weight", L),
        "w_down": _stackT(sd, pre + ".mlp.dense_4h_to_h.weight", L),
    }
    if two_ln:
        blocks["ln1_scale"] = _stack(sd, pre + ".ln_attn.weight", L)
        blocks["ln1_bias"] = _stack(sd, pre + ".ln_attn.bias", L)
        blocks["ln2_scale"] = _stack(sd, pre + ".ln_mlp.weight", L)
        blocks["ln2_bias"] = _stack(sd, pre + ".ln_mlp.bias", L)
    else:
        blocks["ln1_scale"] = _stack(sd, pre + ".input_layernorm.weight", L)
        blocks["ln1_bias"] = _stack(sd, pre + ".input_layernorm.bias", L)
        if not parallel:
            blocks["ln2_scale"] = _stack(sd, pre + ".post_attention_layernorm.weight", L)
            blocks["ln2_bias"] = _stack(sd, pre + ".post_attention_layernorm.bias", L)
    if has_bias:
        blocks["wq_bias"] = jnp.asarray(np.stack([b[0] for _, b in qkv]))
        blocks["wk_bias"] = jnp.asarray(np.stack([b[1] for _, b in qkv]))
        blocks["wv_bias"] = jnp.asarray(np.stack([b[2] for _, b in qkv]))
        blocks["attn_bias"] = _stack(sd, pre + ".self_attention.dense.bias", L)
        blocks["mlp_up_bias"] = _stack(sd, pre + ".mlp.dense_h_to_4h.bias", L)
        blocks["mlp_bias"] = _stack(sd, pre + ".mlp.dense_4h_to_h.bias", L)
    else:
        I = blocks["w_up"].shape[-1]
        blocks["attn_bias"] = jnp.zeros((L, H), jnp.float32)
        blocks["mlp_up_bias"] = jnp.zeros((L, I), jnp.float32)
        blocks["mlp_bias"] = jnp.zeros((L, H), jnp.float32)
    params = {
        "wte": jnp.asarray(sd["transformer.word_embeddings.weight"]),
        "blocks": blocks,
        "lnf_scale": jnp.asarray(sd["transformer.ln_f.weight"]),
        "lnf_bias": jnp.asarray(sd["transformer.ln_f.bias"]),
    }
    if not tie:
        params["lm_head"] = jnp.asarray(sd["lm_head.weight"].T)
    log_dist(f"converted HF Falcon: H={H} L={L} heads={nh}/{kvh} "
             f"parallel={parallel} alibi={use_alibi}", ranks=[0])
    return TransformerLM(cfg), params


def from_hf_phi(model) -> Tuple[TransformerLM, Dict[str, Any]]:
    """Convert an HF Phi causal LM (reference v2 ``model_implementations/phi``).
    Parallel attention+MLP off one shared LayerNorm, partial rotate-half rotary,
    biases on every projection, untied LM head with bias."""
    hf_cfg = model.config
    sd = {k: _np(v) for k, v in model.state_dict().items()}
    H, L, nh = hf_cfg.hidden_size, hf_cfg.num_hidden_layers, hf_cfg.num_attention_heads
    kvh = getattr(hf_cfg, "num_key_value_heads", nh) or nh
    hd = H // nh
    r = int(hd * getattr(hf_cfg, "partial_rotary_factor", 0.5))
    V = hf_cfg.vocab_size
    if getattr(hf_cfg, "qk_layernorm", False):
        raise ValueError("Phi qk_layernorm unsupported")
    cfg = TransformerConfig(
        vocab_size=V, hidden_size=H, num_layers=L, num_heads=nh, num_kv_heads=kvh,
        intermediate_size=hf_cfg.intermediate_size,
        max_seq_len=hf_cfg.max_position_embeddings,
        pos_embedding="rope", rotary_dim=r,
        rope_theta=float(getattr(hf_cfg, "rope_theta", 10000.0)),
        norm="layernorm", norm_eps=hf_cfg.layer_norm_eps,
        activation=_act(hf_cfg.hidden_act), tie_embeddings=False,
        qkv_bias=True, lm_head_bias=True,
        parallel_block=True, parallel_shared_ln=True, name="phi-hf",
    )
    pre = "model.layers.{}"
    params = {
        "wte": jnp.asarray(sd["model.embed_tokens.weight"]),
        "blocks": {
            "ln1_scale": _stack(sd, pre + ".input_layernorm.weight", L),
            "ln1_bias": _stack(sd, pre + ".input_layernorm.bias", L),
            "wq": _stackT(sd, pre + ".self_attn.q_proj.weight", L),
            "wk": _stackT(sd, pre + ".self_attn.k_proj.weight", L),
            "wv": _stackT(sd, pre + ".self_attn.v_proj.weight", L),
            "wq_bias": _stack(sd, pre + ".self_attn.q_proj.bias", L),
            "wk_bias": _stack(sd, pre + ".self_attn.k_proj.bias", L),
            "wv_bias": _stack(sd, pre + ".self_attn.v_proj.bias", L),
            "wo": _stackT(sd, pre + ".self_attn.dense.weight", L),
            "attn_bias": _stack(sd, pre + ".self_attn.dense.bias", L),
            "w_up": _stackT(sd, pre + ".mlp.fc1.weight", L),
            "mlp_up_bias": _stack(sd, pre + ".mlp.fc1.bias", L),
            "w_down": _stackT(sd, pre + ".mlp.fc2.weight", L),
            "mlp_bias": _stack(sd, pre + ".mlp.fc2.bias", L),
        },
        "lnf_scale": jnp.asarray(sd["model.final_layernorm.weight"]),
        "lnf_bias": jnp.asarray(sd["model.final_layernorm.bias"]),
        "lm_head": jnp.asarray(sd["lm_head.weight"].T),
        "lm_head_bias": jnp.asarray(sd["lm_head.bias"]),
    }
    log_dist(f"converted HF Phi: H={H} L={L} heads={nh}/{kvh} rotary={r}", ranks=[0])
    return TransformerLM(cfg), params


def from_hf_mixtral(model) -> Tuple[TransformerLM, Dict[str, Any]]:
    """Convert an HF Mixtral MoE causal LM (reference v2
    ``model_implementations/mixtral``). LLaMA skeleton + top-k routed SwiGLU
    experts; gating matches HF exactly (softmax → top-k → renormalize) and
    token dropping is disabled for parity."""
    hf_cfg = model.config
    sd = {k: _np(v) for k, v in model.state_dict().items()}
    H, L = hf_cfg.hidden_size, hf_cfg.num_hidden_layers
    nh = hf_cfg.num_attention_heads
    kvh = getattr(hf_cfg, "num_key_value_heads", nh)
    E, topk = hf_cfg.num_local_experts, hf_cfg.num_experts_per_tok
    V = hf_cfg.vocab_size
    tie = bool(getattr(hf_cfg, "tie_word_embeddings", False))
    cfg = TransformerConfig(
        vocab_size=V, hidden_size=H, num_layers=L, num_heads=nh, num_kv_heads=kvh,
        intermediate_size=hf_cfg.intermediate_size,
        max_seq_len=getattr(hf_cfg, "max_position_embeddings", 4096),
        pos_embedding="rope", norm="rmsnorm", activation="swiglu",
        tie_embeddings=tie, norm_eps=hf_cfg.rms_norm_eps,
        rope_theta=float(getattr(hf_cfg, "rope_theta", 10000.0)),
        num_experts=E, moe_top_k=topk, moe_drop_tokens=False,
        moe_aux_loss_coef=float(getattr(hf_cfg, "router_aux_loss_coef", 0.01)),
        name="mixtral-hf",
    )
    pre = "model.layers.{}"

    def experts(i, which):
        return np.stack([
            sd[f"model.layers.{i}.block_sparse_moe.experts.{e}.{which}.weight"].T
            for e in range(E)])

    params = {
        "wte": jnp.asarray(sd["model.embed_tokens.weight"]),
        "blocks": {
            "ln1_scale": _stack(sd, pre + ".input_layernorm.weight", L),
            "wq": _stackT(sd, pre + ".self_attn.q_proj.weight", L),
            "wk": _stackT(sd, pre + ".self_attn.k_proj.weight", L),
            "wv": _stackT(sd, pre + ".self_attn.v_proj.weight", L),
            "wo": _stackT(sd, pre + ".self_attn.o_proj.weight", L),
            "ln2_scale": _stack(sd, pre + ".post_attention_layernorm.weight", L),
            "moe_wg": _stackT(sd, pre + ".block_sparse_moe.gate.weight", L),
            "w_gate": jnp.asarray(np.stack([experts(i, "w1") for i in range(L)])),
            "w_down": jnp.asarray(np.stack([experts(i, "w2") for i in range(L)])),
            "wi": jnp.asarray(np.stack([experts(i, "w3") for i in range(L)])),
        },
        "lnf_scale": jnp.asarray(sd["model.norm.weight"]),
    }
    if not tie:
        params["lm_head"] = jnp.asarray(sd["lm_head.weight"].T)
    log_dist(f"converted HF Mixtral: H={H} L={L} heads={nh}/{kvh} experts={E} "
             f"top{topk}", ranks=[0])
    return TransformerLM(cfg), params


def from_hf_gemma(model) -> Tuple[TransformerLM, Dict[str, Any]]:
    """Convert an HF Gemma causal LM. LLaMA skeleton with Gemma's quirks:
    explicit head_dim != H/heads, RMSNorm computing with (1 + weight),
    sqrt(H)-scaled embeddings, and a tanh-gelu gated MLP (geglu)."""
    hf_cfg = model.config
    sd = {k: _np(v) for k, v in model.state_dict().items()}
    H, L = hf_cfg.hidden_size, hf_cfg.num_hidden_layers
    nh = hf_cfg.num_attention_heads
    kvh = getattr(hf_cfg, "num_key_value_heads", nh)
    V = hf_cfg.vocab_size
    cfg = TransformerConfig(
        vocab_size=V, hidden_size=H, num_layers=L, num_heads=nh, num_kv_heads=kvh,
        head_dim_override=int(hf_cfg.head_dim),
        intermediate_size=hf_cfg.intermediate_size,
        max_seq_len=getattr(hf_cfg, "max_position_embeddings", 8192),
        pos_embedding="rope", norm="rmsnorm", activation="geglu",
        tie_embeddings=True, norm_eps=hf_cfg.rms_norm_eps,
        norm_weight_offset=1.0, embed_scale=float(H) ** 0.5,
        rope_theta=float(getattr(hf_cfg, "rope_theta", 10000.0)), name="gemma-hf",
    )
    pre = "model.layers.{}"
    params = {
        "wte": jnp.asarray(sd["model.embed_tokens.weight"]),
        "blocks": {
            "ln1_scale": _stack(sd, pre + ".input_layernorm.weight", L),
            "wq": _stackT(sd, pre + ".self_attn.q_proj.weight", L),
            "wk": _stackT(sd, pre + ".self_attn.k_proj.weight", L),
            "wv": _stackT(sd, pre + ".self_attn.v_proj.weight", L),
            "wo": _stackT(sd, pre + ".self_attn.o_proj.weight", L),
            "ln2_scale": _stack(sd, pre + ".post_attention_layernorm.weight", L),
            "w_gate": _stackT(sd, pre + ".mlp.gate_proj.weight", L),
            "w_up": _stackT(sd, pre + ".mlp.up_proj.weight", L),
            "w_down": _stackT(sd, pre + ".mlp.down_proj.weight", L),
        },
        "lnf_scale": jnp.asarray(sd["model.norm.weight"]),
    }
    log_dist(f"converted HF Gemma: H={H} L={L} heads={nh}/{kvh} "
             f"hd={hf_cfg.head_dim} vocab={V}", ranks=[0])
    return TransformerLM(cfg), params


def from_hf_gpt_bigcode(model) -> Tuple[TransformerLM, Dict[str, Any]]:
    """Convert an HF GPT-BigCode / StarCoder causal LM (reference v2 supports
    it via AutoTP). GPT-2 layout but with torch-Linear (out, in) weights and a
    fused multi-query c_attn: rows = [q (H), k (hd), v (hd)]."""
    hf_cfg = model.config
    sd = {k: _np(v) for k, v in model.state_dict().items()}
    H, L, nh = hf_cfg.n_embd, hf_cfg.n_layer, hf_cfg.n_head
    hd = H // nh
    V = hf_cfg.vocab_size
    if not getattr(hf_cfg, "multi_query", True):
        raise ValueError("GPT-BigCode without multi_query unsupported")
    cfg = TransformerConfig(
        vocab_size=V, hidden_size=H, num_layers=L, num_heads=nh, num_kv_heads=1,
        max_seq_len=hf_cfg.n_positions, pos_embedding="learned",
        norm="layernorm", norm_eps=hf_cfg.layer_norm_epsilon,
        activation=_act(hf_cfg.activation_function),
        tie_embeddings=True, qkv_bias=True, name="gpt_bigcode-hf",
    )
    pre = "transformer.h.{}"

    def split_qkv(i):
        w = sd[pre.format(i) + ".attn.c_attn.weight"]  # (H + 2*hd, H)
        b = sd[pre.format(i) + ".attn.c_attn.bias"]
        return ((w[:H].T, w[H:H + hd].T, w[H + hd:].T),
                (b[:H], b[H:H + hd], b[H + hd:]))

    qkv = [split_qkv(i) for i in range(L)]
    params = {
        "wte": jnp.asarray(sd["transformer.wte.weight"]),
        "wpe": jnp.asarray(sd["transformer.wpe.weight"]),
        "blocks": {
            "ln1_scale": _stack(sd, pre + ".ln_1.weight", L),
            "ln1_bias": _stack(sd, pre + ".ln_1.bias", L),
            "wq": jnp.asarray(np.stack([w[0] for w, _ in qkv])),
            "wk": jnp.asarray(np.stack([w[1] for w, _ in qkv])),
            "wv": jnp.asarray(np.stack([w[2] for w, _ in qkv])),
            "wq_bias": jnp.asarray(np.stack([b[0] for _, b in qkv])),
            "wk_bias": jnp.asarray(np.stack([b[1] for _, b in qkv])),
            "wv_bias": jnp.asarray(np.stack([b[2] for _, b in qkv])),
            "wo": _stackT(sd, pre + ".attn.c_proj.weight", L),
            "attn_bias": _stack(sd, pre + ".attn.c_proj.bias", L),
            "ln2_scale": _stack(sd, pre + ".ln_2.weight", L),
            "ln2_bias": _stack(sd, pre + ".ln_2.bias", L),
            "w_up": _stackT(sd, pre + ".mlp.c_fc.weight", L),
            "mlp_up_bias": _stack(sd, pre + ".mlp.c_fc.bias", L),
            "w_down": _stackT(sd, pre + ".mlp.c_proj.weight", L),
            "mlp_bias": _stack(sd, pre + ".mlp.c_proj.bias", L),
        },
        "lnf_scale": jnp.asarray(sd["transformer.ln_f.weight"]),
        "lnf_bias": jnp.asarray(sd["transformer.ln_f.bias"]),
    }
    log_dist(f"converted HF GPT-BigCode: H={H} L={L} heads={nh}/1 vocab={V}",
             ranks=[0])
    return TransformerLM(cfg), params


def from_hf_mpt(model) -> Tuple[TransformerLM, Dict[str, Any]]:
    """Convert an HF MPT causal LM (reference AutoTP-supported family).
    ALiBi positions (MPT's slope formula equals the standard closest-power
    form for power-of-two head counts — others are rejected), bias-free
    LayerNorm blocks, straight-split fused Wqkv."""
    hf_cfg = model.config
    sd = {k: _np(v) for k, v in model.state_dict().items()}
    H, L, nh = hf_cfg.d_model, hf_cfg.n_layers, hf_cfg.n_heads
    V = hf_cfg.vocab_size
    if nh & (nh - 1):
        raise ValueError("MPT with non-power-of-two heads uses a different "
                         "ALiBi slope selection — unsupported")
    attn_cfg = getattr(hf_cfg, "attn_config", None)
    # HF MptModel applies ALiBi unconditionally and MptMLP hardcodes 4*H;
    # clip_qkv / softmax_scale change attention math — reject rather than
    # silently diverge from the logits-exact contract
    if attn_cfg is not None:
        if getattr(attn_cfg, "clip_qkv", None):
            raise ValueError("MPT attn_config.clip_qkv unsupported")
        if getattr(attn_cfg, "softmax_scale", None):
            raise ValueError("MPT attn_config.softmax_scale unsupported")
    if int(getattr(hf_cfg, "expansion_ratio", 4)) != 4:
        raise ValueError("MPT expansion_ratio != 4 unsupported "
                         "(HF MptMLP hardcodes 4*hidden_size)")
    cfg = TransformerConfig(
        vocab_size=V, hidden_size=H, num_layers=L, num_heads=nh,
        intermediate_size=4 * H,
        max_seq_len=hf_cfg.max_seq_len,
        pos_embedding="alibi",
        norm="layernorm", norm_eps=getattr(hf_cfg, "layer_norm_epsilon", 1e-5),
        activation="gelu_exact", tie_embeddings=True, qkv_bias=False,
        name="mpt-hf",
    )
    pre = "transformer.blocks.{}"

    def split_qkv(i):
        w = sd[pre.format(i) + ".attn.Wqkv.weight"]  # (3H, H), straight [q;k;v]
        return w[:H].T, w[H:2 * H].T, w[2 * H:].T

    qkv = [split_qkv(i) for i in range(L)]
    zeros_h = jnp.zeros((L, H), jnp.float32)
    params = {
        "wte": jnp.asarray(sd["transformer.wte.weight"]),
        "blocks": {
            "ln1_scale": _stack(sd, pre + ".norm_1.weight", L),
            "ln1_bias": zeros_h,
            "wq": jnp.asarray(np.stack([w[0] for w in qkv])),
            "wk": jnp.asarray(np.stack([w[1] for w in qkv])),
            "wv": jnp.asarray(np.stack([w[2] for w in qkv])),
            "wo": _stackT(sd, pre + ".attn.out_proj.weight", L),
            "attn_bias": zeros_h,
            "ln2_scale": _stack(sd, pre + ".norm_2.weight", L),
            "ln2_bias": zeros_h,
            "w_up": _stackT(sd, pre + ".ffn.up_proj.weight", L),
            "mlp_up_bias": jnp.zeros((L, cfg.mlp_dim), jnp.float32),
            "w_down": _stackT(sd, pre + ".ffn.down_proj.weight", L),
            "mlp_bias": zeros_h,
        },
        "lnf_scale": jnp.asarray(sd["transformer.norm_f.weight"]),
        "lnf_bias": jnp.zeros((H,), jnp.float32),
    }
    log_dist(f"converted HF MPT: H={H} L={L} heads={nh} (alibi)", ranks=[0])
    return TransformerLM(cfg), params


def from_hf_bert(model) -> Tuple[TransformerLM, Dict[str, Any]]:
    """Convert an HF BERT/RoBERTa MaskedLM (reference
    ``module_inject/containers/bert.py`` + the fused BERT training kernel
    ``ops/transformer/transformer.py:296``). Post-LN encoder trunk with
    segment embeddings, embedding LayerNorm and the MLM prediction head;
    RoBERTa's +2 position offset is baked out like OPT's.

    Positions are arange-based: RIGHT-padded batches match HF exactly
    (HF's mask-cumsum position ids equal arange+offset on the unpadded
    prefix); left padding would shift real-token positions and is not
    supported."""
    hf_cfg = model.config
    sd = {k: _np(v) for k, v in model.state_dict().items()}
    roberta = "roberta" in type(model).__name__.lower() or \
        hf_cfg.model_type == "roberta"
    base = "roberta" if roberta else "bert"
    if getattr(hf_cfg, "position_embedding_type", "absolute") != "absolute":
        raise ValueError(
            f"{base} position_embedding_type="
            f"'{hf_cfg.position_embedding_type}' unsupported (absolute only)")
    if f"{base}.embeddings.word_embeddings.weight" not in sd:
        raise ValueError(
            f"no converter for this {base}-named architecture — pass a "
            f"{'RobertaForMaskedLM' if roberta else 'BertForMaskedLM'} module")
    H, L, nh = hf_cfg.hidden_size, hf_cfg.num_hidden_layers, hf_cfg.num_attention_heads
    V = hf_cfg.vocab_size
    pos_off = 2 if roberta else 0  # roberta: padding_idx+1 baked into wpe
    cfg = TransformerConfig(
        vocab_size=V, hidden_size=H, num_layers=L, num_heads=nh,
        intermediate_size=hf_cfg.intermediate_size,
        max_seq_len=hf_cfg.max_position_embeddings - pos_off,
        causal=False, norm_position="post", mlm_head=True,
        token_type_embedding=hf_cfg.type_vocab_size,
        embed_layernorm=True, pos_embedding="learned", norm="layernorm",
        norm_eps=hf_cfg.layer_norm_eps, activation=_act(hf_cfg.hidden_act),
        tie_embeddings=True, qkv_bias=True, name=f"{base}-hf",
    )
    pre = base + ".encoder.layer.{}"
    params = {
        "wte": jnp.asarray(sd[f"{base}.embeddings.word_embeddings.weight"]),
        "wpe": jnp.asarray(
            sd[f"{base}.embeddings.position_embeddings.weight"][pos_off:]),
        "wtt": jnp.asarray(sd[f"{base}.embeddings.token_type_embeddings.weight"]),
        "ln_emb_scale": jnp.asarray(sd[f"{base}.embeddings.LayerNorm.weight"]),
        "ln_emb_bias": jnp.asarray(sd[f"{base}.embeddings.LayerNorm.bias"]),
        "blocks": {
            "wq": _stackT(sd, pre + ".attention.self.query.weight", L),
            "wk": _stackT(sd, pre + ".attention.self.key.weight", L),
            "wv": _stackT(sd, pre + ".attention.self.value.weight", L),
            "wq_bias": _stack(sd, pre + ".attention.self.query.bias", L),
            "wk_bias": _stack(sd, pre + ".attention.self.key.bias", L),
            "wv_bias": _stack(sd, pre + ".attention.self.value.bias", L),
            "wo": _stackT(sd, pre + ".attention.output.dense.weight", L),
            "attn_bias": _stack(sd, pre + ".attention.output.dense.bias", L),
            "ln1_scale": _stack(sd, pre + ".attention.output.LayerNorm.weight", L),
            "ln1_bias": _stack(sd, pre + ".attention.output.LayerNorm.bias", L),
            "w_up": _stackT(sd, pre + ".intermediate.dense.weight", L),
            "mlp_up_bias": _stack(sd, pre + ".intermediate.dense.bias", L),
            "w_down": _stackT(sd, pre + ".output.dense.weight", L),
            "mlp_bias": _stack(sd, pre + ".output.dense.bias", L),
            "ln2_scale": _stack(sd, pre + ".output.LayerNorm.weight", L),
            "ln2_bias": _stack(sd, pre + ".output.LayerNorm.bias", L),
        },
    }
    if roberta:
        params.update({
            "mlm_dense": jnp.asarray(sd["lm_head.dense.weight"].T),
            "mlm_dense_bias": jnp.asarray(sd["lm_head.dense.bias"]),
            "mlm_ln_scale": jnp.asarray(sd["lm_head.layer_norm.weight"]),
            "mlm_ln_bias": jnp.asarray(sd["lm_head.layer_norm.bias"]),
            "mlm_bias": jnp.asarray(sd["lm_head.bias"]),
        })
    else:
        params.update({
            "mlm_dense": jnp.asarray(sd["cls.predictions.transform.dense.weight"].T),
            "mlm_dense_bias": jnp.asarray(sd["cls.predictions.transform.dense.bias"]),
            "mlm_ln_scale": jnp.asarray(sd["cls.predictions.transform.LayerNorm.weight"]),
            "mlm_ln_bias": jnp.asarray(sd["cls.predictions.transform.LayerNorm.bias"]),
            "mlm_bias": jnp.asarray(sd["cls.predictions.bias"]),
        })
    log_dist(f"converted HF {base.upper()}: H={H} L={L} heads={nh} vocab={V}",
             ranks=[0])
    return TransformerLM(cfg), params


def from_hf_distilbert(model) -> Tuple[TransformerLM, Dict[str, Any]]:
    """Convert an HF DistilBERT MaskedLM (reference
    ``module_inject/containers/distil_bert.py``). BERT trunk without segment
    embeddings; MLM head = vocab_transform + vocab_layer_norm + tied projector."""
    hf_cfg = model.config
    sd = {k: _np(v) for k, v in model.state_dict().items()}
    H, L, nh = hf_cfg.dim, hf_cfg.n_layers, hf_cfg.n_heads
    V = hf_cfg.vocab_size
    cfg = TransformerConfig(
        vocab_size=V, hidden_size=H, num_layers=L, num_heads=nh,
        intermediate_size=hf_cfg.hidden_dim,
        max_seq_len=hf_cfg.max_position_embeddings,
        causal=False, norm_position="post", mlm_head=True,
        embed_layernorm=True, pos_embedding="learned", norm="layernorm",
        norm_eps=1e-12, activation=_act(hf_cfg.activation),
        tie_embeddings=True, qkv_bias=True, name="distilbert-hf",
    )
    pre = "distilbert.transformer.layer.{}"
    params = {
        "wte": jnp.asarray(sd["distilbert.embeddings.word_embeddings.weight"]),
        "wpe": jnp.asarray(sd["distilbert.embeddings.position_embeddings.weight"]),
        "ln_emb_scale": jnp.asarray(sd["distilbert.embeddings.LayerNorm.weight"]),
        "ln_emb_bias": jnp.asarray(sd["distilbert.embeddings.LayerNorm.bias"]),
        "blocks": {
            "wq": _stackT(sd, pre + ".attention.q_lin.weight", L),
            "wk": _stackT(sd, pre + ".attention.k_lin.weight", L),
            "wv": _stackT(sd, pre + ".attention.v_lin.weight", L),
            "wq_bias": _stack(sd, pre + ".attention.q_lin.bias", L),
            "wk_bias": _stack(sd, pre + ".attention.k_lin.bias", L),
            "wv_bias": _stack(sd, pre + ".attention.v_lin.bias", L),
            "wo": _stackT(sd, pre + ".attention.out_lin.weight", L),
            "attn_bias": _stack(sd, pre + ".attention.out_lin.bias", L),
            "ln1_scale": _stack(sd, pre + ".sa_layer_norm.weight", L),
            "ln1_bias": _stack(sd, pre + ".sa_layer_norm.bias", L),
            "w_up": _stackT(sd, pre + ".ffn.lin1.weight", L),
            "mlp_up_bias": _stack(sd, pre + ".ffn.lin1.bias", L),
            "w_down": _stackT(sd, pre + ".ffn.lin2.weight", L),
            "mlp_bias": _stack(sd, pre + ".ffn.lin2.bias", L),
            "ln2_scale": _stack(sd, pre + ".output_layer_norm.weight", L),
            "ln2_bias": _stack(sd, pre + ".output_layer_norm.bias", L),
        },
        "mlm_dense": jnp.asarray(sd["vocab_transform.weight"].T),
        "mlm_dense_bias": jnp.asarray(sd["vocab_transform.bias"]),
        "mlm_ln_scale": jnp.asarray(sd["vocab_layer_norm.weight"]),
        "mlm_ln_bias": jnp.asarray(sd["vocab_layer_norm.bias"]),
        "mlm_bias": jnp.asarray(sd["vocab_projector.bias"]),
    }
    log_dist(f"converted HF DistilBERT: H={H} L={L} heads={nh} vocab={V}",
             ranks=[0])
    return TransformerLM(cfg), params


_CONVERTERS = {
    "gpt2": from_hf_gpt2,
    "llama": from_hf_llama,
    "mistral": from_hf_llama,
    "qwen2": from_hf_llama,
    "internlm": from_hf_llama,
    "mixtral": from_hf_mixtral,
    "opt": from_hf_opt,
    "gptj": from_hf_gptj,
    "gptneox": from_hf_gptneox,
    "ouro": from_hf_ouro,
    "bloom": from_hf_bloom,
    "falcon": from_hf_falcon,
    "rwforcausallm": from_hf_falcon,  # pre-rename Falcon checkpoints
    "phi": from_hf_phi,
    "distilbert": from_hf_distilbert,
    "roberta": from_hf_bert,
    "bert": from_hf_bert,
    "gemma": from_hf_gemma,
    "gptbigcode": from_hf_gpt_bigcode,
    "mpt": from_hf_mpt,
}

# look-alike architectures with incompatible weight layouts — reject cleanly
# instead of dispatching to a converter that would die on missing keys
_UNSUPPORTED = ["phi3", "phimoe", "internlm2", "qwen2moe", "gptneoforcausallm",
                "albert", "camembert", "deberta", "mobilebert", "squeezebert",
                "flaubert", "gemma2", "gemma3", "recurrentgemma",
                "paligemma"]  # look-alike names, different layouts

# match order matters: more specific names first ("gptneox" before "gptneo",
# "mixtral" before "llama"-substring families)
_MATCH_ORDER = ["ouro", "gptneox", "gptj", "gptbigcode", "gpt2", "mixtral", "qwen2",
                "internlm", "mistral", "llama", "opt", "bloom", "falcon",
                "rwforcausallm", "phi", "distilbert", "roberta", "bert",
                "gemma", "mpt"]


def from_hf(model, **kw):
    """Dispatch on HF architecture (reference ``replace_module`` policy match,
    ``module_inject/replace_policy.py``)."""
    arch = getattr(getattr(model, "config", None), "architectures", None) or []
    name = (arch[0] if arch else type(model).__name__).lower()
    if any(key in name for key in _UNSUPPORTED):
        raise ValueError(f"no converter for HF architecture '{name}' "
                         f"(supported: {sorted(set(_MATCH_ORDER))})")
    for key in _MATCH_ORDER:
        if key in name:
            return _CONVERTERS[key](model, **kw)
    raise ValueError(f"no converter for HF architecture '{name}' "
                     f"(supported: {sorted(set(_MATCH_ORDER))})")
