"""Falcon-H1 (TII 2025, ``tiiuae/Falcon-H1-34B-Instruct``, ``model_type``
``falcon_h1``): every block runs GQA attention and a Mamba-2 (SSD) mixer side
by side off one norm and sums them into the stream, then a gated-SiLU
feed-forward, with a muP scalar on every branch. With ``N`` an RMSNorm::

    x0 = embedding_multiplier * E[ids]
    h  = N(x)
    x  = x + Attn(h * attention_in_multiplier) * attention_out_multiplier
           + SSM(h) * ssm_out_multiplier
    g  = N(x)
    x  = x + W_down(W_up g * silu(W_gate g * m0)) * m1         mlp_multipliers
    logits = W_head N(x) * lm_head_multiplier      (applied in ``final``)

**Attention.** ``q`` in ``num_attention_heads`` heads of ``head_dim``, ``k, v``
in ``num_key_value_heads``; no biases; ``k = (h W_k) * key_multiplier``;
rotate-half rotary (``rope_theta``) on the whole head; scores ``q.k /
sqrt(head_dim)``, causal over the whole context, by blocks of queries.

**SSM** (``mamba_d_ssm`` = ``mamba_n_heads`` heads of ``mamba_d_head``;
``mamba_n_groups`` groups share B and C of ``mamba_d_state``; a depthwise
causal convolution of ``mamba_d_conv`` taps with bias)::

    [z | x | B | C | dt] = (W_in (h * ssm_in_multiplier)) * ssm_multipliers
                                                       (one a zone, in order)
    [x | B | C]_t = silu(sum_i w_i [x | B | C]_{t - d_conv + 1 + i} + b)
    dt_t,h = softplus(dt_t,h + dt_bias_h);   A_h = -exp(A_log_h)
    S_t,h  = exp(dt_t,h A_h) S_{t-1,h} + dt_t,h B_t,g(h)^T x_t,h
    y_t,h  = C_t,g(h) S_t,h + D_h x_t,h
    o      = N_groups(y * silu(z))          (mamba_rms_norm, norm_before_gate
                                             false: groups of d_ssm / n_groups)
    SSM(h) = W_out o

computed as the plain recurrence, one ``lax.scan`` step a position, the state
(heads, d_state, d_head) in float32.

Leaves (one stacked group ``blocks_0``): ``ln1_scale``, ``wq``, ``wk``,
``wv``, ``wo``, ``ssm_w_in``, ``ssm_conv_scale`` (the taps, (d_conv,
channels), the oldest first), ``ssm_conv_bias``, ``a_log``, ``dt_bias``,
``ssm_d_scale`` (D), ``ssm_norm_scale``, ``ssm_w_out``, ``ln2_scale``,
``w_gate``, ``w_up``, ``w_down``; on top ``wte``, ``lnf_scale``, ``lm_head``.

``ablate`` in ``cfg`` (the controls of the tests and of the builder's scratch
runs, never set by a benchmark run) names one mechanism to leave out:
``readout`` (y = D x alone), ``conv`` (the current tap alone), ``decay`` (each
head's decay fixed at its mean over the sequence), ``rotary``.
"""


import jax
import jax.numpy as jnp

from . import causal_attention, scan_layers
from .deepseek_v3 import rms_norm
from .gptneox import rotary


def groups(cfg):
    """The stacked layer groups in the order the forward walks them."""
    return [("blocks_0", cfg["num_hidden_layers"])]


def embed(w, ids, cfg):
    return cfg["embedding_multiplier"] * w["wte"][ids].astype(jnp.float32)


def _divisor(n, most):
    """The largest divisor of ``n`` that is at most ``most``."""
    return max(d for d in range(1, min(n, most) + 1) if n % d == 0)


def zones(cfg):
    """Widths of the in-projection's five zones: z, x, B, C, dt."""
    d_ssm = cfg["mamba_d_ssm"]
    bc = cfg["mamba_n_groups"] * cfg["mamba_d_state"]
    return [d_ssm, d_ssm, bc, bc, cfg["mamba_n_heads"]]


def causal_conv(x, taps, bias, ablate=None):
    """Depthwise: ``y_t = sum_i taps[i] * x_{t - K + 1 + i} + bias`` over (S,
    channels), zeros before the sequence."""
    k = taps.shape[0]
    if ablate == "conv":
        return taps[-1] * x + bias
    padded = jnp.concatenate([jnp.zeros((k - 1, x.shape[1]), x.dtype), x])
    return sum(taps[i] * padded[i:i + x.shape[0]] for i in range(k)) + bias


def ssd(x, b, c, dt, a, ein, ablate=None):
    """The recurrence over positions. x (S, heads, d_head), b, c (S, groups,
    d_state), dt (S, heads), a (heads,); returns C S (S, heads, d_head), the
    skip left to the caller."""
    heads = x.shape[1]
    per = heads // b.shape[1]
    decay = jnp.exp(dt * a)
    if ablate == "decay":
        decay = jnp.broadcast_to(jnp.mean(decay, axis=0, keepdims=True),
                                 decay.shape)

    def step(state, args):
        x, b, c, dt, decay = args
        b, c = (jnp.repeat(t, per, axis=0) for t in (b, c))
        state = decay[:, None, None] * state \
            + ein("hn,hp->hnp", b, dt[:, None] * x)
        return state, ein("hn,hnp->hp", c, state)

    zero = jnp.zeros((heads, b.shape[2], x.shape[2]), jnp.float32)
    return jax.lax.scan(step, zero, (x, b, c, dt, decay))[1]


def mixer(h, blk, cfg, ein):
    """``SSM(h)`` before ``ssm_out_multiplier``: (S, hidden)."""
    s = h.shape[0]
    heads, d_head = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    n_groups, d_state = cfg["mamba_n_groups"], cfg["mamba_d_state"]
    widths, ablate = zones(cfg), cfg.get("ablate")
    p = ein("sh,hd->sd", h * cfg["ssm_in_multiplier"], blk["ssm_w_in"])
    p = p * jnp.concatenate([jnp.full((n,), m, jnp.float32) for n, m in
                             zip(widths, cfg["ssm_multipliers"])])
    d_ssm, bc = widths[0], widths[2]
    z, xbc, dt = p[:, :d_ssm], p[:, d_ssm:2 * d_ssm + 2 * bc], p[:, -heads:]
    xbc = jax.nn.silu(causal_conv(xbc, blk["ssm_conv_scale"],
                                  blk["ssm_conv_bias"], ablate))
    x = xbc[:, :d_ssm].reshape(s, heads, d_head)
    b = xbc[:, d_ssm:d_ssm + bc].reshape(s, n_groups, d_state)
    c = xbc[:, d_ssm + bc:].reshape(s, n_groups, d_state)
    dt = jax.nn.softplus(dt + blk["dt_bias"])
    y = blk["ssm_d_scale"][None, :, None] * x
    if ablate != "readout":
        y = y + ssd(x, b, c, dt, -jnp.exp(blk["a_log"]), ein, ablate)
    y = (y.reshape(s, d_ssm) * jax.nn.silu(z)).reshape(s, n_groups, -1)
    y = y * jax.lax.rsqrt(jnp.mean(jnp.square(y), axis=-1, keepdims=True)
                          + cfg["rms_norm_eps"])
    return ein("sd,dh->sh", y.reshape(s, d_ssm) * blk["ssm_norm_scale"],
               blk["ssm_w_out"])


def attention(h, blk, cfg, ein, q_block=512):
    """``Attn(h)`` before ``attention_out_multiplier``: (S, hidden)."""
    s, d = h.shape[0], cfg["head_dim"]
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    h = h * cfg["attention_in_multiplier"]
    q = ein("sh,hd->sd", h, blk["wq"]).reshape(s, heads, d)
    k = ein("sh,hd->sd", h, blk["wk"]).reshape(s, kv, d) \
        * cfg["key_multiplier"]
    v = ein("sh,hd->sd", h, blk["wv"]).reshape(s, kv, d)
    if cfg.get("ablate") != "rotary":
        theta = float(cfg["rope_theta"])    # 1e11: no int32
        q, k = rotary(q, d, theta), rotary(k, d, theta)
    k, v = (jnp.repeat(t, heads // kv, axis=1) for t in (k, v))
    o = causal_attention(q, k, v, ein, q_block=_divisor(s, q_block))
    return ein("sd,dh->sh", o.reshape(s, heads * d), blk["wo"])


def feed_forward(g, blk, cfg, ein, row_block=2048):
    """``W_down(W_up g * silu(W_gate g * m0)) * m1``, ``row_block`` rows at a
    time."""
    s = g.shape[0]
    rb = _divisor(s, row_block)
    m0, m1 = cfg["mlp_multipliers"]

    def rows(g):
        return ein("si,ih->sh", jax.nn.silu(
            ein("sh,hi->si", g, blk["w_gate"]) * m0)
            * ein("sh,hi->si", g, blk["w_up"]), blk["w_down"]) * m1

    return jax.lax.map(rows, g.reshape(s // rb, rb, -1)).reshape(s, -1)


def layer(x, blk, cfg, ein):
    """One block on (S, H); ``blk`` holds its leaves."""
    eps = cfg["rms_norm_eps"]
    h = rms_norm(x, blk["ln1_scale"], eps)
    x = x + attention(h, blk, cfg, ein) * cfg["attention_out_multiplier"] \
        + mixer(h, blk, cfg, ein) * cfg["ssm_out_multiplier"]
    return x + feed_forward(rms_norm(x, blk["ln2_scale"], eps), blk, cfg, ein)


def final(w, x, cfg):
    """The closing norm, times ``lm_head_multiplier``: ``logits`` gets no
    configuration, and ``W (x m) = (W x) m`` (m is 2^-7 as published, so the
    same bits in float32)."""
    return rms_norm(x, w["lnf_scale"], cfg["rms_norm_eps"]) \
        * cfg["lm_head_multiplier"]


def logits(w, h, ein):
    return ein("sh,hv->sv", h, w["lm_head"])


def hidden(w, ids, cfg, ein):
    """Final-norm hidden states (S, H) of one sequence ``ids`` (S,): the
    parts above over a whole tree."""
    x = embed(w, ids, cfg)
    for group, _ in groups(cfg):
        x = scan_layers(lambda x, b: layer(x, b, cfg, ein), x, w[group])
    return final(w, x, cfg)
