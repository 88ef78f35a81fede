"""Ouro (ByteDance 2025, ``ByteDance/Ouro-2.6B``, ``model_type`` ``ouro``): a
looped language model. ONE stack of ``num_hidden_layers`` identical decoder
layers is run ``total_ut_steps`` times a token; the model's one final norm
closes every step and its output feeds the next; an exit gate reads each
step's closed state and the head reads the state of the step the exit
distribution selects. With ``N`` an RMSNorm, ``T`` the steps, ``L`` the
layers::

    x = E[ids]                                    no embedding scale
    for t in 1..T:                                the SAME L layers each time
      for l in 1..L:
        h = N1_l(x);  q, k, v = h Wq_l, h Wk_l, h Wv_l     no bias
        q, k = rope(q, k)                         the token's position, every t
        a = softmax(q K_{t,l}^T / sqrt(d)) V_{t,l}         causal; the keys and
                                                  values of THIS step and layer
        x = x + N2_l(a Wo_l)                      sandwich: the sublayer's
        f = (silu(N3_l(x) Wgate_l) * (N3_l(x) Wup_l)) Wdown_l     output normed
        x = x + N4_l(f)
      x = Nf(x);  h_t = x                         closes EVERY step
      lam_t = sigmoid(h_t . w_g + b_g)
    p_t = lam_t prod_{j<t} (1 - lam_j)  (t < T);   p_T = prod_{j<T} (1 - lam_j)
    exit = first t with sum_{j<=t} p_j >= early_exit_threshold, else T
    logits = h_exit W_head

Every step is computed and then one is selected: the exit saves no
arithmetic. At the published threshold of 1.0 the last step is every token's.
A token's keys and values of step ``t``, layer ``l`` are a cache layer of
their own (``(t-1) L + l``): ``T L`` cache layers over ``L`` layers of
weights.

**Attention.** ``num_attention_heads`` heads and as many key/value heads of
``head_dim``; rotate-half rotary (``rope_theta``) on the whole head; scores
``q.k / sqrt(head_dim)``, causal over the whole context, by blocks of queries.

**Leaves.** The group ``blocks`` (``L`` layers): ``ln1_scale`` (N1), ``wq``,
``wk``, ``wv``, ``wo``, ``post_attn_scale`` (N2), ``ln2_scale`` (N3),
``w_gate``, ``w_up``, ``w_down``, ``post_mlp_scale`` (N4). The group ``loop``
(ONE layer): ``norm_scale`` (Nf), ``exit_w``, ``exit_b`` (the gate). On top
``wte`` and ``lm_head``.

**What a looped reference lists** (``benchmark/harness/check.py`` walks
``groups(cfg)`` once, hands ``layer`` one group's layer-``l`` leaves and
nothing from the top of the tree, and checks only that a listed count is the
group's): a group may be listed more than once, so ``groups`` is ``[("blocks",
L), ("loop", 1)]`` ``T`` times; ``layer`` handed the ``loop`` leaves is the
closing norm; ``final`` is the identity and ``logits`` the head. The walk
keeps no step's state for a later one, so it is the published threshold's
(1.0: the last step); a lower threshold is ``hidden``'s, which holds the
whole tree.

``ablate`` in ``cfg`` (the controls of the tests and of the builder's scratch
runs, never set by a benchmark run) names one mechanism to leave out:
``steps:<n>`` (``n`` steps for ``T``), ``no_close`` (no norm between steps,
the last one kept), ``no_post_norms`` (N2 and N4 left out), all three in the
walk and in ``hidden``; and in ``hidden`` alone ``shared_cache`` (steps 2..T
attend step 1's keys and values: what a cache without the step's offset
computes).
"""

import jax
import jax.numpy as jnp

from . import causal_attention
from .deepseek_v3 import gated_mlp, rms_norm
from .falcon_h1 import _divisor
from .gptneox import rotary


def steps(cfg):
    ablate = cfg.get("ablate") or ""
    return int(ablate.split(":")[1]) if ablate.startswith("steps:") \
        else cfg["total_ut_steps"]


def groups(cfg):
    """The stacked groups in the order the forward walks them: the layers and
    the closing norm, a step after a step."""
    if cfg["early_exit_threshold"] < 1:
        raise ValueError("ouro reference: the layer-by-layer walk keeps no "
                         "step's state, so it selects the last step "
                         "(early_exit_threshold 1.0); a lower one is hidden()'s")
    if cfg.get("ablate") == "shared_cache":
        raise ValueError("ouro reference: ablate shared_cache is hidden()'s")
    n, layers = steps(cfg), cfg["num_hidden_layers"]
    if cfg.get("ablate") == "no_close":
        return [("blocks", layers)] * n + [("loop", 1)]
    return [("blocks", layers), ("loop", 1)] * n


def embed(w, ids, cfg):
    return w["wte"][ids].astype(jnp.float32)


def keys_values(h, b, cfg, ein):
    """A layer's rotated keys and its values of the normed ``h``: (S, heads,
    head_dim) each, what a step keeps of a token in this layer."""
    s, d, heads = h.shape[0], cfg["head_dim"], cfg["num_key_value_heads"]
    k = ein("sh,hd->sd", h, b["wk"]).reshape(s, heads, d)
    v = ein("sh,hd->sd", h, b["wv"]).reshape(s, heads, d)
    return rotary(k, d, float(cfg["rope_theta"])), v


def block(x, b, cfg, ein, kv=None, q_block=512):
    """One decoder layer on (S, H) over its leaves ``b``; ``kv``: keys and
    values to attend in place of the layer's own (``shared_cache``). Returns
    (x, (k, v))."""
    eps, post = cfg["rms_norm_eps"], cfg.get("ablate") != "no_post_norms"
    s, d = x.shape[0], cfg["head_dim"]
    heads, kvh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    h = rms_norm(x, b["ln1_scale"], eps)
    q = rotary(ein("sh,hd->sd", h, b["wq"]).reshape(s, heads, d), d,
               float(cfg["rope_theta"]))
    own = keys_values(h, b, cfg, ein)
    k, v = (jnp.repeat(t, heads // kvh, axis=1) for t in (kv or own))
    a = causal_attention(q, k, v, ein, q_block=_divisor(s, q_block))
    a = ein("sd,dh->sh", a.reshape(s, heads * d), b["wo"])
    x = x + (rms_norm(a, b["post_attn_scale"], eps) if post else a)
    f = gated_mlp(rms_norm(x, b["ln2_scale"], eps), b["w_gate"], b["w_up"],
                  b["w_down"], ein)
    return x + (rms_norm(f, b["post_mlp_scale"], eps) if post else f), own


def close(x, b, cfg):
    """The end of a step over the ``loop`` leaves ``b``: the final norm."""
    return rms_norm(x, b["norm_scale"], cfg["rms_norm_eps"])


def gate(h, b, ein):
    """``lam`` (S,) of a step's closed state ``h``."""
    return jax.nn.sigmoid(ein("sh,h->s", h, b["exit_w"]) + b["exit_b"])


def layer(x, b, cfg, ein):
    """One entry of the walk: a ``blocks`` layer, or the closing norm where
    ``b`` holds the ``loop`` leaves."""
    if "norm_scale" in b:
        return close(x, b, cfg)
    return block(x, b, cfg, ein)[0]


def final(w, x, cfg):
    """Nothing: the last step's closing norm was the walk's last entry."""
    return x


def logits(w, h, ein):
    return ein("sh,hv->sv", h, w["lm_head"])


def hidden(w, ids, cfg, ein):
    """What the head reads, (S, H), of one sequence ``ids`` (S,): every step
    over the whole tree, then each token's selected step."""
    x = embed(w, ids, cfg)
    loop = jax.tree.map(lambda a: a[0], w["loop"])
    n, ablate = steps(cfg), cfg.get("ablate")

    def walk(x, kv):
        """The layers once; ``kv``: the keys and values each attends, a
        layer each, or None (its own). Returns (x, each layer's own)."""
        def body(x, args):
            return jax.checkpoint(lambda x, b, kv: block(
                x, b, cfg, ein, kv=kv))(x, *args)

        return jax.lax.scan(body, x, (w["blocks"], kv))

    first = None                # step 1's keys and values
    states, lams = [], []
    for t in range(n):
        x, kept = walk(x, first if ablate == "shared_cache" else None)
        first = first or kept
        if ablate != "no_close" or t == n - 1:
            x = close(x, loop, cfg)
        states.append(x)
        lams.append(gate(x, loop, ein))
    # the exit distribution and its running sum; the last step takes the rest
    survive, reached = jnp.ones_like(lams[0]), jnp.zeros_like(lams[0])
    done, out = jnp.zeros(lams[0].shape, bool), jnp.zeros_like(x)
    for t in range(n):
        p = survive if t == n - 1 else lams[t] * survive
        reached = reached + p
        take = ~done & ((reached >= cfg["early_exit_threshold"])
                        | (t == n - 1))
        out = jnp.where(take[:, None], states[t], out)
        done, survive = done | take, survive * (1.0 - lams[t])
    return out
