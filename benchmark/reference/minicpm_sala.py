"""MiniCPM-SALA (OpenBMB 2026, ``openbmb/MiniCPM-SALA``, ``model_type``
``minicpm_sala``): a stack whose layers are of two kinds (``mixer_types``),
block-sparse attention (``minicpm4``) and lightning linear attention
(``lightning-attn``), each followed by a gated-SiLU feed-forward, with the
muP scalars of the MiniCPM family. With ``N`` an RMSNorm and ``a =
scale_depth / sqrt(published num_hidden_layers)``::

    x0 = scale_emb * E[ids]
    x  <- x + a * M(N(x));   x <- x + a * F(N(x))
    logits = W_head (N(x) * dim_model_base / hidden_size)

**Lightning mixer.** ``q, k, v = W_q h, W_k h, W_v h`` as heads; ``q, k``
RMS-normed per head (``qk_norm``), rotary (rotate-half, ``rope_theta``) on
``q, k``; per head the recurrence over positions, in float32::

    S_t = lambda_h S_{t-1} + k_t^T v_t        o_t = (q_t / sqrt(d)) S_t

``lambda_h = exp(-2^(-8 (h + 1) / heads))`` (assumed: the lightning-attention
family's fixed decay, no parameter). Output: ``W_o(sigmoid(W_gate h) *
N(concat_h o_t))``, the norm over all heads' values together. Computed here as
the plain recurrence, one ``lax.scan`` step a position.

**Sparse mixer.** ``q`` in ``num_attention_heads`` heads, ``k, v`` in
``num_key_value_heads``; ``q, k`` RMS-normed per head; no positional term
(``attn_use_rope`` false); scale ``1 / sqrt(d)``. With ``sparse_config``'s
``block_size`` B, ``kernel_size`` K, ``kernel_stride`` s, ``window_size``,
``init_blocks``, ``topk`` and ``dense_len``: a query at position ``t``
(context ``n = t + 1``) attends every token ``<= t`` if ``n <= dense_len``.
Else, with compressed keys ``c_j = mean(k[s j : s j + K])`` for every ``j``
with ``s j + K <= n``: for each KV head, ``s_j`` is the sum over its query
heads of ``softmax_j(q_h . c_j / sqrt(d))``; a block's score is the largest
``s_j`` of the compressed keys that overlap it; the first ``init_blocks``
blocks and the ``window_size / B`` blocks ending at the query's own are always
taken; the ``topk`` highest-scoring blocks in all are attended, causally, the
KV head's query heads sharing the choice; ties go to the lower block. Output:
``W_o(sigmoid(W_gate h) * attention)``. Every score of every query is
computed and the unchosen blocks are masked, ``q_block`` queries at a time, so
that (heads, q_block, S) is the largest array.

Leaves: a run of equal ``mixer_types`` is one stacked group ``blocks_<i>``;
both kinds hold ``ln1_scale``, ``wq``, ``wk``, ``wv``, ``q_norm_scale``,
``k_norm_scale``, ``w_ogate``, ``wo``, ``ln2_scale``, ``w_gate``, ``w_up``,
``w_down``; a lightning layer also ``o_norm_scale``, and its ``wk``, ``wv``
are full width.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

from . import scan_layers
from .deepseek_v3 import rms_norm
from .gptneox import rotary

LIGHTNING, SPARSE = "lightning-attn", "minicpm4"


def runs(cfg):
    """[(mixer type, layers)] of each run of equal ``mixer_types``."""
    out = []
    for t in cfg["mixer_types"]:
        if out and out[-1][0] == t:
            out[-1][1] += 1
        else:
            out.append([t, 1])
    return [tuple(r) for r in out]


def groups(cfg):
    """The stacked layer groups in the order the forward walks them."""
    return [(f"blocks_{i}", n) for i, (_, n) in enumerate(runs(cfg))]


def residual_scale(cfg):
    return cfg["scale_depth"] / math.sqrt(cfg["published"]["num_hidden_layers"])


def embed(w, ids, cfg):
    return cfg["scale_emb"] * w["wte"][ids].astype(jnp.float32)


def _divisor(n, most):
    """The largest divisor of ``n`` that is at most ``most``."""
    return max(d for d in range(1, min(n, most) + 1) if n % d == 0)


def feed_forward(h, b, ein, row_block=4096):
    """``W_d(silu(W_g h) * W_u h)``, ``row_block`` rows at a time."""
    s = h.shape[0]
    rb = _divisor(s, row_block)

    def rows(h):
        return ein("si,ih->sh", jax.nn.silu(ein("sh,hi->si", h, b["w_gate"]))
                   * ein("sh,hi->si", h, b["w_up"]), b["w_down"])

    return jax.lax.map(rows, h.reshape(s // rb, rb, -1)).reshape(s, -1)


def head_decays(heads):
    """(heads,) ``lambda_h``."""
    return jnp.exp(-jnp.exp2(-8.0 * jnp.arange(1, heads + 1, dtype=jnp.float32)
                             / heads))


def lightning(q, k, v, ein):
    """The recurrence over positions. q, k, v (S, heads, d), q already
    scaled; returns (S, heads, d)."""
    lam = head_decays(q.shape[1])[:, None, None]

    def step(state, qkv):
        q, k, v = qkv
        state = lam * state + ein("hk,hv->hkv", k, v)
        return state, ein("hk,hkv->hv", q, state)

    zero = jnp.zeros((q.shape[1], q.shape[2], v.shape[2]), jnp.float32)
    return jax.lax.scan(step, zero, (q, k, v))[1]


def compressed_keys(k, sc):
    """(J, kv heads, d): ``c_j = mean(k[s j : s j + K])`` of one sequence's
    keys (S, kv heads, d), every ``j`` whose window lies inside S."""
    K, s = sc["kernel_size"], sc["kernel_stride"]
    n_j = (k.shape[0] - K) // s + 1
    idx = s * np.arange(n_j)[:, None] + np.arange(K)[None]
    return jnp.mean(k[idx], axis=1)


def chosen_blocks(q, c, pos, sc, n_blocks, ein):
    """(Q, kv heads, n_blocks) bool: the blocks each query attends. q (Q,
    heads, d) at positions ``pos`` (Q,); c (J, kv heads, d) the sequence's
    compressed keys."""
    B, K, s = sc["block_size"], sc["kernel_size"], sc["kernel_stride"]
    n_q, heads, d = q.shape
    kvh = c.shape[1]
    j = np.arange(c.shape[0])
    n = pos + 1
    logit = ein("qhgd,jhd->hgqj", q.reshape(n_q, kvh, heads // kvh, d),
                c) / math.sqrt(d)
    defined = (s * j + K)[None] <= n[:, None]                        # (Q, J)
    p = jax.nn.softmax(jnp.where(defined, logit, -1e30), axis=-1)
    s_j = jnp.where(defined, jnp.sum(p, axis=1), -1.0)               # (h, Q, J)
    blocks = np.arange(n_blocks)
    overlap = ((s * j + K - 1)[None] >= (B * blocks)[:, None]) \
        & ((s * j)[None] <= (B * blocks + B - 1)[:, None])           # (nb, J)
    score = jnp.max(jnp.where(overlap, s_j[:, :, None, :], -1.0), axis=-1)
    own = (pos // B)[None, :, None]
    b = blocks[None, None]
    forced = (b < sc["init_blocks"]) | ((b > own - sc["window_size"] // B)
                                        & (b <= own))
    score = jnp.where(b > own, -2.0, jnp.where(forced, jnp.inf, score))
    order = jnp.argsort(-score, axis=-1, stable=True)  # ties: lower block
    rank = jnp.argsort(order, axis=-1, stable=True)
    chosen = (rank < sc["topk"]) & (b <= own)
    chosen = chosen | ((n <= sc["dense_len"])[None, :, None] & (b <= own))
    return chosen.transpose(1, 0, 2)


def sparse_attention(q, k, v, sc, ein, q_block=128):
    """q (S, heads, d), k, v (S, kv heads, d) of one sequence; returns (S,
    heads, d). Every score is computed; what a query did not choose is
    masked."""
    S, heads, d = q.shape
    kvh, B = k.shape[1], sc["block_size"]
    g = heads // kvh
    c = compressed_keys(k, sc)
    n_blocks = -(-S // B)
    block_of = np.arange(S) // B
    qb = _divisor(S, q_block)

    def rows(args):
        q, first = args
        pos = first + jnp.arange(qb)
        chosen = chosen_blocks(q, c, pos, sc, n_blocks, ein)     # (Q, kvh, nb)
        mask = chosen[:, :, block_of] \
            & (np.arange(S)[None] <= pos[:, None])[:, None]      # (Q, kvh, S)
        scores = ein("qhgd,khd->hgqk", q.reshape(qb, kvh, g, d), k) \
            / math.sqrt(d)
        probs = jax.nn.softmax(
            jnp.where(mask.transpose(1, 0, 2)[:, None], scores, -jnp.inf),
            axis=-1)
        return ein("hgqk,khd->qhgd", probs, v).reshape(qb, heads, d)

    out = jax.lax.map(rows, (q.reshape(S // qb, qb, heads, d),
                             jnp.arange(0, S, qb)))
    return out.reshape(S, heads, d)


def layer(x, b, cfg, ein):
    """One layer on (S, H): ``b`` holds its leaves; a lightning layer is the
    one with an output norm."""
    eps, a = cfg["rms_norm_eps"], residual_scale(cfg)
    heads, d = cfg["num_attention_heads"], cfg["head_dim"]
    s = x.shape[0]
    h = rms_norm(x, b["ln1_scale"], eps)

    def proj(name, norm=None):
        y = ein("sh,hd->sd", h, b[name]).reshape(s, -1, d)
        return y if norm is None else rms_norm(y, b[norm], eps)

    q, k, v = proj("wq", "q_norm_scale"), proj("wk", "k_norm_scale"), proj("wv")
    if "o_norm_scale" in b:
        theta = cfg["rope_theta"]
        o = lightning(rotary(q, d, theta) / math.sqrt(d), rotary(k, d, theta),
                      v, ein)
        o = rms_norm(o.reshape(s, -1), b["o_norm_scale"], eps)
    else:
        o = sparse_attention(q, k, v, cfg["sparse_config"], ein).reshape(s, -1)
    gate = jax.nn.sigmoid(ein("sh,hd->sd", h, b["w_ogate"]))
    x = x + a * ein("sd,dh->sh", gate * o, b["wo"])
    return x + a * feed_forward(rms_norm(x, b["ln2_scale"], eps), b, ein)


def final(w, x, cfg):
    return rms_norm(x, w["lnf_scale"], cfg["rms_norm_eps"]) \
        * (cfg["dim_model_base"] / cfg["hidden_size"])


def logits(w, h, ein):
    return ein("sh,hv->sv", h, w["lm_head"])


def hidden(w, ids, cfg, ein):
    """Final-norm hidden states (S, H) of one sequence ``ids`` (S,): the
    parts above over a whole tree."""
    x = embed(w, ids, cfg)
    for group, _ in groups(cfg):
        x = scan_layers(lambda x, b: layer(x, b, cfg, ein), x, w[group])
    return final(w, x, cfg)
