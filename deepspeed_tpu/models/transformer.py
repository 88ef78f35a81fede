"""Decoder-only transformer LM — the flagship model family.

The reference ships models as torch ``nn.Module`` graphs that the engine wraps
(e.g. the hand-fused BERT layer ``deepspeed/ops/transformer/transformer.py:296``,
the inference model implementations ``deepspeed/inference/v2/model_implementations/
{llama_v2,mistral,...}``). The TPU-native design is one functional LM whose config
spans both families:

- GPT-2 style: learned positions, LayerNorm (with bias), GELU MLP, tied embeddings.
- LLaMA style: rotary positions, RMSNorm, SwiGLU MLP, grouped-query attention.

Architecture choices driven by XLA/TPU:
- **scan over layers**: block weights are stacked along a leading layer axis and the
  body is a single traced block → compile time is O(1) in depth, and
  ``jax.checkpoint`` on the block gives per-layer rematerialisation (the analogue of
  reference ``runtime/activation_checkpointing/checkpointing.py``).
- **sharding by annotation**: tensor parallelism is a pytree of ``PartitionSpec``
  (``tp_specs``) over the mesh's ``model`` axis — column-parallel QKV/up-proj,
  row-parallel out/down-proj, vocab-parallel embedding. Sequence parallelism
  (Ulysses, reference ``deepspeed/sequence/layer.py:60``) is expressed as sharding
  constraints: activations live seq-sharded; inside attention heads are re-sharded
  over the ``seq`` axis so XLA inserts the same all-to-alls the reference issues
  manually.
- bf16 compute / fp32 softmax+loss; static shapes throughout; causal masking via
  iota comparison (no materialised (S,S) bool tensor at peak memory).
"""

import contextlib
import math
import re
from dataclasses import dataclass, field, replace
from functools import partial
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..comm.topology import ZERO_AXES
from ..ops.quantizer.woq import dequant_params as _dequant_woq
from ..ops.transformer.attention import attention as _attention_op
from ..ops.transformer.fused_ce import head_logits, head_nll, stream_block
from ..ops.transformer.gelu_exact import gelu_exact
from ..utils import tracing
from ..utils.logging import logger


#: the mixers a ``layer_types`` model may name
LAYER_TYPES = ("sparse_attn", "linear_attn", "window_attn", "full_attn",
               "hybrid_ssm", "delta_attn", "latent_attn")


@dataclass(frozen=True)
class LayerKind:
    """One kind of layer, described once: what the tree, the specs, the
    counts, the cache declaration, the paged forward and the tracer ask of
    it. ``TransformerConfig.type_runs`` turns a configuration into kinds and
    everything else reads the kind's record in ``LAYER_KINDS``, so a new
    architecture is its configuration fields, a record here and the layer
    function the record names. ``cfg`` is the model's
    :class:`TransformerConfig`, ``lm`` its :class:`TransformerLM`."""
    #: cfg -> {leaf: shape a layer}: a layer's leaves but for its
    #: feed-forward's, which ``TransformerConfig.tree_shapes`` adds. None:
    #: the GPT-2 family's tree, which ``init_params`` writes out itself
    leaves: Optional[Callable]
    #: cfg -> (heads, (key width, value width)) of a token's row of the pool
    row: Callable
    #: (cfg, S) -> the attention's FLOPs a token, forward and backward, in a
    #: sequence of S tokens, beside its matrices' (``flops_per_token``)
    attn_flops: Callable
    #: cfg -> rows of a chunk-segment tile of a paged step (1: a prefill
    #: chunk is one-token rows like any other)
    tile: Callable
    #: lm -> the layer function ``forward_paged`` scans over a group
    layer: Callable
    #: a paged row may hold more than one token
    wide_rows: bool = False
    #: pool layers a layer: KV blocks a token for each (0: none)
    pool_layers: int = 1
    #: the class of those blocks, and for a bounded class cfg -> the tokens
    #: behind which a sequence's blocks are freed
    block_class: str = "full"
    bound: Optional[Callable] = None
    #: where the kind keeps a state slot a sequence a layer: (cfg, layers,
    #: max_seqs, max_seq_len, dtype) -> a group's slot array (layers, 1 +
    #: max_seqs, ...), slot 0 the trash slot; or a small pytree of such
    #: arrays, where a slot is more than one thing (a recurrence's state and
    #: its convolution's window): a slot stays one row index into all of them
    slots: Optional[Callable] = None
    #: the int32 counts its layer function reports (``step_counts``)
    counts: Tuple[str, ...] = ()
    #: the ``jax.named_scope`` names it opens that are a layer's own part
    #: on the device (``utils/tracing.py`` ``classify``)
    scopes: Tuple[str, ...] = ()


def _gqa_row(cfg):
    return cfg.kv_heads, (cfg.head_dim, cfg.head_dim)


def _latent_row(cfg):
    from ..ops.transformer.paged_attention import latent_row

    return 1, latent_row(cfg.kv_lora_rank, cfg.qk_rope_head_dim)


def _latent_tile(cfg):
    from ..ops.transformer.paged_attention import SEGMENT_TILE

    return SEGMENT_TILE


def _gated_mlp_leaves(cfg, width, prefix=""):
    H = cfg.hidden_size
    return {prefix + "w_gate": (H, width), prefix + "w_up": (H, width),
            prefix + "w_down": (width, H)}


def _latent_leaves(cfg):
    """A latent-attention layer's: the queries through a low-rank step
    (``wq_a``, its norm, ``wq_b``) or, with ``q_lora_rank`` 0, one matrix
    ``wq``; with ``attn_head_gate`` the gate's one projection, a scalar a
    head."""
    H, nh = cfg.hidden_size, cfg.num_heads
    qr, kvr = cfg.q_lora_rank, cfg.kv_lora_rank
    nope, rope, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    q = {"wq_a": (H, qr), "q_a_scale": (qr,),
         "wq_b": (qr, nh * (nope + rope))} if qr \
        else {"wq": (H, nh * (nope + rope))}
    return {"ln1_scale": (H,), **q, "wkv_a": (H, kvr + rope),
            "kv_a_scale": (kvr,), "wkv_b": (kvr, nh * (nope + vd)),
            **({"w_ogate": (H, nh)} if cfg.attn_head_gate else {}),
            "wo": (nh * vd, H), "ln2_scale": (H,)}


def _scmoe_leaves(cfg):
    # a sublayer's leaves are a dense layer's, under its prefix
    dense = {**_latent_leaves(cfg),
             **_gated_mlp_leaves(cfg, cfg.dense_mlp_dim)}
    return {sublayer_prefix(i) + k: v for i in range(cfg.sublayers)
            for k, v in dense.items()}


def _mixer_leaves(cfg, kv_width, **own):
    """A ``layer_types`` layer's: the norms, q, k (``kv_width`` wide) and v,
    ``wo``, the head norms and the gate where the model has them, the
    mixer's ``own``."""
    H, hd = cfg.hidden_size, cfg.head_dim
    qd = cfg.num_heads * hd
    leaves = {"ln1_scale": (H,), "wq": (H, qd), "wk": (H, kv_width),
              "wv": (H, kv_width), "wo": (qd, H), "ln2_scale": (H,)}
    if cfg.qk_norm:
        leaves.update(q_norm_scale=(hd,), k_norm_scale=(hd,))
    if cfg.attn_output_gate:
        leaves["w_ogate"] = (H, qd)
    if cfg.post_norms:
        leaves.update(post_attn_scale=(H,), post_mlp_scale=(H,))
    return {**leaves, **own}


def _gqa_leaves(cfg):
    return _mixer_leaves(cfg, cfg.kv_heads * cfg.head_dim)


def _linear_leaves(cfg):
    qd = cfg.num_heads * cfg.head_dim
    return _mixer_leaves(cfg, qd, o_norm_scale=(qd,))


def _hybrid_leaves(cfg):
    """A ``hybrid_ssm`` layer's: GQA attention and an SSD (Mamba-2) mixer off
    one norm. The mixer's in-projection gives ``[z | x | B | C | dt]``
    (``ssm_inner`` | ``ssm_inner`` | 2 x ``ssm_groups * ssm_state`` | a head
    each), the depthwise convolution runs over ``[x | B | C]``; ``a_log``,
    ``dt_bias`` and the skip ``D`` are a head's; the gated norm's scale is
    ``ssm_inner`` wide. The leaves whose published initialisation is near one
    (the taps, ``D``, the norm) are named ``*_scale``."""
    H, hd = cfg.hidden_size, cfg.head_dim
    qd, kvd = cfg.num_heads * hd, cfg.kv_heads * hd
    inner, nh = cfg.ssm_inner, cfg.ssm_heads
    return {"ln1_scale": (H,), "wq": (H, qd), "wk": (H, kvd), "wv": (H, kvd),
            "wo": (qd, H),
            "ssm_w_in": (H, inner + cfg.ssm_conv_channels + nh),
            "ssm_conv_scale": (cfg.ssm_conv, cfg.ssm_conv_channels),
            "ssm_conv_bias": (cfg.ssm_conv_channels,),
            "a_log": (nh,), "dt_bias": (nh,), "ssm_d_scale": (nh,),
            "ssm_norm_scale": (inner,), "ssm_w_out": (inner, H),
            "ln2_scale": (H,)}


def _delta_leaves(cfg):
    """A ``delta_attn`` (KDA) layer's: q, k and v a head each of ``head_dim``,
    the depthwise convolution's taps over ``[q | k | v]`` (no bias), the
    channels' decay (``w_decay`` one full-rank matrix, ``dt_bias`` a channel's,
    ``a_log`` a head's), ``w_beta`` a scalar a head, the output norm's one
    scale of ``head_dim`` and, with ``attn_head_gate``, the gate's
    projection, a scalar a head."""
    H, hd, nh = cfg.hidden_size, cfg.head_dim, cfg.num_heads
    qd = nh * hd
    return {"ln1_scale": (H,), "wq": (H, qd), "wk": (H, qd), "wv": (H, qd),
            "kda_conv_scale": (cfg.ssm_conv, 3 * qd), "w_decay": (H, qd),
            "a_log": (nh,), "dt_bias": (qd,), "w_beta": (H, nh),
            "o_norm_scale": (hd,),
            **({"w_ogate": (H, nh)} if cfg.attn_head_gate else {}),
            "wo": (qd, H), "ln2_scale": (H,)}


def _heads_flops(cfg, seen):
    """q k and p v of every head over ``seen`` positions a token."""
    return 6 * 2 * cfg.num_heads * cfg.head_dim * seen


def _latent_flops(cfg, S):
    return 6 * cfg.num_heads * (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
                                + cfg.v_head_dim) * S


def _sparse_flops(cfg, S):
    # at most topk blocks are scored (the whole context under dense_len)
    return _heads_flops(cfg, S if S <= cfg.sparse_dense_len else min(
        S, cfg.sparse_topk * cfg.sparse_block_size))


def _linear_slots(cfg, n, max_seqs, max_seq_len, dtype):
    from ..ops.transformer import linear_attention as la

    return la.init_state(n, max_seqs, cfg.num_heads, cfg.head_dim,
                         cfg.head_dim)


def _hybrid_slots(cfg, n, max_seqs, max_seq_len, dtype):
    """The SSD state (float32) and the convolution's last ``ssm_conv - 1``
    input rows (the model's dtype), a slot a sequence in both."""
    from ..ops.transformer import linear_attention as la

    return {"ssm": la.init_state(n, max_seqs, cfg.ssm_heads, cfg.ssm_state,
                                 cfg.ssm_head_dim),
            "conv": la.init_conv(n, max_seqs, cfg.ssm_conv,
                                 cfg.ssm_conv_channels, dtype)}


def _delta_slots(cfg, n, max_seqs, max_seq_len, dtype):
    """The delta rule's state (float32) and the convolution's last
    ``ssm_conv - 1`` rows of ``[q | k | v]`` (the model's dtype), a slot a
    sequence in both."""
    from ..ops.transformer import linear_attention as la

    nh, hd = cfg.num_heads, cfg.head_dim
    return {"state": la.init_state(n, max_seqs, nh, hd, hd),
            "conv": la.init_conv(n, max_seqs, cfg.ssm_conv, 3 * nh * hd,
                                 dtype)}


def _sparse_slots(cfg, n, max_seqs, max_seq_len, dtype):
    from ..ops.transformer import sparse_attention as sa

    return sa.init_keys(n, max_seqs, cfg.kv_heads,
                        cfg.sparse_spec.max_keys(max_seq_len), cfg.head_dim,
                        dtype)


def _typed_kind(mixer, attn_flops, leaves=_gqa_leaves, scope=None, **kw):
    """The record of a ``layer_types`` kind: ``_typed_layer`` around the
    kind's ``mixer`` (lm -> its function), the whole attention sublayer under
    ``scope`` where its device time is told apart from the other kinds';
    GQA rows in the pool, tiles of ``linear_chunk`` rows."""
    if scope:
        kw["scopes"] = (scope,)
    return LayerKind(
        leaves=leaves, row=_gqa_row, attn_flops=attn_flops,
        tile=lambda cfg: cfg.linear_chunk,
        layer=lambda lm: partial(lm._typed_layer, mixer(lm), scope=scope),
        **kw)


#: kind (``TransformerConfig.type_runs``' second column) -> its record
LAYER_KINDS: Dict[str, LayerKind] = {
    # GPT-2 and LLaMA style attention (``_block``'s paged branch)
    "full": LayerKind(
        leaves=None, row=_gqa_row, attn_flops=_heads_flops,
        tile=lambda cfg: 1, layer=lambda lm: lm._full_layer, wide_rows=True),
    # latent attention (``_block_mla``), the cache a row of [c_kv | k_rope]
    "latent": LayerKind(
        leaves=_latent_leaves, row=_latent_row, attn_flops=_latent_flops,
        tile=_latent_tile, scopes=("mla_proj",),
        layer=lambda lm: partial(lm._latent_layer, lm._block_mla)),
    # the shortcut-connected double layer (``_block_scmoe``): two latent
    # attentions, each with its pool layer, and two dense feed-forwards
    "scmoe": LayerKind(
        leaves=_scmoe_leaves, row=_latent_row, pool_layers=2,
        attn_flops=lambda cfg, S: 2 * _latent_flops(cfg, S),
        tile=_latent_tile, scopes=("mla_proj", "dense_ffn"),
        layer=lambda lm: partial(lm._latent_layer, lm._block_scmoe)),
    # block-sparse GQA: a slot of compressed keys, for ``max_seq_len``
    # tokens, beside the KV blocks
    "sparse_attn": _typed_kind(
        lambda lm: lm._sparse_mixer, _sparse_flops, slots=_sparse_slots,
        counts=("sel_blocks", "ctx_blocks"), scopes=("sparse_select",)),
    # lightning attention: a float32 state a head, the same at any length
    # (k^T v and q S), and no KV blocks
    "linear_attn": _typed_kind(
        lambda lm: lm._linear_mixer,
        lambda cfg, S: _heads_flops(cfg, cfg.head_dim),
        leaves=_linear_leaves, pool_layers=0, slots=_linear_slots,
        scopes=("linear_attn",)),
    # GQA with rotary over the last ``sliding_window`` tokens, its blocks a
    # class of their own
    "window_attn": _typed_kind(
        lambda lm: partial(lm._attn_mixer, True, True),
        lambda cfg, S: _heads_flops(cfg, min(S, cfg.sliding_window or S)),
        scope="window_attn", block_class="window",
        bound=lambda cfg: cfg.sliding_window),
    # GQA with no positional term over the whole context
    "full_attn": _typed_kind(
        lambda lm: partial(lm._attn_mixer, False, False), _heads_flops,
        scope="full_attn"),
    # GQA with rotary over the whole context and an SSD (Mamba-2) mixer side
    # by side off one norm: KV blocks AND a slot of two arrays a layer (the
    # float32 state, the convolution's window); the recurrence's own scope is
    # declared before the branch's, so it is told apart inside it
    "hybrid_ssm": LayerKind(
        leaves=_hybrid_leaves, row=_gqa_row,
        attn_flops=lambda cfg, S: _heads_flops(cfg, S) + 6 * 2 * (
            cfg.ssm_heads * cfg.ssm_state * cfg.ssm_head_dim),
        tile=lambda cfg: cfg.linear_chunk,
        layer=lambda lm: lm._hybrid_layer, slots=_hybrid_slots,
        scopes=("ssm_scan", "ssm_mixer", "dense_ffn")),
    # the gated delta rule with a decay of each key channel (KDA): a float32
    # state a head and the window of a convolution over [q | k | v], and no KV
    # blocks; the recurrence's own scope is declared before the sublayer's
    "delta_attn": LayerKind(
        leaves=_delta_leaves, row=_gqa_row,
        attn_flops=lambda cfg, S: 6 * 7 * cfg.num_heads * cfg.head_dim ** 2,
        tile=lambda cfg: cfg.linear_chunk, pool_layers=0,
        layer=lambda lm: lm._delta_layer, slots=_delta_slots,
        scopes=("delta_scan", "delta_attn", "dense_ffn")),
    # latent attention as one mixer of a ``layer_types`` model: the latent
    # kind's block, its rows in tiles of the model's ``linear_chunk`` (the
    # kernel takes them ``SEGMENT_TILE`` at a time)
    "latent_attn": LayerKind(
        leaves=_latent_leaves, row=_latent_row, attn_flops=_latent_flops,
        tile=lambda cfg: cfg.linear_chunk, scopes=("mla_proj",),
        layer=lambda lm: partial(lm._latent_layer, lm._block_mla)),
}
# a kind's scopes are declared to the tracer here, where they are opened
tracing.layer_scopes(*(s for kind in LAYER_KINDS.values()
                       for s in kind.scopes))
# and the one the end of a looped model's step is traced under
# (``TransformerLM._loop_close``: the closing norm and the exit gate)
tracing.layer_scopes("loop_close")


@dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 50304  # padded to a multiple of 128 (MXU lane width)
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    num_kv_heads: Optional[int] = None  # GQA; None = MHA
    head_dim_override: Optional[int] = None  # Gemma: head_dim != H/num_heads
    intermediate_size: Optional[int] = None  # None → 4*H (gelu) or 8/3*H (swiglu)
    max_seq_len: int = 1024
    # family knobs
    causal: bool = True  # False = bidirectional encoder (BERT family)
    norm_position: str = "pre"  # "pre" | "post" (BERT-style residual-then-LN)
    token_type_embedding: int = 0  # >0: BERT segment embeddings (type vocab size)
    mlm_head: bool = False  # BERT MLM head: dense+act+LN before the tied decoder
    pos_embedding: str = "learned"  # "learned" | "rope" | "alibi" | "none"
    norm: str = "layernorm"  # "layernorm" | "rmsnorm"
    # "gelu" (tanh) | "gelu_exact" (erf: ops/transformer/gelu_exact.py, one unit
    # with its own backward, evaluated once a pass) | "relu" | "swiglu" | "geglu"
    activation: str = "gelu"
    tie_embeddings: bool = True
    qkv_bias: bool = False  # GPT-2-style biases on q/k/v projections
    attn_out_bias: bool = False  # bias on the attention out-proj even under rmsnorm (InternLM)
    norm_eps: float = 1e-5
    norm_weight_offset: float = 0.0  # Gemma RMSNorm: scale = offset + weight
    embed_scale: Optional[float] = None  # Gemma: embeddings scaled by sqrt(H)
    rope_theta: float = 10000.0
    rotary_dim: Optional[int] = None  # partial rotary (GPT-J/NeoX/Phi); None = head_dim
    # parallel residual: x + attn(ln(x)) + mlp(ln(x)) (GPT-J/NeoX/Falcon/Phi,
    # reference containers ``module_inject/containers/{gptj,gptneox,...}.py``)
    parallel_block: bool = False
    parallel_shared_ln: bool = True  # one LN feeds both branches (GPT-J/Falcon/Phi); False = two LNs (NeoX)
    embed_layernorm: bool = False  # LayerNorm after token embedding (BLOOM)
    # ALiBi slope multiplier: 1.0 (BLOOM adds the bias post-scale); Falcon folds
    # the bias in BEFORE the 1/sqrt(head_dim) scaling, so its converter sets this
    # to head_dim**-0.5
    alibi_slope_scale: float = 1.0
    lm_head_bias: bool = False  # untied LM head carries a bias (GPT-J, Phi)
    dropout: float = 0.0
    # MoE (0 experts = dense MLP; >0 replaces every MLP with a routed MoE FFN)
    num_experts: int = 0
    moe_top_k: int = 1
    moe_capacity_factor: float = 1.25
    moe_aux_loss_coef: float = 0.01
    moe_drop_tokens: bool = True  # False = capacity C=T, no drops (Mixtral parity)
    # Residual/PR-MoE (arXiv:2201.05596; reference moe/layer.py:29,47
    # use_residual): dense MLP alongside the experts, learned 2-way softmax
    # coefficient blends the two outputs per token
    moe_use_residual: bool = False
    # DeepSeek-V3-style routing (``moe_router="group_limited"``): sigmoid
    # scores over ``moe_router_width`` outputs (0 = num_experts), a
    # selection-only bias, ``moe_n_group`` groups of which the
    # ``moe_topk_group`` best are kept, top-k inside them, weights normalised
    # over the chosen and scaled by ``moe_score_scale``; no capacity, no drop.
    # ``num_experts`` then counts the experts HELD HERE (their weights exist),
    # the first being the router's output ``moe_expert_offset``: the share of
    # an expert-parallel deployment (moe/layer.py ``held_experts_ffn``)
    # ``moe_router="softmax_topk"`` (LongCat-Flash): softmax scores over the
    # router's outputs, a selection-only bias in units of the uniform score
    # ``1 / moe_router_width``, top-k with no groups, weights the unbiased
    # scores times ``moe_score_scale`` (``moe_norm_topk`` False: not divided
    # by their sum). The router's last ``moe_zero_experts`` outputs are
    # identity (zero-compute) experts: a choice among them adds ``weight * h``
    # and touches no matrix
    moe_router: str = "gshard"  # "gshard" | "group_limited" | "softmax_topk"
    moe_router_width: int = 0
    moe_expert_offset: int = 0
    moe_n_group: int = 1
    moe_topk_group: int = 1
    moe_norm_topk: bool = True
    moe_score_scale: float = 1.0
    moe_shared_size: int = 0  # width of the shared expert beside the routed ones
    moe_zero_experts: int = 0
    # leading dense layers before the expert layers (two stacked groups:
    # params["dense_blocks"] then params["blocks"]), at their own MLP width
    num_dense_layers: int = 0
    dense_intermediate_size: Optional[int] = None
    # "single": one attention and one feed-forward a layer. "scmoe": the
    # shortcut-connected double layer (LongCat-Flash; attention="mla" only):
    # two attentions and two dense feed-forwards ``dense_intermediate_size``
    # wide a layer, and one expert layer that branches off the first
    # post-attention norm and rejoins the residual stream after the second
    # feed-forward. ``num_layers`` counts double layers
    layer_kind: str = "single"
    # attention kind: "mha" (q/k/v heads, GQA by num_kv_heads) | "mla"
    # (multi-head latent attention, DeepSeek-V2/V3: low-rank q and kv latents,
    # a rope head shared by all heads; the cache holds [c_kv | k_rope])
    attention: str = "mha"
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # the latent norms' outputs times sqrt(hidden_size / rank) (LongCat-Flash)
    mla_scale_q_lora: bool = False
    mla_scale_kv_lora: bool = False
    # a mixer a layer (``layer_types``; None = every layer the one block
    # above): "sparse_attn" (block-sparse attention over the paged pool:
    # GQA, no positional term, the ``sparse_*`` sizes
    # of ops/transformer/sparse_attention.py; the pool's block is the
    # selector's) | "linear_attn" (lightning attention: a fixed float32 state
    # a sequence and head, rotary, an output norm; ops/transformer/
    # linear_attention.py). A run of equal types is one stacked group
    # ``params["blocks_<i>"]``, scanned. Both mixers take ``qk_norm`` (RMSNorm
    # of each q and k head) and ``attn_output_gate`` (``sigmoid(W h)`` on the
    # attention's output before ``wo``). "window_attn" | "full_attn" (afmoe:
    # Trinity): GQA over the paged pool through the decode kernel, a window
    # layer with rotary and a query at ``i`` seeing the keys ``0 <= i - j <
    # sliding_window``, a full layer with no positional term and ``j <= i``;
    # the window layers' blocks are a class of their own, freed behind the
    # window (``bounded_cache``). ``post_norms``: an RMSNorm on each
    # sublayer's output before it joins the residual stream (four norms a
    # layer). The feed-forward of a layer_types model is dense, or (
    # ``num_experts`` > 0) the first ``num_dense_layers`` layers' is dense at
    # ``dense_intermediate_size`` and the others hold experts. Serving only
    # "delta_attn" (Kimi Delta Attention, bailing_hybrid's linear layers):
    # q, k, v a head each through a depthwise causal convolution of
    # ``ssm_conv`` taps (no bias) and SiLU, q and k L2-normed a head, a decay
    # of each key channel ``kda_log_floor * sigmoid(exp(a_log) * (W_f h +
    # dt_bias))`` in (``kda_log_floor``, 0) and the gated delta rule
    # (ops/transformer/linear_attention.py: ``beta``), an RMSNorm of each
    # head's output and the output gate; a state slot and no KV blocks.
    # "latent_attn": latent attention (``kv_lora_rank`` and the three head
    # widths; ``q_lora_rank`` 0: the queries are one matrix) as a mixer beside
    # other kinds, its rows in the latent pool. ``attn_head_gate``: the
    # output gate of these two is one scalar a head (``w_ogate`` (H, heads))
    layer_types: Optional[Tuple[str, ...]] = None
    qk_norm: bool = False
    attn_output_gate: bool = False
    attn_head_gate: bool = False
    kda_log_floor: float = -5.0
    sliding_window: int = 0
    post_norms: bool = False
    # a looped model (Ouro): the SAME ``num_layers`` layers of weights run
    # ``loop_steps`` times a token. The final norm closes every step and its
    # output feeds the next one; a token's keys and values of step ``t``,
    # layer ``l`` live at cache layer ``t * num_layers + l`` (``pool_layers``
    # is ``loop_steps`` walks of the stack); an exit gate ``sigmoid(h_t .
    # exit_w + exit_b)`` reads each step's closed state and the head reads
    # the state of the first step at which the exit distribution's sum
    # reaches ``early_exit_threshold`` (1.0: the last step's, every token;
    # every step is computed either way). The closing norm and the gate are a
    # stacked group of one layer, ``params["loop"]``, in ``lnf_scale``'s
    # place. The ``full`` kind only
    loop_steps: int = 1
    early_exit_threshold: float = 1.0
    sparse_kernel_size: int = 32
    sparse_kernel_stride: int = 16
    sparse_window: int = 2048       # tokens; whole blocks ending at the query's
    sparse_init_blocks: int = 1
    sparse_topk: int = 64
    sparse_dense_len: int = 8192
    # the block a sparse layer scores and picks by: the model's own (its
    # published ``sparse_config.block_size``), and so the only pool block it
    # can be served from (``init_kv_pool`` refuses another)
    sparse_block_size: int = 64
    # rows of a prefill tile of a layer_types model (a linear layer's chunk,
    # an SSD layer's ``mamba_chunk_size``)
    linear_chunk: int = 128
    # "hybrid_ssm" (Falcon-H1): GQA attention with rotary over the whole
    # context and an SSD (Mamba-2) mixer in parallel off one norm, their
    # outputs summed into the stream, then the feed-forward. The mixer:
    # ``ssm_heads`` heads of ``ssm_head_dim`` values, keys and queries (B, C)
    # of ``ssm_state`` shared by the heads of each of ``ssm_groups`` groups, a
    # depthwise causal convolution of ``ssm_conv`` taps over [x | B | C], a
    # decay a token a head ``exp(softplus(dt + dt_bias) * -exp(a_log))``, the
    # skip ``D x``, and an RMSNorm of ``y * silu(z)`` in ``ssm_groups`` groups
    # (ops/transformer/linear_attention.py: the decay as an operand)
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_groups: int = 1
    ssm_state: int = 0
    ssm_conv: int = 4
    # muP scalars of that family, each applied in the program where the
    # published forward has it (1.0 = off; the embedding's is ``embed_scale``):
    # the attention's input, its keys and its output; the mixer's input, its
    # in-projection's five zones (z, x, B, C, dt) and its output; the
    # feed-forward's gate and output; the logits
    attn_in_mult: float = 1.0
    key_mult: float = 1.0
    attn_out_mult: float = 1.0
    ssm_in_mult: float = 1.0
    ssm_zone_mults: Tuple[float, ...] = (1.0, 1.0, 1.0, 1.0, 1.0)
    ssm_out_mult: float = 1.0
    mlp_mults: Tuple[float, float] = (1.0, 1.0)
    head_mult: float = 1.0
    # muP (MiniCPM): the residual branches times ``scale_depth /
    # sqrt(scale_depth_layers or num_layers)`` (the published depth, where
    # the model is a slice of it), the head's input times ``dim_model_base /
    # hidden_size``; 0 = off. ``embed_scale`` is the third scalar
    scale_depth: float = 0.0
    scale_depth_layers: int = 0
    dim_model_base: int = 0
    # YaRN rotary scaling (rope_factor 1 = plain rotary)
    rope_factor: float = 1.0
    rope_original_max: int = 0
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 0.0
    # progressive layer drop (PLD): stochastic depth driven by a per-step theta
    # injected as batch["pld_theta"] (reference progressive_layer_drop.py)
    progressive_layer_drop: bool = False
    # random-LTD: middle layers process a random token subset of scheduled size,
    # injected as a STATIC int batch["ltd_keep"] by the engine (reference
    # data_routing/basic_layer.py RandomLayerTokenDrop); first/last
    # ``random_ltd_skip_ends`` layers always see the full sequence
    random_ltd: bool = False
    random_ltd_skip_ends: int = 1
    # training knobs
    scan_layers: bool = True  # False: unroll the layer loop (no stacked
    # residual buffers / dynamic-update-slice traffic; longer compile)
    remat: bool = False  # per-block activation rematerialisation
    # "full"       min memory, recompute everything
    # "dots"       save weight-side matmul outputs AND the flash-attention
    #              out/lse residuals (no matmul or attention-kernel recompute;
    #              +one B*S*H per layer vs the pre-round-2 "dots" — use
    #              "dots_plain" for the old, smaller behavior)
    # "dots_plain" save weight-side matmul outputs only (attention fwd reruns
    #              in the backward)
    # "dots_batch" save every matmul output incl. batch dims
    # "dots_ln"    "dots" plus the per-layer LN outputs (no LN recompute)
    # "dots_elem"  "dots" plus LN/MLP-activation outputs (no recompute at all)
    # "dots_lean"  "dots" minus MLP up/gate outputs (recompute one matmul,
    #              biggest activation-memory saver)
    remat_policy: str = "full"
    param_dtype: Any = jnp.float32
    # fraction of attention logits softcapped (gemma-style); 0 = off
    logit_softcap: float = 0.0
    name: str = "transformer"

    def __post_init__(self):
        if self.layer_types is not None:
            types = tuple(self.layer_types)
            object.__setattr__(self, "layer_types", types)
            if len(types) != self.num_layers or set(types) - set(LAYER_TYPES):
                raise ValueError(
                    f"layer_types {types}: one of {LAYER_TYPES} for each of "
                    f"the {self.num_layers} layers")
            if "window_attn" in types and self.sliding_window <= 0:
                raise ValueError("a window_attn layer needs sliding_window")
            if "hybrid_ssm" in types and not (
                    self.ssm_heads > 0 and self.ssm_head_dim > 0
                    and self.ssm_state > 0 and self.ssm_conv > 1
                    and self.ssm_heads % self.ssm_groups == 0):
                raise ValueError(
                    "a hybrid_ssm layer needs ssm_heads (a multiple of "
                    "ssm_groups), ssm_head_dim, ssm_state and ssm_conv > 1")
            if "delta_attn" in types:
                from ..ops.transformer.linear_attention import DELTA_LOG_FLOOR

                if not (self.ssm_conv > 1
                        and -DELTA_LOG_FLOOR <= self.kda_log_floor < 0):
                    raise ValueError(
                        "a delta_attn layer needs ssm_conv > 1 taps and a "
                        f"kda_log_floor in [-{DELTA_LOG_FLOOR}, 0): what the "
                        "blocked delta rule's sub-tiles hold in float32")
            if "latent_attn" in types and not (
                    self.kv_lora_rank > 0 and self.qk_nope_head_dim > 0
                    and self.qk_rope_head_dim > 0 and self.v_head_dim > 0):
                raise ValueError(
                    "a latent_attn layer needs kv_lora_rank, "
                    "qk_nope_head_dim, qk_rope_head_dim and v_head_dim")
        if self.loop_steps < 1:
            raise ValueError(f"loop_steps {self.loop_steps}: at least 1")
        if self.looped and (
                self.layer_types is not None or self.is_mla
                or self.norm_position == "post" or self.mlm_head
                or self.num_experts):
            raise NotImplementedError(
                f"loop_steps {self.loop_steps}: a run of layers visited "
                "several times is wired for pre-norm dense `full`-kind "
                "layers only (no layer_types, attention='mla', experts, "
                "post-LN or MLM head): a state slot or a latent row a step "
                "has no layout yet")
        if self.post_norms and self.layer_types is None and (
                self.norm != "rmsnorm" or self.is_mla or self.parallel_block
                or self.norm_position == "post"):
            raise NotImplementedError(
                "post_norms on the `full` kind: RMSNorm, sequential pre-norm "
                "blocks")
        for name in ("ssm_zone_mults", "mlp_mults"):
            object.__setattr__(self, name, tuple(
                float(m) for m in getattr(self, name)))
        if len(self.ssm_zone_mults) != 5 or len(self.mlp_mults) != 2:
            raise ValueError("ssm_zone_mults has five entries (z, x, B, C, "
                             "dt), mlp_mults two (gate, output)")

    @property
    def kv_heads(self) -> int:
        return self.num_kv_heads or self.num_heads

    @property
    def looped(self) -> bool:
        """The layers run more than once a token (``loop_steps``)."""
        return self.loop_steps > 1

    @property
    def ssm_inner(self) -> int:
        """Width of an SSD mixer's values, all heads (``mamba_d_ssm``)."""
        return self.ssm_heads * self.ssm_head_dim

    @property
    def ssm_conv_channels(self) -> int:
        """Channels of an SSD mixer's convolution: [x | B | C]."""
        return self.ssm_inner + 2 * self.ssm_groups * self.ssm_state

    @property
    def type_runs(self) -> Tuple[Tuple[str, str, int, int], ...]:
        """The model's layers as the stacked groups of its tree, in forward
        order: (params key, kind, layers, pool layers a layer). THE place the
        layer pattern is read from (the tree's groups, the pool's layer axis,
        the slot arrays, the paged forward's loop) and the one place the
        three selectors (``attention``, ``layer_kind``, ``layer_types``)
        become kinds. ``kind`` names the group's record in ``LAYER_KINDS``:
        ``full`` attention, ``latent`` attention (a leading dense group
        before the expert layers, where the model has both), the ``scmoe``
        double layer with a pool layer for each of its attentions, or a
        ``layer_types`` mixer, a run of equal types one group."""
        if self.layer_types is not None:
            # a run is of one type and one feed-forward (the leading dense
            # layers of a model with experts are runs of their own)
            runs, last = [], None
            for i, t in enumerate(self.layer_types):
                kind = (t, self.layer_is_dense(i))
                if kind == last:
                    runs[-1][2] += 1
                else:
                    runs.append([f"blocks_{len(runs)}", t, 1])
                last = kind
        elif not self.is_mla:
            runs = [("blocks", "full", self.num_layers)]
        elif self.layer_kind == "scmoe":
            runs = [("blocks", "scmoe", self.num_layers)]
        else:
            dense = self.num_dense_layers if self.num_experts > 0 else 0
            runs = [g for g in (("dense_blocks", "latent", dense),
                                ("blocks", "latent", self.num_layers - dense))
                    if g[2]]
        return tuple((key, kind, n, LAYER_KINDS[kind].pool_layers)
                     for key, kind, n in runs)

    @property
    def kinds(self) -> Tuple[LayerKind, ...]:
        """The record of each group of ``type_runs``."""
        return tuple(LAYER_KINDS[kind] for _, kind, _, _ in self.type_runs)

    def layer_is_dense(self, i: int) -> bool:
        """Is layer ``i``'s feed-forward dense (no experts)?"""
        return self.num_experts == 0 or i < self.num_dense_layers

    @property
    def group_is_dense(self) -> Tuple[bool, ...]:
        """:meth:`layer_is_dense` of each group of ``type_runs``."""
        out, at = [], 0
        for _, _, n, _ in self.type_runs:
            out.append(self.layer_is_dense(at))
            at += n
        return tuple(out)

    @property
    def bounded_cache(self) -> bool:
        """Some kind's KV blocks (``window_attn``'s) are a class of their own,
        bounded by ``sliding_window``: a query sees the last W tokens, so the
        blocks behind every later query's window are freed and a sequence
        holds about ``W / block_size`` of them whatever its length. A block
        then no longer holds a token in every layer."""
        return len(self.class_layers) > 1

    @property
    def class_layers(self) -> Dict[str, int]:
        """Pool layers of each class of KV blocks, the model's declaration to
        the cache manager: ``full`` (a block for every ``block_size`` tokens
        of a sequence's length) and, where ``bounded_cache``, ``window``. A
        class is a pool of its own (its layers, its blocks), a free list and
        a table a sequence."""
        layers = {"full": 0}
        for (_, _, n, per), rec in zip(self.type_runs, self.kinds):
            if per:
                layers[rec.block_class] = layers.get(rec.block_class, 0) \
                    + self.loop_steps * n * per
        return layers

    @property
    def residual_scale(self) -> float:
        if not self.scale_depth:
            return 1.0
        return self.scale_depth / math.sqrt(self.scale_depth_layers
                                            or self.num_layers)

    @property
    def sparse_spec(self):
        from ..ops.transformer.sparse_attention import SparseSpec

        return SparseSpec(
            block=self.sparse_block_size, kernel=self.sparse_kernel_size,
            stride=self.sparse_kernel_stride,
            window_blocks=self.sparse_window // self.sparse_block_size,
            init_blocks=self.sparse_init_blocks, topk=self.sparse_topk,
            dense_len=self.sparse_dense_len).check()

    @property
    def cache_kinds(self) -> Dict[str, Tuple[Tuple[str, int], ...]]:
        """What each layer type keeps a sequence, the model's declaration to
        the engine: {layer type (``attn``: a model that names none): ((kind,
        bytes), ...)} with kind ``kv_blocks``
        (bytes a token a layer, in the paged pool at 2 bytes a value) or
        ``state_slot`` (bytes a sequence a layer, whatever its length: a
        lightning layer's float32 state; a sparse layer's compressed keys for
        ``max_seq_len`` tokens, which lie by slot beside its KV blocks; a
        ``hybrid_ssm`` layer's SSD state and convolution window together,
        beside its KV blocks). A
        window layer's ``kv_blocks`` carry a third entry, the bound: the
        tokens behind which a block is freed (``bounded_cache``)."""
        declared = {}
        for (_, kind, _, _), rec in zip(self.type_runs, self.kinds):
            kept = ()
            if rec.pool_layers:
                heads, row = rec.row(self)
                # a layer of weights visited ``loop_steps`` times keeps a
                # row for each visit
                kept += (("kv_blocks", 2 * heads * sum(row) * self.loop_steps)
                         + ((rec.bound(self),) if rec.bound else ()),)
            if rec.slots:
                slot = jax.eval_shape(lambda: rec.slots(
                    self, 1, 0, self.max_seq_len, jnp.bfloat16))
                kept += (("state_slot", sum(
                    a.size * a.dtype.itemsize
                    for a in jax.tree.leaves(slot))),)
            declared.setdefault(kind if kind in LAYER_TYPES else "attn", kept)
        return declared

    @property
    def holds_state(self) -> bool:
        """Some layer keeps a state slot a sequence beside the paged pool."""
        return any(rec.slots for rec in self.kinds)

    @property
    def head_dim(self) -> int:
        return self.head_dim_override or (self.hidden_size // self.num_heads)

    @property
    def is_mla(self) -> bool:
        return self.attention == "mla"

    @property
    def _pool_row(self):
        """(heads, (key width, value width)) of the pool's rows: one for all
        the model's kinds that keep KV blocks, whose layers are layers of one
        pool (a model none of whose kinds does: its first kind's)."""
        pooled = [rec for rec in self.kinds if rec.pool_layers]
        return (pooled or self.kinds)[0].row(self)

    @property
    def kv_row(self) -> Tuple[int, int]:
        """(key width, value width) of one token's row of the paged pool, a
        layer and a pool head: ``[k | v]`` of one kv head, or the latent
        ``[c_kv | k_rope]`` every head shares. THE place the pool's row width
        is read from (``init_kv_pool``, the engine's block programs)."""
        return self._pool_row[1]

    @property
    def pool_heads(self) -> int:
        """Heads the paged pool keeps rows for: one under latent attention."""
        return self._pool_row[0]

    @property
    def sublayers(self) -> int:
        """Attentions (and dense feed-forwards) of one layer, each with a
        pool layer of its own."""
        return max(1, *(per for *_, per in self.type_runs))

    @property
    def pool_layers(self) -> int:
        """Layers of the paged pool: one for every attention of the model
        that keeps KV blocks, each time a token passes it (``loop_steps``
        walks of ``type_runs``; step ``t`` of a looped model owns the layers
        from ``t *`` one walk's on). THE place the pool's layer axis is read
        from: a reader that means cache reads this, one that means weights
        ``num_layers``."""
        return self.loop_steps * sum(n * per for _, _, n, per in self.type_runs)

    @property
    def mla_latent_scales(self) -> Tuple[float, float]:
        """Factors on the outputs of the query and the kv latent norms."""
        return ((self.hidden_size / self.q_lora_rank) ** 0.5
                if self.mla_scale_q_lora else 1.0,
                (self.hidden_size / self.kv_lora_rank) ** 0.5
                if self.mla_scale_kv_lora else 1.0)

    @property
    def num_moe_layers(self) -> int:
        return (self.num_layers - self.num_dense_layers
                if self.num_experts > 0 else 0)

    @property
    def holds_experts(self) -> bool:
        """The expert layers hold a share of the router's experts
        (moe/layer.py ``held_experts_ffn``)."""
        return self.num_experts > 0 and self.moe_router in (
            "group_limited", "softmax_topk")

    @property
    def router_width(self) -> int:
        return self.moe_router_width or self.num_experts

    @property
    def mlp_dim(self) -> int:
        if self.intermediate_size is not None:
            return self.intermediate_size
        if self.activation == "swiglu":
            # llama convention: 2/3 * 4H rounded to a multiple of 256
            d = int(8 * self.hidden_size / 3)
            return ((d + 255) // 256) * 256
        return 4 * self.hidden_size

    @property
    def dense_mlp_dim(self) -> int:
        return self.dense_intermediate_size or self.mlp_dim

    def _mlp_params(self, width: int) -> int:
        return (3 if self.activation in ("swiglu", "geglu") else 2) \
            * self.hidden_size * width

    def tree_shapes(self):
        """({group: (layers, {leaf: shape a layer})}, {top leaf: shape}) of
        the tree ``init_params`` builds, but for the GPT-2 family's: a group
        a run of ``type_runs``, its leaves its kind's and its feed-forward's:
        gated and dense (``dense_mlp_dim`` wide), or the experts held here,
        their router with its selection bias and the shared expert."""
        H, V, I, E = (self.hidden_size, self.vocab_size, self.mlp_dim,
                      self.num_experts)
        dense = _gated_mlp_leaves(self, self.dense_mlp_dim)
        moe = {"moe_wg": (H, self.router_width),
               "moe_bias": (self.router_width,), "wi": (E, H, I),
               "w_gate": (E, H, I), "w_down": (E, I, H),
               **(_gated_mlp_leaves(self, self.moe_shared_size, "shared_")
                  if self.moe_shared_size else {})}
        groups = {key: (n, {**rec.leaves(self), **(dense if is_dense else moe)})
                  for (key, _, n, _), rec, is_dense
                  in zip(self.type_runs, self.kinds, self.group_is_dense)}
        top = {"wte": (V, H), "lnf_scale": (H,)}
        if not self.tie_embeddings:
            top["lm_head"] = (H, V)
        return groups, top

    @property
    def grouped_tree(self) -> bool:
        """The tree is what :meth:`tree_shapes` says, every kind's leaves
        from its record: every family but GPT-2's, whose tree
        ``init_params`` writes out itself."""
        return all(rec.leaves for rec in self.kinds)

    @property
    def num_parameters(self) -> int:
        """Parameters of the tree ``init_params`` builds: with held experts
        (``holds_experts``) the experts held here, not the router's
        width."""
        H, L, V = self.hidden_size, self.num_layers, self.vocab_size
        if self.grouped_tree:
            groups, top = self.tree_shapes()
            return sum(map(math.prod, top.values())) + sum(
                n * sum(map(math.prod, leaves.values()))
                for n, leaves in groups.values())
        qd, kvd = self.num_heads * self.head_dim, self.kv_heads * self.head_dim
        attn = H * qd + 2 * H * kvd + qd * H  # q, k, v, o
        n_ln = 1 if (self.parallel_block and self.parallel_shared_ln) else 2
        n_ln += 2 * self.post_norms
        norms = n_ln * (1 if self.norm == "rmsnorm" else 2) * H
        mlp = self._mlp_params(self.mlp_dim)
        dense_layer = attn + norms + self._mlp_params(self.dense_mlp_dim)
        if self.num_experts > 0:
            moe = mlp * self.num_experts + H * self.router_width  # + router
            if self.moe_use_residual:
                moe += mlp + 2 * H + 2  # residual MLP + coefficient
            moe_layer = attn + norms + moe
        else:
            moe_layer = dense_layer
        n_moe = self.num_moe_layers if self.num_experts > 0 else L
        emb = V * H + (0 if self.pos_embedding != "learned" else self.max_seq_len * H)
        head = 0 if self.tie_embeddings else V * H
        # a layer is counted once however often it runs; a looped model's
        # closing norm stands in the final norm's place, its gate beside it
        return ((L - n_moe) * dense_layer + n_moe * moe_layer
                + emb + head + H + (H + 1) * self.looped)

    @property
    def num_active_parameters(self) -> int:
        """Parameters touched per token (= num_parameters for dense; for MoE
        only top-k experts are activated, of those held here at most all)."""
        if self.num_experts == 0:
            return self.num_parameters
        E = self.num_experts
        inactive = self.num_moe_layers * (E - min(self.moe_top_k, E)) \
            * self._mlp_params(self.mlp_dim)
        return self.num_parameters - inactive

    def flops_per_token(self, seq_len: Optional[int] = None) -> float:
        """Model FLOPs per token for one fwd+bwd (6·N_active + attention term:
        q·k and p·v over the heads' own widths)."""
        S = seq_len or self.max_seq_len
        once = 6 * self.num_active_parameters + sum(
            n * rec.attn_flops(self, S)
            for (_, _, n, _), rec in zip(self.type_runs, self.kinds))
        if not self.looped:
            return once
        # every walk after the first runs the layers' matrices and their
        # attention again (the embedding and the head run once)
        H, qd, kvd = (self.hidden_size, self.num_heads * self.head_dim,
                      self.kv_heads * self.head_dim)
        layer = 2 * H * (qd + kvd) + self._mlp_params(self.mlp_dim)
        return once + (self.loop_steps - 1) * self.num_layers * (
            6 * layer + _heads_flops(self, S))


# ----------------------------------------------------------------------------
# presets (sizes follow the reference's benchmark configs, BASELINE.md)
# ----------------------------------------------------------------------------

def gpt2_config(size: str = "125m", **kw) -> TransformerConfig:
    tbl = {
        "125m": dict(hidden_size=768, num_layers=12, num_heads=12),
        "350m": dict(hidden_size=1024, num_layers=24, num_heads=16),
        "760m": dict(hidden_size=1536, num_layers=24, num_heads=16),
        "1.3b": dict(hidden_size=2048, num_layers=24, num_heads=16),
        "2.7b": dict(hidden_size=2560, num_layers=32, num_heads=32),
        "6.7b": dict(hidden_size=4096, num_layers=32, num_heads=32),
        "13b": dict(hidden_size=5120, num_layers=40, num_heads=40),
    }
    base = dict(
        vocab_size=50304, max_seq_len=1024, pos_embedding="learned",
        norm="layernorm", activation="gelu", tie_embeddings=True,
        name=f"gpt2-{size}",
    )
    base.update(tbl[size])
    base.update(kw)
    return TransformerConfig(**base)


def llama_config(size: str = "7b", **kw) -> TransformerConfig:
    tbl = {
        "tiny": dict(hidden_size=256, num_layers=4, num_heads=8, num_kv_heads=4,
                     intermediate_size=688, max_seq_len=2048),
        "7b": dict(hidden_size=4096, num_layers=32, num_heads=32,
                   intermediate_size=11008, max_seq_len=4096),
        "13b": dict(hidden_size=5120, num_layers=40, num_heads=40,
                    intermediate_size=13824, max_seq_len=4096),
        "70b": dict(hidden_size=8192, num_layers=80, num_heads=64, num_kv_heads=8,
                    intermediate_size=28672, max_seq_len=4096),
    }
    base = dict(
        vocab_size=32000, pos_embedding="rope", norm="rmsnorm",
        activation="swiglu", tie_embeddings=False, norm_eps=1e-5,
        name=f"llama-{size}",
    )
    base.update(tbl[size])
    base.update(kw)
    return TransformerConfig(**base)


MODEL_PRESETS = {
    "gpt2-125m": lambda **kw: gpt2_config("125m", **kw),
    "gpt2-350m": lambda **kw: gpt2_config("350m", **kw),
    "gpt2-760m": lambda **kw: gpt2_config("760m", **kw),
    "gpt2-1.3b": lambda **kw: gpt2_config("1.3b", **kw),
    "gpt2-2.7b": lambda **kw: gpt2_config("2.7b", **kw),
    "gpt2-6.7b": lambda **kw: gpt2_config("6.7b", **kw),
    "llama-tiny": lambda **kw: llama_config("tiny", **kw),
    "llama-7b": lambda **kw: llama_config("7b", **kw),
    "llama-13b": lambda **kw: llama_config("13b", **kw),
    "llama-70b": lambda **kw: llama_config("70b", **kw),
}


# ----------------------------------------------------------------------------
# functional pieces
# ----------------------------------------------------------------------------

def _norm(x, scale, bias, kind: str, eps: float, weight_offset: float = 0.0):
    xf = x.astype(jnp.float32)
    if kind == "rmsnorm":
        var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        y = xf * jax.lax.rsqrt(var + eps) * (
            weight_offset + scale.astype(jnp.float32))
    else:
        mean = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        y = (xf - mean) * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)
        if bias is not None:
            y = y + bias.astype(jnp.float32)
    return y.astype(x.dtype)


def _times(x, m: float):
    """``x * m`` for a muP scalar ``m``, in float32 and back to ``x``'s dtype
    (the scalar itself is not rounded to bfloat16); ``x`` where ``m`` is 1."""
    if m == 1.0:
        return x
    return (x.astype(jnp.float32) * m).astype(x.dtype)


def _rope(q, k, positions, head_dim, theta, rotary_dim=None):
    """Rotary embedding applied to (B,S,h,d) q/k at integer positions (B,S).

    ``rotary_dim`` < head_dim rotates only the leading dims (GPT-J/NeoX/Phi
    partial rotary); the tail passes through. Rotate-half convention —
    interleaved-pair checkpoints (GPT-J) are handled by a column permutation
    at conversion time (``hf_converters._rotary_perm``).
    """
    d = rotary_dim or head_dim
    half = d // 2
    freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    ang = positions[..., None].astype(jnp.float32) * freqs  # (B,S,half)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]

    def rot(x):
        x1, x2 = jnp.split(x[..., :d].astype(jnp.float32), 2, axis=-1)
        out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
        if d < x.shape[-1]:
            out = jnp.concatenate([out, x[..., d:].astype(jnp.float32)], axis=-1)
        return out.astype(x.dtype)

    return rot(q), rot(k)


def yarn_mscale(factor: float, m: float) -> float:
    return 1.0 if factor <= 1 or m <= 0 else 0.1 * m * math.log(factor) + 1.0


def yarn_inv_freq(cfg: "TransformerConfig") -> np.ndarray:
    """(rotary_dim/2,) rotary frequencies of the latent-attention rope head
    under YaRN (Peng et al. 2023, as ``deepseek_v3`` applies it):
    interpolated by ``rope_factor`` below the ``rope_beta_slow`` correction
    dimension, kept above the ``rope_beta_fast`` one, a linear ramp between.
    ``rope_factor`` 1 gives the plain frequencies."""
    dim, theta = cfg.qk_rope_head_dim, cfg.rope_theta
    f = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    if cfg.rope_factor <= 1:
        return f.astype(np.float32)

    def correction(rotations):
        return dim * math.log(cfg.rope_original_max / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))

    lo = max(math.floor(correction(cfg.rope_beta_fast)), 0)
    hi = min(math.ceil(correction(cfg.rope_beta_slow)), dim // 2 - 1)
    ramp = np.clip((np.arange(dim // 2) - lo) / max(hi - lo, 1e-3), 0.0, 1.0)
    return (f / cfg.rope_factor * ramp + f * (1.0 - ramp)).astype(np.float32)


def _rope_interleaved(x, positions, cfg: "TransformerConfig"):
    """Rotate the interleaved pairs ``(x[2i], x[2i+1])`` of (B, S, h, d) at
    integer positions (B, S): the ``deepseek_v3`` pairing. cos and sin carry
    ``mscale(factor, mscale) / mscale(factor, mscale_all_dim)``."""
    ang = positions[..., None].astype(jnp.float32) * yarn_inv_freq(cfg)
    amp = (yarn_mscale(cfg.rope_factor, cfg.rope_mscale)
           / yarn_mscale(cfg.rope_factor, cfg.rope_mscale_all_dim))
    cos = (jnp.cos(ang) * amp)[:, :, None, :]
    sin = (jnp.sin(ang) * amp)[:, :, None, :]
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., 0::2], xf[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape).astype(x.dtype)


def mla_softmax_scale(cfg: "TransformerConfig") -> float:
    """``(nope + rope)^-1/2`` times ``mscale(factor, mscale_all_dim)^2``."""
    return ((cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
            * yarn_mscale(cfg.rope_factor, cfg.rope_mscale_all_dim) ** 2)


def paged_limits(tables, positions):
    """The pool tokens each one-token row of a paged step attends over:
    its position + 1, and 0 for a padding row, whose table names no block
    (block 0 is the trash block no sequence holds). A row with 0 is dead for
    the decode kernels (``paged_attention.paged_decode``, ``mla_decode``)."""
    return jnp.where(tables[:, 0] > 0, positions + 1, 0)


@jax.tree_util.register_dataclass
@dataclass(frozen=True)
class PagedStep:
    """What one paged step tells each of its layers, built once a call
    (:meth:`of`). Its T rows are one-token rows: those before ``cut`` one a
    sequence; those from ``cut`` to ``end`` chunk segments in tiles of
    ``tile`` rows, each tile consecutive tokens of one sequence (its first
    row carries the table, the position and the slot; its valid rows are a
    prefix); what is left behind the last whole tile is padding. A padding
    row carries the all-zero table (trash block 0, which no sequence holds)."""
    tables: Any        # (T, MAXB) pool block ids, 0-padded
    starts: Any        # (T,) each row's (first) position
    positions: Any     # (T, S)
    limits: Any        # (T,) pool tokens a row attends over (paged_limits)
    live: Any          # (T,) bool: a real token (routed, counted, written)
    slots: Any         # (T,) each row's state slot, or None
    tile_counts: Any   # valid rows of each tile, or None (no tile)
    #: the rows as the window class of blocks sees them (``window_frame``),
    #: or None: a model with no bounded class
    window: Any
    cut: int = field(metadata=dict(static=True))
    end: int = field(metadata=dict(static=True))
    tile: int = field(metadata=dict(static=True))
    #: the builder's promise that no two rows write one pool block (a decode
    #: round: one row a sequence, shared blocks copied on write before the
    #: dispatch), which lets ``paged_attention.write_rows`` write the live
    #: rows alone
    rows_apart: bool = field(metadata=dict(static=True))

    @classmethod
    def of(cls, tables, starts, width=1, *, seg_from=None, tile=1,
           rows_apart=False, slots=None, window=None):
        """``window``: (tables (T, WB), base (T,), W) of the window class, or
        None (:func:`window_frame`)."""
        T = tables.shape[0]
        cut = T if seg_from is None else seg_from
        end = cut + (T - cut) // tile * tile
        live = tables[:, 0] > 0
        positions = starts[:, None]
        if width > 1:
            positions = positions + jnp.arange(width, dtype=jnp.int32)
        tile_counts = jnp.sum(live[cut:end].reshape(-1, tile), axis=1,
                              dtype=jnp.int32) if cut < end else None
        limits = paged_limits(tables, starts)
        if window is not None:
            window = window_frame(*window, starts, limits)
        return cls(tables, starts, positions, limits, live, slots,
                   tile_counts, window, cut, end, tile, rows_apart)


def window_frame(tables, base, bound, starts, limits):
    """A step's rows as the window class of blocks sees them. A sequence
    holds the blocks its later queries can still see, so its window table
    (T, WB) starts at the block of position ``base`` (T,), and everything
    that counts tokens along a table counts from there: (tables, starts,
    limits, first), each row's position, the tokens it attends up to and the
    first it attends from (its query sees the last ``bound`` tokens), all
    less ``base``. A padding row's are 0."""
    rel = starts - base
    first = jnp.maximum(starts - (bound - 1), 0) - base
    live = limits > 0
    return (tables, jnp.where(live, rel, 0), jnp.where(live, rel + 1, 0),
            jnp.where(live, jnp.maximum(first, 0), 0))


def _rows_then_tiles(step, one, many, *xs):
    """``one`` over the one-token rows of each of ``xs`` ((T, ...) arrays of a
    :class:`PagedStep`'s rows), ``many`` over its tiles (the carry ``one``
    returned first, then each of ``xs`` as (tiles, tile, ...)), the results
    joined and padded to T rows: (y, the last carry). How a slot-keeping
    mixer walks a step: ``linear_attention``'s ``*_rows`` then ``*_tiles``."""
    cut, end, tile, T = step.cut, step.end, step.tile, xs[0].shape[0]
    y, carry = one(*(a[:cut] for a in xs))
    if cut < end:
        y2, carry = many(carry, *(
            a[cut:end].reshape(-1, tile, *a.shape[1:]) for a in xs))
        y = jnp.concatenate([y, y2.reshape(-1, *y.shape[1:])])
    return jnp.pad(y, ((0, T - y.shape[0]),)
                   + ((0, 0),) * (y.ndim - 1)), carry


def sublayer_prefix(i: int) -> str:
    """Prefix of sublayer ``i``'s leaves in a double layer's ``blocks``."""
    return f"s{i}_"


def sublayer_leaf(name: str) -> str:
    """A leaf's name without its sublayer prefix."""
    return name[3:] if re.match(r"s\d_", name) else name


def sublayer(blk, i: int):
    """Sublayer ``i`` of a double layer's leaves, under a dense layer's own
    names."""
    prefix = sublayer_prefix(i)
    return {k[len(prefix):]: v for k, v in blk.items() if k.startswith(prefix)}


#: per-layer expert matrices: the paged program indexes them by (layer,
#: expert) where they lie instead of slicing a layer out of the stack
EXPERT_LEAVES = ("wi", "w_gate", "w_down")


def alibi_slopes(n_heads: int) -> np.ndarray:
    """Per-head ALiBi slopes (geometric sequence, closest-power-of-2 rule —
    same formula as HF ``build_alibi_tensor`` used by the reference's BLOOM
    container ``module_inject/containers/bloom.py``)."""
    import math

    closest = 2 ** int(math.floor(math.log2(n_heads)))
    base = 2.0 ** (-(2.0 ** -(math.log2(closest) - 3)))
    slopes = [base ** (i + 1) for i in range(closest)]
    if closest != n_heads:
        extra_base = 2.0 ** (-(2.0 ** -(math.log2(2 * closest) - 3)))
        slopes += [extra_base ** (2 * i + 1) for i in range(n_heads - closest)]
    return np.asarray(slopes, np.float32)


def _dropout(x, rate, rng, train):
    if rate == 0.0 or not train or rng is None:
        return x
    keep = jax.random.bernoulli(rng, 1.0 - rate, x.shape)
    return jnp.where(keep, x / (1.0 - rate), jnp.zeros_like(x))


class TransformerLM:
    """Functional decoder LM implementing the engine model protocol
    (``init_params`` / ``apply`` / ``tp_specs``) plus inference entry points
    (``logits`` / ``decode_step``) used by the inference engine."""

    def __init__(self, config: TransformerConfig, mesh_axes: Tuple[str, str] = ("model", "seq")):
        self.config = config
        self.model_axis, self.seq_axis = mesh_axes
        if config.holds_experts and not (config.is_mla
                                         or config.layer_types is not None):
            raise ValueError(f"moe_router='{config.moe_router}' (held "
                             "experts) is wired into attention='mla' blocks "
                             "and layer_types models only")
        if config.layer_kind == "scmoe" and not (
                config.holds_experts and config.num_dense_layers == 0):
            raise ValueError("layer_kind='scmoe' is a double layer of latent "
                             "attention around held experts, with no leading "
                             "dense layers")

    # ------------------------------------------------------------------
    def init_params(self, rng) -> Dict[str, Any]:
        cfg = self.config
        if cfg.grouped_tree:
            return self._init_grouped(rng)
        H, L, V, I = cfg.hidden_size, cfg.num_layers, cfg.vocab_size, cfg.mlp_dim
        nh, kvh, hd = cfg.num_heads, cfg.kv_heads, cfg.head_dim
        dt = cfg.param_dtype
        k = jax.random.split(rng, 12)
        init = jax.nn.initializers.normal(0.02)
        # residual-branch projections get the depth-scaled init (GPT-2 paper)
        resid_init = jax.nn.initializers.normal(0.02 / np.sqrt(2 * L))

        def stacked(key, shape, initializer=init):
            return initializer(key, (L,) + shape, dt)

        single_ln = cfg.parallel_block and cfg.parallel_shared_ln
        post_ln = cfg.norm_position == "post"
        params: Dict[str, Any] = {
            "wte": init(k[0], (V, H), dt),
            "blocks": {
                "ln1_scale": jnp.ones((L, H), dt),
                "wq": stacked(k[1], (H, nh * hd)),
                "wk": stacked(k[2], (H, kvh * hd)),
                "wv": stacked(k[3], (H, kvh * hd)),
                "wo": stacked(k[4], (nh * hd, H), resid_init),
            },
        }
        if cfg.looped:
            # the norm that closes every step and the exit gate: a stacked
            # group of one layer in the final norm's place
            params["loop"] = {"norm_scale": jnp.ones((1, H), dt),
                              "exit_w": init(k[10], (1, H), dt),
                              "exit_b": jnp.zeros((1,), dt)}
        elif not post_ln:  # post-LN trunks end normalized; no final LN
            params["lnf_scale"] = jnp.ones((H,), dt)
        if not single_ln:
            params["blocks"]["ln2_scale"] = jnp.ones((L, H), dt)
        blocks = params["blocks"]
        if cfg.post_norms:
            blocks["post_attn_scale"] = jnp.ones((L, H), dt)
            blocks["post_mlp_scale"] = jnp.ones((L, H), dt)
        E = cfg.num_experts
        if E > 0:
            blocks["moe_wg"] = stacked(k[10], (H, E))
            blocks["wi"] = stacked(k[5], (E, H, I))
            blocks["w_down"] = stacked(k[6], (E, I, H), resid_init)
            if cfg.activation == "swiglu":
                blocks["w_gate"] = stacked(k[7], (E, H, I))
            if cfg.moe_use_residual:
                # PR-MoE (reference moe/layer.py:80-84): per-layer dense MLP
                # + Linear(H,2) coefficient
                blocks["res_wi"] = stacked(jax.random.fold_in(k[5], 1), (H, I))
                blocks["res_wo"] = stacked(
                    jax.random.fold_in(k[6], 1), (I, H), resid_init)
                blocks["res_coef_w"] = stacked(
                    jax.random.fold_in(k[10], 1), (H, 2))
                blocks["res_coef_b"] = jnp.zeros((L, 2), dt)
                if cfg.activation == "swiglu":
                    blocks["res_wgate"] = stacked(
                        jax.random.fold_in(k[7], 1), (H, I))
        else:
            blocks["w_down"] = stacked(k[6], (I, H), resid_init)
            if cfg.activation in ("swiglu", "geglu"):
                blocks["w_gate"] = stacked(k[5], (H, I))
                blocks["w_up"] = stacked(k[7], (H, I))
            else:
                blocks["w_up"] = stacked(k[5], (H, I))
        if cfg.norm == "layernorm":
            blocks["ln1_bias"] = jnp.zeros((L, H), dt)
            if not single_ln:
                blocks["ln2_bias"] = jnp.zeros((L, H), dt)
            blocks["attn_bias"] = jnp.zeros((L, H), dt)
            blocks["mlp_bias"] = jnp.zeros((L, H), dt)
            if cfg.activation not in ("swiglu", "geglu") and E == 0:
                blocks["mlp_up_bias"] = jnp.zeros((L, I), dt)
            if cfg.norm_position != "post":
                params["lnf_bias"] = jnp.zeros((H,), dt)
        elif cfg.attn_out_bias:
            blocks["attn_bias"] = jnp.zeros((L, H), dt)
        if cfg.qkv_bias:
            blocks["wq_bias"] = jnp.zeros((L, nh * hd), dt)
            blocks["wk_bias"] = jnp.zeros((L, kvh * hd), dt)
            blocks["wv_bias"] = jnp.zeros((L, kvh * hd), dt)
        if cfg.embed_layernorm:
            params["ln_emb_scale"] = jnp.ones((H,), dt)
            if cfg.norm == "layernorm":
                params["ln_emb_bias"] = jnp.zeros((H,), dt)
        if cfg.token_type_embedding > 0:
            params["wtt"] = init(k[11], (cfg.token_type_embedding, H), dt)
        if cfg.mlm_head:
            params["mlm_dense"] = init(k[10], (H, H), dt)
            params["mlm_dense_bias"] = jnp.zeros((H,), dt)
            params["mlm_ln_scale"] = jnp.ones((H,), dt)
            params["mlm_ln_bias"] = jnp.zeros((H,), dt)
            params["mlm_bias"] = jnp.zeros((V,), dt)
        if cfg.pos_embedding == "learned":
            params["wpe"] = init(k[8], (cfg.max_seq_len, H), dt)
        if not cfg.tie_embeddings:
            params["lm_head"] = init(k[9], (H, V), dt)
            if cfg.lm_head_bias:
                params["lm_head_bias"] = jnp.zeros((V,), dt)
        return params

    def _init_grouped(self, rng) -> Dict[str, Any]:
        """{leaf, group: {leaf}, ...} as ``TransformerConfig.tree_shapes``
        says it, a stacked group a run of ``type_runs``. Residual projections
        are ``wo`` / ``w_down`` (a sublayer's too), norm scales ``*_scale``;
        each drawn leaf takes the next key, in the tree's order."""
        cfg = self.config
        if (cfg.activation != "swiglu" or cfg.norm != "rmsnorm"
                or cfg.pos_embedding != "rope"
                or (cfg.num_experts and not cfg.holds_experts)):
            raise ValueError("attention='mla' and layer_types models are "
                             "rmsnorm + swiglu + rope, with a dense "
                             "feed-forward or held experts (moe_router "
                             "'group_limited' | 'softmax_topk')")
        dt = cfg.param_dtype
        groups, top = cfg.tree_shapes()
        init = jax.nn.initializers.normal(0.02)
        resid_init = jax.nn.initializers.normal(
            0.02 / np.sqrt(2 * cfg.num_layers))
        keys = iter(jax.random.split(rng, len(top) + sum(
            len(leaves) for _, leaves in groups.values())))

        def leaf(name, shape):
            if name.endswith("_scale"):
                return jnp.ones(shape, dt)
            if name == "moe_bias":
                return jnp.zeros(shape, dt)
            return (resid_init if sublayer_leaf(name) in ("wo", "w_down")
                    else init)(next(keys), shape, dt)

        params = {k: leaf(k, shape) for k, shape in top.items()}
        for group, (n, leaves) in groups.items():
            params[group] = {k: leaf(k, (n,) + shape)
                             for k, shape in leaves.items()}
        return params

    # ------------------------------------------------------------------
    @property
    def tp_specs(self) -> Dict[str, Any]:
        """PartitionSpec pytree: tensor parallelism over the ``model`` mesh axis.

        Column-parallel wq/wk/wv/w_up/w_gate, row-parallel wo/w_down (Megatron
        layout, reference ``module_inject/auto_tp.py`` sharding rules), vocab-
        parallel embedding/lm_head. Leading dim of block leaves is the layer axis.
        """
        cfg = self.config
        m = self.model_axis
        if cfg.grouped_tree:
            return self._tp_specs_grouped()
        single_ln = cfg.parallel_block and cfg.parallel_shared_ln
        specs: Dict[str, Any] = {
            "wte": P(m, None),
            "blocks": {
                "ln1_scale": P(None, None),
                "wq": P(None, None, m),
                "wk": P(None, None, m),
                "wv": P(None, None, m),
                "wo": P(None, m, None),
            },
        }
        if cfg.looped:
            specs["loop"] = {"norm_scale": P(None, None),
                             "exit_w": P(None, None), "exit_b": P(None)}
        elif cfg.norm_position != "post":
            specs["lnf_scale"] = P(None)
        blocks = specs["blocks"]
        if not single_ln:
            blocks["ln2_scale"] = P(None, None)
        if cfg.post_norms:
            blocks["post_attn_scale"] = P(None, None)
            blocks["post_mlp_scale"] = P(None, None)
        if cfg.num_experts > 0:
            # experts over the expert axis, expert-internal dims over model axis
            e = "expert"
            blocks["moe_wg"] = P(None, None, None)
            blocks["wi"] = P(None, e, None, m)
            blocks["w_down"] = P(None, e, m, None)
            if cfg.activation == "swiglu":
                blocks["w_gate"] = P(None, e, None, m)
            if cfg.moe_use_residual:
                blocks["res_wi"] = P(None, None, m)
                blocks["res_wo"] = P(None, m, None)
                blocks["res_coef_w"] = P(None, None, None)
                blocks["res_coef_b"] = P(None, None)
                if cfg.activation == "swiglu":
                    blocks["res_wgate"] = P(None, None, m)
        else:
            blocks["w_down"] = P(None, m, None)
            blocks["w_up"] = P(None, None, m)
            if cfg.activation in ("swiglu", "geglu"):
                blocks["w_gate"] = P(None, None, m)
        if cfg.norm == "layernorm":
            blocks["ln1_bias"] = P(None, None)
            if not single_ln:
                blocks["ln2_bias"] = P(None, None)
            blocks["attn_bias"] = P(None, None)
            blocks["mlp_bias"] = P(None, None)
            if cfg.activation not in ("swiglu", "geglu") and cfg.num_experts == 0:
                blocks["mlp_up_bias"] = P(None, m)
            if cfg.norm_position != "post":
                specs["lnf_bias"] = P(None)
        elif cfg.attn_out_bias:
            blocks["attn_bias"] = P(None, None)
        if cfg.qkv_bias:
            blocks["wq_bias"] = P(None, m)
            blocks["wk_bias"] = P(None, m)
            blocks["wv_bias"] = P(None, m)
        if cfg.embed_layernorm:
            specs["ln_emb_scale"] = P(None)
            if cfg.norm == "layernorm":
                specs["ln_emb_bias"] = P(None)
        if cfg.token_type_embedding > 0:
            specs["wtt"] = P(None, None)
        if cfg.mlm_head:
            specs["mlm_dense"] = P(None, None)
            specs["mlm_dense_bias"] = P(None)
            specs["mlm_ln_scale"] = P(None)
            specs["mlm_ln_bias"] = P(None)
            specs["mlm_bias"] = P(m)
        if cfg.pos_embedding == "learned":
            specs["wpe"] = P(None, None)
        if not cfg.tie_embeddings:
            specs["lm_head"] = P(None, m)
            if cfg.lm_head_bias:
                specs["lm_head_bias"] = P(m)
        return specs

    def _tp_specs_grouped(self) -> Dict[str, Any]:
        """A grouped tree's (``TransformerConfig.tree_shapes``), by leaf
        name: the products split into heads (``wq`` / ``wk`` / ``wv``, the
        gate's ``w_ogate``, the latent up-projections ``wq_b`` / ``wkv_b``)
        column-parallel and ``wo`` row-parallel over ``model``; the latent
        down-projections, the norms and the router replicated; held experts
        over ``expert``, their widths (and the shared expert's, and the dense
        MLP's) over ``model``."""
        m, e = self.model_axis, "expert"
        col, row = P(None, None, m), P(None, m, None)
        by_name = {
            "wq": col, "wk": col, "wv": col, "w_ogate": col, "w_decay": col,
            "wq_b": col, "wkv_b": col, "wo": row,
            "w_gate": col, "w_up": col, "w_down": row,
            "shared_w_gate": col, "shared_w_up": col, "shared_w_down": row,
        }
        expert = {"wi": P(None, e, None, m), "w_gate": P(None, e, None, m),
                  "w_down": P(None, e, m, None)}
        groups, top = self.config.tree_shapes()
        specs: Dict[str, Any] = {
            "wte": P(m, None), "lnf_scale": P(None), "lm_head": P(None, m)}
        specs = {k: specs[k] for k in top}
        for group, (_, leaves) in groups.items():
            moe = "moe_wg" in leaves
            specs[group] = {
                k: (expert[k] if moe and k in expert else
                    by_name.get(sublayer_leaf(k),
                                P(*([None] * (len(shape) + 1)))))
                for k, shape in leaves.items()}
        return specs

    # ------------------------------------------------------------------
    def _constraint(self, x, spec):
        """Sharding constraint if we are under a mesh; no-op otherwise."""
        try:
            return jax.lax.with_sharding_constraint(x, spec)
        except (ValueError, RuntimeError):
            return x

    def _act_spec(self, seq_sharded: bool):
        # activations: batch over the full DP axes; seq axis when sharded
        return P(ZERO_AXES, self.seq_axis if seq_sharded else None, None)

    def _heads_spec(self):
        # Ulysses: inside attention, seq gathered, heads sharded over seq×model
        return P(ZERO_AXES, None, (self.seq_axis, self.model_axis), None)

    # ------------------------------------------------------------------
    def _block(self, x, blk, *, positions, rng, train, kv_cache=None, cache_index=None,
               paged=None, attn_mask_bias=None, step=None):
        """One transformer block on (B, S, H). Returns (y, new_kv) where new_kv is
        the updated (k, v) when decoding with a cache.

        ``paged``: (pool, layer, tables) for the blocked KV pool — the WHOLE
        stacked pool (``ops/transformer/paged_attention.py`` owns its layout),
        this block's pool layer (traced), tables (B, MAXB) of pool block ids
        (0 = reserved trash block); new_kv is then the updated pool. Tokens
        write at their ``positions`` as whole rows of the pool, in place;
        attention reads the pool where it lies, through the Pallas kernel
        for one-token rows or the table-gathered logical cache with a
        per-sequence position mask otherwise (covers chunked prefill AND
        decode — reference ``inference/v2/ragged_ops/blocked_flash`` +
        ``kv_cache.py BlockedKVCache``). ``step``: the :class:`PagedStep`
        of the call (what the tables and positions alone say, if None)."""
        cfg = self.config
        if cfg.is_mla:
            if kv_cache is not None or attn_mask_bias is not None or (
                    rng is not None and cfg.dropout > 0):
                raise NotImplementedError(
                    "attention='mla' has the full-sequence and the paged "
                    "paths only: no slot cache, padding mask or dropout")
            block = self._block_scmoe if cfg.layer_kind == "scmoe" \
                else self._block_mla
            y, pool, _ = block(x, blk, positions=positions, paged=paged,
                               step=step, experts=None)
            return y, pool, jnp.zeros((), jnp.float32)
        nh, kvh, hd = cfg.num_heads, cfg.kv_heads, cfg.head_dim
        B, S, H = x.shape
        # weight-only-quantized params (ops/quantizer/woq.py): dequant this
        # layer's slice only — XLA fuses the dequant into the matmul reads
        blk = _dequant_woq(blk, x.dtype)

        # post-LN (BERT family): attention reads the raw residual stream and
        # ln1/ln2 normalize AFTER each residual add
        from jax.ad_checkpoint import checkpoint_name

        post_ln = cfg.norm_position == "post"
        with jax.named_scope("attn"):
            h = x if post_ln else checkpoint_name(_norm(
                x, blk["ln1_scale"], blk.get("ln1_bias"), cfg.norm, cfg.norm_eps,
                cfg.norm_weight_offset), "ln_out")
            # activation quantization hook (reference basic_layer.py:17 QuantAct —
            # each compressed linear quantizes its input): set by
            # compression.init_compression; None costs nothing
            act_q = getattr(self, "_act_quant_fn", None)
            if act_q is not None:
                h = act_q(h)
            q = h @ blk["wq"].astype(h.dtype)
            kk = h @ blk["wk"].astype(h.dtype)
            v = h @ blk["wv"].astype(h.dtype)
            if "wq_bias" in blk:
                q = q + blk["wq_bias"].astype(h.dtype)
                kk = kk + blk["wk_bias"].astype(h.dtype)
                v = v + blk["wv_bias"].astype(h.dtype)
            if paged is not None:
                # served: each product is whole before it is split into heads.
                # Without the barrier the TPU compiler pushes the split into
                # the matrix: it slices the layer's (H, H) out of the stack,
                # relays a transposed copy of it and contracts that, a layer
                # a dispatch (0.43 of serve-chat's 2.1 ms round). Behind it
                # the product reads the stack where it lies, as wo's does
                q, kk, v = (jax.lax.optimization_barrier(a) for a in (q, kk, v))
            q = q.reshape(B, S, nh, hd)
            kk = kk.reshape(B, S, kvh, hd)
            v = v.reshape(B, S, kvh, hd)
            if cfg.pos_embedding == "rope":
                q, kk = _rope(q, kk, positions, hd, cfg.rope_theta, cfg.rotary_dim)

            def _alibi_bias(kpos):
                # slopes · key-position; equivalent to slopes · (k-q) distance under
                # softmax's per-query shift invariance. kpos (Skv,) → bias
                # (1, kvh, groups, 1, Skv), or (B, Skv) → (B, kvh, groups, 1, Skv)
                # (random-LTD passes the kept tokens' ORIGINAL positions per batch)
                slopes = jnp.asarray(alibi_slopes(nh) * cfg.alibi_slope_scale
                                     ).reshape(kvh, nh // kvh)
                kpos = kpos.astype(jnp.float32)
                if kpos.ndim == 1:
                    kpos = kpos[None]
                return kpos[:, None, None, None, :] * slopes[None, :, :, None, None]

            new_kv = None
            if paged is not None:
                from ..ops.transformer import paged_attention as pa

                pool, layer, tables = paged
                if step is None:
                    step = PagedStep.of(tables, positions[:, 0])
                BS = pool.shape[3]
                # NOTE: evaluated at TRACE time — the env override (used by tests
                # to exercise this branch in interpret mode) and set_default_impl
                # must be set before the engine compiles its decode program
                want_kernel = S == 1 and pa.kernels_wanted()
                # what the kernel documents as unsupported; each gives way to the
                # gather path below, and says so as the program is traced
                gaps = [why for bad, why in (
                    (cfg.pos_embedding == "alibi", "ALiBi bias"),
                    (bool(cfg.logit_softcap), "logit softcap"),
                    (hd not in (64, 128, 256), f"head_dim {hd}"),
                    (BS % 8 != 0, f"block size {BS} % 8 != 0"),
                ) if bad]
                use_kernel = want_kernel and not gaps
                if want_kernel and gaps:
                    logger.warning("paged decode takes the XLA gather path, not "
                                   f"the Pallas kernel: {', '.join(gaps)}")
                # a decode round (its rows apart) on a pool that takes the
                # live rows' write: the attention kernel writes them itself
                fold = (use_kernel and step.rows_apart
                        and pa.writes_live_rows(pool))
                if not fold:
                    with jax.named_scope("kv_write"):
                        pool = pa.write_rows(pool, layer, tables, positions,
                                             kk, v, rows_apart=step.rows_apart)
                with jax.named_scope("paged_attn"):
                    if fold:
                        # ONE call a layer: q, the new k and v and the result
                        # as (rows, heads * hd), where the products leave and
                        # ``wo`` takes them; the kernel sets each live row's
                        # [k | v] in the block it fetches last and writes the
                        # sub-tile back (paged_attention.py)
                        attn_out, pool = pa.paged_decode(
                            q.reshape(B, nh * hd), pool, layer, tables,
                            step.limits, new_rows=(kk.reshape(B, kvh * hd),
                                                   v.reshape(B, kvh * hd)))
                    elif use_kernel:
                        # Pallas paged decode: the kernel streams this layer's
                        # blocks out of the stacked pool by layer index and block
                        # table — no slice, no gathered copy (paged_attention.py).
                        # A padding row is dead (limit 0): the kernel fetches
                        # nothing for it
                        attn_out = pa.paged_decode(
                            q[:, 0], pool, layer, tables, step.limits)[:, None]
                    else:
                        gk, gv = pa.gather_context(pool, layer, tables)
                        T = gk.shape[1]
                        kpos = jnp.arange(T)
                        mask = kpos[None, None, :] <= positions[:, :, None]  # (B,S,T)
                        bias = jnp.where(mask, 0.0, -1e30)[:, None, None]  # (B,1,1,S,T)
                        if cfg.pos_embedding == "alibi":
                            bias = bias + _alibi_bias(kpos)
                        attn_out = _attention_op(
                            q, gk, gv, causal=False, num_kv_groups=nh // kvh,
                            softcap=cfg.logit_softcap, bias=bias,
                        )
                new_kv = pool
            elif kv_cache is not None:
                ck, cv = kv_cache  # (B, T, kvh, hd)
                ck = jax.lax.dynamic_update_slice(ck, kk.astype(ck.dtype), (0, cache_index, 0, 0))
                cv = jax.lax.dynamic_update_slice(cv, v.astype(cv.dtype), (0, cache_index, 0, 0))
                new_kv = (ck, cv)
                bias = (_alibi_bias(jnp.arange(ck.shape[1]))
                        if cfg.pos_embedding == "alibi" else None)
                attn_out = _attention_op(
                    q, ck, cv, causal=True, q_offset=cache_index,
                    num_kv_groups=nh // kvh, softcap=cfg.logit_softcap, bias=bias,
                )
            else:
                # Ulysses reshard: gather seq, shard heads (no-op when seq axis == 1)
                q = self._constraint(q, self._heads_spec())
                kk = self._constraint(kk, self._heads_spec())
                v = self._constraint(v, self._heads_spec())
                bias = _alibi_bias(positions) if cfg.pos_embedding == "alibi" else None
                if attn_mask_bias is not None:  # encoder padding mask (B,1,1,1,S)
                    bias = attn_mask_bias if bias is None else bias + attn_mask_bias
                attn_out = _attention_op(
                    q, kk, v, causal=cfg.causal, num_kv_groups=nh // kvh,
                    softcap=cfg.logit_softcap, bias=bias,
                )
            attn_out = attn_out.reshape(B, S, nh * hd)
            attn_out = attn_out @ blk["wo"].astype(h.dtype)
            if "attn_bias" in blk:
                attn_out = attn_out + blk["attn_bias"].astype(h.dtype)
            if cfg.post_norms:
                attn_out = self._post_norm(attn_out, blk["post_attn_scale"],
                                           paged is not None)
            attn_out = self._constraint(attn_out, self._act_spec(kv_cache is None))
            if rng is not None:
                rng, r1 = jax.random.split(rng)
                attn_out = _dropout(attn_out, cfg.dropout, r1, train)

        with jax.named_scope("mlp"):
            if post_ln:
                x = _norm(x + attn_out, blk["ln1_scale"], blk.get("ln1_bias"),
                          cfg.norm, cfg.norm_eps, cfg.norm_weight_offset)
                h2 = x
            elif cfg.parallel_block:
                h2 = h if cfg.parallel_shared_ln else _norm(
                    x, blk["ln2_scale"], blk.get("ln2_bias"), cfg.norm, cfg.norm_eps,
                    cfg.norm_weight_offset)
            else:
                x = x + attn_out
                h2 = checkpoint_name(
                    _norm(x, blk["ln2_scale"], blk.get("ln2_bias"), cfg.norm,
                          cfg.norm_eps, cfg.norm_weight_offset), "ln_out")
            if act_q is not None:
                h2 = act_q(h2)
            aux = jnp.zeros((), jnp.float32)
            if cfg.num_experts > 0:
                mlp_out, aux = self._moe_ffn(h2, blk, train)
            else:
                if cfg.activation in ("swiglu", "geglu"):
                    g = checkpoint_name(h2 @ blk["w_gate"].astype(h.dtype), "mlp_up")
                    u = checkpoint_name(h2 @ blk["w_up"].astype(h.dtype), "mlp_up")
                    act = jax.nn.silu if cfg.activation == "swiglu" else \
                        partial(jax.nn.gelu, approximate=True)
                    inter = act(g) * u
                else:
                    up = h2 @ blk["w_up"].astype(h.dtype)
                    if "mlp_up_bias" in blk:
                        up = up + blk["mlp_up_bias"].astype(h.dtype)
                    up = checkpoint_name(up, "mlp_up")
                    if cfg.activation == "relu":
                        inter = jax.nn.relu(up)
                    elif cfg.activation == "gelu_exact":
                        inter = gelu_exact(up)
                    else:
                        inter = jax.nn.gelu(up, approximate=True)
                inter = checkpoint_name(inter, "mlp_act")
                mlp_out = inter @ blk["w_down"].astype(h.dtype)
            if "mlp_bias" in blk:
                mlp_out = mlp_out + blk["mlp_bias"].astype(h.dtype)
            if cfg.post_norms:
                mlp_out = self._post_norm(mlp_out, blk["post_mlp_scale"],
                                          paged is not None)
            mlp_out = self._constraint(mlp_out, self._act_spec(kv_cache is None))
            if rng is not None:
                rng, r2 = jax.random.split(rng)
                mlp_out = _dropout(mlp_out, cfg.dropout, r2, train)
        if post_ln:
            y = _norm(x + mlp_out, blk["ln2_scale"], blk.get("ln2_bias"),
                      cfg.norm, cfg.norm_eps, cfg.norm_weight_offset)
            return y, new_kv, aux
        if cfg.parallel_block:
            return x + attn_out + mlp_out, new_kv, aux
        return x + mlp_out, new_kv, aux

    def _post_norm(self, y, scale, served):
        """``post_norms``: a sublayer's output RMS-normed before it joins the
        residual stream. Served, the product is whole first (the barrier: a
        norm reads it twice, and XLA would stream the matrix for each;
        :meth:`_typed_layer`)."""
        cfg = self.config
        if served:
            y = jax.lax.optimization_barrier(y)
        return _norm(y, scale, None, "rmsnorm", cfg.norm_eps,
                     cfg.norm_weight_offset)

    def _block_mla(self, x, blk, *, positions, paged=None, step=None,
                   experts=None):
        """One latent-attention block on (B, S, H): a dense layer, or an
        expert layer where ``blk`` holds a router. Returns (y, new pool, the
        expert layer's counts (``held_experts_ffn``) or None).

        Full sequence (``paged`` None): the latent is up-projected through
        ``wkv_b`` and attended to causally, un-absorbed. ``paged`` (pool,
        layer, tables): every token is a row (S = 1); its ``[c_kv | k_rope]``
        is written to the latent pool and attention runs in the absorbed form
        (``q_nope W_uk`` against ``c_kv``, the weighted latent through
        ``W_uv``: both views of ``wkv_b``, taken here) over the pool where it
        lies, a tile of the ``step`` (:class:`PagedStep`) streaming its
        sequence's latent once. ``experts``: (stacked expert leaves, layer of
        the group) when the caller kept them out of ``blk``."""
        blk = _dequant_woq(blk, x.dtype)
        attn_out, new_pool = self._mla_attention(
            x, blk, positions=positions, paged=paged, step=step)
        with jax.named_scope("mlp"):
            x = jax.lax.optimization_barrier(x + attn_out)
            h2 = _norm(x, blk["ln2_scale"], None, "rmsnorm",
                       self.config.norm_eps)
            mlp_out, stats = self._feed_forward(h2, blk, experts, step)
            mlp_out = self._constraint(mlp_out, self._act_spec(paged is None))
        return x + mlp_out, new_pool, stats

    def _block_scmoe(self, x, blk, *, positions, paged, step, experts):
        """One shortcut-connected double layer (LongCat-Flash, ScMoE): two
        sublayers of latent attention and a dense feed-forward, and one
        expert layer computed from the first sublayer's post-attention norm
        and added after the second feed-forward::

            x1 = x  + A_0(N(x));   h = N(x1);   m = E(h)
            x2 = x1 + F_0(h)
            x3 = x2 + A_1(N(x2));  y = x3 + F_1(N(x3)) + m

        Sublayer ``i`` attends over pool layer ``layer + i``, ``layer`` the
        handle's: the double layer's first (``TransformerConfig.type_runs``).
        Arguments and result as :meth:`_block_mla`."""
        from ..moe.layer import _gated_mlp

        eps, n_sub = self.config.norm_eps, self.config.sublayers
        blk = _dequant_woq(blk, x.dtype)
        once = jax.lax.optimization_barrier
        pool, layer, tables = paged if paged is not None else (None, 0, None)
        m = stats = None
        for i in range(n_sub):
            sub = sublayer(blk, i)
            attn_out, pool = self._mla_attention(
                x, sub, positions=positions, step=step,
                paged=None if paged is None else (pool, layer + i, tables))
            with jax.named_scope("mlp"):
                x = once(x + attn_out)
                h = _norm(x, sub["ln2_scale"], None, "rmsnorm", eps)
                if i == 0:
                    m, stats = self._held_experts(h, blk, experts, step)
                with jax.named_scope("dense_ffn"):
                    x = x + self._constraint(
                        _gated_mlp(h, sub["w_gate"], sub["w_up"], sub["w_down"]),
                        self._act_spec(paged is None))
        with jax.named_scope("mlp"):
            y = x + self._constraint(m, self._act_spec(paged is None))
        return y, pool, stats

    def _feed_forward(self, h, blk, experts, step):
        """The feed-forward of a layer on its normed (B, S, H) ``h``, by
        what ``blk`` holds: the held experts where it has a router
        (:meth:`_held_experts`), the gated dense one otherwise. (its output,
        the expert layer's counts or None): the one call of the latent and of
        the ``layer_types`` layers."""
        if "moe_wg" in blk:
            return self._held_experts(h, blk, experts, step)
        # W_down(W_up h * silu(W_gate h * gate)) * out (muP's two scalars: 1
        # and no operation for every family but Falcon-H1's)
        gate, out = self.config.mlp_mults
        dt = h.dtype
        y = (jax.nn.silu(_times(h @ blk["w_gate"].astype(dt), gate))
             * (h @ blk["w_up"].astype(dt))) @ blk["w_down"].astype(dt)
        return _times(y, out), None

    def _held_experts(self, h, blk, experts, step):
        """The expert layer of ``blk`` on the normed (B, S, H) ``h``: (its
        output, its counts). ``experts``: see :meth:`_block_mla`. A padding
        row of the ``step`` is routed to no expert."""
        from ..moe.layer import held_experts_ffn

        cfg = self.config
        B, S, H = h.shape
        big, layer_in_group = experts if experts is not None else (blk, None)
        shared = tuple(blk["shared_" + k] for k in
                       ("w_gate", "w_up", "w_down")) \
            if "shared_w_gate" in blk else None
        y, stats = held_experts_ffn(
            h.reshape(B * S, H), blk["moe_wg"], blk["moe_bias"],
            big["wi"], big["w_gate"], big["w_down"], shared,
            k=cfg.moe_top_k, n_group=cfg.moe_n_group,
            topk_group=cfg.moe_topk_group,
            normalize=cfg.moe_norm_topk, scale=cfg.moe_score_scale,
            first=cfg.moe_expert_offset, layer=layer_in_group,
            token_mask=None if step is None else step.live,
            router=cfg.moe_router,
            zero_experts=cfg.moe_zero_experts)
        return y.reshape(B, S, H), stats

    def _mla_attention(self, x, blk, *, positions, paged=None, step=None):
        """Latent attention of one (sub)layer on the residual stream ``x``
        (B, S, H), its input norm and output projection included: (the
        attention's output, the new pool or None). ``blk``: the layer's
        leaves; ``paged`` and ``step`` as :meth:`_block_mla` (without a
        step: rows one a sequence, as the tables and positions say)."""
        from ..ops.transformer import paged_attention as pa

        cfg = self.config
        nh, rank = cfg.num_heads, cfg.kv_lora_rank
        nope, rope, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
        B, S, H = x.shape
        eps, dt = cfg.norm_eps, x.dtype
        scale = mla_softmax_scale(cfg)
        q_scale, kv_scale = cfg.mla_latent_scales
        # a norm over a product reads the product twice (its mean square, then
        # its values): the barrier keeps XLA from fusing the matmul into both
        # reads, which streams the matrix twice (on the chip ``wo`` alone cost
        # 0.2 ms a layer)
        once = jax.lax.optimization_barrier

        def rms(v, name, factor=1.0):
            scale = blk[name] if factor == 1.0 \
                else blk[name].astype(jnp.float32) * factor
            return _norm(v, scale, None, "rmsnorm", eps)

        new_pool = None
        with jax.named_scope("attn"):
            with jax.named_scope("mla_proj"):
                h = rms(x, "ln1_scale")
                if "wq_a" in blk:
                    c_q = rms(once(h @ blk["wq_a"].astype(dt)), "q_a_scale",
                              q_scale)
                    q = c_q @ blk["wq_b"].astype(dt)
                else:                    # no low-rank step (q_lora_rank 0)
                    q = h @ blk["wq"].astype(dt)
                if paged is not None:
                    # whole before the split into heads, or the compiler
                    # relays a transposed copy of the matrix (:meth:`_block`)
                    q = once(q)
                q = q.reshape(B, S, nh, nope + rope)
                kv_a = once(h @ blk["wkv_a"].astype(dt))
                c_kv = rms(kv_a[..., :rank], "kv_a_scale", kv_scale)  # (B, S, rank)
                k_rope = _rope_interleaved(kv_a[..., None, rank:], positions, cfg)
                q_nope = q[..., :nope]
                q_rope = _rope_interleaved(q[..., nope:], positions, cfg)
                # the two views feed products batched by head; the compiler
                # wants that head major and in the stored (rank, nh * (nope +
                # vd)) it lies inside the minor dimension, so this one matrix
                # is relaid a layer whatever form the pair takes (PERF.md 5)
                wkv_b = blk["wkv_b"].astype(dt).reshape(rank, nh, nope + vd)
                w_uk, w_uv = wkv_b[..., :nope], wkv_b[..., nope:]
            if paged is None:
                with jax.named_scope("mla_proj"):
                    k_nope = jnp.einsum("bsr,rhd->bshd", c_kv, w_uk)
                    v = jnp.einsum("bsr,rhd->bshd", c_kv, w_uv)
                    k = jnp.concatenate(
                        [k_nope, jnp.broadcast_to(k_rope, (B, S, nh, rope))], -1)
                    qq = jnp.concatenate([q_nope, q_rope], -1)
                if vd == nope + rope:
                    attn = _attention_op(qq, k, v, causal=True, scale=scale)
                else:
                    from ..ops.transformer.attention import xla_attention

                    attn = xla_attention(qq, k, v, causal=True, scale=scale)
            else:
                pool, layer, tables = paged
                if S != 1:
                    raise ValueError("the latent paged path takes one-token rows")
                if step is None:
                    step = PagedStep.of(tables, positions[:, 0])
                with jax.named_scope("kv_write"):
                    pool = pa.write_rows(
                        pool, layer, tables, positions, c_kv[:, :, None, :],
                        jnp.pad(k_rope, ((0, 0),) * 3
                                + ((0, cfg.kv_row[1] - rope),)),
                        rows_apart=step.rows_apart)
                new_pool = pool
                with jax.named_scope("mla_proj"):
                    q_lat = jnp.einsum("bhd,rhd->bhr", q_nope[:, 0], w_uk)
                with jax.named_scope("paged_attn"):
                    o_lat = self._mla_paged_attention(
                        q_lat, q_rope[:, 0], pool, layer, step, scale)
                with jax.named_scope("mla_proj"):
                    attn = jnp.einsum("bhr,rhd->bhd", o_lat, w_uv)[:, None]
            if "w_ogate" in blk:         # one scalar a head (attn_head_gate)
                attn = attn * jax.nn.sigmoid(
                    once(h @ blk["w_ogate"].astype(dt)))[..., None]
            attn_out = attn.reshape(B, S, nh * vd) @ blk["wo"].astype(dt)
            attn_out = self._constraint(attn_out, self._act_spec(paged is None))
        return attn_out, new_pool

    def _mla_paged_attention(self, q_lat, q_rope, pool, layer, step, scale):
        """Absorbed attention of the ``step``'s T one-token rows over the
        latent pool: rows before its ``cut`` one a sequence, rows from it on
        in segment tiles. The Pallas kernel on a TPU (or forced, as the GPT-2
        path's is), the XLA gather off it. A row with ``limits`` 0 is dead:
        the kernel fetches nothing for a one-token row or a tile of such
        rows. A step whose tiles are longer than the kernel's
        (``SEGMENT_TILE``: a ``layer_types`` model's ``linear_chunk``) hands
        them over ``SEGMENT_TILE`` rows at a time: every live row carries its
        sequence's table, and a tile's valid rows are a prefix."""
        from ..ops.transformer import paged_attention as pa

        attend = pa.mla_decode if pa.kernels_wanted() else pa.mla_attend_xla
        tables, limits = step.tables, step.limits
        cut, end, T = step.cut, step.end, q_lat.shape[0]
        tile = min(step.tile, pa.SEGMENT_TILE)
        if step.tile % tile:
            raise ValueError(f"a step's tile of {step.tile} rows is no "
                             f"multiple of the latent kernel's {tile}")
        parts = []
        if cut:
            parts.append(attend(q_lat[:cut], q_rope[:cut], pool, layer,
                                tables[:cut], limits[:cut], scale=scale))
        if cut < end:
            parts.append(attend(q_lat[cut:end], q_rope[cut:end], pool, layer,
                                tables[cut:end:tile], limits[cut:end],
                                scale=scale, q_tile=tile))
        o = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
        # the rows behind the last whole tile are padding
        return o if end == T else jnp.pad(
            o, ((0, T - end), (0, 0), (0, 0)))

    def _moe_ffn(self, h, blk, train):
        """Routed expert FFN on (B,S,H) — delegates to the shared MoE core
        (reference ``moe/sharded_moe.py MOELayer``); one group per sequence."""
        from ..moe.layer import routed_ffn

        cfg = self.config
        y, l_aux = routed_ffn(
            h, blk["moe_wg"], blk["wi"], blk["w_down"], blk.get("w_gate"),
            k=cfg.moe_top_k,
            drop_tokens=cfg.moe_drop_tokens,
            capacity_factor=cfg.moe_capacity_factor if train else 1.0,
            activation="swiglu" if cfg.activation == "swiglu" else "gelu",
            # batch arrives sharded over the DP axes; inside the expert
            # computation the expert axis moves to the expert dim (the all-to-all)
            data_axes=("data", "hpz"),
        )
        if cfg.moe_use_residual:
            from ..moe.layer import residual_mix

            y = residual_mix(
                h, y, blk["res_wi"], blk["res_wo"],
                blk["res_coef_w"], blk["res_coef_b"],
                activation="swiglu" if cfg.activation == "swiglu" else "gelu",
                mlp_wgate=blk.get("res_wgate"))
        return y, l_aux

    # ------------------------------------------------------------------
    def _embed(self, params, input_ids, positions, dtype, token_type_ids=None):
        cfg = self.config
        x = jnp.take(params["wte"], input_ids, axis=0).astype(dtype)
        if cfg.embed_scale is not None:
            x = x * jnp.asarray(cfg.embed_scale, dtype)
        if cfg.pos_embedding == "learned":
            x = x + jnp.take(params["wpe"], positions, axis=0).astype(dtype)
        if cfg.token_type_embedding > 0:
            tt = token_type_ids if token_type_ids is not None \
                else jnp.zeros_like(input_ids)
            x = x + jnp.take(params["wtt"], tt, axis=0).astype(dtype)
        if cfg.embed_layernorm:
            x = _norm(x, params["ln_emb_scale"], params.get("ln_emb_bias"),
                      cfg.norm, cfg.norm_eps, cfg.norm_weight_offset)
        return x

    def _lean_policy(self):
        """Save no-batch-dim dot outputs EXCEPT tensors wider than 2×hidden
        (the MLP up/gate projections — the bulk of activation memory, one
        cheap matmul to recompute), plus the flash-attention residuals."""
        from jax._src.ad_checkpoint import name_p
        from jax._src.lax import lax as lax_internal

        H = self.config.hidden_size

        def policy(prim, *args, **params):
            if prim is name_p:
                return params["name"] in ("attn_out", "attn_lse")
            if prim is lax_internal.dot_general_p:
                (_, _), (lhs_b, rhs_b) = params["dimension_numbers"]
                if lhs_b or rhs_b:
                    return False
                rhs = args[1] if len(args) > 1 else None
                if rhs is not None and rhs.shape and rhs.shape[-1] >= 2 * H:
                    return False
                return True
            return False

        return policy

    def _ckpt(self, fn):
        policies = jax.checkpoint_policies
        # "dots" saves weight-side matmul outputs AND the flash-attention
        # kernel's named residuals (out/lse) — the backward pass then only
        # recomputes cheap elementwise/norm ops, never a matmul or the
        # attention forward kernel
        policy = {
            "dots": policies.save_from_both_policies(
                policies.dots_with_no_batch_dims_saveable,
                policies.save_only_these_names("attn_out", "attn_lse"),
            ),
            # "dots" plus the two per-layer LN outputs (16 MB/layer at 350M
            # shapes): backward no longer re-runs the mean/rsqrt/scale chain,
            # at a fraction of dots_elem's activation footprint
            "dots_ln": policies.save_from_both_policies(
                policies.dots_with_no_batch_dims_saveable,
                policies.save_only_these_names(
                    "attn_out", "attn_lse", "ln_out"),
            ),
            # additionally keep LN and MLP-activation outputs: the backward
            # pass then recomputes nothing at all (more HBM, fewer VPU passes)
            "dots_elem": policies.save_from_both_policies(
                policies.dots_with_no_batch_dims_saveable,
                policies.save_only_these_names(
                    "attn_out", "attn_lse", "ln_out", "mlp_act"),
            ),
            "dots_plain": policies.dots_with_no_batch_dims_saveable,
            "dots_batch": policies.dots_saveable,
            "dots_lean": self._lean_policy(),
            "full": None,
        }
        name = self.config.remat_policy
        if name not in policy:
            raise ValueError(
                f"unknown remat_policy {name!r} (known: {sorted(policy)})")
        if policy[name] is not None:
            return jax.checkpoint(fn, policy=policy[name])
        return jax.checkpoint(fn)

    def _loop_close(self, params, x, t, picked):
        """The end of step ``t`` of a looped model on (..., H): the closing
        norm, whose output is the step's state ``h_t`` and the next step's
        input, and the exit gate ``lam_t = sigmoid(h_t . exit_w + exit_b)``
        in float32. ``picked`` is the selection so far, ``(h_exit, done,
        survive, reached)``: the exit distribution is ``p_t = lam_t prod_{j<t}
        (1 - lam_j)`` (the last step takes what is left) and a token's
        ``h_exit`` the state of the first step at which its running sum
        reaches ``early_exit_threshold``, else the last step's. Returns
        ``(h_t, picked)``; at a threshold of 1.0 or more the last step is
        every token's, no gate is computed and ``picked`` stays None."""
        cfg = self.config
        loop = params["loop"]
        with jax.named_scope("loop_close"):
            h = _norm(x, loop["norm_scale"][0], None, cfg.norm, cfg.norm_eps,
                      cfg.norm_weight_offset)
            if cfg.early_exit_threshold >= 1.0:
                return h, None
            f32 = jnp.float32
            lam = jax.nn.sigmoid(
                jnp.sum(h.astype(f32) * loop["exit_w"][0].astype(f32), axis=-1)
                + loop["exit_b"][0].astype(f32))
            if picked is None:
                picked = (jnp.zeros_like(h), jnp.zeros(lam.shape, bool),
                          jnp.ones_like(lam), jnp.zeros_like(lam))
            h_exit, done, survive, reached = picked
            last = t == cfg.loop_steps - 1
            reached = reached + (survive if last else lam * survive)
            take = ~done & ((reached >= cfg.early_exit_threshold) | last)
            return h, (jnp.where(take[..., None], h, h_exit), done | take,
                       survive * (1.0 - lam), reached)

    def _looped(self, params, x, walk):
        """``walk(x, t) -> (x, extra)`` over the model's steps: once, or for
        a looped model ``loop_steps`` times with :meth:`_loop_close` behind
        each. Returns (what the head reads, [extra of each step]). Every
        function that walks the layers goes through here, so none runs one
        step of a looped model silently."""
        cfg = self.config
        if not cfg.looped:
            x, extra = walk(x, 0)
            return x, [extra]
        extras, picked = [], None
        for t in range(cfg.loop_steps):
            x, extra = walk(x, t)
            extras.append(extra)
            x, picked = self._loop_close(params, x, t, picked)
        return (x if picked is None else picked[0]), extras

    def _trunk(self, params, x, positions, rng, train, pld_theta=None,
               attn_mask_bias=None):
        """All blocks, a looped model's ``loop_steps`` times (:meth:`_walk`);
        returns (what the head reads, the auxiliary loss)."""
        x, auxes = self._looped(params, x, lambda x, t: self._walk(
            params, x, positions, rng, train, pld_theta=pld_theta,
            attn_mask_bias=attn_mask_bias))
        return x, sum(auxes[1:], auxes[0])

    def _walk(self, params, x, positions, rng, train, pld_theta=None,
              attn_mask_bias=None):
        """Run all blocks once via scan (remat optional). With ``pld_theta``
        (progressive layer drop, reference ``progressive_layer_drop.py``),
        layer l keeps with prob 1 - (l/L)(1 - theta) — deeper layers dropped more."""
        cfg = self.config
        L = cfg.num_layers
        use_pld = pld_theta is not None and train
        use_rng = rng is not None and train and (cfg.dropout > 0 or use_pld)
        if use_rng and len(cfg.type_runs) > 1:
            raise NotImplementedError(
                "dropout / progressive layer drop over two layer groups")
        if use_rng and cfg.looped:
            raise NotImplementedError(
                f"loop_steps {cfg.loop_steps}: dropout / progressive layer "
                "drop draw one key a layer, not one a layer and step")

        if use_rng:
            rngs = jax.random.split(rng, L)

            def body(h, layer):
                blk, rsub, idx = layer
                r_drop, r_pld = jax.random.split(rsub)
                y, _, aux = self._block(h, blk, positions=positions,
                                        rng=r_drop if cfg.dropout > 0 else None,
                                        train=train,
                                        attn_mask_bias=attn_mask_bias)
                if use_pld:
                    keep_p = 1.0 - (idx.astype(jnp.float32) / L) * (1.0 - pld_theta)
                    keep = jax.random.bernoulli(r_pld, keep_p)
                    y = jnp.where(keep, y, h)
                    aux = jnp.where(keep, aux, 0.0)
                return y, aux

            block_fn = self._ckpt(body) if cfg.remat else body
            if not cfg.scan_layers:
                aux_sum = jnp.zeros((), jnp.float32)
                for i in range(L):
                    blk = jax.tree.map(lambda a: a[i], params["blocks"])
                    x, aux = block_fn(x, (blk, rngs[i], jnp.asarray(i)))
                    aux_sum = aux_sum + aux
                return x, aux_sum
            x, auxes = jax.lax.scan(
                block_fn, x, (params["blocks"], rngs, jnp.arange(L)))
        else:
            def body(h, blk):
                y, _, aux = self._block(h, blk, positions=positions, rng=None,
                                        train=train,
                                        attn_mask_bias=attn_mask_bias)
                return y, aux

            block_fn = self._ckpt(body) if cfg.remat else body
            if not cfg.scan_layers:
                aux_sum = jnp.zeros((), jnp.float32)
                for group, *_ in cfg.type_runs:
                    for i in range(jax.tree.leaves(params[group])[0].shape[0]):
                        blk = jax.tree.map(lambda a: a[i], params[group])
                        x, aux = block_fn(x, blk)
                        aux_sum = aux_sum + aux
                return x, aux_sum
            auxes = jnp.zeros((), jnp.float32)
            for group, *_ in cfg.type_runs:
                x, aux = jax.lax.scan(block_fn, x, params[group])
                auxes = auxes + jnp.sum(aux)
        return x, jnp.sum(auxes)

    def _trunk_ltd(self, params, x, positions, rng, keep: int, attn_mask=None):
        """Random-LTD trunk (reference ``data_routing/basic_layer.py``): the
        first/last ``skip_ends`` layers run full-sequence (unrolled); the
        middle layers run under ``lax.scan`` on a random ``keep``-token subset
        each (uniform static shapes across the scan)."""
        from ..runtime.data_pipeline.data_routing import random_ltd_block

        cfg = self.config
        if cfg.looped:
            raise NotImplementedError(
                f"loop_steps {cfg.loop_steps}: random-LTD walks the layers "
                "once")
        L, skip = cfg.num_layers, cfg.random_ltd_skip_ends
        use_drop = cfg.dropout > 0
        rngs = jax.random.split(rng, L)  # rng is never None here (_hidden_aux)
        aux_total = jnp.zeros((), jnp.float32)

        def mask_bias_of(m):
            if m is None:
                return None
            return jnp.where(m.astype(bool), 0.0, -1e30)[:, None, None, None, :]

        def run_full(h, i):
            blk = jax.tree.map(lambda a: a[i], params["blocks"])
            r = rngs[i] if use_drop else None
            y, _, aux = self._block(h, blk, positions=positions, rng=r, train=True,
                                    attn_mask_bias=mask_bias_of(attn_mask))
            return y, aux

        # min()/max() guards tiny models where 2*skip > L — never run a layer
        # twice (JAX clamps out-of-range indices silently)
        for i in range(min(skip, L)):
            x, aux = run_full(x, i)
            aux_total = aux_total + aux

        if skip < L - skip:
            mid = jax.tree.map(lambda a: a[skip:L - skip], params["blocks"])
            mid_rngs = rngs[skip:L - skip]

            def body(h, layer):
                blk, r = layer
                r_drop, r_ltd = jax.random.split(r)

                def fn(hs, ps, ms):
                    y, _, aux = self._block(
                        hs, blk, positions=ps,
                        rng=r_drop if use_drop else None, train=True,
                        attn_mask_bias=mask_bias_of(ms))
                    return y, aux

                return random_ltd_block(fn, h, positions, keep, r_ltd,
                                        key_mask=attn_mask)

            block_fn = self._ckpt(body) if cfg.remat else body
            x, auxes = jax.lax.scan(block_fn, x, (mid, mid_rngs))
            aux_total = aux_total + jnp.sum(auxes)

        for i in range(max(skip, L - skip), L):
            x, aux = run_full(x, i)
            aux_total = aux_total + aux
        return x, aux_total

    def _head_operands(self, params, x):
        """``(x as the head reads it, weight, bias or None, vocab_major)``:
        the weight where it lies, ``(V, H)`` (the tied table, ``vocab_major``)
        or an untied ``lm_head``'s ``(H, V)``."""
        cfg = self.config
        if cfg.mlm_head:
            # BERT prediction head: dense + act + LN, then the tied decoder
            # (reference kernel-injection covers this via the BERT container)
            x = x @ params["mlm_dense"].astype(x.dtype) \
                + params["mlm_dense_bias"].astype(x.dtype)
            if cfg.activation == "relu":  # transform act follows hidden_act
                x = jax.nn.relu(x)
            elif cfg.activation == "gelu_exact":
                x = gelu_exact(x)
            else:
                x = jax.nn.gelu(x, approximate=True)
            x = _norm(x, params["mlm_ln_scale"], params["mlm_ln_bias"],
                      "layernorm", cfg.norm_eps)
            return x, params["wte"], params["mlm_bias"], True
        # post-LN trunks end already normalized, and a looped model's last
        # step was closed by its norm (``_loop_close``)
        if cfg.norm_position != "post" and not cfg.looped:
            x = _norm(x, params["lnf_scale"], params.get("lnf_bias"),
                      cfg.norm, cfg.norm_eps, cfg.norm_weight_offset)
        if cfg.dim_model_base:  # muP: the head reads N(x) * base / width
            x = x * jnp.asarray(cfg.dim_model_base / cfg.hidden_size, x.dtype)
        if cfg.tie_embeddings:
            return x, params["wte"], params.get("lm_head_bias"), True
        return x, params["lm_head"], params.get("lm_head_bias"), False

    def _head(self, params, x):
        cfg = self.config
        x, w, bias, vocab_major = self._head_operands(params, x)
        # a served step's few rows over a tied table: one kernel call that
        # streams the table from HBM once (ops/transformer/fused_ce.py)
        block_v = stream_block(x, w, vocab_major)
        tracing.set_program_attr(head="xla" if block_v is None else "stream")
        if block_v is None:
            out = x @ (w.T if vocab_major else w).astype(x.dtype)  # (B,S,V)
        else:
            out = head_logits(x, w, vocab_major=vocab_major, block_v=block_v)
        if bias is not None:
            out = out + bias.astype(x.dtype)
        return _times(out, cfg.head_mult)

    # ------------------------------------------------------------------
    def _hidden_aux(self, params, input_ids, positions=None, train=False, rng=None,
                    pld_theta=None, ltd_keep=None, attention_mask=None,
                    token_type_ids=None):
        """The trunk's output, which the head reads, and the auxiliary loss."""
        if self.config.layer_types is not None:
            raise NotImplementedError(
                "a layer_types model is served from the paged pool and its "
                "state slots (forward_paged): it has no full-sequence or "
                "training path yet")
        B, S = input_ids.shape
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        # first floating leaf decides compute dtype (skips int8 WOQ codes)
        dtype = next(
            (l.dtype for l in jax.tree.leaves(params)
             if jnp.issubdtype(l.dtype, jnp.floating)), jnp.float32)
        mask_bias = None
        if attention_mask is not None:  # encoder padding: mask keys out
            mask_bias = jnp.where(attention_mask.astype(bool), 0.0, -1e30
                                  )[:, None, None, None, :]
        with jax.named_scope("embed"):
            x = self._embed(params, input_ids, positions, dtype,
                            token_type_ids=token_type_ids)
        x = self._constraint(x, self._act_spec(True))
        if ltd_keep is not None and train:
            if pld_theta is not None:
                raise ValueError(
                    "random-LTD and progressive layer drop cannot be combined "
                    "(the LTD trunk has no stochastic-depth path)")
            if rng is None:
                rng = jax.random.PRNGKey(0)
            x, aux = self._trunk_ltd(params, x, positions, rng, int(ltd_keep),
                                     attn_mask=attention_mask)
        else:
            x, aux = self._trunk(params, x, positions, rng, train,
                                 pld_theta=pld_theta, attn_mask_bias=mask_bias)
        return x, aux

    def logits(self, params, input_ids, positions=None, train=False, rng=None,
               attention_mask=None, token_type_ids=None):
        x, _ = self._hidden_aux(params, input_ids, positions, train, rng,
                                attention_mask=attention_mask,
                                token_type_ids=token_type_ids)
        with jax.named_scope("lm_head_loss"):
            return self._head(params, x)

    def apply(self, params, batch, train=True, rng=None):
        """Next-token LM loss over the batch (engine protocol).

        ``batch``: dict with ``input_ids`` (B,S) int32 and optional ``labels``
        (shifted internally when absent; -100 = ignore), or a bare (B,S) array,
        or an (input_ids, labels) tuple.
        """
        pld_theta = None
        ltd_keep = None
        if isinstance(batch, dict):
            input_ids = batch["input_ids"]
            labels = batch.get("labels")
            positions = batch.get("positions")
            if self.config.progressive_layer_drop:
                pld_theta = batch.get("pld_theta")
            if self.config.random_ltd:
                # static python int injected by the engine's variant machinery
                ltd_keep = batch.get("ltd_keep")
        elif isinstance(batch, (tuple, list)):
            input_ids, labels = batch
            positions = None
        else:
            input_ids, labels, positions = batch, None, None

        attention_mask = token_type_ids = None
        if isinstance(batch, dict):
            attention_mask = batch.get("attention_mask")
            token_type_ids = batch.get("token_type_ids")
        x, aux = self._hidden_aux(params, input_ids, positions=positions,
                                  train=train, rng=rng, pld_theta=pld_theta,
                                  ltd_keep=ltd_keep,
                                  attention_mask=attention_mask,
                                  token_type_ids=token_type_ids)
        if labels is None:
            if not self.config.causal:
                raise ValueError(
                    "encoder (causal=False) models need explicit labels — "
                    "next-token shifting only applies to causal LMs")
            labels = jnp.concatenate(
                [input_ids[:, 1:], jnp.full_like(input_ids[:, :1], -100)], axis=1
            )
        with jax.named_scope("lm_head_loss"):
            x, w, bias, vocab_major = self._head_operands(params, x)
            mask = labels != -100
            nll = head_nll(x, w, jnp.where(mask, labels, 0), bias,
                           vocab_major=vocab_major) * mask
            loss = jnp.sum(nll) / jnp.maximum(jnp.sum(mask), 1)
        if self.config.num_experts > 0:
            loss = loss + self.config.moe_aux_loss_coef * aux
        return loss

    # ------------------------------------------------------------------
    # inference: prefill + single-token decode with a static KV cache
    # ------------------------------------------------------------------
    def init_kv_cache(self, batch_size: int, max_len: int, dtype=jnp.bfloat16):
        cfg = self.config
        # a looped model's step ``t`` owns layers ``t * num_layers`` on
        shape = (cfg.loop_steps * cfg.num_layers, batch_size, max_len,
                 cfg.kv_heads, cfg.head_dim)
        return (jnp.zeros(shape, dtype), jnp.zeros(shape, dtype))

    def _trunk_with_cache(self, params, input_ids, kv_cache, cache_index, positions):
        B, S = input_ids.shape
        if positions is None:
            positions = cache_index + jnp.broadcast_to(
                jnp.arange(S, dtype=jnp.int32), (B, S)
            )
        dtype = kv_cache[0].dtype
        x = self._embed(params, input_ids, positions, dtype)

        def body(h, layer):
            blk, ck, cv = layer
            y, new_kv, _ = self._block(
                h, blk, positions=positions, rng=None, train=False,
                kv_cache=(ck, cv), cache_index=cache_index,
            )
            return y, new_kv

        L = self.config.num_layers

        def walk(x, t):
            # step ``t`` of a looped model has cache layers of its own
            caches = tuple(c[t * L:(t + 1) * L] for c in kv_cache) \
                if self.config.looped else tuple(kv_cache)
            return jax.lax.scan(body, x, (params["blocks"], *caches))

        x, steps = self._looped(params, x, walk)
        if len(steps) == 1:
            return x, steps[0]
        return x, tuple(jnp.concatenate(c) for c in zip(*steps))

    # ------------------------------------------------------------------
    # paged (blocked) KV cache — reference inference/v2 BlockedKVCache path
    # ------------------------------------------------------------------
    def init_kv_pool(self, num_blocks: int, block_size: int, dtype=jnp.bfloat16):
        """The blocked KV pool: ONE array (``pool_layers``, kvh, NB, BS, row)
        whose rows are ``[k_t | v_t]`` or, under latent attention,
        ``[c_kv | k_rope]`` with one pool head (layout and access:
        ``ops/transformer/paged_attention.py``; the widths and the layer
        count: ``TransformerConfig.kv_row``, ``pool_layers``); block 0 is the
        reserved trash block that masked/padded writes land in. A model with
        a bounded class of blocks (``class_layers``) takes ``num_blocks``
        as {class: count} and gives {class: such an array}, block 0 of each
        its trash block."""
        from ..ops.transformer.paged_attention import init_pool

        cfg = self.config
        if "sparse_attn" in cfg.cache_kinds \
                and block_size != cfg.sparse_block_size:
            raise ValueError(
                f"a pool block of {block_size} tokens is not the block the "
                f"model's sparse layers select by ({cfg.sparse_block_size}: "
                "sparse_block_size, the published sparse_config.block_size)")
        if cfg.bounded_cache:
            # a pool a class of blocks: the classes differ in layers and in
            # blocks
            classes = cfg.class_layers
            if not isinstance(num_blocks, dict) \
                    or set(num_blocks) != set(classes):
                raise ValueError(
                    f"num_blocks {num_blocks!r}: a model with window layers "
                    f"takes a count for each class of {sorted(classes)}")
            return {c: init_pool(layers, cfg.pool_heads, num_blocks[c],
                                 block_size, cfg.kv_row, dtype)
                    for c, layers in classes.items()}
        return init_pool(cfg.pool_layers, cfg.pool_heads, num_blocks, block_size,
                         cfg.kv_row, dtype)

    @property
    def segment_tile(self) -> int:
        """Rows of one chunk-segment tile of the paged program (1: the model
        takes a prefill chunk as one-token rows like any other)."""
        cfg = self.config
        # one for all the model's kinds: a step is tiled once
        (tile,) = {rec.tile(cfg) for rec in cfg.kinds}
        return tile

    @property
    def step_counts(self) -> Tuple[str, ...]:
        """Names of the int32 counts ``forward_paged(moe_stats=True)`` returns
        behind its logits: they become attrs of ``engine.dispatch``."""
        cfg = self.config
        own = tuple(dict.fromkeys(
            name for rec in cfg.kinds for name in rec.counts))
        if own or not cfg.holds_experts:
            return own
        return ("moe_rows", "moe_rows_max") + (
            ("moe_zero_picks",) if cfg.moe_zero_experts else ())

    def init_state_cache(self, max_seqs: int, max_seq_len: int,
                         dtype=jnp.bfloat16) -> Dict[str, Any]:
        """What the model keeps a sequence beside the paged pool: one slot
        array a layer group (``TransformerConfig.type_runs``), a slot a
        sequence and slot 0 the trash slot. A group of linear layers: its
        lightning states (layers, 1 + max_seqs, heads, hd, hd) float32
        (ops/transformer/linear_attention.py); a group of sparse layers: the
        selector's compressed keys (layers, 1 + max_seqs, kv heads, keys,
        hd) (ops/transformer/sparse_attention.py). Empty for a model whose
        every layer keeps KV blocks and nothing else."""
        cfg = self.config
        return {key: rec.slots(cfg, n, max_seqs, max_seq_len, dtype)
                for (key, _, n, _), rec in zip(cfg.type_runs, cfg.kinds)
                if rec.slots}

    def forward_paged(self, params, input_ids, kv_pool, tables, starts,
                      logit_rows=None, seg_from=None, moe_stats=False,
                      rows_apart=False, state=None, row_slots=None,
                      window=None):
        """Run T rows against the blocked pool: the one paged forward of
        every served architecture. Embed; then, for each layer group of the
        model (``TransformerConfig.type_runs``), one scan of the group's
        kind over its stacked leaves; then the head.

        tables: (T, MAXB) pool block ids per row (0-padded); starts: (T,)
        each row's position. Rows are one token each, (T, 1) (a ``full``
        attention model also takes (T, S) segments, the last position's
        logits returned). Returns ((T, V) logits, new pool). With
        ``logit_rows`` ((R,) int32), only those rows are projected through
        the vocab head — returns ((R, V), new pool) — so a ragged batch pays
        for R logits, not T (reference ``ragged_ops/logits_gather``).

        ``seg_from`` (static; models with a ``segment_tile``): rows from it
        on are chunk segments in tiles, and ``rows_apart`` (static) the
        builder's promise about its rows: both as :class:`PagedStep` says,
        which is built here, once, and is what every layer reads the step
        from. ``state``, ``row_slots`` (``layer_types`` models): the slot
        arrays of :meth:`init_state_cache` and each row's slot in them ((T,)
        int32, 0 for a padding row); the new ``state`` is returned behind the
        pool. ``window`` (a model with a bounded class of blocks,
        ``TransformerConfig.bounded_cache``): (tables (T, WB), base (T,)),
        each row's table of the window class and the position its first
        entry starts at; ``kv_pool`` is then {class: pool} and so is the
        result, ``tables`` the full class's. ``moe_stats``: also return,
        last, the int32 counts
        :attr:`step_counts` names, where it names any: of held experts
        (rows, rows_max): the (token, choice) pairs that landed on held
        experts summed over the layers, and the busiest held expert's; with
        ``moe_zero_experts`` behind them the live rows' choices of identity
        experts, summed over the layers.

        A kind is a function ``(h, blk, caches, layer, pool_layer, step,
        experts) -> (y, caches, counts or None)``: ``caches`` holds the pool
        (``pool``) if the group's layers keep KV blocks and the group's slot
        array (``own``) if they keep a state slot, ``layer`` counts the
        group's layers and ``pool_layer`` the pool's. A new kind is such a
        function and its record in ``LAYER_KINDS``."""
        cfg = self.config
        S = input_ids.shape[1]
        if S != 1 and not all(rec.wide_rows for rec in cfg.kinds):
            raise ValueError("the paged path of a latent-attention or a "
                             "layer_types model takes one-token rows")
        if cfg.holds_state and (state is None or row_slots is None):
            raise ValueError("a layer_types model's paged path needs its "
                             "slot arrays (init_state_cache) and row_slots")
        if cfg.bounded_cache and window is None:
            raise ValueError("a model with window layers needs its window "
                             "class's tables and base (window=)")
        step = PagedStep.of(tables, starts, S, seg_from=seg_from,
                            tile=self.segment_tile, rows_apart=rows_apart,
                            slots=row_slots,
                            window=None if window is None
                            else (*window, cfg.sliding_window))
        # a pool a class of blocks; one class, one array
        pools = dict(kv_pool) if cfg.bounded_cache else {"full": kv_pool}
        with jax.named_scope("embed"):
            x = self._embed(params, input_ids, step.positions,
                            next(iter(pools.values())).dtype)
        state = dict(state or {})
        names = self.step_counts
        tally = jnp.zeros((len(names),), jnp.int32) if names else None
        pool_layer = dict.fromkeys(pools, 0)  # pool layers before this group
        # a group's scan carries the pool if its layers keep KV blocks, its
        # own slot array if they keep a state slot, and nothing else: an
        # array carried through a scan that does not touch it, or sliced by
        # a constant layer, was copied whole by XLA, once a dispatch. The
        # pool rides as a carry, one donated buffer from entry to exit,
        # written and read where it lies. "kv_carry" is the scans' own ops on
        # the device: what they do to their carried arrays and to the stacked
        # leaves they slice (a layer's matrix materialised out of the stack
        # and relaid reads here, not under the layer)
        # a looped model walks its groups ``loop_steps`` times, each walk a
        # scan of its own behind the last: the pool stays one carry from scan
        # to scan and the stacked leaves are scanned where they lie, where an
        # outer loop around the scans would carry both through a loop that
        # does not touch them. ``pool_layer`` counts on from walk to walk, so
        # step ``t`` reads and writes the cache layers from ``t *`` one
        # walk's on
        def walk(x, t):
            nonlocal tally
            for (key, _, n, per), rec in zip(cfg.type_runs, cfg.kinds):
                leaves = params[key]
                # held experts stay out of the scanned leaves: the grouped
                # product indexes them by (layer, expert) where they lie, so
                # no layer of experts is sliced out of the stack (weight-only-
                # quantized experts are code + scale leaves: they ride the
                # scan and are dequantised a layer at a time)
                experts = None
                if cfg.holds_experts and all(k in leaves for k in EXPERT_LEAVES):
                    experts = {k: leaves[k] for k in EXPERT_LEAVES}
                    leaves = {k: v for k, v in leaves.items()
                              if k not in EXPERT_LEAVES}
                caches = {"own": state[key]} if key in state else {}
                cls = rec.block_class
                if per:
                    caches["pool"] = pools[cls]

                def body(carry, blk, fn=rec.layer(self), base=pool_layer[cls],
                         per=per, experts=experts):
                    h, caches, l, tally = carry
                    y, caches, counts = fn(h, blk, caches, l, base + per * l,
                                           step, experts)
                    return (y, caches, l + 1, self._tally(tally, counts)), None

                (x, caches, _, tally), _ = jax.lax.scan(
                    body, (x, caches, jnp.int32(0), tally), leaves)
                if "own" in caches:
                    state[key] = caches["own"]
                if per:
                    pools[cls] = caches["pool"]
                    pool_layer[cls] += per * n
            return x, None

        with jax.named_scope("kv_carry"):
            x, _ = self._looped(params, x, walk)
        kv_pool = pools if cfg.bounded_cache else pools["full"]
        # only the last position is projected (and of those rows, only
        # ``logit_rows``)
        x_last = x[:, S - 1]
        if logit_rows is not None:
            x_last = x_last[logit_rows]
        with jax.named_scope("lm_head_loss"):
            lg = self._head(params, x_last[:, None])[:, 0]
        out = (lg, kv_pool, state) if cfg.holds_state else (lg, kv_pool)
        return out + (tally,) if moe_stats and names else out

    def _tally(self, total, counts):
        """The step's counts with one layer's taken in: a count named
        ``*_max`` is the largest any layer saw, the others are sums over the
        layers."""
        if counts is None:
            return total
        peak = [name.endswith("_max") for name in self.step_counts]
        if not any(peak):
            return total + counts
        return jnp.stack([jnp.maximum(total[i], counts[i]) if p
                          else total[i] + counts[i]
                          for i, p in enumerate(peak)])

    def _full_layer(self, h, blk, caches, layer, pool_layer, step, experts):
        """The ``full`` kind: :meth:`_block`'s paged branch."""
        y, pool, _ = self._block(
            h, blk, positions=step.positions, rng=None, train=False,
            paged=(caches["pool"], pool_layer, step.tables), step=step)
        return y, {"pool": pool}, None

    def _latent_layer(self, block, h, blk, caches, layer, pool_layer, step,
                      experts):
        """The ``latent`` and ``scmoe`` kinds, around their ``block``
        (:meth:`_block_mla`, :meth:`_block_scmoe`)."""
        y, pool, counts = block(
            h, blk, positions=step.positions, step=step,
            paged=(caches["pool"], pool_layer, step.tables),
            experts=None if experts is None else (experts, layer))
        return y, {"pool": pool}, counts

    # ------------------------------------------------------------------
    # ``layer_types`` models: one small mixer a type, the rest shared
    # ------------------------------------------------------------------
    def _typed_layer(self, mixer, x, blk, caches, layer, pool_layer, step,
                     experts=None, scope=None):
        """One layer of a ``layer_types`` model on (T, 1, H): ``x + a M(N(x))``
        then ``+ a F(N(.))``, ``a`` the muP residual scale; ``mixer`` is the
        layer type's (:meth:`_sparse_mixer`, :meth:`_linear_mixer`,
        :meth:`_attn_mixer`), the norms, the gate, ``wo`` and the
        feed-forward (:meth:`_feed_forward`: dense, or the held experts
        ``experts`` = their stacked leaves) are shared; with ``post_norms``
        each sublayer's output is normed before it joins the stream. Behind
        ``mixer`` a kind of :meth:`forward_paged`. ``scope``: a name the
        attention sublayer is traced under beside ``attn`` (the layer types
        whose device time is told apart, ``utils/tracing.py``)."""
        cfg = self.config
        T, dt, a = x.shape[0], x.dtype, cfg.residual_scale
        nh, hd = cfg.num_heads, cfg.head_dim
        blk = _dequant_woq(blk, dt)
        once = jax.lax.optimization_barrier

        def heads(w, name=None):
            """``h @ w`` as heads, each RMS-normed under ``name`` (the
            barrier: a norm over a product reads it twice, and XLA would
            stream the matrix for each, :meth:`_mla_attention`; it also keeps
            the product's layout from being chosen by its consumer, which
            cost a transposed copy of the matrix a layer)."""
            y = once(h @ blk[w].astype(dt)).reshape(T, -1, hd)
            return _norm(y, blk[name], None, "rmsnorm", cfg.norm_eps) \
                if name and cfg.qk_norm else y

        def post(y, name):
            return self._post_norm(y, blk[name], True) if cfg.post_norms else y

        with jax.named_scope("attn"), (
                jax.named_scope(scope) if scope else contextlib.nullcontext()):
            h = _norm(x[:, 0], blk["ln1_scale"], None, "rmsnorm", cfg.norm_eps)
            q, k = heads("wq", "q_norm_scale"), heads("wk", "k_norm_scale")
            o, caches, counts = mixer(q, k, heads("wv"), blk, caches, layer,
                                      pool_layer, step)
            o = o.reshape(T, nh * hd).astype(dt)
            if cfg.attn_output_gate:
                o = o * jax.nn.sigmoid(once(h @ blk["w_ogate"].astype(dt)))
            x = once(x + a * post(o @ blk["wo"].astype(dt),
                                  "post_attn_scale")[:, None])
        with jax.named_scope("mlp"):
            h2 = _norm(x, blk["ln2_scale"], None, "rmsnorm", cfg.norm_eps)
            f, stats = self._feed_forward(
                h2, blk, None if experts is None else (experts, layer), step)
            x = x + a * post(f, "post_mlp_scale")
        return x, caches, counts if stats is None else stats

    def _attn_mixer(self, window, rotary, q, k, v, blk, caches, layer,
                    pool_layer, step):
        """GQA attention of this step's rows over their class of the paged
        pool, a ``window`` layer (a query sees the last ``sliding_window``
        tokens; the window class's tables, which count from the first block
        a sequence still holds: ``PagedStep.window``) or a full one (the
        whole context), with ``rotary`` on q and k or no positional term
        (afmoe: the window layers rotate, the full ones do not; a
        ``hybrid_ssm`` layer is full with rotary). A decode
        round (rows apart) is one kernel call a layer, the new rows written
        on the way; a mixed step writes its rows by the scatter, its
        one-token rows go through the kernel and its tiles through the
        gather path (``paged_attention.attend_tiles``)."""
        from ..ops.transformer import paged_attention as pa

        cfg = self.config
        pool = caches["pool"]
        T, nh, hd = q.shape
        cut, end, tile = step.cut, step.end, step.tile
        if rotary:
            q, k = (a[:, 0] for a in _rope(q[:, None], k[:, None],
                                           step.positions, hd, cfg.rope_theta))
        if window:
            tables, starts, limits, first = step.window
        else:
            tables, starts, limits, first = (step.tables, step.starts,
                                             step.limits, None)
        fold = (cut == T and step.rows_apart and pa.kernels_wanted()
                and pa.writes_live_rows(pool))
        if fold:
            with jax.named_scope("paged_attn"):
                o, pool = pa.paged_decode(
                    q.reshape(T, nh * hd), pool, pool_layer, tables, limits,
                    new_rows=(k.reshape(T, -1), v.reshape(T, -1)), first=first)
            return o.reshape(T, nh, hd), {"pool": pool}, None
        with jax.named_scope("kv_write"):
            pool = pa.write_rows(pool, pool_layer, tables, starts[:, None],
                                 k[:, None], v[:, None],
                                 rows_apart=step.rows_apart)
        with jax.named_scope("paged_attn"):
            o = pa.attend_rows(
                q[:cut], pool, pool_layer, tables[:cut], limits[:cut],
                first=None if first is None else first[:cut])
            if cut < end:
                tiles = slice(cut, end, tile)   # the first row of each tile
                o2 = pa.attend_tiles(
                    q[cut:end].reshape(-1, tile, nh, hd), pool, pool_layer,
                    tables[tiles], starts[tiles],
                    window=cfg.sliding_window if window else 0)
                o = jnp.concatenate([o, o2.reshape(-1, nh, hd)])
        o = jnp.pad(o, ((0, T - o.shape[0]), (0, 0), (0, 0)))
        return o, {"pool": pool}, None

    def _hybrid_layer(self, x, blk, caches, layer, pool_layer, step,
                      experts=None):
        """One ``hybrid_ssm`` layer on (T, 1, H), a kind of
        :meth:`forward_paged`: attention and the SSD mixer side by side off
        one norm, then the feed-forward, each muP scalar where the published
        forward has it::

            h = N(x)
            x = x + Attn(h * attn_in) * attn_out + SSM(h) * ssm_out
            x = x + F(N(x))

        The attention is :meth:`_attn_mixer` (full context, rotary; keys times
        ``key_mult``) over the layer's KV blocks, the mixer :meth:`_ssm_mixer`
        on the layer's slot (state and window), the feed-forward
        :meth:`_feed_forward`."""
        cfg = self.config
        T, dt = x.shape[0], x.dtype
        nh, hd = cfg.num_heads, cfg.head_dim
        blk = _dequant_woq(blk, dt)
        once = jax.lax.optimization_barrier
        with jax.named_scope("attn"):
            h = _norm(x[:, 0], blk["ln1_scale"], None, "rmsnorm", cfg.norm_eps)
            ha = _times(h, cfg.attn_in_mult)
            # behind barriers, as ``_typed_layer``'s: the products keep
            # their own layout and the matrix is read where it lies
            q, k, v = (once(ha @ blk[w].astype(dt)).reshape(T, -1, hd)
                       for w in ("wq", "wk", "wv"))
            o, kept, _ = self._attn_mixer(
                False, True, q, _times(k, cfg.key_mult), v, blk,
                {"pool": caches["pool"]}, layer, pool_layer, step)
            a = _times(o.reshape(T, nh * hd).astype(dt)
                       @ blk["wo"].astype(dt), cfg.attn_out_mult)
            m, own = self._ssm_mixer(h, blk, caches["own"], layer, step)
            x = once(x + (a + m)[:, None])
        with jax.named_scope("mlp"):
            h2 = _norm(x, blk["ln2_scale"], None, "rmsnorm", cfg.norm_eps)
            with jax.named_scope("dense_ffn"):
                f, _ = self._feed_forward(h2, blk, None, step)
            x = x + f
        return x, {"pool": kept["pool"], "own": own}, None

    def _ssm_mixer(self, h, blk, own, layer, step):
        """The SSD (Mamba-2) mixer of this step's rows on their sequences'
        slots (``own``: ``ssm`` the float32 states, ``conv`` the
        convolution's windows), ``h`` (T, H) the normed stream::

            [z | xBC | dt] = (W_in (h * ssm_in)) * zone multipliers
            [x | B | C]    = silu(conv(xBC) + b)         a window of ssm_conv
            dt = softplus(dt + dt_bias);  A = -exp(a_log)
            S  = exp(dt A) S + B^T (dt x);   y = C S + D x
            out = W_out N_groups(y * silu(z)) * ssm_out

        The one-token rows go through ``conv_rows`` / ``decode_rows``, the
        tiles through ``conv_tiles`` / ``chunk_tiles``
        (ops/transformer/linear_attention.py), the decay ``dt A`` their
        operand, B and C a group's keys and queries, ``dt x`` the value."""
        from ..ops.transformer import linear_attention as la

        cfg = self.config
        T, dt = h.shape[0], h.dtype
        nh, hd, G, N = (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups,
                        cfg.ssm_state)
        inner, ch = cfg.ssm_inner, cfg.ssm_conv_channels
        cut, end, tile = step.cut, step.end, step.tile
        slots, fresh = step.slots, step.starts == 0
        tiles = slice(cut, end, tile)
        f32 = jnp.float32

        rows_then_tiles = partial(_rows_then_tiles, step)

        with jax.named_scope("ssm_mixer"):
            p = jax.lax.optimization_barrier(
                _times(h, cfg.ssm_in_mult) @ blk["ssm_w_in"].astype(dt))
            zones = np.repeat(np.asarray(cfg.ssm_zone_mults, np.float32),
                              [inner, inner, G * N, G * N, nh])
            if not np.all(zones == 1.0):
                p = (p.astype(f32) * zones).astype(dt)
            z, xbc, dtr = p[:, :inner], p[:, inner:inner + ch], p[:, -nh:]
            taps, bias = blk["ssm_conv_scale"], blk["ssm_conv_bias"]
            y, conv = rows_then_tiles(
                lambda x: la.conv_rows(own["conv"], layer, slots[:cut], x,
                                       taps, bias, fresh[:cut]),
                lambda conv, x: la.conv_tiles(
                    conv, layer, slots[tiles], step.tile_counts, x, taps,
                    bias, fresh[tiles]), xbc)
            xbc = jax.nn.silu(y).astype(dt)
            xs = xbc[:, :inner].reshape(T, nh, hd).astype(f32)
            B, C = (xbc[:, inner + i * G * N:inner + (i + 1) * G * N]
                    .reshape(T, G, N) for i in range(2))
            step_dt = jax.nn.softplus(dtr.astype(f32)
                                      + blk["dt_bias"].astype(f32))
            decay = step_dt * -jnp.exp(blk["a_log"].astype(f32))   # <= 0
            y, ssm = rows_then_tiles(
                lambda *a: la.decode_rows(
                    own["ssm"], layer, slots[:cut], *a[:3], fresh[:cut],
                    log_decay=a[3], scope="ssm_scan"),
                lambda ssm, *a: la.chunk_tiles(
                    ssm, layer, slots[tiles], step.tile_counts, *a[:3],
                    fresh[tiles], log_decay=a[3], scope="ssm_scan"),
                C, B, xs * step_dt[:, :, None], decay)
            y = y + blk["ssm_d_scale"].astype(f32)[None, :, None] * xs
            y = y.reshape(T, inner) * jax.nn.silu(z.astype(f32))
            # the gated norm, a group at a time (norm_before_gate false)
            y = _norm(y.reshape(T, G, inner // G),
                      blk["ssm_norm_scale"].reshape(G, -1), None, "rmsnorm",
                      cfg.norm_eps).reshape(T, inner).astype(dt)
            out = _times(y @ blk["ssm_w_out"].astype(dt), cfg.ssm_out_mult)
        return out, {"ssm": ssm, "conv": conv}

    def _delta_layer(self, x, blk, caches, layer, pool_layer, step,
                     experts=None):
        """One ``delta_attn`` (KDA) layer on (T, 1, H), a kind of
        :meth:`forward_paged`, on the layer's slot (``own``: ``state`` the
        float32 states, ``conv`` the convolution's windows)::

            h = N(x);  [q | k | v] = silu(conv(h [W_q | W_k | W_v]))
            q = q / |q| * d^-1/2;  k = k / |k|                      a head
            a = floor * sigmoid(exp(a_log) * (h W_f + dt_bias))     a channel
            b = sigmoid(h W_b)                                      a head
            S' = Diag(exp(a)) S;  S = S' + b k^T (v - k S');  o = q S
            x = x + W_o (N_head(o) * sigmoid(h W_g))

        then the feed-forward (:meth:`_feed_forward`: dense, or the held
        experts). The one-token rows go through ``conv_rows`` /
        ``decode_rows``, the tiles through ``conv_tiles`` / ``chunk_tiles``
        (ops/transformer/linear_attention.py: ``beta`` selects the delta
        rule)."""
        from ..ops.transformer import linear_attention as la

        cfg = self.config
        T, dt, f32 = x.shape[0], x.dtype, jnp.float32
        nh, hd = cfg.num_heads, cfg.head_dim
        own = caches["own"]
        cut, end, tile = step.cut, step.end, step.tile
        slots, fresh = step.slots, step.starts == 0
        tiles = slice(cut, end, tile)
        blk = _dequant_woq(blk, dt)
        once = jax.lax.optimization_barrier
        rows_then_tiles = partial(_rows_then_tiles, step)

        def unit(a):
            """Each head's row over its L2 norm."""
            return a * jax.lax.rsqrt(
                jnp.sum(a * a, axis=-1, keepdims=True) + cfg.norm_eps)

        with jax.named_scope("attn"), jax.named_scope("delta_attn"):
            h = _norm(x[:, 0], blk["ln1_scale"], None, "rmsnorm", cfg.norm_eps)
            # behind barriers, as ``_typed_layer``'s: the products keep
            # their own layout and each matrix is read where it lies
            qkv = jnp.concatenate(
                [once(h @ blk[w].astype(dt)) for w in ("wq", "wk", "wv")], -1)
            taps = blk["kda_conv_scale"]
            y, conv = rows_then_tiles(
                lambda a: la.conv_rows(own["conv"], layer, slots[:cut], a,
                                       taps, None, fresh[:cut]),
                lambda conv, a: la.conv_tiles(
                    conv, layer, slots[tiles], step.tile_counts, a, taps,
                    None, fresh[tiles]), qkv)
            q, k, v = (a.reshape(T, nh, hd) for a in jnp.split(
                jax.nn.silu(y).astype(dt).astype(f32), 3, axis=-1))
            q, k = unit(q) * hd ** -0.5, unit(k)
            decay = cfg.kda_log_floor * jax.nn.sigmoid(
                jnp.exp(blk["a_log"].astype(f32))[None, :, None] * (
                    once(h @ blk["w_decay"].astype(dt)).astype(f32)
                    + blk["dt_bias"].astype(f32)).reshape(T, nh, hd))
            beta = jax.nn.sigmoid(
                once(h @ blk["w_beta"].astype(dt)).astype(f32))
            o, state = rows_then_tiles(
                lambda *a: la.decode_rows(
                    own["state"], layer, slots[:cut], *a[:3], fresh[:cut],
                    log_decay=a[3], scope="delta_scan", beta=a[4]),
                lambda state, *a: la.chunk_tiles(
                    state, layer, slots[tiles], step.tile_counts, *a[:3],
                    fresh[tiles], log_decay=a[3], scope="delta_scan",
                    beta=a[4]),
                q, k, v, decay, beta)
            o = _norm(o, blk["o_norm_scale"], None, "rmsnorm", cfg.norm_eps)
            if "w_ogate" in blk:         # one scalar a head
                o = o * jax.nn.sigmoid(once(
                    h @ blk["w_ogate"].astype(dt)).astype(f32))[..., None]
            x = once(x + (o.reshape(T, nh * hd).astype(dt)
                          @ blk["wo"].astype(dt))[:, None])
        with jax.named_scope("mlp"):
            h2 = _norm(x, blk["ln2_scale"], None, "rmsnorm", cfg.norm_eps)
            if "moe_wg" in blk:
                f, stats = self._feed_forward(
                    h2, blk, None if experts is None else (experts, layer),
                    step)
            else:
                with jax.named_scope("dense_ffn"):
                    f, stats = self._feed_forward(h2, blk, None, step)
            x = x + f
        return x, {"own": {"state": state, "conv": conv}}, stats

    def _sparse_mixer(self, q, k, v, blk, caches, layer, pool_layer, step):
        """Block-sparse attention (ops/transformer/sparse_attention.py) of
        this step's rows: their keys and values into the pool, the compressed
        keys they complete into the sequence's slot, then each one-token row
        over its chosen blocks through the decode kernel and each tile by
        masked attention over its sequence's context."""
        from ..ops.transformer import paged_attention as pa
        from ..ops.transformer import sparse_attention as sa

        cfg = self.config
        pool, ck = caches["pool"], caches["own"]
        spec, scale = cfg.sparse_spec, cfg.head_dim ** -0.5
        tables, starts, slots = step.tables, step.starts, step.slots
        cut, end, tile = step.cut, step.end, step.tile
        T, nh, hd = q.shape
        tiles = slice(cut, end, tile)       # the first row of each tile
        with jax.named_scope("kv_write"):
            pool = pa.write_rows(pool, pool_layer, tables, step.positions,
                                 k[:, None], v[:, None],
                                 rows_apart=step.rows_apart)
        with jax.named_scope("sparse_select"):
            ck = sa.write_keys(ck, pool, pool_layer, layer, tables[:cut],
                               slots[:cut], starts[:cut],
                               step.live[:cut].astype(jnp.int32), spec, 1)
            if cut < end:
                ck = sa.write_keys(ck, pool, pool_layer, layer, tables[tiles],
                                   slots[tiles], starts[tiles],
                                   step.tile_counts, spec, tile)
        o, counts = sa.decode_rows(q[:cut], pool, pool_layer, tables[:cut],
                                   ck[layer, slots[:cut]], step.limits[:cut],
                                   spec, scale)
        if cut < end:
            o2 = sa.tile_rows(q[cut:end].reshape(-1, tile, nh, hd), pool,
                              pool_layer, tables[tiles],
                              ck[layer, slots[tiles]], starts[tiles], spec,
                              scale)
            o = jnp.concatenate([o, o2.reshape(-1, nh, hd)])
        o = jnp.pad(o, ((0, T - o.shape[0]), (0, 0), (0, 0)))
        return o, {"pool": pool, "own": ck}, counts

    def _linear_mixer(self, q, k, v, blk, caches, layer, pool_layer, step):
        """Lightning attention (ops/transformer/linear_attention.py) of this
        step's rows on the sequences' state slots: rotary on q and k, the
        recurrence row by row for the one-token rows and in its blocked form
        for the tiles, then the output norm over all heads' values."""
        from ..ops.transformer import linear_attention as la

        cfg = self.config
        lin = caches["own"]
        starts, slots = step.starts, step.slots
        cut, end, tile = step.cut, step.end, step.tile
        T, nh, hd = q.shape
        tiles = slice(cut, end, tile)
        q, k = (a[:, 0] for a in _rope(q[:, None], k[:, None],
                                       step.positions, hd, cfg.rope_theta))
        q = q * jnp.asarray(hd ** -0.5, q.dtype)
        fresh = starts == 0
        o, lin = la.decode_rows(lin, layer, slots[:cut], q[:cut], k[:cut],
                                v[:cut], fresh[:cut])
        if cut < end:
            tiled = [a[cut:end].reshape(-1, tile, nh, hd) for a in (q, k, v)]
            o2, lin = la.chunk_tiles(lin, layer, slots[tiles],
                                     step.tile_counts, *tiled, fresh[tiles])
            o = jnp.concatenate([o, o2.reshape(-1, nh, hd)])
        o = jnp.pad(o, ((0, T - o.shape[0]), (0, 0), (0, 0)))
        with jax.named_scope("linear_attn"):
            o = _norm(o.reshape(T, nh * hd), blk["o_norm_scale"], None,
                      "rmsnorm", cfg.norm_eps)
        return o, {"own": lin}, None

    def decode_paged_multi(self, params, kv_pool, toks, tables, starts, k: int,
                           sampling=None):
        """Fused K-step decode against the blocked pool: a single
        ``lax.scan`` over ``k`` rounds, each running the length-1
        ``forward_paged`` for all rows and feeding the on-device selection
        back as the next round's input — one dispatch and one (B, k) int32
        transfer per k tokens instead of k of each (the per-token host
        round-trip is steady-state serving's latency floor).

        ``toks`` (B,) int32: each row's last sampled token, written at
        position ``starts[r]`` in round 0. ``tables`` (B, MAXB) block tables
        (all-zero rows = inactive padding, writes land in trash block 0) and
        must already cover positions ``starts .. starts+k-1``. Returns
        ``((B, k) sampled tokens, new pool)``. Each round computes exactly
        what the ragged decode-round program computes per row (same S=1
        ``forward_paged``, same selection), so a k-step fused decode is
        bitwise identical to k single steps — under greedy AND under
        sampling, because the per-position key is folded INSIDE the loop.

        ``sampling``: ``None`` = greedy argmax (the legacy program,
        unchanged); else ``(seeds, temps, top_ks, top_ps, bias)`` per-row
        arrays — (B,) i32/f32/i32/f32 and a (B, V) additive bias — and
        each round selects via :func:`sample_or_argmax` with the
        counter-based key for absolute position ``pos + 1`` (the produced
        token's index; docs/SAMPLING.md)."""

        def round_(carry, _):
            pool, t, pos = carry
            lg, pool = self.forward_paged(params, t[:, None], pool, tables, pos)
            if sampling is None:
                nxt = jnp.argmax(lg, axis=-1).astype(jnp.int32)
            else:
                seeds, temps, top_ks, top_ps, bias = sampling
                nxt = sample_or_argmax(lg + bias, seeds, pos + 1,
                                       temps, top_ks, top_ps)
            return (pool, nxt, pos + 1), nxt

        (kv_pool, _, _), ys = jax.lax.scan(
            round_, (kv_pool, toks, starts), None, length=int(k))
        return ys.T, kv_pool  # (B, k)

    def verify_paged_multi(self, params, kv_pool, segs, tables, starts,
                           sampling=None):
        """Speculative-decoding batch verification against the blocked pool
        (docs/SERVING.md): run B sequences' K-token segments — each row's
        last sampled token followed by K−1 draft tokens — in ONE forward and
        return the greedy argmax at EVERY position, ``(B, K)``.

        Each of the B·K tokens becomes its own length-1 row of the same
        ``forward_paged`` shape the ragged/fused programs use: the segment's
        K/V are scattered into the pool before attention, so position ``j``
        attends to positions ``< j`` of the same dispatch through the shared
        block table (exactly how multi-row prefill chunks compose), and the
        per-row computation — gather, position mask, attention, argmax — is
        the one the sequential decode round runs. Output ``[r, j]`` is the
        model's greedy next token after consuming ``segs[r, :j+1]``; while
        the fed drafts match the model's own choices, those outputs ARE the
        non-speculative greedy rollout, bitwise. Unlike
        ``decode_paged_multi``'s K sequential scan rounds, the whole segment
        runs position-parallel in a single round — the compute win
        speculation banks when drafts are accepted.

        ``segs`` (B, K) int32 (rows past a row's real draft are padding —
        the caller rolls their positions back); ``tables`` (B, MAXB);
        ``starts`` (B,) the first segment position per row.

        ``sampling``: ``None`` = greedy argmax at every position (the
        legacy program); else ``(seeds, temps, top_ks, top_ps, bias)``
        per-ROW arrays as in :meth:`decode_paged_multi`, broadcast across
        the row's K positions. Output ``[r, j]`` is then the TARGET's own
        sample under the counter-based key for absolute position
        ``starts[r] + j + 1`` — exactly the token the sequential sampled
        decode emits at that position given the same history, which is
        what makes draft acceptance-by-prefix-match rejection sampling's
        deterministic specialization (docs/SAMPLING.md) and keeps
        speculative output token-for-token equal to the non-speculative
        sampled stream."""
        B, K = segs.shape
        ids = segs.reshape(B * K, 1)
        tab = jnp.repeat(tables, K, axis=0)  # (B*K, MAXB): row j shares r's table
        pos = (starts[:, None]
               + jnp.arange(K, dtype=jnp.int32)[None, :]).reshape(B * K)
        lg, kv_pool = self.forward_paged(params, ids, kv_pool, tab, pos)
        if sampling is None:
            ys = jnp.argmax(lg, axis=-1).astype(jnp.int32)
        else:
            seeds, temps, top_ks, top_ps, bias = sampling
            ys = sample_or_argmax(
                lg + jnp.repeat(bias, K, axis=0),
                jnp.repeat(seeds, K), pos + 1,
                jnp.repeat(temps, K), jnp.repeat(top_ks, K),
                jnp.repeat(top_ps, K))
        return ys.reshape(B, K), kv_pool

    def draft_greedy(self, params, window, n_valid, k: int):
        """Greedy ``k``-token continuation for a DRAFT model
        (docs/SERVING.md speculative decoding): one ``lax.scan`` over the
        fixed-size token ``window`` (W,) int32, right-padded past ``n_valid``.
        The window is position-rebased (the context tail runs from position
        0), so drafts from a long context are approximate — acceptable,
        because the verifier is the oracle: a wrong draft costs a rollback,
        never a wrong token. The caller guarantees ``n_valid + k <= W``.
        Returns the (k,) drafted tokens."""

        def round_(carry, _):
            win, cur = carry
            lg = self.logits(params, win[None, :])[0]        # (W, V)
            nxt = jnp.argmax(lg[cur - 1], axis=-1).astype(jnp.int32)
            win = jax.lax.dynamic_update_index_in_dim(win, nxt, cur, 0)
            return (win, cur + 1), nxt

        (_, _), ys = jax.lax.scan(
            round_, (window, n_valid), None, length=int(k))
        return ys

    def forward_with_cache(self, params, input_ids, kv_cache, cache_index, positions=None):
        """Run a (possibly length-1) segment against the dense cache and
        project only the LAST position (B, V) — the decode/prefill hot path
        skips the (S, V) logits matmul. Returns (logits, new_cache)."""
        x, new_kv = self._trunk_with_cache(params, input_ids, kv_cache,
                                           cache_index, positions)
        return self._head(params, x[:, -1:, :])[:, 0, :], new_kv


def sample_or_argmax(lg, seeds, positions, temps, top_ks, top_ps):
    """Per-row token selection shared by greedy and sampled serving
    (docs/SAMPLING.md): for each logit row, ``temps[r] == 0`` selects
    plain argmax — bit-identical to the legacy greedy programs — and
    ``temps[r] > 0`` draws one categorical sample from the
    temperature/top-k/top-p-shaped distribution under the **counter-based
    key** ``fold_in(PRNGKey(seeds[r]), positions[r])``. ``positions`` is
    the produced token's 0-based absolute index over prompt + generated,
    so a replay that re-feeds the committed history lands on the same
    (seed, position) pairs and reproduces every sample bitwise — the
    property all five replay paths (preempt/re-admit, journal replay,
    engine rebuild, pool migration, KV swap-in) certify.

    A batch-level ``lax.cond`` on ``any(temps > 0)`` skips the sampling
    math (one descending sort per row, shared by top-k and top-p) when
    every row is greedy, so pure-greedy traffic keeps today's compute
    path inside the same compiled program — no new static mode, no new
    trace. Lives here rather than in ``serve`` because the paged multi
    ops close over it and ``models`` must stay importable without the
    serving stack; ``deepspeed_tpu.serve.sampling`` re-exports it.

    ``lg`` (R, V) logits (bias already added by the caller); ``seeds``/
    ``positions``/``top_ks`` (R,) int32; ``temps``/``top_ps`` (R,)
    float32. Returns (R,) int32 token ids. Zero-filled padding rows are
    safe: temp 0 routes them through argmax."""

    def _greedy(args):
        return jnp.argmax(args[0], axis=-1).astype(jnp.int32)

    def _sampled(args):
        lg, seeds, positions, temps, top_ks, top_ps = args

        def one(lg_r, seed, pos, temp, tk, tp):
            greedy_tok = jnp.argmax(lg_r, axis=-1).astype(jnp.int32)
            key = jax.random.fold_in(jax.random.PRNGKey(seed), pos)
            x = lg_r.astype(jnp.float32) / jnp.where(temp > 0.0, temp, 1.0)
            # one descending sort serves both filters
            srt = jnp.sort(x)[::-1]
            kth = srt[jnp.clip(tk - 1, 0, x.shape[-1] - 1)]
            x = jnp.where((tk > 0) & (x < kth), -jnp.inf, x)
            probs = jax.nn.softmax(srt)
            keep = (jnp.cumsum(probs) - probs) < tp
            keep = keep.at[0].set(True)  # nucleus is never empty
            thr = jnp.min(jnp.where(keep, srt, jnp.inf))
            x = jnp.where((tp < 1.0) & (x < thr), -jnp.inf, x)
            tok = jax.random.categorical(key, x).astype(jnp.int32)
            return jnp.where(temp > 0.0, tok, greedy_tok)

        return jax.vmap(one)(lg, seeds, positions, temps, top_ks, top_ps)

    with jax.named_scope("sample"):
        return jax.lax.cond(jnp.any(temps > 0.0), _sampled, _greedy,
                            (lg, seeds, positions, temps, top_ks, top_ps))


def build_model(preset: str, **overrides) -> TransformerLM:
    if preset not in MODEL_PRESETS:
        raise ValueError(f"unknown model preset '{preset}' (known: {sorted(MODEL_PRESETS)})")
    return TransformerLM(MODEL_PRESETS[preset](**overrides))
