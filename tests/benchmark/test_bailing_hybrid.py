"""Ling-3.0-flash (``bailing_hybrid``) through the program against the plain
reference (``benchmark/reference/bailing_hybrid.py``), at the configuration's
rehearsal preset on the CPU (hidden 128, 4 layers: a dense KDA layer, two KDA
layers and a latent layer with 4 of 16 experts held; 4 heads of 32, latent
rank 32 beside a rope head of 16, conv 4, chunk 16).

(a) prefill in chunks then decode through the slot arrays and the latent pool
against the reference's full forward, float32 and bfloat16, the XLA forms and
the interpreted kernels (a variant with eight heads, which the recurrence's
kernel takes); a slot's reuse and a preemption's recompute through the
scheduler; (b) the controls: the reference with one mechanism left out against
itself, beside the float32 agreement; the fp8 control; what the chip
comparison cannot see of old state; (c) the share test: eight shares of two
experts, the shared expert counted once, add up to the uncut layer; (d) the
cell's configuration and its parameter count; (e) the cell's rehearsal and
the new readers.

The draws here are the harness's rule at another ``std``: 0.02 x sqrt(2560 /
128), so that a product of a 128-wide row has the gain it has at the
published width.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness import check, weights
from benchmark.harness.cell import Cell, load_json, load_spec
from benchmark.harness.serve import engine_logits
from benchmark.harness.train import reference_config
from benchmark.kernels import delta_rule, mla_attention
from benchmark.reference import bailing_hybrid as ref
from benchmark.reference import ein_f32, ein_fp8
from deepspeed_tpu.models import TransformerLM
from deepspeed_tpu.models.transformer import TransformerConfig
from deepspeed_tpu.moe.layer import held_experts_ffn
from deepspeed_tpu.utils import tracing
from tests.benchmark.test_falcon_h1 import (reader_ctx, rehearsal_model,
                                            sampled, spans)

CELL = "ling-3.0-flash.serve-reason"
STD = 0.02 * math.sqrt(2560 / 128)
#: eight heads of 16: what the recurrence's kernel takes
WIDE = {"num_heads": 8, "head_dim_override": 16, "qk_nope_head_dim": 16,
        "qk_rope_head_dim": 16, "v_head_dim": 16}
WIDE_PUBLISHED = {"num_attention_heads": 8, "head_dim": 16,
                  "qk_nope_head_dim": 16, "qk_rope_head_dim": 16,
                  "v_head_dim": 16}


@pytest.fixture(scope="module")
def cell():
    return Cell(CELL, load_spec())


@pytest.fixture(scope="module")
def limit(cell):
    return cell.config["tolerances"]["serve"]["logits_rel_err"]["limit"]


def seeded(model, seed=11, std=STD):
    return weights.Seeded(
        jax.eval_shape(model.init_params, jax.random.PRNGKey(0)), seed, std,
        model.config.num_layers)


# -- (a) --------------------------------------------------------------------

PAGED = [(jnp.float32, False, 3e-5), (jnp.float32, True, 3e-5),
         (jnp.bfloat16, False, None), (jnp.bfloat16, True, None)]


@pytest.mark.parametrize("dtype,kernel,tol", PAGED)
def test_chunked_prefill_then_decode_through_slots_and_latent_pool(
        cell, limit, monkeypatch, dtype, kernel, tol):
    """Prompts of 100, 70 and 5 tokens prefilled in tiles of 16 (chunks of 32
    in a 36-row budget: a state and a window carried over tiles and steps, a
    latent context over steps) and three forced tokens decoded, full logits at
    every step, against the reference's full causal forward over the padded
    ids, layer by layer as a run walks it, the selection bias fitted on the
    way; float32 to 3e-5, bfloat16 inside the configuration's limit."""
    from deepspeed_tpu.inference.v2 import InferenceEngineV2

    if kernel:
        monkeypatch.setenv("DSTPU_FORCE_PAGED_KERNEL", "1")
    # eight heads for the recurrence's kernel in float32; in bfloat16 the
    # rehearsal's own four (the latent kernel alone): at these widths a
    # router's pick that flips on rounding is a tenth of the spread, and
    # which seeds flip one depends on the model (0.02 here, 0.26 on the wide
    # one, kernels or none; without experts 0.03 on both)
    wide = kernel and dtype == jnp.float32
    model = rehearsal_model(cell, **(WIDE if wide else {}))
    w = seeded(model)
    samples, ids, rows = sampled()
    cfg = {**reference_config(cell, True), **(WIDE_PUBLISHED if wide else {})}
    # the reference first: it fits the selection bias the served tree holds
    want = check.serve_reference(cfg, w, ids, rows)
    eng = InferenceEngineV2(model, w.tree_as(dtype), dtype=dtype,
                            **cell.mix(True)["engine"])
    assert eng.kv.shape[:2] == (1, 1) and sorted(eng.slot_cache) == [
        "blocks_0", "blocks_1"]
    assert sorted(eng.slot_cache["blocks_1"]) == ["conv", "state"]
    got = engine_logits(eng, samples)
    if tol:
        np.testing.assert_allclose(got, want, atol=tol)
    else:
        assert check.logits_rel_err(got, want) < limit
    assert check.weights_mismatch_share(eng.params, w, jnp.dtype(dtype)) == 0
    assert eng.block_mgr.slots.in_use == 0      # every sample was flushed


def test_the_engine_refuses_what_a_state_cannot_give(cell):
    """Slots beside a latent pool: no prefix cache and no fused horizon, as
    for every ``holds_state`` model."""
    from deepspeed_tpu.inference.v2 import InferenceEngineV2

    model = rehearsal_model(cell)
    params = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    for bad in ({"prefix_cache": True}, {"decode_horizon": 4}):
        with pytest.raises(ValueError, match="state-slot layers"):
            InferenceEngineV2(model, params, dtype=jnp.float32, **{
                **cell.mix(True)["engine"], **bad})


def test_the_scheduler_reuses_slots_and_recomputes_a_preempted_sequence(cell):
    """Six requests through ``ContinuousBatchScheduler`` on four slots and a
    pool too small for all of them: continuous batching, chunked prefill in
    tiles, decode rounds, a slot handed from a finished sequence to a waiting
    one and a preemption recomputed from its prompt. Every request's greedy
    tokens are those it gets alone on a fresh engine."""
    from deepspeed_tpu.inference.v2 import InferenceEngineV2
    from deepspeed_tpu.serve import ContinuousBatchScheduler
    from deepspeed_tpu.serve.request import RequestState

    model = rehearsal_model(cell)
    params = seeded(model).tree_as(jnp.float32)
    rng = np.random.default_rng(3)
    # short prompts that grow: four live sequences outgrow the 13 blocks
    prompts = [rng.integers(0, 512, n).tolist()
               for n in (20, 25, 30, 22, 18, 28)]

    def serve(prompts, num_blocks):
        engine = InferenceEngineV2(model, params, dtype=jnp.float32, **{
            **cell.mix(True)["engine"], "num_blocks": num_blocks})
        with ContinuousBatchScheduler(engine) as sched:
            reqs = [sched.submit(p, max_new_tokens=50) for p in prompts]
            sched.run_until_complete()
            assert all(r.state is RequestState.DONE for r in reqs)
            return ([list(r.tokens) for r in reqs],
                    sched.metrics.preemptions)

    alone = [serve([p], 40)[0][0] for p in prompts]
    together, preemptions = serve(prompts, 14)
    assert preemptions > 0
    assert together == alone


# -- (b) --------------------------------------------------------------------

CONTROLS = ("gate", "erase", "head_decay", "conv", "forget:4")


@pytest.fixture(scope="module")
def controls(cell):
    """logits_rel_err and the largest logit's error of the reference with one
    mechanism left out, and of the reference in fp8, against the reference
    itself at the rehearsal's widths."""
    model = rehearsal_model(cell)
    w = seeded(model, seed=5)
    _, ids, rows = sampled()
    cfg = reference_config(cell, True)
    want = check.serve_reference(cfg, w, ids, rows)

    def against(got):
        return check.logits_rel_err(got, want), float(np.abs(got - want).max())

    out = {ab: against(check.serve_reference({**cfg, "ablate": ab}, w, ids,
                                             rows)) for ab in CONTROLS}
    out["fp8"] = against(check.serve_reference(cfg, w, ids, rows, ein=ein_fp8))
    return out


def test_every_mechanism_is_far_over_the_float32_agreement(controls):
    """What test (a) holds the float32 program to (3e-5 a logit) is orders
    under every control: a program that left out the gate, the erase term, the
    channel's own decay (a head's mean in its place) or the convolution's
    older taps fails it, by 1e-4 or more of a logit and of the spread."""
    for name, (rel, worst) in controls.items():
        assert rel > 1e-4 and worst > 1e-4, (name, controls)


def test_what_the_drawn_decays_leave_of_old_state(controls, limit):
    """The harness draws ``a_log`` and ``dt_bias`` near 0, so a channel keeps
    3-27% a token: on the chip at the published widths the reference with
    every KDA state zeroed each 16 tokens reads 0.0485 against itself, inside
    the bfloat16 program's own 0.028-0.054, and each 4 tokens 0.154 beside a
    limit of 0.1 (PERF.md 7 row 28 and the configuration's ``assumed`` say
    what the chip comparison is therefore blind to;
    ``tests/unit/test_delta_rule.py`` carries a state a thousand tokens).
    Here, as there, the gate and the convolution read over the serving limit
    and zeroing the states reads under the gate's reading; the fp8 control
    reads several times the bfloat16 program (0.18 against 0.03-0.05 on the
    chip, which is what the limit is set between)."""
    assert controls["gate"][0] > limit and controls["conv"][0] > limit
    assert controls["forget:4"][0] < controls["gate"][0]
    assert controls["fp8"][0] > 0.03, controls


# -- (c) --------------------------------------------------------------------

def test_the_eight_shares_add_up_to_the_uncut_layer(cell):
    """Eight shares of two experts each (the deployment's eight chips of 64,
    at the rehearsal's 16 router outputs in 4 groups of which 2 are kept): the
    routed parts of all shares plus the shared expert, counted once, equal the
    uncut reference layer, and the reference, given a share, computes that
    share's part."""
    model = rehearsal_model(cell, num_experts=16)
    cfg = model.config
    tree = weights.Seeded(
        jax.eval_shape(model.init_params, jax.random.PRNGKey(0)), 5, 0.05,
        cfg.num_layers).tree()
    b = {k: v[0] for k, v in tree["blocks_1"].items()}
    assert b["wi"].shape[0] == b["moe_wg"].shape[1] == 16
    x = jax.random.normal(jax.random.PRNGKey(3), (50, 128), jnp.float32)
    pub = {"num_experts_per_tok": 4, "routed_scaling_factor": 2.5,
           "norm_topk_prob": True, "n_group": 4, "topk_group": 2}
    whole = ref.experts(x, b, pub, ein_f32)
    shared = ref.gated_mlp(x, b["shared_w_gate"], b["shared_w_up"],
                           b["shared_w_down"], ein_f32)
    routed, landed = jnp.zeros_like(x), 0
    for r in range(8):
        sl = slice(2 * r, 2 * r + 2)
        part, (rows, _) = held_experts_ffn(
            x, b["moe_wg"], b["moe_bias"], b["wi"][sl], b["w_gate"][sl],
            b["w_down"][sl], None, k=4, n_group=4, topk_group=2, scale=2.5,
            first=2 * r)
        routed, landed = routed + part, landed + int(rows)
        share = {**b, **{k: b[k][sl] for k in ("wi", "w_gate", "w_down")}}
        want = ref.experts(x, share, {**pub, "expert_offset": 2 * r},
                           ein_f32) - shared
        np.testing.assert_allclose(np.asarray(part), np.asarray(want),
                                   atol=2e-5)
    assert landed == 50 * 4               # every pick lands on one chip
    np.testing.assert_allclose(np.asarray(routed + shared), np.asarray(whole),
                               atol=2e-5)


# -- (d) --------------------------------------------------------------------

def test_the_cells_configuration_is_2_80b_parameters():
    """The served tree of ``benchmark/configs/ling-3.0-flash.json``: published
    layer 1 and the period 6-11 at every published width, 64 of 512 experts,
    an eighth of the vocabulary."""
    file = load_json("configs", "ling-3.0-flash.json")
    cfg = TransformerConfig(**file["model"])
    H, qd = 2560, 4096
    kda = 5 * H * qd + 4 * 3 * qd + 32 + qd + 2 * H * 32 + 128
    latent = H * 32 * 192 + H * 576 + 512 + 512 * 32 * 256 + H * 32 + qd * H
    expert = 3 * H * 768
    moe = 64 * expert + H * 512 + 512 + expert
    assert (kda, latent, expert) == (52_646_048, 31_965_696, 5_898_240)
    dense = 3 * H * 6144
    layers = (kda + dense) + 5 * (kda + moe) + (latent + moe) + 7 * 2 * H
    assert cfg.num_parameters == layers + 2 * 19648 * H + H == 2_803_845_056
    shapes = jax.eval_shape(TransformerLM(cfg).init_params,
                            jax.random.PRNGKey(0))
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes)) \
        == 2_803_845_056
    assert cfg.pool_layers == 1 and cfg.kv_row == (512, 128) \
        and cfg.pool_heads == 1
    assert cfg.cache_kinds == {
        "delta_attn": (("state_slot", 32 * 128 * 128 * 4 + 3 * 12288 * 2),),
        "latent_attn": (("kv_blocks", 1280),)}
    # every number of the catalog row, but the five that are cut
    row = dict(
        head_dim=128, hidden_size=2560, intermediate_size=6144,
        kda_lower_bound=-5, kv_lora_rank=512, layer_group_size=6,
        max_position_embeddings=262144, max_window_layers=20,
        moe_intermediate_size=768, moe_shared_expert_intermediate_size=768,
        mtp_loss_scaling_factor=0, n_group=8, num_attention_heads=32,
        num_experts_per_tok=8, num_key_value_heads=32,
        num_kv_heads_for_linear_attn=0, num_shared_experts=1,
        partial_rotary_factor=0.5, qk_head_dim=192, qk_nope_head_dim=128,
        qk_rope_head_dim=64, rms_norm_eps=1e-06, rope_theta=6000000,
        rotary_dim=64, routed_scaling_factor=2.5, short_conv_kernel_size=4,
        topk_group=4, v_head_dim=128, group_norm_size=1)
    for key, want in row.items():
        assert file[key] == want, key
    assert file["q_lora_rank"] is None and file["rope_scaling"] is None
    assert len(file["expert_swiglu_limit_list"]) == 42 == len(
        file["share_expert_swiglu_limit_list"])
    # the layers kept clamp nothing, and their types are the published ones
    kept = file["layers_kept"]
    assert kept == [1, 6, 7, 8, 9, 10, 11]
    assert not any(file["expert_swiglu_limit_list"][i]
                   or file["share_expert_swiglu_limit_list"][i] for i in kept)
    assert ref.is_latent(file) == [False] * 6 + [True]
    assert ref.groups(file) == [("blocks_0", 1), ("blocks_1", 5),
                                ("blocks_2", 1)]
    cut = {"num_hidden_layers": (7, 42), "first_k_dense_replace": (1, 2),
           "num_experts": (64, 512), "vocab_size": (19648, 157184),
           "num_nextn_predict_layers": (0, 1)}
    assert {k: (file[k], file["published"][k]) for k in cut} == cut
    assert sorted(file["reduced"]) == sorted(cut)
    assert (file["router_outputs"], file["expert_offset"],
            file["router_bias"]) == (512, 0, "balanced")
    # the program's sizes are the published ones
    assert (cfg.hidden_size, cfg.num_heads, cfg.head_dim, cfg.mlp_dim,
            cfg.dense_mlp_dim, cfg.moe_shared_size, cfg.router_width,
            cfg.moe_top_k, cfg.moe_n_group, cfg.moe_topk_group,
            cfg.moe_score_scale, cfg.kda_log_floor, cfg.ssm_conv) == (
        2560, 32, 128, 768, 6144, 768, 512, 8, 8, 4, 2.5, -5.0, 4)
    engine = load_json("traffic", "serve-reason.json")["engine"]
    assert engine["prefix_cache"] is False
    # 128 one-token rows and three tiles of 128: a prompt goes in 384s
    assert (engine["token_budget"] - engine["max_seqs"]) // 128 == 3
    assert engine["prefill_chunk"] == 384


# -- (e) --------------------------------------------------------------------

def test_the_cells_rehearsal_reports_every_metric_but_the_kernels_own(cell):
    """Both runs of the cell at its tiny preset (``test_cells.py``'s way): the
    traced one reports every per-layer metric the cell lists, the shares of a
    roofline and the latent kernel's own time aside (the CPU has no peak and
    runs the XLA forms); slots were in use, nothing compiled in the
    window."""
    from tests.benchmark.test_cells import rehearse

    _, traced = rehearse(CELL, 1)
    got = traced["metrics"]
    listed = {m["name"] for m in cell.per_layer}
    assert {n for n in listed if "roofline" not in n} \
        - {"kernel.mla_decode_ms"} <= set(got)
    assert {"model.delta_attn_ms", "model.delta_scan_ms",
            "kernel.delta_decode_roofline_share.reason",
            "kernel.mla_decode_roofline_share.reason"} <= listed
    assert 0 < got["cache.state_slot_fill"]["value"] <= 100
    # which instruction carries which scope is the chip compiler's to say
    # (``tests/unit/test_layer_kinds.py`` holds the scopes of the trace)
    assert got["model.delta_attn_ms"]["value"] >= 0
    assert got["model.delta_scan_ms"]["value"] >= 0
    assert got["moe.rows_per_expert"]["value"] > 0
    assert got["engine.compiles.serve"]["value"] == 0
    assert got["sched.segment_step_share"]["value"] > 0


def test_the_delta_kernels_share_counts_the_one_token_rows(cell, monkeypatch):
    """``kernel.delta_decode_roofline_share.reason``: rows x 6 KDA layers x
    (the 2 MiB state read and written beside its operands) over 819 GB/s, over
    the seconds of the kernels named ``delta_decode``; bound by bytes (7 FLOPs
    a state element are 0.004 of the bytes' time); nothing where the program
    recorded no dispatch, the chip's peak is unknown, or the kernels are
    another family's."""
    from benchmark.readers import delta_roofline

    flops, nbytes = delta_rule.dispatches(1, 1, 32, 128, 128)
    assert nbytes == 2 * 2 * 1024 * 1024 + 2 * 4096 * 2 + 4096 * 4 \
        + 4096 * 2 + 32 * 4 + 4096 * 4
    assert flops == 7 * 32 * 128 * 128
    assert flops / 197e12 < 0.01 * nbytes / 819e9
    monkeypatch.setattr(tracing, "_buf", spans(
        {"rows": 75, "decode_rows": 75}, {"rows": 459, "decode_rows": 75}))
    least = 150 * 6 * nbytes / 819e9
    ctx = reader_ctx(cell, "delta_decode", 2 * least)
    assert delta_roofline.read(ctx) == pytest.approx(50.0)
    assert delta_roofline.read({**ctx, "peak": None}) is None
    assert delta_roofline.read(reader_ctx(cell, "linear_decode", 1.0)) is None
    monkeypatch.setattr(tracing, "_buf", [])
    assert delta_roofline.read(ctx) is None


def test_the_latent_share_counts_one_pool_layer(cell, monkeypatch):
    """``kernel.mla_decode_roofline_share.reason``: the model's one latent
    layer, not its seven layers."""
    from benchmark.readers import mla_roofline_layers

    args = load_json("metrics",
                     "kernel.mla_decode_roofline_share.reason.json")["args"]
    assert args == {"layers": 1}
    flops, nbytes = mla_attention.dispatches(225_000, 225_000, 75, 1, 32, 512,
                                             64)
    least = max(flops / 197e12, nbytes / 819e9)
    monkeypatch.setattr(tracing, "_buf", spans(
        {"rows": 75, "ctx_tokens": 225_000, "ctx_tokens_by_row": 225_000}))
    ctx = reader_ctx(cell, "mla_decode", 4 * least)
    assert mla_roofline_layers.read(ctx, **args) == pytest.approx(25.0)
