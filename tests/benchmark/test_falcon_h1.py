"""Falcon-H1 through the program against the plain reference
(``benchmark/reference/falcon_h1.py``), at the configuration's rehearsal
preset on the CPU (hidden 128, 3 layers, 4 heads of 32 over 2 KV heads, 4 SSM
heads of 32 in 2 groups, state 16, conv 4, chunk 16), every muP scalar the
published one.

(a) prefill in chunks then decode through the pool and the two slot arrays
against the reference's full forward, float32 and bfloat16, the XLA forms and
the interpreted kernels (a variant with eight SSM heads a group, which the
recurrence's kernel takes); a slot's reuse and a preemption's recompute
through the scheduler; (b) the controls: the reference with one mechanism
left out against itself, beside the serving limit and the float32 agreement;
the fp8 control; (c) the cell's configuration and its parameter count; (d)
the cell's rehearsal and the two new readers.

The draws here are the harness's rule at another ``std``: 0.02 x sqrt(5120 /
128), so that a product of a 128-wide row has the gain it has at the
published width (at 0.02 every branch of a 128-wide model is near zero and
the comparison sees the embedding and the head).
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness import check, weights
from benchmark.harness.cell import Cell, load_json, load_spec
from benchmark.harness.serve import engine_logits
from benchmark.harness.train import build_model, reference_config
from benchmark.kernels import paged_attention, ssd
from benchmark.reference import ein_fp8
from deepspeed_tpu.models import TransformerLM
from deepspeed_tpu.models.transformer import TransformerConfig
from deepspeed_tpu.utils import tracing

CELL = "falcon-h1-34b-instruct.serve-crowd"
STD = 0.02 * math.sqrt(5120 / 128)
#: sixteen SSM heads, eight a group: what the recurrence's kernel takes
WIDE = {"ssm_heads": 16}
WIDE_PUBLISHED = {"mamba_n_heads": 16, "mamba_d_ssm": 512}


@pytest.fixture(scope="module")
def cell():
    return Cell(CELL, load_spec())


@pytest.fixture(scope="module")
def limit(cell):
    return cell.config["tolerances"]["serve"]["logits_rel_err"]["limit"]


def rehearsal_model(cell, **over):
    cfg = build_model(cell, True).config
    return TransformerLM(TransformerConfig(**{**cfg.__dict__, **over}))


def seeded(model, seed=11, std=STD):
    return weights.Seeded(
        jax.eval_shape(model.init_params, jax.random.PRNGKey(0)), seed, std,
        model.config.num_layers)


def sampled(lens=(100, 70, 5), forced=3, width=128):
    rng = np.random.default_rng(1)
    samples = [(rng.integers(0, 512, n).tolist(),
                rng.integers(0, 512, forced).tolist()) for n in lens]
    ids = np.zeros((len(lens), width), np.int32)
    rows = np.zeros((len(lens), forced + 1), np.int32)
    for k, (p, f) in enumerate(samples):
        ids[k, :len(p) + len(f)] = p + f
        rows[k] = np.arange(len(p) - 1, len(p) + len(f))
    return samples, ids, rows


# -- (a) --------------------------------------------------------------------

PAGED = [(jnp.float32, False, 3e-5), (jnp.float32, True, 3e-5),
         (jnp.bfloat16, False, None), (jnp.bfloat16, True, None)]


@pytest.mark.parametrize("dtype,kernel,tol", PAGED)
def test_chunked_prefill_then_decode_through_pool_and_slots(
        cell, limit, monkeypatch, dtype, kernel, tol):
    """Prompts of 100, 70 and 5 tokens prefilled in tiles of 16 (chunks of 32
    in a 36-row budget: a state and a window carried over tiles and steps)
    and three forced tokens decoded, full logits at every step, against the
    reference's full causal forward over the padded ids, layer by layer as a
    run walks it; float32 to 3e-5, bfloat16 inside the configuration's
    limit."""
    from deepspeed_tpu.inference.v2 import InferenceEngineV2

    if kernel:
        monkeypatch.setenv("DSTPU_FORCE_PAGED_KERNEL", "1")
    model = rehearsal_model(cell, **(WIDE if kernel else {}))
    w = seeded(model)
    eng = InferenceEngineV2(model, w.tree_as(dtype), dtype=dtype,
                            **cell.mix(True)["engine"])
    assert eng.kv.shape[0] == 3 and sorted(eng.slot_cache["blocks_0"]) == [
        "conv", "ssm"]
    samples, ids, rows = sampled()
    cfg = {**reference_config(cell, True), **(WIDE_PUBLISHED if kernel else {})}
    want = check.serve_reference(cfg, w, ids, rows)
    got = engine_logits(eng, samples)
    if tol:
        np.testing.assert_allclose(got, want, atol=tol)
    else:
        assert check.logits_rel_err(got, want) < limit
    assert check.weights_mismatch_share(eng.params, w, jnp.dtype(dtype)) == 0
    assert eng.block_mgr.slots.in_use == 0      # every sample was flushed


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

@pytest.fixture(scope="module")
def controls(cell):
    """logits_rel_err of the reference with one mechanism left out, and of
    the reference in fp8, against the reference itself: the rehearsal's
    widths at the cell's depth (9 layers) and state size (256: the read-out's
    share of ``y`` beside ``D x`` grows with the root of it; at 16 it is a
    quarter of what the published size gives)."""
    model = rehearsal_model(cell, ssm_state=256, num_layers=9,
                            layer_types=("hybrid_ssm",) * 9)
    w = seeded(model, seed=5)
    _, ids, rows = sampled()
    cfg = {**reference_config(cell, True), "mamba_d_state": 256,
           "num_hidden_layers": 9}
    want = check.serve_reference(cfg, w, ids, rows)
    out = {ab: check.logits_rel_err(
        check.serve_reference({**cfg, "ablate": ab}, w, ids, rows), want)
        for ab in ("readout", "conv", "decay", "rotary")}
    out["fp8"] = check.logits_rel_err(
        check.serve_reference(cfg, w, ids, rows, ein=ein_fp8), want)
    return out


def test_every_mechanism_is_far_over_the_float32_agreement(controls):
    """What test (a) holds the float32 program to (3e-5 a logit, ~1e-6 of
    the spread) is orders under every control: a program that dropped the
    read-out, the conv, a token's own decay or the rotary fails it."""
    assert all(v > 1e-4 for v in controls.values()), controls


def test_the_serving_limit_sees_the_readout_and_the_conv(controls, limit):
    """The reference without the read-out ``C S`` and with the conv replaced
    by its current tap read over the serving limit (on the chip at the
    published widths 0.175 and 1.17, PERF.md 4). The decay fixed at its mean
    and the rotary dropped read under it, here and on the chip (0.019 and
    0.0002 beside a limit of 0.05): with ``a_log`` and ``dt_bias`` drawn near
    0 a token's decay moves by a few per cent about 0.5, and with
    ``key_multiplier`` 0.011 the scores are nearly flat; the float32
    comparison above and ``tests/unit/test_ssd.py`` hold those (the
    configuration's ``assumed`` and PERF.md 7 say so). The fp8 control reads
    several times the bfloat16 program (0.006 at this width; 0.135 against
    0.018 on the chip, which is what the limit is set between)."""
    assert controls["readout"] > limit, controls
    assert controls["conv"] > limit, controls
    assert controls["decay"] < limit and controls["rotary"] < limit, controls
    assert controls["fp8"] > 0.03, controls


# -- (c) --------------------------------------------------------------------

def test_the_cells_configuration_is_4_205b_parameters():
    """The served tree of ``benchmark/configs/falcon-h1-34b-instruct.json``:
    9 of the 72 layers at every published width, an eighth of the
    vocabulary."""
    file = load_json("configs", "falcon-h1-34b-instruct.json")
    cfg = TransformerConfig(**file["model"])
    attn = 2 * 5120 * 2560 + 2 * 5120 * 512
    mixer = (5120 * 9248 + 4096 * 5120 + 4 * 5120 + 5120 + 3 * 32 + 4096)
    ffn = 3 * 5120 * 21504
    assert (attn, mixer, ffn) == (31_457_280, 68_351_072, 330_301_440)
    layer = attn + mixer + ffn + 2 * 5120
    assert layer == 430_120_032
    assert cfg.num_parameters == cfg.num_active_parameters \
        == 9 * layer + 2 * 32640 * 5120 + 5120 == 4_205_319_008
    shapes = jax.eval_shape(TransformerLM(cfg).init_params,
                            jax.random.PRNGKey(0))
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes)) \
        == 4_205_319_008
    assert cfg.pool_layers == 9 and cfg.kv_row == (128, 128) \
        and cfg.pool_heads == 4
    assert cfg.cache_kinds == {"hybrid_ssm": (
        ("kv_blocks", 2048), ("state_slot", 4 * 1024 * 1024 + 30720))}
    # every published number of the catalog row, but the two that are cut
    row = dict(
        attention_in_multiplier=1, attention_out_multiplier=0.0375,
        embedding_multiplier=5.656854249492381, head_dim=128,
        hidden_size=5120, intermediate_size=21504,
        key_multiplier=0.011048543456039804, lm_head_multiplier=0.0078125,
        mamba_chunk_size=128, mamba_d_conv=4, mamba_d_head=128,
        mamba_d_ssm=4096, mamba_d_state=256, mamba_expand=2,
        mamba_n_groups=2, mamba_n_heads=32, max_position_embeddings=262144,
        mlp_expansion_factor=8, num_attention_heads=20,
        num_key_value_heads=4, num_logits_to_keep=1, rms_norm_eps=1e-05,
        rope_theta=100000000000, ssm_in_multiplier=0.25,
        ssm_out_multiplier=0.08838834764831845)
    for key, want in row.items():
        assert file[key] == want, key
    assert file["mlp_multipliers"] == [0.1767766952966369,
                                       0.011160714285714284]
    assert file["ssm_multipliers"] == [0.3535533905932738, 0.25,
                                       0.1767766952966369, 0.5,
                                       0.3535533905932738]
    assert (file["num_hidden_layers"], file["vocab_size"]) == (9, 32640)
    assert file["published"] == {"num_hidden_layers": 72,
                                 "vocab_size": 261120}
    assert sorted(file["reduced"]) == ["num_hidden_layers", "vocab_size"]
    # the program's scalars are the published ones
    assert (cfg.embed_scale, cfg.attn_in_mult, cfg.key_mult, cfg.attn_out_mult,
            cfg.ssm_in_mult, cfg.ssm_out_mult, cfg.head_mult) == tuple(
        file[k] for k in ("embedding_multiplier", "attention_in_multiplier",
                          "key_multiplier", "attention_out_multiplier",
                          "ssm_in_multiplier", "ssm_out_multiplier",
                          "lm_head_multiplier"))
    assert list(cfg.ssm_zone_mults) == file["ssm_multipliers"]
    assert list(cfg.mlp_mults) == file["mlp_multipliers"]
    engine = load_json("traffic", "serve-crowd.json")["engine"]
    assert engine["prefix_cache"] is False
    # 64 one-token rows and three tiles of 128: a prompt goes in 384s
    assert (engine["token_budget"] - engine["max_seqs"]) // 128 == 3


# -- (d) --------------------------------------------------------------------

def test_the_cells_rehearsal_reports_every_metric_but_the_peak_shares(cell):
    """Both runs of the cell at its tiny preset (``test_cells.py``'s way): the
    traced one reports every per-layer metric the cell lists, the shares of a
    roofline and the decode kernel's own time aside (the CPU has no peak and
    runs the XLA forms); slots were in use, nothing compiled in the
    window."""
    from tests.benchmark.test_cells import rehearse

    _, traced = rehearse(CELL, 1)
    got = traced["metrics"]
    listed = {m["name"] for m in cell.per_layer}
    # the CPU runs the XLA forms: no kernel's own time
    assert {n for n in listed if "roofline" not in n} \
        - {"kernel.paged_decode_ms"} <= set(got)
    assert 0 < got["cache.state_slot_fill"]["value"] <= 100
    # which instruction carries which scope is the chip compiler's to say
    # (``tests/unit/test_layer_kinds.py`` holds the scopes of the trace)
    assert got["model.ssm_mixer_ms"]["value"] >= 0
    assert got["model.ssm_scan_ms"]["value"] >= 0
    assert got["model.dense_ffn_ms"]["value"] >= 0
    assert got["engine.compiles.serve"]["value"] == 0
    assert got["sched.segment_step_share"]["value"] > 0


def spans(*attrs):
    return [tracing.Record(i + 1, "engine.dispatch", 0, 1, 0, a)
            for i, a in enumerate(attrs)]


def reader_ctx(cell, kernel, seconds):
    """A synthetic reduced trace: ``seconds`` of the named kernel, no clock
    anchor (every span counts)."""
    trace = {"ops": {f"%{kernel}.3 = f32[64,4096]{{1,0}} custom-call(%p.1), "
                     'custom_call_target="tpu_custom_call"': (seconds, 4)},
             "clock_offset_ns": None}
    return {"trace": trace, "cell": cell, "counters": {}, "spans": {},
            "peak": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}


def test_the_recurrences_share_counts_the_one_token_rows(cell, monkeypatch):
    """``kernel.linear_decode_roofline_share.crowd``: rows x 9 layers x (the
    4 MiB state read and written beside its operands) over 819 GB/s, over the
    kernels' seconds; bound by bytes (5 FLOPs a state element are 0.04 of
    the bytes' time); nothing where the program recorded no dispatch or the
    chip's peak is unknown."""
    from benchmark.readers import ssd_roofline

    flops, nbytes = ssd.dispatches(1, 1, 32, 2, 256, 128)
    assert nbytes == 2 * 4 * 1024 * 1024 + 2 * 2 * 256 * 2 \
        + 2 * 32 * 128 * 4 + 32 * 4
    assert flops == 5 * 32 * 256 * 128
    assert flops / 197e12 < 0.05 * nbytes / 819e9
    monkeypatch.setattr(tracing, "_buf", spans(
        {"rows": 30, "decode_rows": 30}, {"rows": 414, "decode_rows": 30}))
    least = 60 * 9 * nbytes / 819e9
    ctx = reader_ctx(cell, "linear_decode", 2 * least)
    assert ssd_roofline.read(ctx) == pytest.approx(50.0)
    assert ssd_roofline.read({**ctx, "peak": None}) is None
    monkeypatch.setattr(tracing, "_buf", [])
    assert ssd_roofline.read(ctx) is None


def test_the_paged_share_takes_the_configurations_own_head(cell, monkeypatch):
    """``kernel.paged_roofline_share.crowd``: the one-token rows' contexts
    (``decode_ctx_tokens`` of every dispatch; of the decode rounds alone
    where a program does not say) x 9 layers x 4 KV heads of 128, the head
    the configuration states and not ``hidden_size // num_heads`` (256)."""
    from benchmark.readers import gqa_paged_roofline

    flops, nbytes = paged_attention.dispatches(24_000, 24_000, 30, 9, 20, 4,
                                               128)
    least = max(flops / 197e12, nbytes / 819e9)
    assert least == nbytes / 819e9
    ctx = reader_ctx(cell, "paged_decode", 4 * least)
    monkeypatch.setattr(tracing, "_buf", spans(
        {"rows": 30, "decode_rows": 30, "decode_ctx_tokens": 24_000,
         "ctx_tokens": 24_000, "prefill_tokens": 0},
        {"rows": 414, "decode_rows": 0, "decode_ctx_tokens": 0,
         "ctx_tokens": 3_000, "prefill_tokens": 384}))
    assert gqa_paged_roofline.read(ctx) == pytest.approx(25.0)
    # a program of before the attribute: the rounds alone, by their totals
    monkeypatch.setattr(tracing, "_buf", spans(
        {"rows": 30, "decode_rows": 30, "ctx_tokens": 24_000,
         "prefill_tokens": 0},
        {"rows": 414, "decode_rows": 30, "ctx_tokens": 27_000,
         "prefill_tokens": 384}))
    assert gqa_paged_roofline.read(ctx) == pytest.approx(25.0)
    assert gqa_paged_roofline.read({**ctx, "peak": None}) is None
