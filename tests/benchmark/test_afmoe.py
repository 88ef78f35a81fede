"""Trinity-Mini (``afmoe``) through the program against the plain reference
(``benchmark/reference/afmoe.py``), at the configuration's rehearsal preset on
the CPU (hidden 128, 4 heads of 32 over 2 KV heads, a window of 32 over blocks
of 16, layers window-dense, window, full, window, full, 4 of 8 router outputs
held, top 2, a shared expert).

(a) prefill in chunks then decode through both classes of blocks against the
reference's full forward, prompts longer than the window so that blocks are
freed before the compared rows; (b) the shares of an expert layer add up to
the uncut layer; (c) the fp8 control fails the limit; (d) the reference's
window mask and its layer index; (e) the cell's configuration and its
parameter count; (f) the cell's rehearsal and its readers.
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
from benchmark.reference import afmoe as ref
from benchmark.reference import ein_f32, ein_fp8
from deepspeed_tpu.models.transformer import TransformerConfig
from deepspeed_tpu.moe.layer import held_experts_ffn

CELL = "trinity-mini.serve-win16k"


@pytest.fixture(scope="module")
def cell():
    return Cell(CELL, load_spec())


@pytest.fixture(scope="module")
def model(cell):
    return build_model(cell, True)


def seeded(model, seed=13, std=0.05):
    return weights.Seeded(
        jax.eval_shape(model.init_params, jax.random.PRNGKey(0)), seed, std,
        model.config.num_layers)


def sample_rows(lengths, forced=3, width=128, seed=1):
    rng = np.random.default_rng(seed)
    samples = [(rng.integers(0, 512, n).tolist(),
                rng.integers(0, 512, forced).tolist()) for n in lengths]
    ids = np.zeros((len(samples), width), np.int32)
    rows = np.zeros((len(samples), forced + 1), np.int32)
    for k, (p, f) in enumerate(samples):
        ids[k, :len(p) + len(f)] = p + f
        rows[k] = np.arange(len(p) - 1, len(p) + len(f))
    return samples, ids, rows


# -- (a) --------------------------------------------------------------------

PAGED = [(jnp.float32, False, 3e-5), (jnp.float32, True, 3e-5),
         (jnp.bfloat16, False, 0.05)]


@pytest.mark.parametrize("dtype,kernel,tol", PAGED)
def test_chunked_prefill_then_decode_through_both_classes(
        cell, model, monkeypatch, dtype, kernel, tol):
    """Prompts of 100, 70 and 5 tokens prefilled in tiles of 16 (chunks of 32
    in a 36-row budget) and three forced tokens decoded, full logits at every
    step, against the reference's full causal forward over the padded ids,
    layer by layer as a run walks it, the selection bias fitted on the way.
    The first two are three and two windows long: their window-class blocks
    are freed while they prefill, before any compared row. With the paged
    kernels in interpret mode and without."""
    from deepspeed_tpu.inference.v2 import InferenceEngineV2

    if kernel:
        monkeypatch.setenv("DSTPU_FORCE_PAGED_KERNEL", "1")
        # the kernel takes head sizes of 64 and up: the same model, wider heads
        cfg = TransformerConfig(**{**model.config.__dict__,
                                   "head_dim_override": 64})
        model = type(model)(cfg)
        published = {**reference_config(cell, True), "head_dim": 64}
    else:
        published = reference_config(cell, True)
    w = seeded(model)
    samples, ids, rows = sample_rows((100, 70, 5))
    want = check.serve_reference(published, w, ids, rows)
    assert sorted(w.made) == [("blocks_1", 0), ("blocks_2", 0),
                              ("blocks_3", 0), ("blocks_4", 0)]
    eng = InferenceEngineV2(model, w.tree_as(dtype), dtype=dtype,
                            **cell.mix(True)["engine"])
    assert {k: v.shape[0] for k, v in eng.kv.items()} == {"full": 2,
                                                         "window": 3}
    got = engine_logits(eng, samples)
    if dtype == jnp.float32:
        np.testing.assert_allclose(got, want, atol=tol)
    else:
        assert check.logits_rel_err(got, want) < tol
    assert check.weights_mismatch_share(eng.params, w, jnp.dtype(dtype)) == 0
    window = eng.block_mgr.window
    assert window.freed_behind >= 4 + 2 and window.in_use == 0
    assert window.allocations > window.freed_behind    # the rest at the flush
    eng.block_mgr.check_invariants([])


# -- (b) --------------------------------------------------------------------

def test_the_eight_shares_add_up_to_the_uncut_layer(model):
    """Eight shares of one expert each (the deployment's eight chips, at the
    rehearsal's eight router outputs): the routed parts of all shares plus
    the shared expert, counted once, equal the uncut reference layer, and the
    reference, given a share, computes that share's part."""
    cfg = TransformerConfig(**{**model.config.__dict__, "num_experts": 8})
    tree = weights.Seeded(
        jax.eval_shape(type(model)(cfg).init_params, jax.random.PRNGKey(0)),
        5, 0.05, cfg.num_layers).tree()
    b = {k: v[0] for k, v in tree["blocks_1"].items()}
    assert b["wi"].shape[0] == b["moe_wg"].shape[1] == 8
    x = jax.random.normal(jax.random.PRNGKey(3), (50, 128), jnp.float32)
    pub = {"num_experts_per_tok": 2, "route_scale": 2.826, "route_norm": True,
           "n_group": 1, "topk_group": 1}
    whole = ref.experts(x, b, pub, ein_f32)
    shared = ref.gated_mlp(x, b["shared_w_gate"], b["shared_w_up"],
                           b["shared_w_down"], ein_f32)
    routed, landed = jnp.zeros_like(x), 0
    for r in range(8):
        sl = slice(r, r + 1)
        part, (rows, _) = held_experts_ffn(
            x, b["moe_wg"], b["moe_bias"], b["wi"][sl], b["w_gate"][sl],
            b["w_down"][sl], None, k=2, n_group=1, topk_group=1, scale=2.826,
            first=r)
        routed, landed = routed + part, landed + int(rows)
        share = {**b, **{k: b[k][sl] for k in ("wi", "w_gate", "w_down")}}
        want = ref.experts(x, share, {**pub, "expert_offset": r},
                           ein_f32) - shared
        np.testing.assert_allclose(np.asarray(part), np.asarray(want),
                                   atol=2e-5)
    assert landed == 50 * 2               # every pick lands on one chip
    np.testing.assert_allclose(np.asarray(routed + shared), np.asarray(whole),
                               atol=2e-5)


# -- (c) --------------------------------------------------------------------

def test_the_fp8_control_fails_the_limit(cell, model):
    """The reference with every contraction's operands rounded to fp8 against
    itself in float32 reads over the cell's limit, which the bfloat16
    program's reading (test (a)) is under."""
    w = seeded(model)
    _, ids, rows = sample_rows((100, 96), seed=2)
    cfg = reference_config(cell, True)
    want = check.serve_reference(cfg, w, ids, rows)
    control = check.serve_reference(cfg, w, ids, rows, ein=ein_fp8)
    limit = cell.config["tolerances"]["serve"]["logits_rel_err"]["limit"]
    assert check.logits_rel_err(control, want) > limit
    assert cell.config["tolerances"]["serve"]["weights_mismatch_share"][
        "limit"] == 0.0


# -- (d) --------------------------------------------------------------------

def test_a_window_layer_sees_its_window_and_a_full_layer_everything():
    """The reference's attention, every score with its mask: against a loop
    over the queries, for both types, blocked by queries and by kv heads."""
    s, heads, kvh, d, bound = 256, 4, 2, 8, 32
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (s, heads, d))
    k = jax.random.normal(ks[1], (s, kvh, d))
    v = jax.random.normal(ks[2], (s, kvh, d))
    for window in (True, False):
        got = ref.masked_attention(q, k, v, jnp.asarray(window), bound,
                                   ein_f32, q_block=128)
        want = np.zeros((s, heads, d), np.float32)
        for i in range(0, s, 37):
            lo = max(0, i - bound + 1) if window else 0
            for h in range(heads):
                sc = np.asarray(k)[lo:i + 1, h // 2] @ np.asarray(q)[i, h] \
                    / math.sqrt(d)
                p = np.exp(sc - sc.max())
                want[i, h] = (p / p.sum()) @ np.asarray(v)[lo:i + 1, h // 2]
            np.testing.assert_allclose(np.asarray(got[i]), want[i], atol=2e-5)


def test_the_hidden_states_carry_the_layers_index(cell):
    cfg = reference_config(cell, True)
    assert ref.is_window(cfg) == [True, True, False, True, False]
    assert ref.groups(cfg) == [(f"blocks_{i}", 1) for i in range(5)]
    full = cell.config
    assert ref.groups(full) == [("blocks_0", 1), ("blocks_1", 3),
                                ("blocks_2", 1), ("blocks_3", 3),
                                ("blocks_4", 1), ("blocks_5", 3),
                                ("blocks_6", 1)]
    w = {"wte": jnp.ones((4, 128))}
    x = ref.embed(w, jnp.asarray([1, 2]), {**cfg, "mup_enabled": True})
    assert x.shape == (2, 129) and float(x[0, 0]) == pytest.approx(
        math.sqrt(128)) and not x[:, -1].any()
    assert ref.final({"lnf_scale": jnp.ones(128)}, x, cfg).shape == (2, 128)


# -- (e) --------------------------------------------------------------------

def test_the_cells_configuration_is_1_78b_parameters(cell):
    """The served tree of ``benchmark/configs/trinity-mini.json``: 13 layers
    (one dense, twelve of 16 held experts beside the shared one), an eighth
    of the vocabulary, every width as published."""
    published = cell.config
    cfg = TransformerConfig(**{**published["model"],
                               **cell.traffic["model"]})
    attn = 3 * 2048 * 4096 + 2 * 2048 * 512 + 2 * 128
    norms = 4 * 2048
    expert = 3 * 2048 * 1024
    dense = attn + norms + 3 * 2048 * 6144
    moe = attn + norms + 17 * expert + 2048 * 128 + 128
    assert cfg.num_parameters == dense + 12 * moe + 2 * 25024 * 2048 + 2048
    assert round(cfg.num_parameters / 1e9, 2) == 1.78
    assert cfg.num_active_parameters == cfg.num_parameters - 12 * 8 * expert
    assert cfg.kv_row == (128, 128) and cfg.pool_heads == 4
    assert cfg.class_layers == {"full": 3, "window": 10}
    # every published width is kept
    for key, want in (("hidden_size", 2048), ("num_attention_heads", 32),
                      ("num_key_value_heads", 4), ("head_dim", 128),
                      ("sliding_window", 2048), ("intermediate_size", 6144),
                      ("moe_intermediate_size", 1024),
                      ("num_experts_per_tok", 8), ("route_scale", 2.826),
                      ("router_outputs", 128)):
        assert published[key] == want, key
    assert published["published"]["num_experts"] == 128
    assert published["reduced"] == [
        "num_hidden_layers", "num_dense_layers", "num_experts", "vocab_size",
        "layer_types"]
    assert cfg.embed_scale == pytest.approx(math.sqrt(2048))
    engine = cell.traffic["engine"]
    assert engine["max_seq_len"] == cfg.max_seq_len == 17536
    assert engine["prefix_cache"] is False
    assert set(engine["num_blocks"]) == {"full", "window"}


# -- (f) --------------------------------------------------------------------

def test_the_cells_rehearsal_reports_every_metric_but_the_peak_shares(cell):
    """Both runs of the cell at its tiny preset (``test_cells.py``'s way): the
    traced one reports every per-layer metric the cell lists, the shares of a
    roofline aside (the CPU has no peak); blocks were freed behind the
    window, a sequence held its window's blocks and no more, nothing compiled
    in the window."""
    from tests.benchmark.test_cells import rehearse

    _, traced = rehearse(CELL, 1)
    got = traced["metrics"]
    listed = {m["name"] for m in cell.per_layer}
    # the decode kernel runs on the chip alone: its time and its share of the
    # roofline are the two the CPU cannot read
    assert {n for n in listed if not n.startswith("kernel.paged")} <= set(got)
    assert 0 < got["cache.freed_behind_share.win16k"]["value"] < 100
    assert 0 < got["cache.window_blocks_per_seq.win16k"]["value"] <= 6
    assert got["cache.full_blocks_per_seq.win16k"]["value"] \
        > got["cache.window_blocks_per_seq.win16k"]["value"]
    assert 0 < got["cache.window_pool_fill.win16k"]["value"] <= 100
    assert 0 < got["cache.full_pool_fill.win16k"]["value"] <= 100
    assert got["engine.compiles.serve"]["value"] == 0
    assert got["sched.segment_step_share"]["value"] > 0


def test_the_class_readers_read_the_dispatch_attrs(cell, monkeypatch):
    """``class_fill``: the peak of a class's blocks in use over its usable
    blocks; the roofline reader counts decode rounds by class and says
    nothing for a program without window layers' attrs."""
    from benchmark.kernels import window_paged_attention
    from benchmark.readers import class_fill
    from deepspeed_tpu.utils import tracing

    def spans(*attrs):
        return [tracing.Record(i + 1, "engine.dispatch", 0, 1, 0, a)
                for i, a in enumerate(attrs)]

    monkeypatch.setattr(tracing, "_buf", spans(
        {"window_blocks": 10, "window_free": 30},
        {"window_blocks": 24, "window_free": 16}, {"rows": 3}))
    assert class_fill.read({}, "window_blocks", "window_free") == 60.0
    assert class_fill.read({}, "full_blocks", "full_free") is None
    monkeypatch.setattr(tracing, "_buf", [])
    assert class_fill.read({}, "window_blocks", "window_free") is None
    # ten rows of 8192 tokens: a full layer reads them all, a window layer
    # 2048 a row; bytes are k and v of 4 heads of 128 in bfloat16
    cut = window_paged_attention.window_tokens(81920, 10, 2048)
    flops, nbytes = window_paged_attention.work(
        81920, cut, 10, 3, 10, 32, 4, 128)
    attended = 3 * 81920 + 10 * 20480
    assert nbytes == 2 * attended * 4 * 128 * 2 + 2 * 13 * 10 * 32 * 128 * 2
    assert flops == 4 * attended * 32 * 128
    # contexts shorter than a window in all: the whole context
    assert window_paged_attention.window_tokens(100, 2, 2048) == 100


def test_the_roofline_reader_counts_a_mixed_steps_one_token_rows(
        cell, monkeypatch):
    """Where the program says what the one-token rows attend, every dispatch
    is counted row by row: a mixed step's rows beside its chunk, and a row
    shorter than the window by its own length. A trace without those
    attributes counts the decode rounds alone, by their totals."""
    from benchmark.kernels import window_paged_attention
    from benchmark.readers import window_paged_roofline as reader
    from deepspeed_tpu.utils import tracing

    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    monkeypatch.setattr(reader, "kernel_seconds", lambda trace, kernel: 1e-3)
    monkeypatch.setattr(reader, "inside", lambda ctx, found: found)
    w = cell.config["model"]["sliding_window"]
    a_round = {"decode_rows": 2, "prefill_tokens": 0, "ctx_tokens": 8 * w + 5}
    mixed = {"decode_rows": 1, "prefill_tokens": 3, "ctx_tokens": 9 * w}
    by_row = ({**a_round, "decode_ctx_tokens": 8 * w + 5,
               "decode_window_tokens": w + 5},
              {**mixed, "decode_ctx_tokens": 5 * w, "decode_window_tokens": w})

    def share(full, cut, rows):
        m = cell.config["model"]
        types = m["layer_types"]
        _, nbytes = window_paged_attention.work(
            full, cut, rows, types.count("full_attn"),
            types.count("window_attn"), m["num_heads"], m["num_kv_heads"],
            m["head_dim_override"])
        return 100.0 * nbytes / peak["hbm_bytes_per_s"] / 1e-3

    ctx = {"trace": {"ops": ()}, "peak": peak, "cell": cell}
    monkeypatch.setattr(tracing, "_buf", [
        tracing.Record(i + 1, "engine.dispatch", 0, 1, 0, a)
        for i, a in enumerate(by_row)])
    assert reader.read(ctx) == pytest.approx(share(13 * w + 5, 2 * w + 5, 3))
    monkeypatch.setattr(tracing, "_buf", [
        tracing.Record(i + 1, "engine.dispatch", 0, 1, 0, a)
        for i, a in enumerate((a_round, mixed))])
    assert reader.read(ctx) == pytest.approx(share(8 * w + 5, 2 * w, 2))


def test_class_peak_reads_the_engines_own_peaks():
    """The builder's tool that sizes the classes (``benchmark/class_peak.py``)
    at the rehearsal preset: every request completes and each class's peak is
    something, within its pool."""
    from benchmark import class_peak

    found = class_peak.main(["--workload", CELL, "--rehearsal", "--seconds",
                             "2", "--blocks", "full=40,window=25"])
    assert found["completed"] == found["sent"] > 0 and found["alive"] >= 1
    assert 0 < found["peak_blocks"]["window"] <= 24
    assert 0 < found["peak_blocks"]["full"] <= 39
    assert found["num_blocks"] == {"full": 40, "window": 25}
