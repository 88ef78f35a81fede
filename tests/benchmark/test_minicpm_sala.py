"""MiniCPM-SALA through the program against the plain reference
(``benchmark/reference/minicpm_sala.py``), at the configuration's rehearsal
preset on the CPU (hidden 128, 4 heads of 32 over 2 KV heads, sparse, 2 x
lightning, sparse; selector block 16, kernel 8, stride 4, window 32, top 4,
dense_len 64, so that 128-token contexts take the sparse branch).

(a) prefill in chunks then decode through the pool and the state slots
against the reference's full forward; (b) the program's chosen blocks are the
reference's on float32 inputs; (c) the program's lightning forms against the
reference's recurrence; (d) the cell's configuration and its parameter count;
(e) the cell's rehearsal: every metric file resolves, the sparse branch ran.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness import check, weights
from benchmark.harness.cell import Cell, load_json, load_spec
from benchmark.harness.serve import engine_logits
from benchmark.harness.train import build_model, reference_config
from benchmark.reference import ein_f32, ein_fp8
from benchmark.reference import minicpm_sala as ref
from deepspeed_tpu.models.transformer import TransformerConfig
from deepspeed_tpu.ops.transformer import linear_attention as la
from deepspeed_tpu.ops.transformer import sparse_attention as sa

CELL = "minicpm-sala.serve-doc16k"


@pytest.fixture(scope="module")
def cell():
    return Cell(CELL, load_spec())


@pytest.fixture(scope="module")
def model(cell):
    return build_model(cell, True)


def seeded(model, seed=11, std=0.05):
    return weights.Seeded(
        jax.eval_shape(model.init_params, jax.random.PRNGKey(0)), seed, std,
        model.config.num_layers)


# -- (a) --------------------------------------------------------------------

PAGED = [(jnp.float32, False, 3e-5), (jnp.float32, True, 3e-5),
         (jnp.bfloat16, False, 0.03)]


@pytest.mark.parametrize("dtype,kernel,tol", PAGED)
def test_chunked_prefill_then_decode_through_pool_and_slots(
        cell, model, monkeypatch, dtype, kernel, tol):
    """Prompts of 100, 70 and 5 tokens prefilled in tiles of 16 (chunks of 32
    in a 36-row budget) and three forced tokens decoded, full logits at every
    step, against the reference's full causal forward over the padded ids,
    layer by layer as a run walks it. The first two are past dense_len: their
    rows select. With the paged kernels in interpret mode and without."""
    from deepspeed_tpu.inference.v2 import InferenceEngineV2

    if kernel:
        monkeypatch.setenv("DSTPU_FORCE_PAGED_KERNEL", "1")
    w = seeded(model)
    eng = InferenceEngineV2(model, w.tree_as(dtype), dtype=dtype,
                            **cell.mix(True)["engine"])
    assert eng.kv.shape[0] == 2 and sorted(eng.slot_cache) == [
        "blocks_0", "blocks_1", "blocks_2"]
    rng = np.random.default_rng(1)
    samples = [(rng.integers(0, 512, n).tolist(),
                rng.integers(0, 512, 3).tolist()) for n in (100, 70, 5)]
    ids = np.zeros((3, 128), np.int32)
    rows = np.zeros((3, 4), np.int32)
    for k, (p, f) in enumerate(samples):
        ids[k, :len(p) + len(f)] = p + f
        rows[k] = np.arange(len(p) - 1, len(p) + len(f))
    want = check.serve_reference(reference_config(cell, True), w, ids, rows)
    got = engine_logits(eng, samples)
    if dtype == jnp.float32:
        np.testing.assert_allclose(got, want, atol=tol)
    else:
        assert check.logits_rel_err(got, want) < tol
    assert check.weights_mismatch_share(eng.params, w, jnp.dtype(dtype)) == 0
    assert eng.block_mgr.slots.in_use == 0      # every sample was flushed


def test_the_fp8_control_reads_several_times_the_program(cell, model):
    """The reference with every contraction's operands rounded to fp8 against
    itself in float32, beside the bfloat16 program's reading (test (a): under
    0.03 here): the control the chip's limit is set below."""
    w = seeded(model)
    rng = np.random.default_rng(2)
    ids = rng.integers(0, 512, (2, 128)).astype(np.int32)
    rows = np.tile(np.arange(96, 100), (2, 1)).astype(np.int32)
    cfg = reference_config(cell, True)
    want = check.serve_reference(cfg, w, ids, rows)
    control = check.serve_reference(cfg, w, ids, rows, ein=ein_fp8)
    assert check.logits_rel_err(control, want) > 0.03


# -- (b) --------------------------------------------------------------------

def test_the_programs_chosen_blocks_are_the_references(cell):
    """On float32 queries and keys the selector (comparisons, the cached keys
    by rows) and the reference's (a stable sort, every score computed) choose
    the same blocks, query by query, KV head by KV head."""
    cfg = reference_config(cell, True)
    sc = cfg["sparse_config"]
    spec = TransformerConfig(**{**cell.config["model"],
                                **cell.config["rehearsal"]["model"]}).sparse_spec
    rng = np.random.default_rng(3)
    S, nh, kvh, hd = 128, 4, 2, 32
    q = jnp.asarray(rng.normal(size=(S, nh, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(S, kvh, hd)), jnp.float32)
    c = ref.compressed_keys(k, sc)                            # (J, kvh, hd)
    pos = jnp.arange(S)
    want = ref.chosen_blocks(q, c, pos, sc, S // sc["block_size"], ein_f32)
    keys = jnp.pad(c.reshape(c.shape[0], kvh * hd),
                   ((0, spec.max_keys(S) - c.shape[0]), (0, 0)))
    got, count = sa.choose(q, keys, pos + 1, spec, S // spec.block, hd ** -0.5)
    np.testing.assert_array_equal(got, want)
    assert int(count[-1, 0]) == sc["topk"] and int(count[40, 0]) == 3


# -- (c) --------------------------------------------------------------------

def test_the_programs_lightning_forms_are_the_references_recurrence():
    rng = np.random.default_rng(4)
    S, H, d, C = 48, 4, 32, 16
    q, k, v = (jnp.asarray(rng.normal(size=(S, H, d)), jnp.float32)
               for _ in range(3))
    want = ref.lightning(q, k, v, ein_f32)
    np.testing.assert_allclose(
        np.exp(-la.head_decay_rates(H)), ref.head_decays(H), rtol=1e-6)
    state = la.init_state(1, 2, H, d, d)
    tiled = [a.reshape(S // C, C, H, d) for a in (q, k, v)]
    o, _ = la.chunk_tiles(state, jnp.int32(0), jnp.full((3,), 1, jnp.int32),
                          jnp.full((3,), C, jnp.int32), *tiled,
                          jnp.asarray([True, False, False]))
    np.testing.assert_allclose(o.reshape(S, H, d), want, rtol=2e-4, atol=2e-4)
    rows = []
    for t in range(S):
        o, state = la.decode_rows(state, jnp.int32(0),
                                  jnp.asarray([2], jnp.int32), q[t:t + 1],
                                  k[t:t + 1], v[t:t + 1],
                                  jnp.asarray([t == 0]))
        rows.append(o[0])
    np.testing.assert_allclose(np.stack(rows), want, rtol=2e-4, atol=2e-4)


# -- (d) --------------------------------------------------------------------

def test_the_cells_configuration_is_2_82b_parameters():
    """The served tree of ``benchmark/configs/minicpm-sala.json``: the
    published layers 9-16 (sparse, 6 x lightning, sparse) at every published
    width, the whole vocabulary."""
    file = load_json("configs", "minicpm-sala.json")
    cfg = TransformerConfig(**file["model"])
    ffn = 3 * 4096 * 16384
    sparse = 3 * 4096 * 4096 + 2 * 4096 * 256 + 2 * 128
    lightning = 5 * 4096 * 4096 + 2 * 128 + 4096
    assert (ffn, sparse, lightning) == (201_326_592, 52_429_056, 83_890_432)
    sparse_layer, lightning_layer = (m + ffn + 2 * 4096
                                     for m in (sparse, lightning))
    assert (sparse_layer, lightning_layer) == (253_763_840, 285_225_216)
    assert cfg.num_parameters == cfg.num_active_parameters \
        == 2 * sparse_layer + 6 * lightning_layer + 2 * 73448 * 4096 + 4096 \
        == 2_820_569_088
    from deepspeed_tpu.models import TransformerLM

    shapes = jax.eval_shape(TransformerLM(cfg).init_params,
                            jax.random.PRNGKey(0))
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes)) \
        == 2_820_569_088
    assert cfg.pool_layers == 2 and cfg.kv_row == (128, 128)
    # 1120 compressed keys of 2 kv heads of 128 for 17920 tokens
    assert cfg.cache_kinds == {
        "sparse_attn": (("kv_blocks", 1024), ("state_slot", 1120 * 512)),
        "linear_attn": (("state_slot", 2 * 1024 * 1024),)}
    assert cfg.residual_scale == pytest.approx(1.4 / 32 ** 0.5)
    spec = cfg.sparse_spec
    assert (spec.block, spec.kernel, spec.stride, spec.window_blocks,
            spec.init_blocks, spec.topk, spec.dense_len, spec.table_width) \
        == (64, 32, 16, 32, 1, 64, 8192, 128)
    for key, want in dict(
            hidden_size=4096, intermediate_size=16384, head_dim=128,
            num_attention_heads=32, num_key_value_heads=2, lightning_nh=32,
            lightning_nkv=32, lightning_head_dim=128, vocab_size=73448,
            scale_emb=12, scale_depth=1.4, dim_model_base=256,
            mup_denominator=32, rope_theta=10000, rms_norm_eps=1e-6,
            max_position_embeddings=524288, num_hidden_layers=8).items():
        assert file[key] == want, key
    assert file["mixer_types"] == ["minicpm4"] + ["lightning-attn"] * 6 \
        + ["minicpm4"]
    assert file["published"]["num_hidden_layers"] == 32
    assert sorted(file["reduced"]) == ["mixer_types", "num_hidden_layers"]
    engine = load_json("traffic", "serve-doc16k.json")["engine"]
    # every slot can reach max_seq_len, and the trash block
    assert engine["num_blocks"] >= 1 + engine["max_seqs"] * (
        engine["max_seq_len"] // engine["block_size"]) == 1 + 48 * 280
    assert engine["prefix_cache"] is False


# -- (e) --------------------------------------------------------------------

def test_the_cells_rehearsal_reports_every_metric_but_the_peak_shares(cell):
    """Both runs of the cell at its tiny preset (``test_cells.py``'s way): the
    traced one reports every per-layer metric the cell lists, the shares of a
    roofline aside (the CPU has no peak); the sparse branch ran (chosen blocks
    under the contexts'), slots were in use, nothing compiled in the window."""
    from tests.benchmark.test_cells import rehearse

    _, traced = rehearse(CELL, 1)
    got = traced["metrics"]
    listed = {m["name"] for m in cell.per_layer}
    assert {n for n in listed if "roofline" not in n} <= set(got)
    assert 0 < got["cache.selected_block_share"]["value"] < 100
    assert 0 < got["cache.state_slot_fill"]["value"] <= 100
    assert got["engine.compiles.serve"]["value"] == 0
    assert got["sched.segment_step_share"]["value"] > 0


def test_slot_fill_reads_the_slots_in_use_over_the_cells_max_seqs(
        cell, monkeypatch):
    """The mean of ``state_slots`` over the dispatches that carry it, over the
    ``max_seqs`` of the cell's traffic file; a program that sets no such
    attribute (the parent) or recorded nothing says nothing."""
    from benchmark.readers import slot_fill
    from deepspeed_tpu.utils import tracing

    def spans(*attrs):
        return [tracing.Record(i + 1, "engine.dispatch", 0, 1, 0, a)
                for i, a in enumerate(attrs)]

    ctx = {"cell": cell}
    monkeypatch.setattr(tracing, "_buf", spans(
        {"rows": 8, "state_slots": 12}, {"rows": 9, "state_slots": 24}))
    assert slot_fill.read(ctx) == pytest.approx(100 * 18 / 48)
    monkeypatch.setattr(tracing, "_buf", spans({"rows": 8}, {"rows": 9}))
    assert slot_fill.read(ctx) is None
    monkeypatch.setattr(tracing, "_buf", [])
    assert slot_fill.read(ctx) is None
