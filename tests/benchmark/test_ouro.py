"""Ouro through the program against the plain reference
(``benchmark/reference/ouro.py``), at the configuration's rehearsal preset on
the CPU (hidden 128, 3 layers run 3 times a token, 4 heads of 32, width 352,
vocabulary 512; a pool of 40 blocks of 16 and NINE cache layers).

(a) the full forward, the gradients of ``apply()`` and the exit selection
against ``hidden``; (b) prefill in chunks then decode through the paged pool
against the reference's walk, float32 and bfloat16, the XLA form and the
interpreted kernel, and the selection at a threshold of 0.5; a prefix served
from the prefix cache, a copy-on-write, a swap and a preemption, which move a
block's nine cache layers together; (c) the controls: the reference with one
mechanism left out against itself, beside the test's tolerance; the walk
against ``hidden``; (d) the cell's configuration and its counts; (e) the
cell's rehearsal and the new reader.

Tolerances. Float32 against float32 at ``highest``: 3e-5 a logit (the logits'
spread is ~0.3, the sandwich norms hold every branch at order one, so this is
1e-4 of what decides anything; the same arithmetic in another order reads
~1e-6). bfloat16: the configuration's own limit, which the chip's readings
set. A control: over 1e-2 of the spread, three hundred times the float32
agreement.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness import check, weights
from benchmark.harness.cell import Cell, load_json, load_spec
from benchmark.harness.serve import engine_logits
from benchmark.harness.train import build_model, reference_config
from benchmark.kernels import paged_attention
from benchmark.reference import ein_f32, ein_fp8, ouro
from deepspeed_tpu.models import TransformerLM
from deepspeed_tpu.models.transformer import TransformerConfig
from deepspeed_tpu.utils import tracing

CELL = "ouro-2.6b.serve-qa"
#: two heads of 64: a head the decode kernel takes
WIDE = {"num_heads": 2, "num_kv_heads": 2, "head_dim_override": 64}
WIDE_PUBLISHED = {"num_attention_heads": 2, "num_key_value_heads": 2,
                  "head_dim": 64}
F32_TOL = 3e-5


@pytest.fixture(scope="module")
def cell():
    return Cell(CELL, load_spec())


@pytest.fixture(scope="module")
def limit(cell):
    return cell.config["tolerances"]["serve"]["logits_rel_err"]["limit"]


def rehearsal_model(cell, **over):
    cfg = build_model(cell, True).config
    return TransformerLM(TransformerConfig(**{**cfg.__dict__, **over}))


def seeded(model, seed=11, std=0.02):
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


def reference_logits(cfg, w, ids):
    return jnp.stack([ouro.logits(w, ouro.hidden(w, seq, cfg, ein_f32),
                                  ein_f32) for seq in ids])


# -- (a) --------------------------------------------------------------------

@pytest.mark.parametrize("threshold", [1.0, 0.5])
def test_the_full_forward_is_the_references(cell, threshold):
    """``logits()`` of two sequences of 40 against ``hidden`` and the head,
    float32: every step over the same three layers, the closing norm behind
    each, the last step's state at the published threshold and each token's
    selected step's at 0.5 (where some tokens leave at every step)."""
    model = rehearsal_model(cell, early_exit_threshold=threshold)
    w = seeded(model).tree()
    cfg = {**reference_config(cell, True), "early_exit_threshold": threshold}
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 40), 0, 512)
    want = reference_logits(cfg, w, ids)
    np.testing.assert_allclose(jax.jit(model.logits)(w, ids), want,
                               atol=F32_TOL)
    if threshold < 1:
        # the selection is not one step's: each step alone is a control
        alone = [reference_logits({**cfg, "early_exit_threshold": 1.0,
                                   "ablate": f"steps:{n}"}, w, ids)
                 for n in (1, 2, 3)]
        assert all(check.logits_rel_err(a, want) > 1e-2 for a in alone)


def test_the_gradients_of_apply_are_the_references(cell):
    """``apply()`` is next-token cross-entropy on the LAST step's logits (the
    published objective, a loss over the exit distribution, is not in
    ``config.json``): its gradients against ``jax.grad`` of the same loss
    over the reference, float32; a layer's gradient sums its three visits.
    Relative 1e-3 of each leaf's largest entry: float32 sums in another
    order."""
    model = rehearsal_model(cell)
    w = seeded(model).tree()
    cfg = reference_config(cell, True)
    ids = jax.random.randint(jax.random.PRNGKey(2), (2, 24), 0, 512)

    def ref_loss(w):
        lg = reference_logits(cfg, w, ids)[:, :-1]
        nll = jax.nn.logsumexp(lg, axis=-1) - jnp.take_along_axis(
            lg, ids[:, 1:, None], axis=-1)[..., 0]
        return jnp.mean(nll)

    got_loss, got = jax.jit(jax.value_and_grad(
        lambda w: model.apply(w, {"input_ids": ids}, train=True)))(w)
    want_loss, want = jax.jit(jax.value_and_grad(ref_loss))(w)
    assert float(got_loss) == pytest.approx(float(want_loss), rel=1e-5)
    for path, g in jax.tree_util.tree_leaves_with_path(want):
        h = got
        for key in path:
            h = h[key.key]
        scale = float(jnp.max(jnp.abs(g)))
        if "exit" in str(path):
            # the last step is every token's: the gate moves nothing
            assert scale == 0 and not np.any(np.asarray(h))
            continue
        np.testing.assert_allclose(h, g, atol=1e-3 * scale, err_msg=str(path))


def test_one_step_is_the_model_without_the_field(cell):
    """``loop_steps=1`` is today's model: the same configuration, tree and
    bits as the model that names no ``loop_steps``."""
    base = {k: v for k, v in build_model(cell, True).config.__dict__.items()
            if k not in ("loop_steps", "early_exit_threshold")}
    plain = TransformerLM(TransformerConfig(**base))
    one = TransformerLM(TransformerConfig(**base, loop_steps=1))
    assert plain.config == one.config and not one.config.looped
    key = jax.random.PRNGKey(0)
    a, b = plain.init_params(key), one.init_params(key)
    assert "lnf_scale" in a and "loop" not in a
    assert jax.tree.structure(a) == jax.tree.structure(b)
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, 512)
    assert np.array_equal(plain.logits(a, ids), one.logits(b, ids))
    assert one.config.pool_layers == one.config.num_layers == 3


# -- (b) --------------------------------------------------------------------

PAGED = [(jnp.float32, False, 1.0), (jnp.float32, True, 1.0),
         (jnp.float32, False, 0.5), (jnp.bfloat16, False, 1.0),
         (jnp.bfloat16, True, 1.0)]


@pytest.mark.parametrize("dtype,kernel,threshold", PAGED)
def test_chunked_prefill_then_decode_through_the_pool(
        cell, limit, monkeypatch, dtype, kernel, threshold):
    """Prompts of 100, 70 and 5 tokens prefilled in chunks of 32 and three
    forced tokens decoded, full logits at every step, against the
    reference's full causal forward over the padded ids: step ``t`` of a
    dispatch reads and writes the cache layers ``3 t .. 3 t + 2`` of a pool of
    nine. At the published threshold the reference is walked layer by layer
    as a run walks it; at 0.5 it is ``hidden``, a token's step selected."""
    from deepspeed_tpu.inference.v2 import InferenceEngineV2

    if kernel:
        monkeypatch.setenv("DSTPU_FORCE_PAGED_KERNEL", "1")
    over = {**(WIDE if kernel else {}), "early_exit_threshold": threshold}
    model = rehearsal_model(cell, **over)
    w = seeded(model)
    eng = InferenceEngineV2(model, w.tree_as(dtype), dtype=dtype,
                            **cell.mix(True)["engine"])
    assert eng.kv.shape[0] == 9 == model.config.pool_layers
    samples, ids, rows = sampled()
    cfg = {**reference_config(cell, True),
           **(WIDE_PUBLISHED if kernel else {}),
           "early_exit_threshold": threshold}
    if threshold < 1:
        lg = reference_logits(cfg, w.tree(), ids)
        want = np.stack([np.asarray(lg[k])[rows[k]] for k in range(len(rows))])
    else:
        want = check.serve_reference(cfg, w, ids, rows)
    got = engine_logits(eng, samples)
    if dtype == jnp.float32:
        np.testing.assert_allclose(got, want, atol=F32_TOL)
    else:
        assert check.logits_rel_err(got, want) < limit
    assert check.weights_mismatch_share(eng.params, w, jnp.dtype(dtype)) == 0


@pytest.fixture(scope="module")
def served(cell):
    """(model, float32 tree, the rehearsal engine's geometry)."""
    model = rehearsal_model(cell)
    return model, seeded(model).tree_as(jnp.float32), cell.mix(True)["engine"]


def test_a_prefix_from_the_prefix_cache_is_the_cold_request(served):
    """A prompt of 70 tokens served, flushed and served again with the prefix
    cache on: the second time its first four blocks are hits, so only what
    follows them is computed, over keys and values of ALL nine cache layers
    that the first request left (a block is one index of the pool's block
    axis, every layer with it). Its logits and those of three decoded
    tokens are the cold engine's."""
    from deepspeed_tpu.inference.v2 import InferenceEngineV2

    model, params, geometry = served
    rng = np.random.default_rng(5)
    prompt, forced = rng.integers(0, 512, 70).tolist(), [3, 7, 11]

    def serve(engine, uid):
        rows = [engine.put([uid], [prompt], greedy=False)[uid]]
        for tok in forced:
            rows.append(engine.decode_step({uid: tok}, greedy=False)[uid])
        engine.flush(uid)
        return np.stack(rows)

    cold = serve(InferenceEngineV2(model, params, dtype=jnp.float32,
                                   **geometry), 1)
    warm = InferenceEngineV2(model, params, dtype=jnp.float32,
                             **{**geometry, "prefix_cache": True})
    first = serve(warm, 1)
    hits = warm.block_mgr.stats["hit_blocks"]
    again = serve(warm, 2)
    assert warm.block_mgr.stats["hit_blocks"] - hits == 4
    np.testing.assert_allclose(first, cold, atol=1e-6)
    np.testing.assert_allclose(again, cold, atol=1e-5)


def test_a_copy_on_write_and_a_swap_move_all_nine_cache_layers(served):
    """The engine's block programs address a block as one index of the
    pool's block axis: a copy-on-write copies every cache layer of it, and a
    sequence swapped out to the host and back decodes the token it would
    have (a swap that kept one step's layers would read zeros in the
    others)."""
    from deepspeed_tpu.inference.v2 import InferenceEngineV2

    model, params, geometry = served
    eng = InferenceEngineV2(model, params, dtype=jnp.float32,
                            **{**geometry, "prefix_cache": True,
                               "host_tier_blocks": 16})
    rng = np.random.default_rng(7)
    prompt = rng.integers(0, 512, 40).tolist()
    eng.put([1], [prompt], greedy=False)
    kv = eng.kv
    src = eng.state.seqs[1].blocks[0]
    assert all(float(jnp.max(jnp.abs(kv[layer, :, src]))) > 0
               for layer in range(9))
    copied = eng._get_cow()(jnp.copy(kv), jnp.int32(src), jnp.int32(39))
    assert np.array_equal(copied[:, :, 39], kv[:, :, src])
    stay = np.asarray(eng.decode_step({1: 5}, greedy=False)[1])
    eng.flush(1)
    eng.put([2], [prompt], greedy=False)
    assert eng.swap_out(2) and eng.swap_resident(2)
    assert eng.swap_in(2)
    back = np.asarray(eng.decode_step({2: 5}, greedy=False)[2])
    np.testing.assert_allclose(back, stay, atol=1e-6)


def test_the_scheduler_recomputes_a_preempted_sequence(served):
    """Six requests through ``ContinuousBatchScheduler`` on four slots and a
    pool too small for all of them (here the pool binds before the slots do,
    as in the cell): a preemption is recomputed from its prompt into all nine
    cache layers, and every request's greedy tokens are those it gets alone
    on a fresh engine."""
    from deepspeed_tpu.inference.v2 import InferenceEngineV2
    from deepspeed_tpu.serve import ContinuousBatchScheduler
    from deepspeed_tpu.serve.request import RequestState

    model, params, geometry = served
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 512, n).tolist()
               for n in (20, 25, 30, 22, 18, 28)]

    def serve(prompts, num_blocks):
        engine = InferenceEngineV2(model, params, dtype=jnp.float32, **{
            **geometry, "num_blocks": num_blocks})
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


# -- (c) --------------------------------------------------------------------

@pytest.fixture(scope="module")
def walked(cell):
    """(configuration, seeded weights, ids, rows, the sound walk's logits)."""
    w = seeded(rehearsal_model(cell), seed=5)
    _, ids, rows = sampled()
    cfg = reference_config(cell, True)
    return cfg, w, ids, rows, check.serve_reference(cfg, w, ids, rows)


def test_the_walk_of_groups_is_hidden(walked):
    """``groups(cfg)`` = the layers and the closing norm, three times, walked
    by ``check.serve_reference`` layer by layer with ``final`` the identity,
    gives ``hidden``'s logits at the rows the check compares."""
    cfg, w, ids, rows, want = walked
    assert ouro.groups(cfg) == [("blocks", 3), ("loop", 1)] * 3
    lg = reference_logits(cfg, w.tree(), ids)
    np.testing.assert_allclose(
        want, np.stack([np.asarray(lg[k])[rows[k]] for k in range(len(rows))]),
        atol=1e-5)
    with pytest.raises(ValueError, match="hidden"):
        ouro.groups({**cfg, "early_exit_threshold": 0.5})


@pytest.mark.parametrize("ablate", ["steps:2", "no_close", "no_post_norms",
                                    "shared_cache", "fp8"])
def test_every_control_is_far_over_the_float32_agreement(walked, ablate):
    """The reference with one step fewer, without the norm between steps,
    without the two post-sublayer norms, with steps 2 and 3 attending step
    1's keys and values (what a missing step offset in ``pool_layer``
    computes), and in fp8: each differs from the sound reference by over
    1e-2 of the logits' spread, where test (b) holds the float32 program to
    ~1e-4 of it."""
    cfg, w, ids, rows, want = walked
    if ablate == "fp8":
        got = check.serve_reference(cfg, w, ids, rows, ein=ein_fp8)
    elif ablate == "shared_cache":
        lg = reference_logits({**cfg, "ablate": ablate}, w.tree(), ids)
        got = np.stack([np.asarray(lg[k])[rows[k]] for k in range(len(rows))])
    else:
        assert len(ouro.groups({**cfg, "ablate": ablate})) == {
            "steps:2": 4, "no_close": 4, "no_post_norms": 6}[ablate]
        got = check.serve_reference({**cfg, "ablate": ablate}, w, ids, rows)
    assert check.logits_rel_err(got, want) > 1e-2


# -- (d) --------------------------------------------------------------------

def test_the_cells_configuration_is_the_whole_model():
    """``benchmark/configs/ouro-2.6b.json``: every number of the catalog's
    row, nothing reduced: 48 layers, the whole vocabulary, four steps."""
    file = load_json("configs", "ouro-2.6b.json")
    cfg = TransformerConfig(**file["model"])
    layer = 4 * 2048 * 2048 + 3 * 2048 * 5632 + 4 * 2048
    assert layer == 51_388_416
    # a layer of weights counts once; the closing norm and the gate 2 x 2048 + 1
    assert cfg.num_parameters == cfg.num_active_parameters \
        == 48 * layer + 2 * 49152 * 2048 + 2 * 2048 + 1 == 2_667_974_657
    shapes = jax.eval_shape(TransformerLM(cfg).init_params,
                            jax.random.PRNGKey(0))
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes)) \
        == 2_667_974_657
    assert jax.tree.map(lambda a: a.shape, shapes["loop"]) == {
        "norm_scale": (1, 2048), "exit_w": (1, 2048), "exit_b": (1,)}
    # cache layers are the weights' four times over: 1.5 MiB a token
    assert (cfg.num_layers, cfg.loop_steps, cfg.pool_layers) == (48, 4, 192)
    assert cfg.kv_row == (128, 128) and cfg.pool_heads == 16
    assert cfg.pool_layers * cfg.pool_heads * sum(cfg.kv_row) * 2 == 1_572_864
    assert cfg.cache_kinds == {"attn": (("kv_blocks", 4 * 2 * 16 * 128 * 2),)}
    # a step's layers run again: 6 N for the head and the embedding once,
    # the layers' matrices and attention four times
    S = 1024
    once = 6 * 2 * 49152 * 2048 + 6 * (2 * 2048 + 1 + 48 * 4 * 2048)
    again = 48 * (6 * (layer - 4 * 2048) + 12 * 16 * 128 * S)
    assert cfg.flops_per_token(S) == once + 4 * again
    row = dict(head_dim=128, hidden_act="silu", hidden_size=2048,
               intermediate_size=5632, max_position_embeddings=65536,
               max_window_layers=48, model_type="ouro",
               num_attention_heads=16, num_hidden_layers=48,
               num_key_value_heads=16, rms_norm_eps=1e-06, rope_scaling=None,
               rope_theta=1000000, sliding_window=None,
               tie_word_embeddings=False, total_ut_steps=4,
               early_exit_threshold=1, use_sliding_window=False,
               vocab_size=49152)
    for key, want in row.items():
        assert file[key] == want, key
    assert file["layer_types"] == ["full_attention"] * 48
    assert file["reduced"] == [] and file["changed"] == {}
    assert (cfg.rope_theta, cfg.norm_eps, cfg.post_norms,
            cfg.early_exit_threshold) == (1e6, 1e-6, True, 1.0)
    engine = load_json("traffic", "serve-qa.json")["engine"]
    assert engine["prefix_cache"] is False
    # 16 one-token rows and a chunk of 256
    assert engine["token_budget"] == engine["max_seqs"] + engine[
        "prefill_chunk"] == 272
    # the pool, not the slots, caps a round: 16 sequences of the longest
    # request would hold 16 x 20 blocks
    assert engine["num_blocks"] - 1 < engine["max_seqs"] * (
        engine["max_seq_len"] // engine["block_size"]) // 3
    assert engine["num_blocks"] * 192 * 16 * 64 * 256 * 2 < 10 * 2 ** 30


# -- (e) --------------------------------------------------------------------

def test_the_cells_rehearsal_reports_the_pool_and_the_loops_seam(cell):
    """Both runs of the cell at its tiny preset (``test_cells.py``'s way): the
    traced one reports every per-layer metric the cell lists, the share of
    the roofline and the decode kernel's own time aside (the CPU has no peak
    and runs the XLA form); the pool's fill is read off the new pair of
    attributes and the closing norms have a scope of their own."""
    from tests.benchmark.test_cells import rehearse

    _, traced = rehearse(CELL, 1)
    got = traced["metrics"]
    listed = {m["name"] for m in cell.per_layer}
    assert {n for n in listed if "roofline" not in n} \
        - {"kernel.paged_decode_ms"} <= set(got)
    assert 0 < got["cache.pool_fill.qa"]["value"] <= 100
    assert got["model.loop_close_ms"]["value"] >= 0
    assert got["engine.compiles.serve"]["value"] == 0


def spans(*attrs):
    return [tracing.Record(i + 1, "engine.dispatch", 0, 1, 0, a)
            for i, a in enumerate(attrs)]


def test_the_paged_share_counts_every_steps_cache_layers(cell, monkeypatch):
    """``kernel.paged_roofline_share.qa``: the dispatches' contexts x 48
    layers x 4 steps x 16 KV heads of 128 (the two readers before it count
    ``num_layers`` and would read a quarter of the kernel's work); bound by
    bytes; nothing where the model names no ``loop_steps``, the program
    recorded no dispatch or the chip's peak is unknown."""
    from benchmark.readers import looped_paged_roofline

    flops, nbytes = paged_attention.dispatches(3_000, 3_000, 7, 192, 16, 16,
                                               128)
    least = max(flops / 197e12, nbytes / 819e9)
    assert least == nbytes / 819e9
    assert nbytes == 192 * (2 * 3_000 * 16 * 128 * 2 + 2 * 7 * 16 * 128 * 2)
    trace = {"ops": {"%paged_decode.3 = f32[16,2048]{1,0} custom-call(%p.1), "
                     'custom_call_target="tpu_custom_call"': (4 * least, 4)},
             "clock_offset_ns": None}
    ctx = {"trace": trace, "cell": cell, "counters": {}, "spans": {},
           "peak": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}
    monkeypatch.setattr(tracing, "_buf", spans(
        {"rows": 4, "ctx_tokens": 1_800, "ctx_tokens_by_row": 1_800},
        {"rows": 3, "ctx_tokens": 1_200, "ctx_tokens_by_row": 1_200}))
    assert looped_paged_roofline.read(ctx) == pytest.approx(25.0)
    assert looped_paged_roofline.read({**ctx, "peak": None}) is None
    plain = Cell("gpt2-medium.serve-chat", load_spec())
    assert looped_paged_roofline.read({**ctx, "cell": plain}) is None
    monkeypatch.setattr(tracing, "_buf", [])
    assert looped_paged_roofline.read(ctx) is None
