"""The two mixers of a ``layer_types`` model and the second kind of cache, at
small sizes on the CPU: (a) lightning attention: the blocked form and the
Pallas decode kernel against the plain recurrence; (b) the sparse selector:
the chosen blocks against a sort, the compacted tables against masked dense
attention, the compressed-key cache however a context is cut into steps; (c)
state slots: the allocator's invariants, reuse, finish, preemption, and what
the engine refuses for such a model; (d) the configuration's counts.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.v2 import InferenceEngineV2
from deepspeed_tpu.inference.v2.ragged_manager import (BlockedKVCache,
                                                       StateSlots)
from deepspeed_tpu.models.transformer import TransformerConfig, TransformerLM
from deepspeed_tpu.ops.transformer import linear_attention as la
from deepspeed_tpu.ops.transformer import paged_attention as pa
from deepspeed_tpu.ops.transformer import sparse_attention as sa
from deepspeed_tpu.resilience.errors import (EngineUsageError,
                                             PoolExhaustedError)

TYPES = ("sparse_attn", "linear_attn", "linear_attn", "sparse_attn")
SPEC = sa.SparseSpec(block=16, kernel=8, stride=4, window_blocks=2,
                     init_blocks=1, topk=4, dense_len=64).check()


def model_config(**kw):
    return TransformerConfig(**{**dict(
        vocab_size=256, hidden_size=128, num_layers=4, num_heads=8,
        num_kv_heads=2, intermediate_size=192, max_seq_len=128,
        pos_embedding="rope", norm="rmsnorm", activation="swiglu",
        tie_embeddings=False, norm_eps=1e-6, layer_types=TYPES, qk_norm=True,
        attn_output_gate=True, sparse_kernel_size=8, sparse_kernel_stride=4,
        sparse_window=32, sparse_init_blocks=1, sparse_topk=4,
        sparse_dense_len=64, sparse_block_size=16, linear_chunk=16,
        embed_scale=12.0, scale_depth=1.4, scale_depth_layers=32,
        dim_model_base=32), **kw})


ENGINE = dict(max_seqs=4, max_seq_len=128, block_size=16, token_budget=36,
              prefill_chunk=32, num_blocks=40, prefix_cache=False)


@pytest.fixture(scope="module")
def model():
    return TransformerLM(model_config())


@pytest.fixture(scope="module")
def params(model):
    tree = model.init_params(jax.random.PRNGKey(3))
    # norm scales away from one, so that no norm is the identity
    return jax.tree_util.tree_map_with_path(
        lambda p, a: a * 1.3 if str(p[-1].key).endswith("_scale") else a, tree)


def engine(model, params, **kw):
    return InferenceEngineV2(model, params, paged=True, dtype=jnp.float32,
                             **{**ENGINE, **kw})


@pytest.fixture(scope="module")
def spare_engines(model, params):
    """``take(n)``: n engines of the default ``ENGINE`` with no sequence in
    them, built once a module and flushed between uses (an engine compiles
    its own programs: five tests' oracles share three). Their slots keep
    what the last sequence left, which is what a slot is allowed to hold."""
    built = []

    def take(n):
        while len(built) < n:
            built.append(engine(model, params))
        for eng in built[:n]:
            for uid in list(eng.state.seqs):
                eng.flush(uid)
            assert eng.block_mgr.slots.in_use == 0
        return built[:n]

    return take


# -- (a) lightning attention ------------------------------------------------

def recurrence(q, k, v, state=None):
    """o (S, h, d) and the last state of the plain recurrence, in float64."""
    S, H, d = q.shape
    lam = np.exp(-np.asarray(la.head_decay_rates(H), np.float64))
    s = np.zeros((H, d, d)) if state is None else np.asarray(state, np.float64)
    out = []
    for t in range(S):
        s = lam[:, None, None] * s + np.einsum("hk,hv->hkv", k[t], v[t])
        out.append(np.einsum("hk,hkv->hv", q[t], s))
    return np.stack(out), s


@pytest.mark.parametrize("lens", [(16, 16), (16, 5), (3,)])
def test_the_blocked_form_is_the_recurrence(lens):
    """Tiles of one sequence in one call (the second partly filled), a fresh
    start, and a start from a state another step left."""
    rng = np.random.default_rng(0)
    H, d, C = 4, 32, 16
    state = jnp.asarray(rng.normal(size=(2, 3, H, d, d)), jnp.float32)
    n = len(lens)
    q, k, v = (rng.normal(size=(n, C, H, d)).astype(np.float32)
               for _ in range(3))
    for fresh in (True, False):
        o, new = la.chunk_tiles(
            state, jnp.int32(1), jnp.full((n,), 2, jnp.int32),
            jnp.asarray(lens, jnp.int32), *(jnp.asarray(a) for a in (q, k, v)),
            jnp.asarray([fresh] + [False] * (n - 1)))
        flat = [np.concatenate([a[i, :m] for i, m in enumerate(lens)])
                for a in (q, k, v)]
        want, last = recurrence(*flat, None if fresh else state[1, 2])
        got = np.concatenate([np.asarray(o)[i, :m] for i, m in enumerate(lens)])
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(new[1, 2], last, rtol=2e-4, atol=2e-4)
        # nothing else of the slot array moved
        np.testing.assert_array_equal(new[0], state[0])
        np.testing.assert_array_equal(new[1, :2], state[1, :2])


#: (rows' slots, fresh rows, heads, head width, rows a cell at most) of a step
#: of one-token rows: what the kernel's walk over its live rows has to get
#: right. Slot 0 is a dead row; five slots a layer beside the trash slot
DECODE_STEPS = {
    # live rows on their slots, a fresh one, then padding rows
    "live_prefix": ([2, 4, 1, 0, 0, 0], [1, 3], 16, 32, 64),
    "no_live_row": ([0] * 8, [0, 5], 16, 32, 64),
    "every_row_live": ([3, 1, 5, 2, 4], [2], 16, 32, 64),
    "live_rows_scattered": ([0, 3, 0, 0, 5, 0, 1, 0], [4], 16, 32, 64),
    # two cells of four rows: the first has nothing to walk
    "one_live_row_in_the_last_cell": ([0, 0, 0, 0, 0, 0, 2, 0], [], 16, 32, 4),
    # seven rows have no divisor up to four but one: a row a cell
    "a_row_a_cell": ([4, 0, 0, 1, 0, 5, 2], [3], 16, 32, 4),
    "a_fresh_row_beside_a_started_one": ([0, 2, 5, 0], [1], 16, 32, 64),
    # the serve-doc16k cell's heads, bfloat16 operands: four head blocks
    "the_cells_heads": ([0, 2, 0, 1], [3], 32, 128, 64),
}


@pytest.mark.parametrize("step", DECODE_STEPS)
def test_decode_rows_kernel_and_xla_are_the_recurrence(monkeypatch, step):
    """One token a row, in the Pallas kernel (interpreted) and in the XLA
    form: o and the new state of a live row are the recurrence's, a dead
    row's o is zeros, and nothing else of the slot array moved: the other
    layers, every slot no live row names and, under the kernel, the trash
    slot are bit for bit what they were."""
    slots, fresh_rows, H, d, cell_rows = DECODE_STEPS[step]
    rng = np.random.default_rng(1)
    R, layer = len(slots), 1
    dtype = jnp.bfloat16 if d == 128 else jnp.float32
    tol = dict(rtol=1e-4, atol=1e-4) if d == 128 else dict(rtol=1e-5,
                                                           atol=1e-5)
    state = jnp.asarray(rng.normal(size=(3, 6, H, d, d)), jnp.float32)
    fresh = np.zeros(R, bool)
    fresh[fresh_rows] = True
    q, k, v = (jnp.asarray(rng.normal(size=(R, H, d)), dtype)
               for _ in range(3))
    args = (state, jnp.int32(layer), jnp.asarray(slots, jnp.int32), q, k, v,
            jnp.asarray(fresh))
    xla = la.decode_rows(*args)
    monkeypatch.setenv("DSTPU_FORCE_PAGED_KERNEL", "1")
    monkeypatch.setattr(la, "DECODE_CELL_ROWS", cell_rows)
    assert pa.kernels_wanted()
    assert la.rows_per_cell(R) == {"one_live_row_in_the_last_cell": 4,
                                   "a_row_a_cell": 1}.get(step, R)
    kernel = jax.jit(lambda *a: la.decode_rows(*a))(*args)
    live = [r for r in range(R) if slots[r]]
    unnamed = [n for n in range(1, state.shape[1]) if n not in slots]
    for name, (o, new) in (("xla", xla), ("kernel", kernel)):
        assert o.shape == (R, H, d) and o.dtype == jnp.float32, name
        for r in live:
            want, last = recurrence(
                *(np.asarray(a, np.float64)[r:r + 1] for a in (q, k, v)),
                None if fresh[r] else state[layer, slots[r]])
            np.testing.assert_allclose(o[r], want[0], err_msg=name, **tol)
            np.testing.assert_allclose(new[layer, slots[r]], last,
                                       err_msg=name, **tol)
        np.testing.assert_array_equal(new[0], state[0], err_msg=name)
        np.testing.assert_array_equal(new[2], state[2], err_msg=name)
        np.testing.assert_array_equal(new[layer, unnamed],
                                      state[layer, unnamed], err_msg=name)
    o, new = kernel
    dead = [r for r in range(R) if not slots[r]]
    np.testing.assert_array_equal(np.asarray(o)[dead], 0.0)
    np.testing.assert_array_equal(new[layer, 0], state[layer, 0])


@pytest.mark.parametrize("width,kernel", [(128, True), (64, False)])
def test_on_a_chip_the_kernel_takes_heads_that_are_whole_lane_tiles(
        monkeypatch, width, kernel):
    """Where Mosaic compiles it (a TPU backend, no interpreter) the kernel is
    taken for heads of 128 and the XLA form for any other width: read off the
    traced program, nothing compiles."""
    monkeypatch.delenv("DSTPU_PALLAS_INTERPRET", raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    sds = jax.ShapeDtypeStruct
    act = sds((8, 16, width), jnp.bfloat16)
    traced = jax.make_jaxpr(lambda *args: la.decode_rows(*args))(
        sds((2, 9, 16, width, width), jnp.float32), sds((), jnp.int32),
        sds((8,), jnp.int32), act, act, act, sds((8,), jnp.bool_))
    assert ("pallas_call" in str(traced)) == kernel


# -- (b) the sparse selector ------------------------------------------------

def sorted_choice(score, n):
    """The topk best blocks of each row by a stable sort (ties: the lower
    block), every block of a row under dense_len."""
    nb = score.shape[-1]
    order = np.argsort(-score, axis=-1, kind="stable")
    chosen = np.zeros(score.shape, bool)
    np.put_along_axis(chosen, order[..., :SPEC.topk], True, axis=-1)
    own = np.arange(nb)[None, None] < (-(-n // SPEC.block))[:, None, None]
    return np.where((n > SPEC.dense_len)[:, None, None], chosen, True) & own


def test_the_chosen_blocks_are_the_topk_with_ties_to_the_lower_block():
    rng = np.random.default_rng(2)
    R, nh, kvh, hd, nb = 9, 8, 2, 16, 8
    J = SPEC.max_keys(nb * SPEC.block)
    q = jnp.asarray(rng.normal(size=(R, nh, hd)), jnp.float32)
    keys = jnp.asarray(rng.normal(size=(R, J, kvh * hd)), jnp.float32)
    # tied keys: rows 0 and 1 score every undecided block alike
    keys = keys.at[:2].set(0.0)
    n = jnp.asarray([128, 97, 128, 113, 81, 65, 64, 17, 0], jnp.int32)
    score = np.asarray(sa.block_scores(q, keys, n, SPEC, nb, hd ** -0.5))
    chosen, count = sa.choose(q, keys, n, SPEC, nb, hd ** -0.5)
    np.testing.assert_array_equal(chosen, sorted_choice(score, np.asarray(n)))
    np.testing.assert_array_equal(count, np.asarray(chosen).sum(-1))
    assert int(count[0, 0]) == SPEC.topk and int(count[6, 0]) == 4
    # forced: block 0 and the window ending at the row's own block
    assert bool(chosen[0, 0, 0]) and bool(chosen[0, 0, 7]) \
        and bool(chosen[0, 0, 6])
    # the tie among blocks 1..5 of row 0 goes to block 1
    assert np.asarray(chosen[0, 0]).tolist() == [True, True, False, False,
                                                 False, False, True, True]


def test_compacted_tables_attend_what_masked_dense_attention_attends(
        monkeypatch):
    """Decode rows over their chosen blocks, through the gather path and
    through the kernel (interpreted), against attention over the whole
    context with the unchosen blocks masked."""
    rng = np.random.default_rng(3)
    R, nh, kvh, hd, nb, NB = 4, 8, 2, 16, 8, 40
    J = SPEC.max_keys(nb * SPEC.block)
    pool = jnp.asarray(rng.normal(size=(2, kvh, NB, SPEC.block, 2 * hd)),
                       jnp.float32)
    tables = jnp.asarray(rng.permutation(np.arange(1, NB))[:R * nb].reshape(
        R, nb), jnp.int32)
    n = jnp.asarray([128, 100, 40, 0], jnp.int32)
    tables = tables.at[3].set(0)
    q = jnp.asarray(rng.normal(size=(R, nh, hd)), jnp.float32)
    keys = jnp.asarray(rng.normal(size=(R, J, kvh * hd)), jnp.float32)
    scale = hd ** -0.5
    chosen, _ = sa.choose(q, keys, n, SPEC, nb, scale)
    gk, gv = pa.gather_context(pool, 1, tables)          # (R, T, kvh, hd)
    kpos = np.arange(nb * SPEC.block)
    mask = np.repeat(np.asarray(chosen), SPEC.block, -1) \
        & (kpos[None, None] < np.asarray(n)[:, None, None])
    logit = np.einsum("rhgd,rthd->rhgt", np.asarray(q).reshape(R, kvh, -1, hd),
                      np.asarray(gk)) * scale
    logit = np.where(mask[:, :, None], logit, -1e30)
    p = np.exp(logit - logit.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    want = np.einsum("rhgt,rthd->rhgd", p, np.asarray(gv)).reshape(R, nh, hd)
    for kernel in (False, True):
        if kernel:
            monkeypatch.setenv("DSTPU_FORCE_PAGED_KERNEL", "1")
        got, counts = jax.jit(lambda *a: sa.decode_rows(*a, SPEC, scale))(
            q, pool, jnp.int32(1), tables, keys, n)
        np.testing.assert_allclose(got[:3], want[:3], rtol=2e-5, atol=2e-5)
        np.testing.assert_array_equal(np.asarray(got[3]), 0.0)
        # rows 0 and 1 chose 4 of their 8 and 7 blocks, row 2 attends its 3
        assert counts.tolist() == [kvh * (4 + 4 + 3), kvh * (8 + 7 + 3)]


@pytest.mark.parametrize("cuts", [(100,), (32, 16, 16, 16, 16, 4),
                                  (7, 1, 1, 23, 50, 1, 17)])
def test_the_compressed_keys_do_not_depend_on_how_a_context_is_cut(cuts):
    """Keys written run by run (a run: one step's tokens of the sequence)
    equal the means of the pool's keys over each window."""
    rng = np.random.default_rng(4)
    kvh, hd, nb, NB = 2, 16, 8, 12
    pool = jnp.asarray(rng.normal(size=(3, kvh, NB, SPEC.block, 2 * hd)),
                       jnp.float32)
    table = jnp.asarray(rng.permutation(np.arange(1, NB))[:nb], jnp.int32)[None]
    ck = sa.init_keys(2, 3, kvh, SPEC.max_keys(nb * SPEC.block), hd,
                      jnp.float32)
    first = 0
    for m in cuts:
        ck = sa.write_keys(ck, pool, 2, 1, table, jnp.asarray([2], jnp.int32),
                           jnp.asarray([first], jnp.int32),
                           jnp.asarray([m], jnp.int32), SPEC, max(cuts))
        first += m
    k = np.asarray(pa.gather_context(pool, 2, table)[0])[0]     # (T, kvh, hd)
    n_keys = (first - SPEC.kernel) // SPEC.stride + 1
    want = np.stack([k[SPEC.stride * j:SPEC.stride * j + SPEC.kernel].mean(0)
                     for j in range(n_keys)]).reshape(n_keys, kvh * hd)
    np.testing.assert_allclose(ck[1, 2, :n_keys], want, rtol=1e-5, atol=1e-6)
    # no other slot or layer was written, the trash slot aside
    np.testing.assert_array_equal(ck[0], 0.0)
    np.testing.assert_array_equal(ck[1, 1], 0.0)
    np.testing.assert_array_equal(ck[1, 2, n_keys:], 0.0)


def test_the_selector_refuses_sizes_it_cannot_hold():
    with pytest.raises(ValueError, match="multiples of the stride"):
        SPEC._replace(kernel=6).check()
    with pytest.raises(ValueError, match="dense_len"):
        SPEC._replace(dense_len=48).check()
    with pytest.raises(ValueError, match="forced"):
        SPEC._replace(window_blocks=4).check()


# -- (c) state slots ----------------------------------------------------------

def test_a_slot_has_one_owner_and_is_free_after_its_sequence():
    slots = StateSlots(2)
    assert slots.take(10) == slots.take(10) and slots.in_use == 1
    other = slots.take(11)
    assert other != slots.slot_of(10) and slots.free_slots == 0
    with pytest.raises(PoolExhaustedError):
        slots.take(12)
    slots.check_invariants([10, 11])
    with pytest.raises(AssertionError, match="held by"):
        slots.check_invariants([10])
    # the owner's first step resets the slot, so it must start at position 0
    with pytest.raises(AssertionError, match="last owner"):
        slots.begin(10, 5)
    assert slots.begin(10, 0) == 1 + slots.slot_of(10)
    assert slots.begin(10, 32) == 1 + slots.slot_of(10)
    freed = slots.slot_of(10)
    slots.free(10)
    slots.free(10)                                   # idempotent
    assert slots.free_slots == 1 and slots.take(12) == freed
    with pytest.raises(AssertionError, match="last owner"):
        slots.begin(12, 3)                           # clean went with 10
    slots.check_invariants([11, 12])


def test_the_block_cache_refuses_a_prefix_index_beside_state_slots():
    with pytest.raises(ValueError, match="prefix"):
        BlockedKVCache(8, 16, 4, prefix_cache=True, state_slots=2)
    mgr = BlockedKVCache(8, 16, 4, state_slots=2)
    assert mgr.slots.n_slots == 2
    assert BlockedKVCache(8, 16, 4).slots is None


def test_the_engine_refuses_what_a_state_cannot_do(model, params,
                                                   spare_engines):
    with pytest.raises(ValueError, match="prefix_cache=False"):
        engine(model, params, prefix_cache=True)
    with pytest.raises(ValueError, match="decode_horizon=1"):
        engine(model, params, decode_horizon=4)
    eng, = spare_engines(1)
    with pytest.raises(EngineUsageError, match="state-slot"):
        eng._get_fused()
    with pytest.raises(EngineUsageError, match="state-slot"):
        eng._get_verify()
    eng.put([1], [[5, 6, 7]])
    assert eng.swap_out(1) is False and eng.export_ready(1) is False
    # the sparse layers select by the model's block, so the pool has no other
    with pytest.raises(ValueError, match="sparse_block_size"):
        engine(model, params, block_size=32, num_blocks=20)


def test_sparse_layers_alone_hold_state_slots_too():
    """A sparse layer's compressed keys lie by state slot, so a model of
    sparse layers alone declares slots, gets them from the engine, is refused
    a prefix index, and answers the same however its prompt is cut."""
    cfg = model_config(num_layers=2, layer_types=("sparse_attn",) * 2)
    assert cfg.holds_state and cfg.cache_kinds == {
        "sparse_attn": (("kv_blocks", 2 * 2 * 2 * 16),
                        ("state_slot", 2 * 32 * 2 * 16))}
    lm = TransformerLM(cfg)
    tree = lm.init_params(jax.random.PRNGKey(4))
    with pytest.raises(ValueError, match="prefix_cache=False"):
        engine(lm, tree, prefix_cache=True)
    eng = engine(lm, tree)
    assert {k: v.shape for k, v in eng.slot_cache.items()} == {
        "blocks_0": (2, 5, 32, 32)}
    rng = np.random.default_rng(8)
    prompt, forced = rng.integers(0, 256, 90).tolist(), [3, 4, 5]
    got = decode(eng, 1, prompt, forced)
    assert eng.block_mgr.slots.in_use == 1
    other = decode(engine(lm, tree, prefill_chunk=16, token_budget=20), 1,
                   prompt, forced)
    np.testing.assert_allclose(got, other, rtol=1e-5, atol=1e-5)


def decode(eng, uid, prompt, forced):
    rows = [eng.put([uid], [prompt])[uid]]
    for tok in forced:
        rows.append(eng.decode_step({uid: int(tok)})[uid])
    return np.stack(rows)


def test_a_second_sequence_never_reads_the_firsts_state(model, params,
                                                        spare_engines):
    """Two sequences through one slot, one after the other, and a third that
    is preempted half way and recomputed: each equals a fresh engine's."""
    rng = np.random.default_rng(5)
    a, b = (rng.integers(0, 256, n).tolist() for n in (90, 75))
    forced = rng.integers(0, 256, 3).tolist()
    eng = engine(model, params, max_seqs=1)
    slots = eng.block_mgr.slots
    first = decode(eng, 1, a, forced)
    assert slots.in_use == 1 and not eng.can_schedule(1)
    state_after_a = np.asarray(eng.slot_cache["blocks_1"][:, 1])
    assert np.abs(state_after_a).max() > 0
    eng.flush(1)
    assert slots.in_use == 0 and eng.can_schedule(1)
    eng.block_mgr.check_invariants(eng.state.seqs.values())
    second = decode(eng, 2, b, forced)          # the same slot, never zeroed
    assert slots.slot_of(2) == 0
    np.testing.assert_allclose(
        second, decode(engine(model, params, max_seqs=1), 7, b, forced),
        rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        first, decode(spare_engines(1)[0], 8, a, forced), rtol=1e-5,
        atol=1e-5)
    # preemption frees the slot; the victim recomputes from its prompt
    eng.preempt(2)
    assert slots.in_use == 0
    again = decode(eng, 2, b, forced)
    np.testing.assert_allclose(again, second, rtol=1e-5, atol=1e-5)
    eng.block_mgr.check_invariants(eng.state.seqs.values())


def test_rows_of_two_sequences_in_one_step_keep_their_own_state(spare_engines):
    """A decode row and another sequence's prefill chunk in the same mixed
    step, and two decode rows in one round, against engines of their own."""
    rng = np.random.default_rng(6)
    a, b = (rng.integers(0, 256, n).tolist() for n in (80, 70))
    eng, solo_a, solo_b = spare_engines(3)
    eng.put([1], [a])
    eng.put([2], [b], max_steps=1)              # b's first chunk alone
    both = eng.put([1, 2], [[9], None])         # a decodes while b prefills
    solo_a.put([1], [a])
    np.testing.assert_allclose(both[1], solo_a.decode_step({1: 9})[1],
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(both[2], solo_b.put([2], [b])[2], rtol=1e-5,
                               atol=1e-5)
    got = eng.decode_step({1: 3, 2: 4})
    np.testing.assert_allclose(got[1], solo_a.decode_step({1: 3})[1],
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(got[2], solo_b.decode_step({2: 4})[2],
                               rtol=1e-5, atol=1e-5)


def test_the_greedy_program_counts_chosen_and_context_blocks(model, params):
    """A greedy dispatch fetches two counts behind its tokens: the blocks its
    one-token rows chose and their contexts', over kv heads and sparse
    layers. A context past dense_len chooses fewer than it has."""
    eng = engine(model, params)
    assert eng._step_counts == ("sel_blocks", "ctx_blocks")
    rng = np.random.default_rng(7)
    eng.put([1, 2], [rng.integers(0, 256, n).tolist() for n in (100, 40)],
            greedy=True)
    handle = eng.decode_dispatch({1: 5, 2: 6})
    fetched = np.asarray(handle._dev)
    handle.fetch()
    sel, ctx = fetched[eng.max_seqs:].tolist()
    # contexts of 102 and 42 tokens: 7 and 3 blocks, over 2 kv heads and 2
    # sparse layers; the first is past dense_len (64) and chooses 4
    assert ctx == 2 * 2 * (7 + 3) and sel == 2 * 2 * (4 + 3)
    assert eng.ragged_cache_size <= 2


# -- (d) the configuration ----------------------------------------------------

def test_the_config_counts_both_mixers(model, params):
    cfg = model.config
    assert cfg.type_runs == (("blocks_0", "sparse_attn", 1, 1),
                             ("blocks_1", "linear_attn", 2, 0),
                             ("blocks_2", "sparse_attn", 1, 1))
    assert sorted(k for k in params if k.startswith("blocks")) == [
        "blocks_0", "blocks_1", "blocks_2"]
    assert cfg.num_parameters == cfg.num_active_parameters \
        == sum(a.size for a in jax.tree.leaves(params))
    assert cfg.pool_layers == 2 and cfg.holds_state
    # 32 compressed keys of 2 kv heads of 16 for max_seq_len 128, beside the
    # sparse layer's KV blocks
    assert cfg.cache_kinds == {
        "sparse_attn": (("kv_blocks", 2 * 2 * 2 * 16),
                        ("state_slot", 2 * 32 * 2 * 16)),
        "linear_attn": (("state_slot", 4 * 8 * 16 * 16),)}
    assert TransformerConfig().cache_kinds == {"attn": (("kv_blocks",
                                                         2 * 12 * 128),)}
    assert not TransformerConfig().holds_state
    assert cfg.residual_scale == pytest.approx(1.4 / 32 ** 0.5)
    nh, hd = 8, 16
    # a sparse layer scores the whole context up to dense_len, topk blocks
    # beyond it; a linear layer costs the same at any length
    short = 6 * cfg.num_parameters + 6 * 2 * 2 * nh * hd * 48 \
        + 6 * 2 * 2 * nh * hd * hd
    assert cfg.flops_per_token(48) == short
    assert cfg.flops_per_token(128) == cfg.flops_per_token(100_000) \
        == short + 6 * 2 * 2 * nh * hd * (64 - 48)
    with pytest.raises(ValueError, match="layer_types"):
        model_config(layer_types=("sparse_attn", "window"))
    with pytest.raises(NotImplementedError, match="paged"):
        model.logits(params, jnp.zeros((1, 4), jnp.int32))
    state = model.init_state_cache(4, 128, jnp.float32)
    assert {k: v.shape for k, v in state.items()} == {
        "blocks_0": (1, 5, 32, 32), "blocks_1": (2, 5, 8, 16, 16),
        "blocks_2": (1, 5, 32, 32)}
    assert model.segment_tile == 16
