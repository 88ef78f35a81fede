"""Pallas paged-decode attention kernel vs the XLA gather oracle
(reference ``tests/unit/inference/v2/kernels/ragged_ops`` blocked-flash
numerics). Interpret mode on the CPU mesh; the identical code path lowers via
Mosaic on TPU (validated on-chip)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.transformer.paged_attention import paged_decode_attention


@jax.jit
def oracle(q, kp, vp, tables, lens):
    kvh, NB, BS, hd = kp.shape
    B, MAXB = tables.shape
    gk = jnp.moveaxis(kp[:, tables], 0, 3).reshape(B, MAXB * BS, kvh, hd)
    gv = jnp.moveaxis(vp[:, tables], 0, 3).reshape(B, MAXB * BS, kvh, hd)
    nh = q.shape[1]
    qg = q.reshape(B, kvh, nh // kvh, hd)
    s = jnp.einsum("bhgd,bkhd->bhgk", qg.astype(jnp.float32),
                   gk.astype(jnp.float32)) * hd ** -0.5
    s = jnp.where(jnp.arange(MAXB * BS)[None, None, None] < lens[:, None, None, None],
                  s, -1e30)
    p = jax.nn.softmax(s, -1)
    out = jnp.einsum("bhgk,bkhd->bhgd", p, gv.astype(jnp.float32))
    return out.reshape(B, nh, hd).astype(q.dtype)


@pytest.mark.parametrize("kvh,nh", [(4, 4), (2, 8), (1, 8)])  # MHA, GQA, MQA
def test_paged_decode_matches_oracle(kvh, nh):
    B, hd, BS, MAXB = 3, 64, 16, 5
    NB = 1 + B * MAXB
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, nh, hd))
    kp = jax.random.normal(ks[1], (kvh, NB, BS, hd))
    vp = jax.random.normal(ks[2], (kvh, NB, BS, hd))
    lens = jnp.asarray([7, 33, 61], jnp.int32)
    tables = np.zeros((B, MAXB), np.int32)
    nxt = 1
    for b in range(B):
        for j in range(-(-int(lens[b]) // BS)):
            tables[b, j] = nxt
            nxt += 1
    out = paged_decode_attention(q, kp, vp, jnp.asarray(tables), lens)
    ref = oracle(q, kp, vp, jnp.asarray(tables), lens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_trash_rows_produce_finite_output():
    """What ``lens`` says is what the kernel does: an all-zero table with a
    ``lens`` of 1 attends to the trash block's first token, whose value comes
    back (softmax over one position), finite."""
    B, nh, kvh, hd, BS, MAXB = 2, 4, 4, 64, 16, 3
    NB = 4
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (B, nh, hd))
    kp = jax.random.normal(ks[1], (kvh, NB, BS, hd))
    vp = jax.random.normal(ks[2], (kvh, NB, BS, hd))
    tables = jnp.zeros((B, MAXB), jnp.int32)
    lens = jnp.asarray([1, 1], jnp.int32)
    out = paged_decode_attention(q, kp, vp, tables, lens)
    assert np.isfinite(np.asarray(out)).all()
    np.testing.assert_allclose(
        np.asarray(out), np.broadcast_to(np.asarray(vp)[:, 0, 0], out.shape),
        atol=1e-6)


def test_engine_kernel_path_matches_xla_path(monkeypatch):
    """Force the _block kernel branch in interpret mode: the full paged engine
    must produce identical logits either way (guards the call-site wiring —
    q slice, lens = pos+1, re-expand)."""
    import jax
    import deepspeed_tpu.comm.topology as topo_mod
    from deepspeed_tpu.inference.v2 import InferenceEngineV2
    from deepspeed_tpu.models import build_model

    topo_mod.reset_topology()
    m = build_model("llama-tiny", vocab_size=128, hidden_size=64, num_layers=2,
                    num_heads=4, num_kv_heads=2, intermediate_size=128,
                    max_seq_len=64)
    params = m.init_params(jax.random.PRNGKey(0))
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, 128, (9,)).tolist()]

    def run(force):
        if force:
            monkeypatch.setenv("DSTPU_FORCE_PAGED_KERNEL", "1")
        else:
            monkeypatch.delenv("DSTPU_FORCE_PAGED_KERNEL", raising=False)
        eng = InferenceEngineV2(m, params, max_seqs=2, max_seq_len=64,
                                prefill_chunk=16, paged=True, block_size=16,
                                dtype=jnp.float32)
        out = eng.put([1], prompts)
        hist = [np.asarray(out[1])]
        for _ in range(4):
            out = eng.decode_step({1: int(np.argmax(out[1]))})
            hist.append(np.asarray(out[1]))
        return hist

    xla = run(False)
    ker = run(True)
    for a, b in zip(ker, xla):
        np.testing.assert_allclose(a, b, atol=3e-5)


def test_engine_live_write_matches_the_scatter(monkeypatch):
    """Decode rounds write their rows inside ``paged_decode`` (the engine
    says their rows are apart), a mixed step through the scatter: a run of a
    prefill, decode rounds, a second prompt admitted beside a live decode (a
    mixed step) and more rounds gives the tokens and, on every block but the
    trash block, the pool of the engine that scatters throughout. Both take the decode
    kernel, so the pools are equal bit for bit."""
    import deepspeed_tpu.comm.topology as topo_mod
    from deepspeed_tpu.inference.v2 import InferenceEngineV2
    from deepspeed_tpu.models import build_model
    from deepspeed_tpu.ops.transformer import paged_attention as pa
    from deepspeed_tpu.utils import tracing

    topo_mod.reset_topology()
    monkeypatch.setenv("DSTPU_FORCE_PAGED_KERNEL", "1")
    m = build_model("llama-tiny", vocab_size=128, hidden_size=128, num_layers=2,
                    num_heads=2, num_kv_heads=2, intermediate_size=128,
                    max_seq_len=64)
    params = m.init_params(jax.random.PRNGKey(0))
    rng = np.random.default_rng(11)
    first, second = (rng.integers(0, 128, (n,)).tolist() for n in (9, 21))

    def run(live_write):
        if not live_write:
            monkeypatch.setattr(pa, "writes_live_rows", lambda pool: False)
        eng = InferenceEngineV2(m, params, max_seqs=4, max_seq_len=64,
                                prefill_chunk=8, token_budget=12, paged=True,
                                block_size=16, dtype=jnp.bfloat16)
        assert eng._rows_apart(4) and not eng._rows_apart(12)
        toks = [eng.put([1], [first], greedy=True)[1]]
        for _ in range(3):                                   # decode rounds
            toks.append(eng.decode_step({1: toks[-1]}, greedy=True)[1])
        # the second prompt's first chunk rides with uid 1's decode: mixed
        out = eng.put([1, 2], [[toks[-1]], second], greedy=True)
        toks.append(out[1])
        last = {1: out[1], 2: out[2]}
        for _ in range(10):                                  # crosses a block
            last = eng.decode_step(last, greedy=True)
            toks += [last[1], last[2]]
        held = sorted({b for d in eng.state.seqs.values() for b in d.blocks})
        return toks, np.asarray(eng.kv.astype(jnp.float32)), held

    toks_k, pool_k, held = run(True)
    toks_s, pool_s, held_s = run(False)
    assert toks_k == toks_s and held == held_s and 0 not in held
    np.testing.assert_array_equal(pool_k[:, :, 1:], pool_s[:, :, 1:])
    assert np.abs(pool_k[:, :, held]).sum() > 0
    # the decode rounds' padding rows wrote nothing: what the trash block
    # holds is what the mixed steps' scatter left there
    assert np.abs(pool_s[:, :, 0]).sum() > 0


def test_paged_decode_long_context_8k():
    """ctx >= 8k stays on the Pallas path: the kernel streams one pool block
    per grid step (no VMEM window over the whole context), so an 8192-token
    table-addressed sequence must match the oracle with no fallback."""
    B, nh, kvh, hd, BS = 2, 4, 4, 64, 512
    MAXB = 16  # 16 x 512 = 8192-token logical context
    NB = 1 + B * MAXB
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (B, nh, hd))
    kp = jax.random.normal(ks[1], (kvh, NB, BS, hd))
    vp = jax.random.normal(ks[2], (kvh, NB, BS, hd))
    lens = jnp.asarray([8192, 5000], jnp.int32)
    tables = np.zeros((B, MAXB), np.int32)
    nxt = 1
    for b in range(B):
        for j in range(-(-int(lens[b]) // BS)):
            tables[b, j] = nxt
            nxt += 1
    out = paged_decode_attention(q, kp, vp, jnp.asarray(tables), lens)
    ref = oracle(q, kp, vp, jnp.asarray(tables), lens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-5)


# ----------------------------------------------------------------------
# one cell a sequence over its kv heads; a row with lens 0 is dead
# ----------------------------------------------------------------------
@jax.jit
def plain_attention(q, pool, layer, tables, lens, first=None):
    """``gather_context`` + plain float32 attention over the tokens ``first
    <= t < lens`` (from 0 without ``first``): what the kernel must give for
    every row, and zeros for a row with ``lens`` 0. (Jitted: eagerly each of
    its operations is compiled by itself, a second a call.)"""
    from deepspeed_tpu.ops.transformer import paged_attention as pa

    gk, gv = pa.gather_context(pool, layer, tables)   # (B, T, kvh, hd)
    B, T, kvh, hd = gk.shape
    qg = q.astype(jnp.float32).reshape(B, kvh, -1, hd)
    s = jnp.einsum("bhgd,bkhd->bhgk", qg, gk.astype(jnp.float32),
                   precision="highest") * hd ** -0.5
    kpos = jnp.arange(T)[None, None, None]
    seen = kpos < lens[:, None, None, None]
    if first is not None:
        seen &= kpos >= first[:, None, None, None]
    p = jax.nn.softmax(jnp.where(seen, s, -1e30), -1) * seen
    out = jnp.einsum("bhgk,bkhd->bhgd", p, gv.astype(jnp.float32),
                     precision="highest")
    return out.reshape(q.shape)


# (kvh, nh, hd, BS, heads a cell): MHA, GQA, MQA, a wide head, and a pool
# whose blocks are too large for one cell to buffer every head's
SHAPES = [(16, 16, 64, 16, 16), (2, 8, 128, 16, 2), (1, 8, 64, 16, 1),
          (4, 4, 256, 16, 4), (8, 8, 128, 512, 2)]
#: tokens of each row by name, given (BS, MAXB): one token, a block boundary
#: and one past it, the whole table
LENS = {"mixed": lambda BS, MAXB: [1, BS, BS + 1, MAXB * BS],
        "full": lambda BS, MAXB: [MAXB * BS, MAXB * BS - 1, 2 * BS, BS - 1]}


def _case(kvh, nh, hd, BS, lens, L=2, MAXB=3, dead=0, seed=3, at=None):
    """A stacked float32 pool of random rows, the live rows' tables in a
    scrambled order, ``dead`` rows of ``lens`` 0 and an all-zero table
    shuffled among them (or the live rows at the row numbers ``at``)."""
    from deepspeed_tpu.ops.transformer import paged_attention as pa

    rng = np.random.default_rng(seed)
    B = len(lens) + dead
    NB = 1 + len(lens) * MAXB
    # drawn on the host: ``jax.random`` compiles a program a shape
    pool = jnp.asarray(rng.standard_normal(
        pa.init_pool(L, kvh, NB, BS, hd, jnp.float32).shape), jnp.float32)
    q = jnp.asarray(rng.standard_normal((B, nh, hd)), jnp.float32)
    live = np.sort(rng.permutation(B)[:len(lens)] if at is None else at)
    row_lens = np.zeros(B, np.int32)
    row_lens[live] = lens
    tables = np.zeros((B, MAXB), np.int32)
    ids = iter(rng.permutation(np.arange(1, NB)))
    for b in live:
        for j in range(-(-int(row_lens[b]) // BS)):
            tables[b, j] = next(ids)
    return pa, pool, q, jnp.asarray(tables), jnp.asarray(row_lens), live


@functools.lru_cache(maxsize=None)
def _jitted(name, *static):
    """``paged_attention``'s function ``name`` under one ``jax.jit``: cases
    of one shape share a compile (an eager call of an interpreted kernel
    compiles it anew every time)."""
    from deepspeed_tpu.ops.transformer import paged_attention as pa

    return jax.jit(getattr(pa, name), static_argnames=static)


@pytest.mark.parametrize("lens", sorted(LENS))
@pytest.mark.parametrize("kvh,nh,hd,BS,hpc", SHAPES)
def test_folded_kernel_matches_gather_plus_plain_attention(kvh, nh, hd, BS,
                                                           hpc, lens):
    pa, pool, q, tables, row_lens, _ = _case(kvh, nh, hd, BS,
                                             LENS[lens](BS, 3))
    assert pa.heads_per_cell(pool) == hpc
    out = _jitted("paged_decode")(q, pool, jnp.int32(1), tables, row_lens)
    ref = plain_attention(q, pool, jnp.int32(1), tables, row_lens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-5)


@pytest.mark.parametrize("dtype,hd,BS,hpc", [
    (jnp.bfloat16, 64, 64, 16),    # gpt2-medium's pool: 2 x 256 KB
    (jnp.bfloat16, 128, 64, 16),   # Pythia-1.4B's: 2 x 512 KB
    (jnp.bfloat16, 256, 64, 16),   # the budget, to the byte
    (jnp.float32, 256, 64, 8), (jnp.bfloat16, 128, 512, 4),
    (jnp.float32, 256, 1024, 1),   # one head's block alone is over it
])
def test_heads_per_cell_follows_the_pools_shape(dtype, hd, BS, hpc):
    from deepspeed_tpu.ops.transformer import paged_attention as pa

    pool = jax.ShapeDtypeStruct((24, 16, 832, BS, 2 * hd), dtype)
    assert pa.heads_per_cell(pool) == hpc


@pytest.mark.parametrize("rows,rpc", [(64, 32), (256, 32), (96, 32), (3, 3),
                                      (48, 24), (37, 1), (1, 1)])
def test_rows_per_cell_follows_the_row_count(rows, rpc):
    """A round of 64, a mixed step's 256, the sparse view's 96: 32 rows a
    cell; a count with no divisor up to 32 but one keeps a row a cell."""
    from deepspeed_tpu.ops.transformer import paged_attention as pa

    assert pa.rows_per_cell(rows) == rpc
    assert rows % rpc == 0


#: each row's tokens by name, 0 a dead row, for a pool of blocks of 16 and
#: tables three wide, at EIGHT rows a cell at most (``DECODE_CELL_ROWS``
#: lowered for the test's size). CELLS: one cell each, run as ONE batch (a
#: cell's work does not depend on its neighbours: one compile for all);
#: BATCHES: batches of their own
CELLS = {
    "dead_first": [0, 0, 0, 0, 0, 5, 17, 48],
    "dead_last": [5, 17, 48, 0, 0, 0, 0, 0],
    "a_cell_of_dead_rows": [0] * 8,
    "dead_scattered": [0, 5, 0, 0, 17, 0, 48, 0],
    "one_live_last_of_its_cell": [0] * 7 + [33],
}
BATCHES = {
    "a_batch_of_dead_rows": [0] * 8,
    "three_rows": [5, 0, 17],                                  # one cell
    "thirteen_rows": [0, 0, 31, 0, 0, 0, 2, 48, 0, 0, 0, 0, 16],  # a row a cell
}


@functools.lru_cache(maxsize=None)
def _dead_and_alone(kvh, nh, hd, BS, rows):
    """The batch ``rows`` names through the kernel, whole and its live rows
    alone, and plainly: (lens, out, alone, plain). ``13_among_16``: three
    live rows shuffled among 13 dead; ``cells``: every cell of CELLS."""
    if rows == "13_among_16":
        pa, pool, q, tables, lens, live = _case(
            kvh, nh, hd, BS, LENS["mixed"](BS, 3)[:3], dead=13)
        assert (np.asarray(lens) == 0).sum() == 13
    else:
        want = BATCHES[rows] if rows in BATCHES else sum(CELLS.values(), [])
        live = np.flatnonzero(want)
        pa, pool, q, tables, lens, _ = _case(
            kvh, nh, hd, BS, [want[b] for b in live],
            dead=len(want) - len(live), at=live)
        np.testing.assert_array_equal(np.asarray(lens), want)
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(pa, "DECODE_CELL_ROWS", 8)
        out = np.asarray(pa.paged_decode(q, pool, jnp.int32(1), tables, lens))
        alone = np.zeros_like(out)
        if len(live):
            alone[live] = np.asarray(pa.paged_decode(
                q[live], pool, jnp.int32(1), tables[live], lens[live]))
    plain = np.asarray(plain_attention(q, pool, jnp.int32(1), tables, lens))
    return np.asarray(lens), out, alone, plain


@pytest.mark.parametrize("kvh,nh,hd,BS,rows", [
    (kvh, nh, hd, BS, "13_among_16") for kvh, nh, hd, BS, _ in
    SHAPES[:2] + SHAPES[4:]] + [
    (2, 4, 64, 16, rows) for rows in list(CELLS) + list(BATCHES)])
def test_dead_rows_cost_nothing_and_change_nothing(kvh, nh, hd, BS, rows):
    """Rows dead (``lens`` 0, an all-zero table) first, last and scattered in
    a cell, a cell and a batch of them alone, row counts of one cell and of a
    row a cell, grouped queries: their outputs are zeros, and the live rows'
    outputs are bit for bit those of the batch without the dead rows."""
    lens, out, alone, plain = _dead_and_alone(
        kvh, nh, hd, BS, "cells" if rows in CELLS else rows)
    mine = slice(None)
    if rows in CELLS:
        first = 8 * list(CELLS).index(rows)
        mine = slice(first, first + 8)
        np.testing.assert_array_equal(lens[mine], CELLS[rows])
    assert not out[mine][lens[mine] == 0].any()
    np.testing.assert_array_equal(out[mine], alone[mine])
    np.testing.assert_allclose(out[mine], plain[mine], atol=3e-5)


# ----------------------------------------------------------------------
# the stacked pool: one array (L, kvh, NB, BS, 2*hd), read by layer index
# ----------------------------------------------------------------------
@pytest.mark.parametrize("hd,kvh,nh", [(64, 4, 4), (128, 2, 2), (256, 1, 2),
                                       (64, 2, 8), (128, 1, 4)])
def test_stacked_kernel_reads_its_layer(hd, kvh, nh):
    """The kernel on the stacked pool at a non-zero layer == the per-layer
    entry on that layer's K and V == the XLA gather path."""
    L, layer = 3, 2
    pa, pool, q, tables, lens, _ = _case(kvh, nh, hd, 16, [5, 16, 50], L=L,
                                         MAXB=4)
    out = _jitted("paged_decode")(q, pool, jnp.int32(layer), tables, lens)
    kp, vp = pool[layer, ..., :hd], pool[layer, ..., hd:]
    per_layer = paged_decode_attention(q, kp, vp, tables, lens)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(per_layer))
    gk, gv = pa.gather_context(pool, jnp.int32(layer), tables)
    B, T = gk.shape[:2]
    np.testing.assert_array_equal(
        np.asarray(gk), np.asarray(jnp.moveaxis(kp[:, tables], 0, 3)
                                   .reshape(B, T, kvh, hd)))
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(oracle(q, kp, vp, tables, lens)),
                               atol=3e-5)
    # and it is that layer it read: another layer's answer differs
    other = _jitted("paged_decode")(q, pool, jnp.int32(0), tables, lens)
    assert not np.allclose(np.asarray(out), np.asarray(other), atol=1e-3)


def test_pool_block_payload_round_trip():
    """get_block / set_block: the (2, L, kvh, BS, hd) payload the tiers,
    swaps and hand-offs keep is K stacked on V whatever the pool's row
    layout, and writing it back touches that block only."""
    pa, pool = _case(2, 4, 64, 16, [5, 16, 50], MAXB=4)[:2]
    hd = 64
    blk = pa.get_block(pool, jnp.int32(5))
    assert blk.shape == pa.payload_shape(pool) == (2, 2, 2, 16, hd)
    np.testing.assert_array_equal(np.asarray(blk[0]),
                                  np.asarray(pool[:, :, 5, :, :hd]))
    np.testing.assert_array_equal(np.asarray(blk[1]),
                                  np.asarray(pool[:, :, 5, :, hd:]))
    moved = pa.set_block(pool, jnp.int32(9), blk)
    np.testing.assert_array_equal(np.asarray(pa.get_block(moved, 9)),
                                  np.asarray(blk))
    keep = np.arange(pool.shape[2]) != 9
    np.testing.assert_array_equal(np.asarray(moved)[:, :, keep],
                                  np.asarray(pool)[:, :, keep])


@functools.lru_cache(maxsize=None)
def _model_and_pool(L, kvh, hd, BS, NB, MAXB):
    """A tiny llama with 4 heads of ``hd``, its parameters and a pool of
    random rows (so that a row nobody wrote is told from one that was).
    Built once a shape: four tests step the same one."""
    import deepspeed_tpu.comm.topology as topo_mod
    from deepspeed_tpu.models import build_model

    topo_mod.reset_topology()
    m = build_model("llama-tiny", vocab_size=128, hidden_size=4 * hd,
                    num_layers=L, num_heads=4, num_kv_heads=kvh,
                    intermediate_size=128, max_seq_len=MAXB * BS)
    params = m.init_params(jax.random.PRNGKey(0))
    before = jax.random.normal(jax.random.PRNGKey(1),
                               m.init_kv_pool(NB, BS, jnp.float32).shape)
    return m, params, before


@functools.lru_cache(maxsize=None)
def _forward(m, kernel, rows_apart=False):
    """``m.forward_paged`` jitted, one program a shape for the tests that
    step the same model. ``kernel`` only keys the cache: the caller has set
    (or cleared) DSTPU_FORCE_PAGED_KERNEL, which is read as a call traces."""
    return jax.jit(lambda *a: m.forward_paged(*a, rows_apart=rows_apart))


def _mostly_padding_step(BS, NB, MAXB=3):
    """A three-layer model, a pool of random rows and a step of 12 one-token
    rows of which rows 2 and 9 are live sequences (the rest carry the
    all-zero table): (model, params, pool, live, tables, starts, ids)."""
    m, params, before = _model_and_pool(3, 2, 64, BS, NB, MAXB)
    live = np.asarray([2, 9])
    tables = np.zeros((12, MAXB), np.int32)
    tables[live] = [[7, 2, 0], [4, 9, 5]]
    starts = np.zeros(12, np.int32)
    starts[live] = [6, 17]
    ids = jax.random.randint(jax.random.PRNGKey(2), (12, 1), 0, 128)
    return m, params, before, live, tables, starts, ids


@pytest.mark.parametrize("S", [1, 3])  # ragged rows of one token; a segment
def test_forward_paged_writes_only_its_rows(monkeypatch, S):
    """A three-layer ``forward_paged`` writes exactly the rows of its tokens
    (layer x kv head x block x offset): every other row of the pool is bit
    for bit what it was, the trash block excepted. The kernel path and the
    gather path then hold the same pool (to float noise below layer 0, whose
    inputs are each path's own attention output) and give the same logits."""
    L, kvh, hd, BS, NB, MAXB = 3, 2, 64, 8, 12, 3
    m, params, before = _model_and_pool(L, kvh, hd, BS, NB, MAXB)
    assert before.shape == (L, kvh, NB, BS, 2 * hd)
    # rows: two live sequences and one padding row (all-zero table)
    tables = jnp.asarray([[7, 2, 0], [4, 9, 5], [0, 0, 0]], jnp.int32)
    starts = jnp.asarray([6, 17, 0], jnp.int32)
    ids = jax.random.randint(jax.random.PRNGKey(2), (3, S), 0, 128)

    def run(kernel):
        if kernel:
            monkeypatch.setenv("DSTPU_FORCE_PAGED_KERNEL", "1")
        else:
            monkeypatch.delenv("DSTPU_FORCE_PAGED_KERNEL", raising=False)
        return _forward(m, kernel)(params, ids, before, tables, starts)

    lg, after = run(kernel=False)
    written = np.zeros((NB, BS), bool)
    for r in range(2):
        for p in range(int(starts[r]), int(starts[r]) + S):
            written[int(tables[r, p // BS]), p % BS] = True
    written[0] = True  # the trash block takes the padding row's writes
    a, b = np.asarray(after), np.asarray(before)
    np.testing.assert_array_equal(a[:, :, ~written], b[:, :, ~written])
    live = written.copy()
    live[0] = False
    assert (a[:, :, live] != b[:, :, live]).all(axis=-1).all(), \
        "a token's row was not written in some layer or head"
    if S == 1:  # the kernel takes one-token rows
        lg_k, after_k = run(kernel=True)
        k = np.asarray(after_k)
        np.testing.assert_array_equal(k[:, :, ~written], b[:, :, ~written])
        np.testing.assert_array_equal(k[0, :, 1:], a[0, :, 1:])  # layer 0: same inputs
        np.testing.assert_allclose(k[:, :, 1:], a[:, :, 1:], atol=3e-5)
        np.testing.assert_allclose(np.asarray(lg_k)[:2], np.asarray(lg)[:2],
                                   atol=3e-5)


def test_forward_paged_marks_padding_rows_dead(monkeypatch):
    """The model's call: a row whose table is all zero goes to the kernel
    with ``lens`` 0. Through a three-layer ``forward_paged`` with most rows
    padding, no block of the pool other than the trash block and the live
    rows' own tokens differs, and the live rows' logits and pool rows are
    those of the batch without the padding rows."""
    monkeypatch.setenv("DSTPU_FORCE_PAGED_KERNEL", "1")
    BS, NB = 8, 12
    m, params, before, live, tables, starts, ids = _mostly_padding_step(BS, NB)

    fwd = _forward(m, True)
    lg, after = fwd(params, ids, before, jnp.asarray(tables), jnp.asarray(starts))
    lg_live, after_live = fwd(params, ids[live], before, jnp.asarray(tables[live]),
                              jnp.asarray(starts[live]))
    a, b = np.asarray(after), np.asarray(before)
    written = np.zeros((NB, BS), bool)
    for r in live:                 # the one token each live row writes
        written[tables[r, starts[r] // BS], starts[r] % BS] = True
    same = ~written
    same[0] = False                # the trash block takes the padding rows' writes
    np.testing.assert_array_equal(a[:, :, same], b[:, :, same])
    assert (a[:, :, written] != b[:, :, written]).all(axis=-1).all()
    np.testing.assert_allclose(a[:, :, 1:], np.asarray(after_live)[:, :, 1:],
                               atol=3e-5)
    np.testing.assert_allclose(np.asarray(lg)[live], np.asarray(lg_live),
                               atol=3e-5)
    assert np.isfinite(np.asarray(lg)).all()


def test_the_models_call_gives_padding_rows_lens_zero(monkeypatch):
    """What the kernel is told: position + 1 for a row that holds a block,
    0 for a row whose table is all zero."""
    import deepspeed_tpu.comm.topology as topo_mod
    from deepspeed_tpu.models import build_model
    from deepspeed_tpu.ops.transformer import paged_attention as pa

    topo_mod.reset_topology()
    monkeypatch.setenv("DSTPU_FORCE_PAGED_KERNEL", "1")
    m = build_model("llama-tiny", vocab_size=128, hidden_size=128,
                    num_layers=1, num_heads=2, num_kv_heads=2,
                    intermediate_size=128, max_seq_len=32)
    params = m.init_params(jax.random.PRNGKey(0))
    pool = m.init_kv_pool(6, 8, jnp.float32)
    tables = jnp.asarray([[0, 0], [3, 0], [0, 0], [1, 4]], jnp.int32)
    starts = jnp.asarray([0, 5, 0, 11], jnp.int32)
    told = []
    kernel = pa.paged_decode

    def listen(q, pool, layer, tables, lens, **kw):
        jax.debug.callback(lambda x: told.append(np.asarray(x)), lens)
        return kernel(q, pool, layer, tables, lens, **kw)

    monkeypatch.setattr(pa, "paged_decode", listen)
    jax.block_until_ready(m.forward_paged(
        params, jnp.zeros((4, 1), jnp.int32), pool, tables, starts))
    assert told and all(t.tolist() == [0, 6, 0, 12] for t in told)


# ----------------------------------------------------------------------
# kv_write: a decode round's live rows, one copy each over all kv heads
# ----------------------------------------------------------------------
#: which of 8 rows hold a block
LIVE = {"none": [], "one": [0], "a-few": [0, 1, 2], "all": list(range(8)),
        "not-a-prefix": [1, 4, 7]}
#: (kv heads, head size or (key, value) widths of a latent row)
LAYOUTS = {"kvh1-hd64": (1, 64), "kvh2-hd128": (2, 128),
           "kvh16-hd256": (16, 256), "latent-640": (1, (512, 128))}


def _write_case(kvh, hd, live, *, L=3, BS=32, MAXB=2, dtype=jnp.bfloat16,
                seed=0):
    """A pool of random rows, a step of 8 one-token rows of which ``live``
    hold blocks (their own: no two rows share one), and the rows' k, v."""
    from deepspeed_tpu.ops.transformer import paged_attention as pa

    rng = np.random.default_rng(seed)
    B = 8
    NB = 1 + B * MAXB
    shape = pa.init_pool(L, kvh, NB, BS, hd, dtype).shape
    pool = jnp.asarray(rng.standard_normal(shape), dtype)
    tables = np.zeros((B, MAXB), np.int32)
    pos = np.zeros((B, 1), np.int32)
    blocks = rng.permutation(np.arange(1, NB)).reshape(B, MAXB)
    for b in live:
        tables[b] = blocks[b]
        pos[b, 0] = rng.integers(0, MAXB * BS)
    kw, vw = (hd, hd) if isinstance(hd, int) else hd
    k = jnp.asarray(rng.standard_normal((B, 1, kvh, kw)), dtype)
    v = jnp.asarray(rng.standard_normal((B, 1, kvh, vw)), dtype)
    return pa, pool, jnp.asarray(tables), jnp.asarray(pos), k, v


def _bits(x):
    return np.asarray(x.astype(jnp.float32))


@pytest.mark.parametrize("layer", ["first", "last"])
@pytest.mark.parametrize("layout", list(LAYOUTS))
@pytest.mark.parametrize("live", list(LIVE))
def test_kv_write_equals_the_scatter_off_the_trash_block(monkeypatch, live,
                                                         layout, layer):
    """``write_rows`` of rows that are apart, through the kernel: every
    block but block 0 is bit for bit the scatter's, block 0 is as it was (a
    padding row writes nothing), and with no live row the whole pool is."""
    monkeypatch.setenv("DSTPU_FORCE_PAGED_KERNEL", "1")
    kvh, hd = LAYOUTS[layout]
    pa, pool, tables, pos, k, v = _write_case(kvh, hd, LIVE[live])
    assert pa.writes_live_rows(pool)
    l = jnp.int32(0 if layer == "first" else pool.shape[0] - 1)
    scattered = pa.write_rows(pool, l, tables, pos, k, v)
    written = _jitted("write_rows", "rows_apart")(
        pool, l, tables, pos, k, v, rows_apart=True)
    before, s, w = _bits(pool), _bits(scattered), _bits(written)
    np.testing.assert_array_equal(w[:, :, 1:], s[:, :, 1:])
    np.testing.assert_array_equal(w[:, :, 0], before[:, :, 0])
    assert (w != before).any() == bool(LIVE[live])
    if len(LIVE[live]) < 8:
        assert (s[:, :, 0] != before[:, :, 0]).any(), \
            "the scatter's padding rows land in the trash block"


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_kv_write_keeps_a_neighbours_sub_tile(monkeypatch, dtype):
    """Two live rows in neighbouring pool blocks, one at the last token of
    its block and one at the first of the next: each row's read-modify-write
    of its sub-tile leaves the other's alone, and every row of the pool but
    the two is what it was, in every layer's turn and every head."""
    monkeypatch.setenv("DSTPU_FORCE_PAGED_KERNEL", "1")
    BS = 32
    pa, pool, _, _, k, v = _write_case(2, 64, [0, 1], BS=BS, dtype=dtype)
    assert BS // pa.SUB_TILE >= 2
    tables = np.zeros((8, 2), np.int32)
    tables[0], tables[1] = [5, 9], [6, 10]
    pos = np.zeros((8, 1), np.int32)
    pos[0, 0], pos[1, 0] = BS - 1, 0
    out = pool
    for l in range(pool.shape[0]):
        out = pa.write_rows(out, jnp.int32(l), jnp.asarray(tables),
                            jnp.asarray(pos), k, v, rows_apart=True)
    before, after = _bits(pool), _bits(out)
    kv = _bits(jnp.concatenate((k, v), axis=-1))[:, 0]       # (B, kvh, row)
    for r, (blk, off) in enumerate(((5, BS - 1), (6, 0))):
        np.testing.assert_array_equal(
            after[:, :, blk, off], np.broadcast_to(kv[r], after.shape[:2]
                                                   + kv.shape[2:]))
    untouched = np.ones(before.shape[2:4], bool)
    untouched[5, BS - 1] = untouched[6, 0] = False
    np.testing.assert_array_equal(after[:, :, untouched],
                                  before[:, :, untouched])


@pytest.mark.parametrize("BS,hd,dtype,forced,takes", [
    (16, 64, jnp.bfloat16, True, True),
    (8, 64, jnp.float32, True, True),
    (4, 64, jnp.bfloat16, True, False),     # a block is half a sub-tile
    (16, 32, jnp.bfloat16, True, False),    # a row of 64 lanes
    (16, 64, jnp.bfloat16, False, False),   # no TPU and not forced
])
def test_write_rows_takes_the_kernel_where_the_pool_allows(monkeypatch, BS, hd,
                                                           dtype, forced,
                                                           takes):
    """``writes_live_rows`` reads the pool's shape and the same rule that
    chooses ``paged_decode``; where it says no, rows that are apart go
    through the scatter, trash block and all."""
    if forced:
        monkeypatch.setenv("DSTPU_FORCE_PAGED_KERNEL", "1")
    else:
        monkeypatch.delenv("DSTPU_FORCE_PAGED_KERNEL", raising=False)
    pa, pool, tables, pos, k, v = _write_case(2, hd, [0, 3], BS=BS, MAXB=1,
                                              dtype=dtype)
    assert pa.writes_live_rows(pool) == takes
    called = []
    kernel = pa.kv_write
    monkeypatch.setattr(pa, "kv_write",
                        lambda *a: called.append(1) or kernel(*a))
    apart = pa.write_rows(pool, 1, tables, pos, k, v, rows_apart=True)
    assert bool(called) == takes
    scattered = pa.write_rows(pool, 1, tables, pos, k, v)
    assert not called[1:]
    if takes:
        np.testing.assert_array_equal(_bits(apart)[:, :, 1:],
                                      _bits(scattered)[:, :, 1:])
    else:
        np.testing.assert_array_equal(_bits(apart), _bits(scattered))


def test_forward_paged_rows_apart_writes_the_live_rows_alone(monkeypatch):
    """The model's call: ``rows_apart`` reaches every layer, whose one
    ``paged_decode`` call writes the live rows. Through a three-layer
    ``forward_paged`` with most rows padding the
    trash block is untouched, the live rows' tokens and the logits are those
    of the scattering program."""
    monkeypatch.setenv("DSTPU_FORCE_PAGED_KERNEL", "1")
    m, params, before, live, tables, starts, ids = _mostly_padding_step(8, 12)
    args = (params, ids, before, jnp.asarray(tables), jnp.asarray(starts))
    lg_s, after_s = _forward(m, True)(*args)
    lg_k, after_k = _forward(m, True, rows_apart=True)(*args)
    k, s, b = np.asarray(after_k), np.asarray(after_s), np.asarray(before)
    np.testing.assert_array_equal(k[:, :, 0], b[:, :, 0])
    assert (s[:, :, 0] != b[:, :, 0]).any()
    np.testing.assert_array_equal(k[:, :, 1:], s[:, :, 1:])
    np.testing.assert_array_equal(np.asarray(lg_k)[live],
                                  np.asarray(lg_s)[live])


@pytest.mark.parametrize("shape,dtype,bpt", [
    ((24, 16, 832, 64, 128), jnp.bfloat16, 1),     # gpt2-medium's: 1024 rows
    ((24, 16, 832, 64, 256), jnp.bfloat16, 1),     # Pythia-1.4B's: 512 KB
    ((10, 4, 608, 64, 256), jnp.bfloat16, 4),      # trinity-mini's window
    ((3, 4, 2048, 64, 256), jnp.bfloat16, 4),      # class and its full one
    ((2, 1, 27136, 64, 256), jnp.bfloat16, 16),    # sparse attention's view
    ((2, 2, 13568, 64, 256), jnp.bfloat16, 8),
    ((5, 1, 3072, 64, 640), jnp.bfloat16, 16),
    ((2, 8, 129, 64, 512), jnp.bfloat16, 2),       # heads of 256: 1 MB
    ((24, 16, 832, 64, 128), jnp.float32, 1),      # rows, not bytes
    ((1, 1, 64, 16, 128), jnp.float32, 16),        # never more than sixteen
])
def test_blocks_per_trip_follows_the_pools_shape(shape, dtype, bpt):
    from deepspeed_tpu.ops.transformer import paged_attention as pa

    assert pa.blocks_per_trip(jax.ShapeDtypeStruct(shape, dtype)) == bpt


@pytest.mark.parametrize("lens", [(19 * 16, 8 * 16 + 1, 1), (24 * 16 - 5, 16)])
def test_trips_of_several_blocks_match_plain_attention(lens):
    """Rows of more blocks than one trip fetches: whole trips, a trip that
    the row's blocks end inside (its last block fetched again in their
    place and masked), a row shorter than one trip."""
    pa, pool, q, tables, row_lens, _ = _case(1, 4, 64, 16, list(lens),
                                             MAXB=24, dead=2)
    assert pa.blocks_per_trip(pool) == 16
    out = pa.paged_decode(q, pool, jnp.int32(1), tables, row_lens)
    ref = plain_attention(q, pool, jnp.int32(1), tables, row_lens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-5)


# ----------------------------------------------------------------------
# the folded write: a decode round's attention sublayer in one call
# ----------------------------------------------------------------------
#: the most live rows of a round of :func:`_round_case`
LIVE_MAX = 7


def _round_case(kvh, g, hd, rows, lens_by_row, *, BS=16, MAXB=3, L=2,
                dtype=jnp.bfloat16, seed=0):
    """A decode round of ``rows`` one-token rows, ``lens_by_row`` {row:
    tokens, the new one counted} live (each on blocks of its own) and the
    rest padding: (pool of random rows, q, k, v as the projections leave
    them, tables, lens)."""
    from deepspeed_tpu.ops.transformer import paged_attention as pa

    rng = np.random.default_rng(seed)
    # blocks for the most live rows any case has, however many this one has:
    # the cases of one (head size, group, row count) then share a compile
    assert len(lens_by_row) <= LIVE_MAX
    NB = 1 + LIVE_MAX * MAXB + 2
    pool = jnp.asarray(rng.standard_normal(
        pa.init_pool(L, kvh, NB, BS, hd, dtype).shape), dtype)
    q, k, v = (jnp.asarray(rng.standard_normal((rows, n * hd)), dtype)
               for n in (kvh * g, kvh, kvh))
    tables = np.zeros((rows, MAXB), np.int32)
    lens = np.zeros(rows, np.int32)
    ids = iter(rng.permutation(np.arange(1, NB)))
    for b, n in sorted(lens_by_row.items()):
        lens[b] = n
        for j in range(-(-n // BS)):
            tables[b, j] = next(ids)
    return pool, q, k, v, jnp.asarray(tables), jnp.asarray(lens)


@functools.lru_cache(maxsize=None)
def _round_forms():
    """(the parent's two calls, the one call): ``write_rows`` of rows that
    are apart (``kv_write``) then the read-only ``paged_decode`` on q as
    heads, each under a ``jax.jit`` of its own (the write's shapes know
    nothing of the query groups: one compile for every ``g``), against
    ``paged_decode`` handed the new rows, everything in the model's ``(rows,
    heads * hd)`` layout."""
    from deepspeed_tpu.ops.transformer import paged_attention as pa

    def heads(a, pool):
        return a.reshape(a.shape[0], 1, -1, pool.shape[-1] // 2)

    @jax.jit
    def write(k, v, pool, layer, tables, lens):
        assert pa.writes_live_rows(pool)
        return pa.write_rows(pool, layer, tables,
                             jnp.maximum(lens - 1, 0)[:, None],
                             heads(k, pool), heads(v, pool), rows_apart=True)

    @jax.jit
    def read(q, pool, layer, tables, lens):
        out = pa.paged_decode(heads(q, pool)[:, 0], pool, layer, tables, lens)
        return out.reshape(q.shape)

    def two_calls(q, k, v, pool, layer, tables, lens):
        pool = write(k, v, pool, layer, tables, lens)
        return read(q, pool, layer, tables, lens), pool

    def one_call(q, k, v, pool, layer, tables, lens):
        return pa.paged_decode(q, pool, layer, tables, lens, new_rows=(k, v))

    return two_calls, jax.jit(one_call)


def _assert_the_one_call_is_the_two(case, layer=1):
    """Result and pool of the one call are bit for bit the two calls', and
    the pool differs from what it was in the live rows' new tokens alone."""
    pool, q, k, v, tables, lens = case
    two, one = (f(q, k, v, pool, jnp.int32(layer), tables, lens)
                for f in _round_forms())
    np.testing.assert_array_equal(_bits(one[0]), _bits(two[0]))
    np.testing.assert_array_equal(_bits(one[1]), _bits(two[1]))
    BS = pool.shape[3]
    written = np.zeros(pool.shape[:1] + pool.shape[2:4], bool)  # (L, NB, BS)
    for b in np.flatnonzero(np.asarray(lens)):
        n = int(lens[b]) - 1
        written[layer, int(tables[b, n // BS]), n % BS] = True
    # heads first, so that one (L, NB, BS) mask picks every head's tokens
    before, after = (np.moveaxis(_bits(a), 1, 0) for a in (pool, one[1]))
    np.testing.assert_array_equal(after[:, ~written], before[:, ~written])
    assert (after[:, written] != before[:, written]).any(axis=-1).all()
    assert not _bits(one[0])[np.asarray(lens) == 0].any()
    return one


#: where a round's live rows sit among its padding rows, given the row count
PLACES = {
    "first": lambda rows: [0, 1, 2],
    "last": lambda rows: [rows - 3, rows - 2, rows - 1],
    "scattered": lambda rows: sorted({1, rows // 3, rows // 2 + 1, rows - 2}),
}


@pytest.mark.parametrize("place", list(PLACES))
@pytest.mark.parametrize("rows", [8, 64, 256])
@pytest.mark.parametrize("g", [1, 4])
@pytest.mark.parametrize("hd", [64, 128, 256])
def test_the_folded_write_is_kv_write_then_paged_decode(monkeypatch, hd, g,
                                                        rows, place):
    """Head sizes, grouped queries and row counts of one cell, two and
    eight, the live rows first, last and scattered among dead ones."""
    monkeypatch.setenv("DSTPU_FORCE_PAGED_KERNEL", "1")
    live = PLACES[place](rows)
    lens = dict(zip(live, (5, 32, 33, 48)))
    _assert_the_one_call_is_the_two(_round_case(
        2, g, hd, rows, lens, seed=rows + hd + g,
        dtype=jnp.float32 if hd == 128 else jnp.bfloat16))


#: a live row's tokens (the new one counted) by the edge it stands for, over
#: blocks of 16 (two sub-tiles) fetched sixteen a trip
EDGES = {
    "offset_0_of_a_fresh_block": 2 * 16 + 1,
    "last_offset_of_a_block": 2 * 16,
    "first_sub_tile": 16 + 3,
    "last_sub_tile": 16 + 15,
    "blocks_end_inside_a_trip": 10 * 16 + 5,
    "last_block_of_a_whole_trip": 16 * 16,
    "context_of_one_token": 1,
}


@functools.lru_cache(maxsize=None)
def _edges():
    """Every edge a row of ONE round (a row's work does not depend on its
    neighbours), a padding row between them: (lens, result, pool)."""
    with pytest.MonkeyPatch.context() as patch:
        patch.setenv("DSTPU_FORCE_PAGED_KERNEL", "1")
        case = _round_case(1, 4, 64, 8, dict(zip((0, 1, 2, 3, 5, 6, 7),
                                                 EDGES.values())),
                           MAXB=16, dtype=jnp.bfloat16, seed=5)
        from deepspeed_tpu.ops.transformer import paged_attention as pa
        assert pa.blocks_per_trip(case[0]) == 16
        out, pool = _assert_the_one_call_is_the_two(case)
    return np.asarray(case[5]), _bits(out), _bits(pool), case


@pytest.mark.parametrize("edge", list(EDGES))
def test_the_folded_write_at_the_edges(edge):
    """The new token at offset 0 of a fresh block, at a block's last offset,
    in the first and the last sub-tile, in a row whose blocks end inside a
    trip or fill it, alone in its context: the row's ``[k | v]`` lies at its
    place in every head and the row attends over it."""
    lens, out, pool, (before, q, k, v, tables, _) = _edges()
    b = (0, 1, 2, 3, 5, 6, 7)[list(EDGES).index(edge)]
    n = EDGES[edge] - 1
    assert lens[b] == n + 1 and lens[4] == 0
    hd = before.shape[-1] // 2
    np.testing.assert_array_equal(
        pool[1, :, int(tables[b, n // 16]), n % 16],
        np.concatenate((_bits(k)[b].reshape(-1, hd),
                        _bits(v)[b].reshape(-1, hd)), axis=-1))
    alone = plain_attention(
        q[b:b + 1].reshape(1, -1, hd), jnp.asarray(pool), jnp.int32(1),
        tables[b:b + 1], jnp.asarray(lens[b:b + 1]))
    np.testing.assert_allclose(out[b], np.asarray(alone).reshape(-1),
                               atol=2e-2)     # bfloat16 result
    if edge == "context_of_one_token":        # softmax over the new token
        np.testing.assert_array_equal(
            out[b].reshape(-1, 4, hd), np.broadcast_to(
                _bits(v)[b].reshape(-1, 1, hd), (1, 4, hd)))


def test_the_folded_write_of_a_round_of_dead_rows(monkeypatch):
    """Every row dead: no store starts, the pool is bit for bit what it was
    and the result is zeros."""
    monkeypatch.setenv("DSTPU_FORCE_PAGED_KERNEL", "1")
    case = _round_case(1, 4, 64, 8, {}, MAXB=16, seed=6)
    out, pool = _assert_the_one_call_is_the_two(case)
    np.testing.assert_array_equal(_bits(pool), _bits(case[0]))
    assert not _bits(out).any()


def test_the_models_decode_round_is_one_call(monkeypatch):
    """``_block`` hands a round whose rows are apart to ``paged_decode`` with
    its new rows, in the model's layout, and calls no ``kv_write``; a step
    whose rows are not apart keeps ``write_rows`` and the read-only call."""
    from deepspeed_tpu.ops.transformer import paged_attention as pa

    monkeypatch.setenv("DSTPU_FORCE_PAGED_KERNEL", "1")
    m, params, before, live, tables, starts, ids = _mostly_padding_step(8, 12)
    calls = []
    kernel, writer = pa.paged_decode, pa.kv_write

    def listen(q, *a, new_rows=None, **kw):
        calls.append(("paged_decode", q.ndim, new_rows is not None))
        return kernel(q, *a, new_rows=new_rows, **kw)

    monkeypatch.setattr(pa, "paged_decode", listen)
    monkeypatch.setattr(pa, "kv_write",
                        lambda *a: calls.append(("kv_write",)) or writer(*a))
    args = (params, ids, before, jnp.asarray(tables), jnp.asarray(starts))
    jax.eval_shape(lambda *a: m.forward_paged(*a, rows_apart=True), *args)
    assert calls == [("paged_decode", 2, True)]      # one scanned layer body
    del calls[:]
    jax.eval_shape(m.forward_paged, *args)
    assert calls == [("paged_decode", 3, False)]


# ----------------------------------------------------------------------
# a trip computes in the pool's dtype
# ----------------------------------------------------------------------
#: (kv heads, query heads a kv head, head size): gpt2-medium's pool of
#: heads, trinity-mini's grouped queries, minicpm-sala's sparse view
OPERAND_SHAPES = {"chat": (16, 1, 64), "win16k": (4, 8, 128),
                  "doc16k": (1, 16, 128)}


def _operand_case(kvh, g, hd, dtype, *, BS=16, MAXB=12, seed=11):
    """Five rows over a pool of ``dtype`` (a dead one among them): several
    trips, a trip the blocks end inside, one token; q float32 numbers that
    the pool's dtype holds (the result is float32, the products are the
    pool's); each row's lower bound for a bounded call."""
    from deepspeed_tpu.ops.transformer import paged_attention as pa

    rng = np.random.default_rng(seed)
    lens = np.asarray([1, 40, 0, MAXB * BS, 7 * BS + 3], np.int32)
    first = np.asarray([0, 17, 0, 5 * BS + 1, 2 * BS], np.int32)
    NB = 1 + len(lens) * MAXB
    pool = jnp.asarray(rng.standard_normal(
        pa.init_pool(2, kvh, NB, BS, hd, dtype).shape), dtype)
    q = jnp.asarray(rng.standard_normal((len(lens), kvh * g, hd)), dtype) \
        .astype(jnp.float32)
    tables = np.zeros((len(lens), MAXB), np.int32)
    ids = iter(rng.permutation(np.arange(1, NB)))
    for b, n in enumerate(lens):
        for j in range(-(-int(n) // BS)):
            tables[b, j] = next(ids)
    return pa, pool, q, jnp.asarray(tables), jnp.asarray(lens), \
        jnp.asarray(first)


@pytest.mark.parametrize("bounded", [False, True], ids=["whole", "bounded"])
@pytest.mark.parametrize("cell", sorted(OPERAND_SHAPES))
def test_a_bfloat16_pools_trip_is_attend_rows_arithmetic(monkeypatch, cell,
                                                         bounded):
    """The kernel on a bfloat16 pool against its XLA twin, which multiplies
    bfloat16 by bfloat16 into float32 and rounds ``p`` to bfloat16 as the
    kernel now does, and against float32 attention over the same bfloat16
    numbers. The scores are exact in both; what is left is ``p`` rounded to
    8 bits, by the twin after it is normalised and by the kernel before, so
    each is within 2^-9 of the largest value of float32 attention and of
    the other (found: 3-6e-4 the kernel, 5-9e-4 the twin, 6-11e-4 between
    them; the float32 kernel was 4e-7 from float32 and the twin's 9e-4 from
    the twin)."""
    monkeypatch.delenv("DSTPU_FORCE_PAGED_KERNEL", raising=False)
    pa, pool, q, tables, lens, first = _operand_case(
        *OPERAND_SHAPES[cell], jnp.bfloat16)
    if not bounded:
        first = jnp.zeros_like(first)
    bound = {"first": first} if bounded else {}
    got = jax.jit(functools.partial(pa.paged_decode, layer=1))(
        q, pool, tables=tables, lens=lens, **bound)
    assert not np.asarray(got[2]).any()
    twin = jax.jit(functools.partial(pa.attend_rows, layer=1))(
        q, pool, tables=tables, lens=lens, **bound)
    want = np.asarray(plain_attention(q, pool, 1, tables, lens, first))
    got, twin = (np.asarray(a, np.float32) for a in (got, twin))
    top = np.abs(want).max()
    assert np.abs(got - twin).max() <= 2 ** -9 * top
    assert 1e-5 * top < np.abs(got - want).max() <= 2 ** -9 * top


def test_a_trips_scores_are_sums_of_exact_products():
    """``v`` a one-hot selector of the token: a row of one trip then returns
    its ``p`` over the trip's tokens, rounded to bfloat16 and divided by
    ``l``. Relative to the largest, that is ``bfloat16(exp(s - max s))`` with
    ``s`` the scaled sum of the exact products of bfloat16 numbers: a
    product rounded to bfloat16, or a ``q * scale`` rounded before the
    product, moves ``p`` by 2^-9 and flips its rounding in every other
    element."""
    from deepspeed_tpu.ops.transformer import paged_attention as pa

    kvh, g, hd, BS, n = 2, 4, 128, 16, 100
    rng = np.random.default_rng(5)
    pool = np.zeros(pa.init_pool(1, kvh, 9, BS, hd).shape, np.float32)
    pool[..., :hd] = rng.standard_normal(pool[..., :hd].shape)
    tables = np.arange(1, 8, dtype=np.int32)[None]
    for t in range(n):                  # token t's value is the unit vector t
        pool[0, :, tables[0, t // BS], t % BS, hd + t] = 1.0
    pool = jnp.asarray(pool, jnp.bfloat16)
    assert pa.blocks_per_trip(pool) * BS >= n           # one trip
    # float32 queries that bfloat16 holds exactly: the result is float32
    q = jnp.asarray(rng.standard_normal((1, kvh * g, hd)), jnp.bfloat16)
    got = np.asarray(pa.paged_decode(
        q.astype(jnp.float32), pool, 0, jnp.asarray(tables),
        jnp.asarray([n], jnp.int32)), np.float64)[0].reshape(kvh, g, hd)
    keys = np.asarray(pool[0, :, tables[0], :, :hd], np.float64)
    keys = np.moveaxis(keys, 1, 0).reshape(kvh, -1, hd)[:, :n]
    s = np.einsum("hgd,htd->hgt", np.asarray(q, np.float64)[0].reshape(
        kvh, g, hd), keys) * hd ** -0.5
    p = np.exp(s - s.max(-1, keepdims=True))
    rounded = np.asarray(jnp.asarray(p, jnp.bfloat16), np.float64)
    # but for a p within float32's reach of a rounding boundary
    both = np.asarray(jnp.asarray(np.stack([p * (1 - 1e-5), p * (1 + 1e-5)]),
                                  jnp.bfloat16), np.float64)
    clear = both[0] == both[1]
    assert clear.mean() > 0.98
    ratio = got[..., :n] / got[..., :n].max(-1, keepdims=True)
    np.testing.assert_allclose(ratio[clear], rounded[clear], rtol=1e-6)
    assert not got[..., n:].any()


@pytest.mark.parametrize("write", [False, True], ids=["read", "write"])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bfloat16", "float32"])
def test_a_trips_products_take_the_pools_dtype(dtype, write):
    """Both products of a trip multiply in the pool's dtype and accumulate
    in float32 (a float32 pool still multiplies at float32), and nothing of
    the trip's buffer is converted: the one convert of pool numbers to
    float32 left is the :data:`SUB_TILE` tokens around a written row."""
    from deepspeed_tpu.analysis.program_audit import _iter_eqns
    from deepspeed_tpu.ops.transformer import paged_attention as pa

    kvh, g, hd, BS, rows = 4, 8, 128, 64, 8
    pool = jnp.zeros(pa.init_pool(2, kvh, 9, BS, hd, dtype).shape, dtype)
    tables, lens = jnp.zeros((rows, 4), jnp.int32), jnp.zeros(rows, jnp.int32)
    if write:
        q, k = (jnp.zeros((rows, kvh * n * hd), jnp.bfloat16) for n in (g, 1))
        fn = lambda: pa.paged_decode(q, pool, 0, tables, lens, new_rows=(k, k))
    else:
        q = jnp.zeros((rows, kvh * g, hd), jnp.bfloat16)
        fn = lambda: pa.paged_decode(q, pool, 0, tables, lens)
    (call,) = [e for e in jax.make_jaxpr(fn)().eqns
               if e.primitive.name == "pallas_call"]
    eqns = list(_iter_eqns(call.params["jaxpr"]))
    dots = [e for e in eqns if e.primitive.name == "dot_general"]
    assert len(dots) == 2
    for dot in dots:
        assert [v.aval.dtype for v in dot.invars] == [dtype, dtype]
        assert dot.outvars[0].aval.dtype == jnp.float32
    trip = pa.blocks_per_trip(pool) * BS
    widened = [e.invars[0].aval.shape for e in eqns
               if e.primitive.name == "convert_element_type"
               and e.invars[0].aval.dtype == jnp.bfloat16
               and e.outvars[0].aval.dtype == jnp.float32
               and e.invars[0].aval.shape[-2:-1] == (trip,)]
    assert widened == []
