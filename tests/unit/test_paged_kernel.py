"""Pallas paged-decode attention kernel vs the XLA gather oracle
(reference ``tests/unit/inference/v2/kernels/ragged_ops`` blocked-flash
numerics). Interpret mode on the CPU mesh; the identical code path lowers via
Mosaic on TPU (validated on-chip)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.transformer.paged_attention import paged_decode_attention


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


@pytest.mark.parametrize("stream", [True, False])  # DMA-loop vs grid-per-block
@pytest.mark.parametrize("kvh,nh", [(4, 4), (2, 8), (1, 8)])  # MHA, GQA, MQA
def test_paged_decode_matches_oracle(kvh, nh, stream):
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
    out = paged_decode_attention(q, kp, vp, jnp.asarray(tables), lens,
                                 stream=stream)
    ref = oracle(q, kp, vp, jnp.asarray(tables), lens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_trash_rows_produce_finite_output():
    """Inactive sequences (all-zero tables, len 0... clamped to 1) stay finite."""
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
# the stacked pool: one array (L, kvh, NB, BS, 2*hd), read by layer index
# ----------------------------------------------------------------------
def _stacked_case(L, kvh, nh, hd, seed=2):
    from deepspeed_tpu.ops.transformer import paged_attention as pa

    B, BS, MAXB = 3, 16, 4
    NB = 1 + B * MAXB
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    pool = pa.init_pool(L, kvh, NB, BS, hd, jnp.float32)
    pool = jax.random.normal(ks[0], pool.shape)
    q = jax.random.normal(ks[1], (B, nh, hd))
    lens = jnp.asarray([5, 16, 50], jnp.int32)
    tables = np.zeros((B, MAXB), np.int32)
    # blocks handed out in a scrambled order: the table, not the block id,
    # says where a sequence's tokens are
    ids = iter(np.random.default_rng(seed).permutation(np.arange(1, NB)))
    for b in range(B):
        for j in range(-(-int(lens[b]) // BS)):
            tables[b, j] = next(ids)
    return pa, pool, q, jnp.asarray(tables), lens


@pytest.mark.parametrize("hd,kvh,nh", [(64, 4, 4), (128, 2, 2), (256, 1, 2),
                                       (64, 2, 8), (128, 1, 4)])
def test_stacked_kernel_reads_its_layer(hd, kvh, nh):
    """The kernel on the stacked pool at a non-zero layer == the per-layer
    entry on that layer's K and V == the XLA gather path."""
    L, layer = 3, 2
    pa, pool, q, tables, lens = _stacked_case(L, kvh, nh, hd)
    out = pa.paged_decode(q, pool, jnp.int32(layer), tables, lens)
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
    other = pa.paged_decode(q, pool, jnp.int32(0), tables, lens)
    assert not np.allclose(np.asarray(out), np.asarray(other), atol=1e-3)


def test_pool_block_payload_round_trip():
    """get_block / set_block: the (2, L, kvh, BS, hd) payload the tiers,
    swaps and hand-offs keep is K stacked on V whatever the pool's row
    layout, and writing it back touches that block only."""
    pa, pool, _, _, _ = _stacked_case(2, 2, 4, 64)
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


@pytest.mark.parametrize("S", [1, 3])  # ragged rows of one token; a segment
def test_forward_paged_writes_only_its_rows(monkeypatch, S):
    """A three-layer ``forward_paged`` writes exactly the rows of its tokens
    (layer x kv head x block x offset): every other row of the pool is bit
    for bit what it was, the trash block excepted. The kernel path and the
    gather path then hold the same pool (to float noise below layer 0, whose
    inputs are each path's own attention output) and give the same logits."""
    import deepspeed_tpu.comm.topology as topo_mod
    from deepspeed_tpu.models import build_model

    topo_mod.reset_topology()
    L, kvh, hd, BS, NB, MAXB = 3, 2, 64, 8, 12, 3
    m = build_model("llama-tiny", vocab_size=128, hidden_size=4 * hd,
                    num_layers=L, num_heads=4, num_kv_heads=kvh,
                    intermediate_size=128, max_seq_len=MAXB * BS)
    params = m.init_params(jax.random.PRNGKey(0))
    before = jax.random.normal(jax.random.PRNGKey(1),
                               m.init_kv_pool(NB, BS, jnp.float32).shape)
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
        return jax.jit(m.forward_paged)(params, ids, before, tables, starts)

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
