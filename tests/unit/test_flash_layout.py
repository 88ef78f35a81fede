"""The flash kernels in the model's own layout, against ``xla_attention``.

The kernels address ``(B, S, heads * head_dim)``, a whole lane tile of heads a
grid cell (two heads of 64, one of 128, one of 256), and walk a block a chunk
at a time. Interpreted here on the CPU: forward and ``jax.grad`` for every
tile form, with and without the causal mask, under grouped-query attention,
over one and several blocks and chunks, with ``Sq != Skv``, in float32 and
bfloat16, and with chunks of 256 and 512 whose causal corner is walked as a
staircase of 128-wide strips. Shapes that do not fill lane tiles give way to
XLA with the reason logged.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.transformer import flash_attention as fa
from deepspeed_tpu.ops.transformer.attention import (UnsupportedShape,
                                                     attention, xla_attention)


def case(id_, sq, nh, hd, *, skv=None, g=1, causal=True, dtype=jnp.float32,
         block=2048, chunk=None):
    return pytest.param(dict(sq=sq, skv=skv or sq, nh=nh, hd=hd, g=g,
                             causal=causal, dtype=dtype, block=block,
                             chunk=chunk), id=id_)


CASES = [
    # a pair of heads of 64 in one lane tile, one head of 128, one of 256
    case("hd64-pair", 256, 2, 64),
    case("hd64-two-pairs", 128, 4, 64),
    case("hd64-one-head", 128, 1, 64),
    case("hd128", 256, 2, 128),
    case("hd256", 128, 1, 256),
    case("hd64-pair-full", 256, 2, 64, causal=False),
    case("hd128-full", 256, 2, 128, causal=False),
    case("hd256-full", 128, 1, 256, causal=False),
    # grouped-query attention: the K/V tile and half follow (q head) // g
    case("hd64-g2", 256, 8, 64, g=2),
    case("hd64-g4", 256, 8, 64, g=4),
    case("hd64-g2-full", 128, 4, 64, g=2, causal=False),
    case("hd128-g2", 256, 4, 128, g=2),
    case("hd128-g4", 128, 4, 128, g=4),
    case("hd128-g2-full", 128, 2, 128, g=2, causal=False),
    # one, two and four blocks a sequence
    case("hd64-1-block", 128, 2, 64, block=128),
    case("hd64-2-blocks", 256, 2, 64, block=128),
    case("hd64-4-blocks", 512, 2, 64, block=128),
    case("hd128-2-blocks", 256, 1, 128, block=128),
    case("hd128-4-blocks", 512, 1, 128, block=128),
    case("hd64-4-blocks-full", 512, 2, 64, block=128, causal=False),
    # several chunks a block: the straight-line walk under the diagonal
    case("hd64-2-chunks", 256, 2, 64, chunk=128),
    case("hd64-4-chunks", 512, 2, 64, chunk=128),
    case("hd128-4-chunks", 512, 1, 128, chunk=128),
    case("hd64-g2-2-blocks-of-2-chunks", 512, 4, 64, g=2, block=256, chunk=128),
    case("hd128-4-chunks-full", 512, 1, 128, chunk=128, causal=False),
    # Sq != Skv: a block may lie past the other sequence's end
    case("hd64-short-q", 128, 2, 64, skv=256),
    case("hd64-long-q", 256, 2, 64, skv=128),
    case("hd128-short-q-blocks", 256, 1, 128, skv=512, block=128),
    case("hd128-long-q-blocks", 512, 1, 128, skv=256, block=128),
    case("hd64-short-q-full", 128, 2, 64, skv=384, causal=False),
    case("hd64-long-q-full", 384, 2, 64, skv=128, causal=False),
    # bfloat16, the training dtype
    case("bf16-hd64-pair", 256, 2, 64, dtype=jnp.bfloat16),
    case("bf16-hd64-g2-chunks", 256, 4, 64, g=2, chunk=128, dtype=jnp.bfloat16),
    case("bf16-hd128", 256, 2, 128, dtype=jnp.bfloat16),
    case("bf16-hd128-blocks", 256, 1, 128, block=128, dtype=jnp.bfloat16),
    case("bf16-hd256", 128, 1, 256, dtype=jnp.bfloat16),
    case("bf16-hd64-full", 128, 2, 64, causal=False, dtype=jnp.bfloat16),
    # chunks wider than a lane tile: the corner is a staircase of strips of
    # 128 queries, in both kernels
    case("hd64-pair-stairs-256", 512, 2, 64, chunk=256),
    case("hd128-stairs-256", 512, 1, 128, chunk=256),
    case("hd64-g2-stairs-256", 512, 4, 64, g=2, chunk=256),
    case("hd128-2-blocks-stairs-256", 512, 1, 128, block=256, chunk=256),
    case("hd64-stairs-256-full", 512, 2, 64, chunk=256, causal=False),
    case("bf16-hd64-pair-stairs-512", 1024, 2, 64, chunk=512,
         dtype=jnp.bfloat16),
    case("bf16-hd128-stairs-512", 1024, 1, 128, chunk=512, dtype=jnp.bfloat16),
    # the training cell's own chunks (512 forward, 256 backward)
    case("bf16-hd64-pair-seq1024", 1024, 2, 64, dtype=jnp.bfloat16),
    # Sq != Skv: the last block's corners end where the shorter sequence does
    case("hd64-short-q-stairs-256", 512, 2, 64, skv=1024, chunk=256),
    case("hd128-long-q-stairs-256", 1024, 1, 128, skv=512, chunk=256),
]


@pytest.mark.parametrize("c", CASES)
def test_flash_matches_xla(c, monkeypatch):
    if c["chunk"]:
        monkeypatch.setattr(fa, "FWD_CHUNK", c["chunk"])
        monkeypatch.setattr(fa, "BWD_CHUNK", c["chunk"])
    B, nh, hd, g, dtype = 1, c["nh"], c["hd"], c["g"], c["dtype"]
    keys = jax.random.split(jax.random.PRNGKey(c["sq"] + nh + hd), 4)

    def draw(key, s, heads):
        return jax.random.normal(key, (B, s, heads, hd), jnp.float32).astype(dtype)

    q = draw(keys[0], c["sq"], nh)
    k, v = draw(keys[1], c["skv"], nh // g), draw(keys[2], c["skv"], nh // g)
    w = draw(keys[3], c["sq"], nh).astype(jnp.float32)
    kw = dict(causal=c["causal"], num_kv_groups=g)

    def flash(q, k, v):
        return fa.flash_attention(q, k, v, block_q=c["block"],
                                  block_k=c["block"], **kw)

    def ref(q, k, v):
        return xla_attention(q, k, v, **kw)

    def out_and_grads(attn):
        def loss(*a):
            out = attn(*a)
            return jnp.sum(out.astype(jnp.float32) * w), out
        (_, out), grads = jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1, 2), has_aux=True))(q, k, v)
        return out, grads

    out_tol, grad_tol = (2e-5, 1e-4) if dtype == jnp.float32 else (3e-2, 3e-2)
    (out, got), (ref_out, want) = out_and_grads(flash), out_and_grads(ref)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref_out, np.float32),
                               atol=out_tol, rtol=out_tol)
    for name, a, b in zip("qkv", got, want):
        assert a.shape == b.shape and a.dtype == b.dtype
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.abs(a - b).max() <= grad_tol * np.abs(b).max(), f"d{name}"


@pytest.mark.parametrize("chunk", [128, 256, 512])
def test_staircase_covers_the_causal_triangle_once(chunk):
    """The tiling alone: tile for tile of 128 the strips hold the diagonal
    and what lies under it exactly once and nothing above it, each strip is
    128 queries against the keys up to theirs and meets the diagonal in one
    tile, its last, and a chunk of 128 is one masked square."""
    n = chunk // fa.LANES
    held = np.zeros((n, n), int)                    # (query tile, key tile)
    strips = fa._staircase(chunk)
    assert len(strips) == n
    for q0, q1, k0, k1 in strips:
        assert 0 <= q0 < q1 <= chunk and 0 == k0 < k1 <= chunk
        assert not any(x % fa.LANES for x in (q0, q1, k0, k1))
        assert q1 - q0 == fa.LANES and k1 == q1
        held[q0 // fa.LANES:q1 // fa.LANES, k0 // fa.LANES:k1 // fa.LANES] += 1
    np.testing.assert_array_equal(held, np.tril(np.ones((n, n), int)))
    if chunk == fa.LANES:
        assert strips == [(0, 128, 0, 128)]


@pytest.mark.parametrize("queries", [0, 1])
@pytest.mark.parametrize("q0,k0,nq,nk", [
    (0, 0, 128, 128),       # a chunk of a tile: the one masked square
    (256, 0, 128, 384),     # a forward strip: its last tile
    (128, 0, 384, 256),     # the backward's last strip with the queries after
    (384, 0, 128, 512),
])
def test_causal_masks_the_diagonals_tile_alone(q0, k0, nq, nk, queries):
    """``_causal`` on a strip's scores: NEG_INF exactly where key > query, in
    the one tile both ranges hold; everything else as it was."""
    shape = (nq, nk) if queries == 0 else (nk, nq)
    s = jnp.arange(nq * nk, dtype=jnp.float32).reshape(shape)
    got = np.asarray(fa._causal(s, q0, k0, queries))
    q = q0 + np.arange(nq)[:, None]
    k = k0 + np.arange(nk)[None, :]
    above = k > q if queries == 0 else (k > q).T
    np.testing.assert_array_equal(got, np.where(above, fa.NEG_INF, np.asarray(s)))
    # nothing above the diagonal outside that tile: the strip stops there
    lo = max(q0, k0)
    assert not (k > q)[:, :lo - k0].any() and (k > q)[:lo - q0 + 128, lo - k0 + 128:].all()


@pytest.mark.parametrize("s,chunk,computed", [
    (1024, 512, 589_824),       # the forward at the training cell
    (1024, 256, 589_824),       # its backward
    (2048, 512, 2_228_224),     # four chips
    (2048, 256, 2_228_224),
    (512, 128, 163_840),        # a chunk of a tile: the same walk
])
def test_pairs_computed_are_the_tiles_on_and_under_the_diagonal(
        s, chunk, computed):
    """What the kernels' bind records carry: a head's scores multiplied on the
    MXU are the 128 x 128 tiles on and under the diagonal (the parent's one
    masked square a chunk made 786,432 forward and 655,360 backward of the
    524,800 causal pairs at 1024), all of them without a mask, and whole
    blocks off the diagonal when a sequence has several."""
    def pairs(*walk):
        said = fa._pairs(*walk)
        return said["pairs_computed"], said["pairs_causal"]

    n = s // fa.LANES
    assert computed == fa.LANES ** 2 * n * (n + 1) // 2
    assert pairs(s, s, s, chunk, True) == (computed, s * (s + 1) // 2)
    assert pairs(s, s, s, chunk, False) == (s * s, s * s)
    # two blocks a sequence: one whole block under the diagonal
    half, small = s // 2, min(chunk, s // 2)
    own = pairs(half, half, half, small, True)[0]
    assert pairs(s, s, half, small, True)[0] == 2 * own + half * half
    # Sq != Skv: the needed pairs end with the shorter sequence
    assert pairs(s, half, half, small, True) == (
        own + half * half, half * (half + 1) // 2 + half * half)
    assert pairs(half, s, half, small, True) == (own, half * (half + 1) // 2)


@pytest.mark.parametrize("nh,g,why", [
    (3, 1, "q 3 x 64"),            # an odd head count at head 64
    (6, 2, "k/v 3 x 64"),          # an odd count of K/V heads under it
    (2, 2, "k/v 1 x 64"),          # one K/V head beside a full q tile
])
def test_half_filled_lane_tiles_give_way_to_xla(nh, g, why, monkeypatch):
    """``heads * head_dim`` that is no whole number of lane tiles: the kernel
    refuses the shape, ``attention()`` logs why and takes the XLA path (and a
    caller that named the kernel gets the error). Nothing is compiled: the
    refusal comes before any kernel."""
    from deepspeed_tpu.utils.logging import logger

    warned = []
    monkeypatch.setattr(logger, "warning", warned.append)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    q = jnp.ones((1, 128, nh, 64), jnp.float32)
    kv = jnp.ones((1, 128, nh // g, 64), jnp.float32)
    out = attention(q, kv, kv, causal=True, num_kv_groups=g)
    assert jnp.allclose(out, xla_attention(q, kv, kv, causal=True,
                                           num_kv_groups=g))
    assert len(warned) == 1 and "whole lane tiles" in warned[0] \
        and why in warned[0]
    with pytest.raises(UnsupportedShape):
        attention(q, kv, kv, causal=True, num_kv_groups=g, impl="pallas_flash")
