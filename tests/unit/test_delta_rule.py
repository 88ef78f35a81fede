"""The gated delta rule of ``ops/transformer/linear_attention.py`` (Kimi Delta
Attention): a decay of each key channel, and before a token writes ``k^T v``
it erases what the decayed state already answers to its key.

(a) The chunk form (tiles in sub-tiles of ``DELTA_SUB`` rows, the factors
taken relative to a sub-tile's first row) against the plain recurrence, one
token at a time in float64, with the log-decay drawn over the whole published
range: rows at -4.99 through a whole tile (``exp(-c)`` reaches ``exp(80)``
inside a sub-tile), rows at -1e-3 (a state that lasts a thousand tokens),
mixed; states over several tiles, a tile valid in its first ``n`` rows, a
slot's reuse (``fresh``), a preempted sequence's recompute.
(b) The decode form, XLA and the interpreted kernel, against the recurrence;
nothing but the live rows' slots moves.
(c) Which part is which: without the erase term, or with a head's decay and
not a channel's, the result is another.
(d) The convolution without a bias, rows and tiles.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.transformer import linear_attention as la

H, DK, DV, C = 8, 32, 16, 32
FLOOR = -la.DELTA_LOG_FLOOR


def drawn(rng, tokens, decay="mixed"):
    """q, k (T, H, DK) (k of unit length a head), v (T, H, DV), the log-decay
    (T, H, DK) and beta (T, H) of ``tokens`` consecutive tokens."""
    q = rng.normal(size=(tokens, H, DK)).astype(np.float32)
    k = rng.normal(size=(tokens, H, DK))
    k = (k / np.linalg.norm(k, axis=-1, keepdims=True)).astype(np.float32)
    v = rng.normal(size=(tokens, H, DV)).astype(np.float32)
    ld = {"mixed": rng.uniform(FLOOR, 0.0, size=(tokens, H, DK)),
          "floor": np.full((tokens, H, DK), -4.99),
          "slow": np.full((tokens, H, DK), -1e-3)}[decay]
    if decay == "mixed":
        # whole rows at either end of the range among the drawn ones
        ld[::5], ld[2::7] = -4.99, -1e-3
    beta = rng.uniform(0.05, 0.95, size=(tokens, H)).astype(np.float32)
    return q, k, v, ld.astype(np.float32), beta


def recurrence(q, k, v, ld, beta, s0=None, erase=True):
    """(o (T, H, DV), last state) of the plain recurrence in float64."""
    q, k, v, ld, beta = (np.asarray(a, np.float64)
                         for a in (q, k, v, ld, beta))
    s = np.zeros((H, DK, DV)) if s0 is None else np.asarray(s0, np.float64)
    out = []
    for t in range(len(q)):
        s = np.exp(ld[t])[..., None] * s
        seen = np.einsum("hk,hkv->hv", k[t], s) if erase else 0.0
        s = s + k[t][..., None] * (beta[t][:, None] * (v[t] - seen))[:, None]
        out.append(np.einsum("hk,hkv->hv", q[t], s))
    return np.stack(out), s


def tiled(a, n_tiles):
    """(T, ...) padded to ``n_tiles`` tiles of ``C`` rows."""
    pad = n_tiles * C - len(a)
    return jnp.asarray(np.concatenate(
        [a, np.zeros((pad,) + a.shape[1:], a.dtype)]).reshape(
            n_tiles, C, *a.shape[1:]))


@pytest.fixture(scope="module")
def forms():
    """The jitted forms, one trace each for the whole file."""
    def decode(*a):
        return la.decode_rows(*a[:-2], log_decay=a[-2], scope="delta_scan",
                              beta=a[-1])

    def chunks(*a):
        return la.chunk_tiles(*a[:-2], log_decay=a[-2], scope="delta_scan",
                              beta=a[-1])

    return jax.jit(decode), jax.jit(chunks)


def run_chunks(chunks, state, slot, xs, tokens, fresh=True, layer=1):
    n = -(-tokens // C)
    counts = np.minimum(C, tokens - C * np.arange(n)).astype(np.int32)
    q, k, v, ld, beta = (tiled(a[:tokens], n) for a in xs)
    return chunks(state, jnp.int32(layer), jnp.full((n,), slot, jnp.int32),
                  jnp.asarray(counts), q, k, v,
                  jnp.asarray([fresh] + [False] * (n - 1)), ld, beta)


@pytest.mark.parametrize("decay", ["mixed", "floor", "slow"])
@pytest.mark.parametrize("tokens", [3 * C, 2 * C + 5, 7])
def test_chunk_form_is_the_recurrence_over_several_tiles(forms, tokens, decay):
    """A sequence's tiles in one call (the last partly filled) on a slot
    another sequence left dirty, then the next call from the state it left:
    at every decay of the published range, a whole tile at the floor
    included, nothing overflows and every row is the recurrence's."""
    _, chunks = forms
    rng = np.random.default_rng(tokens)
    xs = drawn(rng, tokens + C, decay)
    state = jnp.asarray(rng.normal(size=(2, 4, H, DK, DV)), jnp.float32)
    o, new = run_chunks(chunks, state, 3, xs, tokens)
    want, last = recurrence(*(a[:tokens] for a in xs))
    got = np.asarray(o).reshape(-1, H, DV)[:tokens]
    assert np.isfinite(np.asarray(o)).all()
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(new[1, 3], last, rtol=2e-4, atol=2e-4)
    np.testing.assert_array_equal(new[0], state[0])
    np.testing.assert_array_equal(new[1, :3], state[1, :3])
    # the next chunk of the same sequence, one whole tile, carries on
    more = [a[tokens:tokens + C] for a in xs]
    o2, new2 = run_chunks(chunks, new, 3, more, C, fresh=False)
    want2, last2 = recurrence(*more, s0=last)
    np.testing.assert_allclose(np.asarray(o2)[0], want2, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(new2[1, 3], last2, rtol=2e-4, atol=2e-4)


def test_a_state_outlives_many_tiles_at_slow_decays(forms):
    """With a channel losing 1e-3 a token the first tile's tokens still weigh
    in the state after four tiles (the chip comparison's drawn ``a_log`` and
    ``dt_bias`` forget in two or three tokens): drop the first tile and the
    last state is another; at the floor it is the same."""
    _, chunks = forms
    gaps = {}
    for decay in ("slow", "floor"):
        xs = drawn(np.random.default_rng(5), 4 * C, decay)
        _, new = run_chunks(chunks, la.init_state(1, 1, H, DK, DV), 1, xs,
                            4 * C, layer=0)
        _, last = recurrence(*xs)
        np.testing.assert_allclose(new[0, 1], last, rtol=5e-4, atol=5e-4)
        _, late = recurrence(*(a[C:] for a in xs))
        gaps[decay] = np.abs(last - late).max() / np.abs(last).max()
    assert gaps["slow"] > 0.05 and gaps["floor"] < 1e-6, gaps


def test_a_reused_slot_starts_clean_and_a_recompute_gives_the_state_again(
        forms):
    """A slot handed on is not zeroed: the next owner's first tile is
    ``fresh`` and starts from zeros. A preempted sequence recomputes from its
    prompt into whatever slot it gets and ends in the state it had."""
    _, chunks = forms
    rng = np.random.default_rng(9)
    first, second = drawn(rng, 2 * C), drawn(rng, C + 3)
    state = la.init_state(2, 3, H, DK, DV)
    _, state = run_chunks(chunks, state, 2, first, 2 * C)
    had = np.asarray(state[1, 2])
    # the slot's next owner
    o, state = run_chunks(chunks, state, 2, second, C + 3)
    want, last = recurrence(*second)
    np.testing.assert_allclose(
        np.asarray(o).reshape(-1, H, DV)[:C + 3], want, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(state[1, 2], last, rtol=2e-4, atol=2e-4)
    # the first sequence again, from its prompt, in another (dirty) slot
    state = state.at[1, 1].set(7.0)
    _, state = run_chunks(chunks, state, 1, first, 2 * C)
    np.testing.assert_allclose(state[1, 1], had, rtol=1e-5, atol=1e-6)


#: rows' slots (0: a dead row) and the fresh rows of a step of one-token rows
DECODE_STEPS = {
    "live_prefix": ([2, 4, 1, 0, 0, 0, 0, 0], [1]),
    "scattered": ([0, 3, 0, 0, 5, 0, 1, 0], [4]),
    "no_live_row": ([0] * 8, []),
}


@pytest.mark.parametrize("kernel", [False, True])
@pytest.mark.parametrize("step", sorted(DECODE_STEPS))
def test_decode_rows_is_one_step_of_the_recurrence(monkeypatch, kernel, step):
    """A step of one-token rows, XLA and the interpreted kernel
    (``delta_decode``): a live row's slot moves by one step of the recurrence
    (a fresh row from zeros whatever its slot held), a dead row gives zeros
    from the kernel, and of the slot array nothing but the live rows' slots in
    the call's layer changes."""
    if kernel:
        monkeypatch.setenv("DSTPU_FORCE_PAGED_KERNEL", "1")
    slots, fresh_rows = DECODE_STEPS[step]
    R = len(slots)
    rng = np.random.default_rng(R + len(fresh_rows))
    q, k, v, ld, beta = drawn(rng, R)
    state = jnp.asarray(rng.normal(size=(2, 6, H, DK, DV)), jnp.float32)
    fresh = np.zeros(R, bool)
    fresh[fresh_rows] = True

    # a lambda around it: the trace is this test's own (the kernel's call is
    # cached by shape behind an inlined jit)
    o, new = jax.jit(lambda *a: la.decode_rows(
        *a[:-2], log_decay=a[-2], scope="delta_scan", beta=a[-1]))(
        state, jnp.int32(1), jnp.asarray(slots, jnp.int32), jnp.asarray(q),
        jnp.asarray(k), jnp.asarray(v), jnp.asarray(fresh), jnp.asarray(ld),
        jnp.asarray(beta))
    o, new = np.asarray(o), np.asarray(new)
    untouched = np.ones(6, bool)
    for r, slot in enumerate(slots):
        if not slot:
            if kernel:
                np.testing.assert_array_equal(o[r], 0.0)
            continue
        untouched[slot] = False
        s0 = None if fresh[r] else state[1, slot]
        want, last = recurrence(*(a[r:r + 1] for a in (q, k, v, ld, beta)),
                                s0=s0)
        np.testing.assert_allclose(o[r], want[0], rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(new[1, slot], last, rtol=2e-5, atol=2e-5)
    np.testing.assert_array_equal(new[0], state[0])
    if kernel:       # the trash slot too
        np.testing.assert_array_equal(new[1, untouched], state[1, untouched])
    else:
        np.testing.assert_array_equal(new[1, untouched][1:],
                                      np.asarray(state)[1, untouched][1:])


def test_the_kernel_takes_a_key_axis_in_pieces(monkeypatch):
    """Keys of 256 (two pieces of a state block's key axis): the read-out
    ``k S'`` is summed over both pieces before either is written."""
    monkeypatch.setenv("DSTPU_FORCE_PAGED_KERNEL", "1")
    rng = np.random.default_rng(3)
    R, dk = 4, 256
    q = rng.normal(size=(R, H, dk)).astype(np.float32)
    k = rng.normal(size=(R, H, dk))
    k = (k / np.linalg.norm(k, axis=-1, keepdims=True)).astype(np.float32)
    v = rng.normal(size=(R, H, DV)).astype(np.float32)
    ld = rng.uniform(FLOOR, 0.0, size=(R, H, dk)).astype(np.float32)
    beta = rng.uniform(0.05, 0.95, size=(R, H)).astype(np.float32)
    state = jnp.asarray(rng.normal(size=(1, 5, H, dk, DV)), jnp.float32)
    slots = jnp.asarray([4, 0, 1, 2], jnp.int32)
    args = (state, jnp.int32(0), slots, jnp.asarray(q), jnp.asarray(k),
            jnp.asarray(v), jnp.zeros(R, bool))
    o, new = jax.jit(lambda *a: la.linear_decode(
        *a, jnp.asarray(ld), "delta_scan", jnp.asarray(beta)))(*args)
    monkeypatch.setenv("DSTPU_FORCE_PAGED_KERNEL", "0")
    o2, new2 = jax.jit(lambda *a: la.decode_rows(
        *a, log_decay=jnp.asarray(ld), scope="delta_scan",
        beta=jnp.asarray(beta)))(*args)
    live = np.asarray(slots) > 0
    np.testing.assert_allclose(np.asarray(o)[live], np.asarray(o2)[live],
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(new)[0, 1:], np.asarray(new2)[0, 1:],
                               rtol=2e-5, atol=2e-5)


def test_the_erase_term_and_the_channels_decay_are_seen(forms):
    """The chunk form against the recurrence WITHOUT the erase term, and
    against the recurrence with each head's channels all decaying by their
    mean: both are far off (the comparison of (a) holds 2e-4)."""
    _, chunks = forms
    xs = drawn(np.random.default_rng(2), 2 * C)
    o, _ = run_chunks(chunks, la.init_state(2, 1, H, DK, DV), 1, xs, 2 * C)
    got = np.asarray(o).reshape(-1, H, DV)
    want, _ = recurrence(*xs)
    scale = np.abs(want).max()
    no_erase, _ = recurrence(*xs, erase=False)
    q, k, v, ld, beta = xs
    of_head, _ = recurrence(q, k, v, np.broadcast_to(
        ld.mean(axis=-1, keepdims=True), ld.shape), beta)
    assert np.abs(got - want).max() < 2e-4 * scale
    assert np.abs(got - no_erase).max() > 1e-2 * scale
    assert np.abs(got - of_head).max() > 1e-2 * scale


def test_a_tile_is_whole_sub_tiles():
    """A tile longer than a sub-tile that is no multiple of it would hold
    ``exp(-c)`` past float32's range: refused, not computed."""
    xs = drawn(np.random.default_rng(0), 24)
    with pytest.raises(ValueError, match="no multiple of the delta"):
        la.chunk_tiles(la.init_state(1, 1, H, DK, DV), 0,
                       jnp.ones((1,), jnp.int32), jnp.full((1,), 24),
                       *(jnp.asarray(a)[None] for a in xs[:3]),
                       jnp.asarray([True]), log_decay=jnp.asarray(xs[3])[None],
                       beta=jnp.asarray(xs[4])[None])


def test_a_convolution_without_bias_rows_and_tiles():
    """``conv_rows`` / ``conv_tiles`` with ``bias`` None over a sequence's
    tokens, the first as tiles (the last partly filled), the rest one-token
    rows, against the plain causal convolution."""
    rng = np.random.default_rng(4)
    ch, taps_n, tokens = 24, 4, C + 9
    x = rng.normal(size=(tokens + 3, ch)).astype(np.float32)
    taps = rng.normal(size=(taps_n, ch)).astype(np.float32)
    padded = np.concatenate([np.zeros((taps_n - 1, ch), np.float32), x])
    want = sum(taps[i] * padded[i:i + len(x)] for i in range(taps_n))
    window = jnp.asarray(rng.normal(size=(1, 3, taps_n - 1, ch)), jnp.float32)
    n = -(-tokens // C)
    counts = np.minimum(C, tokens - C * np.arange(n)).astype(np.int32)
    y, window = la.conv_tiles(
        window, 0, jnp.full((n,), 2, jnp.int32), jnp.asarray(counts),
        tiled(x[:tokens], n), jnp.asarray(taps), None,
        jnp.asarray([True] + [False] * (n - 1)))
    np.testing.assert_allclose(np.asarray(y).reshape(-1, ch)[:tokens],
                               want[:tokens], rtol=1e-5, atol=1e-5)
    for t in range(tokens, tokens + 3):
        y, window = la.conv_rows(window, 0, jnp.asarray([2], jnp.int32),
                                 jnp.asarray(x[t:t + 1]), jnp.asarray(taps),
                                 None, jnp.asarray([False]))
        np.testing.assert_allclose(np.asarray(y)[0], want[t], rtol=1e-5,
                                   atol=1e-5)
