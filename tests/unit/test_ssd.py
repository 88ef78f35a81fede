"""The recurrence family of ``ops/transformer/linear_attention.py`` with the
decay as an operand (the SSD / Mamba-2 form): a decay a row a head, keys and
queries a group's, ``dk`` beside another ``dv``; and the causal convolution's
window slots.

(a) The decode form (XLA and the interpreted kernel), the chunk form and the
plain recurrence, one token at a time in float64, agree, with ``A`` in 1..16
and ``dt`` in 1e-3..1e-1 (the published initialisation's ranges: a state that
lasts hundreds of tokens) over several tiles, the last partly filled; a
reused slot starts from zeros and a recompute from the prompt gives the same
state again.
(b) The convolution: one-token rows, tiles and the plain causal form agree
across a tile's edge, a partial tile and a slot's reuse.
(c) Lightning through the generalised entry points is the parent's, bit for
bit: the XLA forms against the parent's text (kept here), the kernel by its
traced body (the parent's digest).
"""

import functools
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.transformer import linear_attention as la
from deepspeed_tpu.ops.transformer import paged_attention as pa

H, G, DK, DV, C = 16, 2, 32, 16, 16


def drawn(rng, tokens):
    """q, k (T, G, DK), v (T, H, DV) and the log-decay (T, H) of ``tokens``
    consecutive tokens, ``A`` (1 for the first head up to 16 for the last)
    and ``dt`` in the published ranges."""
    q, k = (rng.normal(size=(tokens, G, DK)).astype(np.float32)
            for _ in range(2))
    v = rng.normal(size=(tokens, H, DV)).astype(np.float32)
    a = np.linspace(1.0, 16.0, H)
    dt = np.exp(rng.uniform(np.log(1e-3), np.log(1e-1), size=(tokens, H)))
    return q, k, v, (-dt * a).astype(np.float32)


def recurrence(q, k, v, ld, s0=None):
    """(o (T, H, DV), last state) of the plain recurrence in float64."""
    q, k = (np.repeat(np.asarray(a, np.float64), H // G, axis=1)
            for a in (q, k))
    v, ld = np.asarray(v, np.float64), np.asarray(ld, np.float64)
    s = np.zeros((H, DK, DV)) if s0 is None else np.asarray(s0, np.float64)
    out = []
    for t in range(len(q)):
        s = np.exp(ld[t])[:, None, None] * s \
            + k[t][:, :, None] * v[t][:, None, :]
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
        return la.decode_rows(*a[:-1], log_decay=a[-1], scope="ssm_scan")

    def chunks(*a):
        return la.chunk_tiles(*a[:-1], log_decay=a[-1], scope="ssm_scan")

    return jax.jit(decode), jax.jit(chunks)


@pytest.mark.parametrize("tokens", [3 * C, 2 * C + 5, 7])
def test_chunk_form_is_the_recurrence_over_several_tiles(forms, tokens):
    """A sequence's tiles in one call (the last partly filled) on a slot
    another sequence left dirty, then the next call from the state it left."""
    _, chunks = forms
    rng = np.random.default_rng(tokens)
    q, k, v, ld = drawn(rng, tokens + C)
    state = jnp.asarray(rng.normal(size=(2, 4, H, DK, DV)), jnp.float32)
    n = -(-tokens // C)
    counts = np.minimum(C, tokens - C * np.arange(n)).astype(np.int32)
    o, new = chunks(state, jnp.int32(1), jnp.full((n,), 3, jnp.int32),
                    jnp.asarray(counts), *(tiled(a[:tokens], n)
                                           for a in (q, k, v)),
                    jnp.asarray([True] + [False] * (n - 1)),
                    tiled(ld[:tokens], n))
    want, last = recurrence(q[:tokens], k[:tokens], v[:tokens], ld[:tokens])
    got = np.asarray(o).reshape(n * C, H, DV)[:tokens]
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(new[1, 3], last, rtol=2e-4, atol=2e-4)
    np.testing.assert_array_equal(new[0], state[0])
    np.testing.assert_array_equal(new[1, :3], state[1, :3])
    # the next chunk of the same sequence, one whole tile, carries on
    more = slice(tokens, tokens + C)
    o2, new2 = chunks(new, jnp.int32(1), jnp.asarray([3], jnp.int32),
                      jnp.asarray([C], jnp.int32),
                      *(tiled(a[more], 1) for a in (q, k, v)),
                      jnp.asarray([False]), tiled(ld[more], 1))
    want2, last2 = recurrence(q[more], k[more], v[more], ld[more], last)
    np.testing.assert_allclose(np.asarray(o2)[0], want2, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(new2[1, 3], last2, rtol=2e-4, atol=2e-4)


def test_a_state_outlives_many_tiles_at_the_published_decays(forms):
    """With ``dt A`` of the published ranges the first tile's tokens still
    weigh in the slowest head's state after four tiles (the chip comparison's
    drawn ``a_log`` and ``dt_bias`` forget in ~25 tokens): drop the first
    tile and that head's last state is another, the fastest head's the
    same."""
    _, chunks = forms
    rng = np.random.default_rng(5)
    tokens, n = 4 * C, 4
    q, k, v, ld = drawn(rng, tokens)
    state = la.init_state(1, 1, H, DK, DV)
    args = (jnp.int32(0), jnp.ones((n,), jnp.int32),
            jnp.full((n,), C, jnp.int32))
    fresh = jnp.asarray([True] + [False] * (n - 1))
    _, new = chunks(state, *args, *(tiled(a, n) for a in (q, k, v)), fresh,
                    tiled(ld, n))
    _, last = recurrence(q, k, v, ld)
    np.testing.assert_allclose(new[0, 1], last, rtol=5e-4, atol=5e-4)
    _, late = recurrence(q[C:], k[C:], v[C:], ld[C:])
    gap = np.abs(last - late).max(axis=(1, 2)) / np.abs(last).max(axis=(1, 2))
    assert gap[0] > 0.05 and gap[-1] < 1e-3, gap


#: rows' slots (0: a dead row) and the fresh rows of a step of one-token rows
DECODE_STEPS = {
    "live_prefix": ([2, 4, 1, 0, 0, 0], [1]),
    "scattered": ([0, 3, 0, 0, 5, 0, 1, 0], [4]),
    "no_live_row": ([0] * 8, []),
}


@pytest.mark.parametrize("step", DECODE_STEPS)
def test_decode_forms_are_the_recurrence(monkeypatch, forms, step):
    """One token a row, XLA and the interpreted kernel: a live row's output
    and state are the recurrence's, a fresh row starts from zeros whatever
    the slot held (a slot's reuse), nothing else of the slot array moves."""
    decode, _ = forms
    slots, fresh_rows = DECODE_STEPS[step]
    rng = np.random.default_rng(2)
    R = len(slots)
    q, k, v, ld = drawn(rng, R)
    state = jnp.asarray(rng.normal(size=(3, 6, H, DK, DV)), jnp.float32)
    fresh = np.zeros(R, bool)
    fresh[fresh_rows] = True
    args = (state, jnp.int32(1), jnp.asarray(slots, jnp.int32),
            *(jnp.asarray(a) for a in (q, k, v)), jnp.asarray(fresh),
            jnp.asarray(ld))
    xla = decode(*args)
    monkeypatch.setenv("DSTPU_FORCE_PAGED_KERNEL", "1")
    assert pa.kernels_wanted()
    kernel = jax.jit(lambda *a: la.decode_rows(
        *a[:-1], log_decay=a[-1], scope="ssm_scan"))(*args)
    unnamed = [n for n in range(1, 6) if n not in slots]
    for name, (o, new) in (("xla", xla), ("kernel", kernel)):
        assert o.shape == (R, H, DV) and o.dtype == jnp.float32, name
        for r in (r for r in range(R) if slots[r]):
            want, last = recurrence(
                q[r:r + 1], k[r:r + 1], v[r:r + 1], ld[r:r + 1],
                None if fresh[r] else state[1, slots[r]])
            np.testing.assert_allclose(o[r], want[0], rtol=1e-5, atol=1e-5,
                                       err_msg=name)
            np.testing.assert_allclose(new[1, slots[r]], last, rtol=1e-5,
                                       atol=1e-5, err_msg=name)
        np.testing.assert_array_equal(new[0], state[0], err_msg=name)
        np.testing.assert_array_equal(new[2], state[2], err_msg=name)
        np.testing.assert_array_equal(new[1, unnamed], state[1, unnamed],
                                      err_msg=name)
    o, new = kernel
    dead = [r for r in range(R) if not slots[r]]
    np.testing.assert_array_equal(np.asarray(o)[dead], 0.0)
    np.testing.assert_array_equal(new[1, 0], state[1, 0])


def test_prefill_then_decode_then_a_recompute_on_the_same_slot(forms):
    """A prompt in tiles, then tokens one a step, is the recurrence over all
    of them; the same again on the slot as it was left (a preemption's
    recompute: ``fresh`` and not a zeroed slot) gives the same bits."""
    decode, chunks = forms
    rng = np.random.default_rng(9)
    prompt, steps = C + 6, 5
    q, k, v, ld = drawn(rng, prompt + steps)

    def run(state):
        counts = jnp.asarray([C, prompt - C], jnp.int32)
        o, state = chunks(state, jnp.int32(0), jnp.asarray([2, 2], jnp.int32),
                          counts, *(tiled(a[:prompt], 2) for a in (q, k, v)),
                          jnp.asarray([True, False]), tiled(ld[:prompt], 2))
        out = [np.asarray(o).reshape(2 * C, H, DV)[:prompt]]
        for t in range(prompt, prompt + steps):
            o, state = decode(state, jnp.int32(0), jnp.asarray([0, 2]),
                              *(jnp.asarray(np.stack([a[t], a[t]]))
                                for a in (q, k, v)),
                              jnp.asarray([False, False]),
                              jnp.asarray(np.stack([ld[t], ld[t]])))
            out.append(np.asarray(o)[1:])
        return np.concatenate(out), state

    first, state = run(la.init_state(1, 2, H, DK, DV))
    want, last = recurrence(q, k, v, ld)
    np.testing.assert_allclose(first, want, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(state[0, 2], last, rtol=2e-4, atol=2e-4)
    again, state2 = run(state)
    np.testing.assert_array_equal(again, first)
    np.testing.assert_array_equal(state2[0, 2], state[0, 2])


# -- (b) the convolution's window -------------------------------------------

def causal_conv(x, taps, bias):
    K = len(taps)
    padded = np.concatenate([np.zeros((K - 1, x.shape[1])), x])
    return sum(taps[i] * padded[i:i + len(x)] for i in range(K)) + bias


@pytest.mark.parametrize("tokens", [2 * C, C + 2, 2])
def test_the_conv_window_crosses_a_tiles_edge_and_a_slots_reuse(tokens):
    """Tiles (the last with ``tokens % C`` valid rows, fewer than the window
    where that is 2), then one-token rows, against the plain causal
    convolution; the slot held another sequence's rows before."""
    rng = np.random.default_rng(tokens)
    K, ch, steps = 4, 24, 4
    x = rng.normal(size=(tokens + steps, ch)).astype(np.float32)
    taps = rng.normal(size=(K, ch)).astype(np.float32)
    bias = rng.normal(size=(ch,)).astype(np.float32)
    window = jnp.asarray(rng.normal(size=(2, 3, K - 1, ch)), jnp.float32)
    n = -(-tokens // C)
    counts = np.minimum(C, tokens - C * np.arange(n)).astype(np.int32)
    y, new = la.conv_tiles(window, jnp.int32(1), jnp.full((n,), 2, jnp.int32),
                           jnp.asarray(counts), tiled(x[:tokens], n),
                           jnp.asarray(taps), jnp.asarray(bias),
                           jnp.asarray([True] + [False] * (n - 1)))
    want = causal_conv(x.astype(np.float64), taps, bias)
    got = [np.asarray(y).reshape(n * C, ch)[:tokens]]
    np.testing.assert_array_equal(new[0], window[0])
    np.testing.assert_array_equal(new[1, :2], window[1, :2])
    held = np.concatenate([np.zeros((K - 1, ch), np.float32), x[:tokens]])
    np.testing.assert_array_equal(new[1, 2], held[-(K - 1):])
    for t in range(tokens, tokens + steps):
        y, new = la.conv_rows(new, jnp.int32(1), jnp.asarray([2, 0]),
                              jnp.asarray(np.stack([x[t], x[t]])),
                              jnp.asarray(taps), jnp.asarray(bias),
                              jnp.asarray([False, False]))
        got.append(np.asarray(y)[:1])
    np.testing.assert_allclose(np.concatenate(got), want, rtol=1e-5,
                               atol=1e-5)
    # a fresh one-token row on the dirty slot: zeros behind it
    y, new = la.conv_rows(new, jnp.int32(1), jnp.asarray([2]),
                          jnp.asarray(x[:1]), jnp.asarray(taps),
                          jnp.asarray(bias), jnp.asarray([True]))
    np.testing.assert_allclose(np.asarray(y)[0], want[0], rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(new[1, 2, :-1]), 0.0)


def conv_rows_as(form, monkeypatch, *args):
    """``conv_rows`` traced as the XLA form or as the interpreted kernel
    ``conv_decode`` (called by its name with a bias: ``conv_rows`` hands it
    the convolutions without one); a trace a form and a shape (an eager call
    of an interpreted kernel compiles it anew every time)."""
    if form == "kernel":
        monkeypatch.setenv("DSTPU_FORCE_PAGED_KERNEL", "1")
    else:
        monkeypatch.delenv("DSTPU_FORCE_PAGED_KERNEL", raising=False)
    assert pa.kernels_wanted() == (form == "kernel")
    return _conv_jit(form, form == "kernel" and args[5] is not None)(*args)


@functools.lru_cache(maxsize=None)
def _conv_jit(form, by_name):
    return jax.jit(lambda *a: (la.conv_decode if by_name else la.conv_rows)(*a))


#: which of eight rows hold a slot (of 1..9; 0: a padding row)
LIVE_ROWS = {"none": [0] * 8, "a prefix": [4, 7, 1, 0, 0, 0, 0, 0],
             "scattered": [0, 5, 0, 0, 2, 0, 9, 0],
             "all": [3, 8, 1, 9, 2, 7, 4, 6]}


@pytest.mark.parametrize("live", LIVE_ROWS)
@pytest.mark.parametrize("ch", [640, 1536])
@pytest.mark.parametrize("biased", [True, False], ids=["bias", "no bias"])
def test_the_convolutions_kernel_is_conv_rows_on_the_live_rows_slots(
        monkeypatch, biased, ch, live):
    """``conv_decode`` (interpreted; 1536 channels are two cells) against the
    XLA form on a bfloat16 window array: a live row's ``y`` to float32
    rounding and its slot bit for bit; a fresh row, live or dead, reads zeros
    whatever the slot held; a dead row gets the ``y`` of a zero window and
    changes no slot, the trash slot included (where the XLA form writes what
    it pleases); the other layers are not touched."""
    rng = np.random.default_rng(ch)
    K, R, slots = 4, 8, np.asarray(LIVE_ROWS[live], np.int32)
    bf16, f32 = jnp.bfloat16, np.float32
    window = jnp.asarray(rng.normal(size=(3, 10, K - 1, ch)), bf16)
    x = jnp.asarray(rng.normal(size=(R, ch)), bf16)
    taps = jnp.asarray(rng.normal(size=(K, ch)), bf16)
    bias = jnp.asarray(rng.normal(size=(ch,)), bf16) if biased else None
    fresh = np.asarray([0, 0, 1, 0, 1, 0, 1, 1], bool)
    args = (window, jnp.int32(1), jnp.asarray(slots), x, taps, bias,
            jnp.asarray(fresh))
    want_y, want_w = conv_rows_as("xla", monkeypatch, *args)
    y, new = conv_rows_as("kernel", monkeypatch, *args)
    assert y.shape == (R, ch) and y.dtype == jnp.float32
    assert new.shape == window.shape and new.dtype == window.dtype
    held = slots > 0
    np.testing.assert_allclose(np.asarray(y)[held], np.asarray(want_y)[held],
                               rtol=2e-6, atol=2e-6)
    named = slots[held]
    np.testing.assert_array_equal(np.asarray(new[1, named], f32),
                                  np.asarray(want_w[1, named], f32))
    rest = [s for s in range(10) if s not in named]          # slot 0 too
    np.testing.assert_array_equal(np.asarray(new[1, rest], f32),
                                  np.asarray(window[1, rest], f32))
    for layer in (0, 2):
        np.testing.assert_array_equal(np.asarray(new[layer], f32),
                                      np.asarray(window[layer], f32))
    zero_window = np.asarray(taps, f32)[-1] * np.asarray(x, f32) + (
        np.asarray(bias, f32) if biased else 0.0)
    alone = ~held | fresh
    np.testing.assert_allclose(np.asarray(y)[alone], zero_window[alone],
                               rtol=2e-6, atol=2e-6)
    for r in np.flatnonzero(held & fresh):
        np.testing.assert_array_equal(
            np.asarray(new[1, slots[r], :-1], f32), 0.0)
        np.testing.assert_array_equal(np.asarray(new[1, slots[r], -1], f32),
                                      np.asarray(x[r], f32))


def test_the_convolutions_cells_hand_their_channels_on(monkeypatch):
    """Five cells of 128 channels (``CONV_LANES`` lowered so that the test's
    640 channels are more cells than one) give what one cell gives: the live
    rows' list and the slots' modes are made by the first cell and read by
    all."""
    rng = np.random.default_rng(5)
    window = jnp.asarray(rng.normal(size=(2, 10, 3, 640)), jnp.bfloat16)
    args = (window, jnp.int32(0), jnp.asarray(LIVE_ROWS["scattered"]),
            jnp.asarray(rng.normal(size=(8, 640)), jnp.bfloat16),
            jnp.asarray(rng.normal(size=(4, 640)), jnp.bfloat16), None,
            jnp.asarray([False, True] * 4))
    want_y, want_w = conv_rows_as("kernel", monkeypatch, *args)
    monkeypatch.setattr(la, "CONV_LANES", 128)
    assert la.conv_lanes(640) == 128 and la.conv_lanes(12288) == 128
    y, new = jax.jit(lambda *a: la.conv_rows(*a))(*args)
    np.testing.assert_array_equal(y, want_y)
    np.testing.assert_array_equal(np.asarray(new, np.float32),
                                  np.asarray(want_w, np.float32))


def test_channels_that_are_no_lane_tiles_keep_the_xla_form(monkeypatch):
    """The kernel's cells are whole lane tiles of channels (640 of the 5120
    and 12288 of the served models are); another count keeps the XLA form,
    kernels wanted or not, and so do a convolution of one tap and one with a
    bias (the benchmark's compile test of the SSD mixer's cell pins its
    layer's custom calls)."""
    monkeypatch.setenv("DSTPU_FORCE_PAGED_KERNEL", "1")

    def traced(ch, taps=4, bias=None):
        return str(jax.make_jaxpr(
            lambda w, x, t: la.conv_rows(w, 0, jnp.asarray([1, 0]), x, t,
                                         bias, jnp.asarray([False, False])))(
            jnp.zeros((1, 3, taps - 1, ch)), jnp.zeros((2, ch)),
            jnp.zeros((taps, ch))))

    assert "pallas_call" in traced(640) and "conv_decode" in traced(640)
    assert "pallas_call" not in traced(24)
    assert "pallas_call" not in traced(200)
    assert "pallas_call" not in traced(640, taps=1)
    assert "pallas_call" not in traced(640, bias=jnp.zeros((640,)))
    assert [la.conv_lanes(c) for c in (128, 640, 1536, 5120, 12288)] == [
        128, 640, 768, 1024, 1024]


@pytest.mark.parametrize("form", ["xla", "kernel"])
@pytest.mark.parametrize("biased", [True, False], ids=["bias", "no bias"])
def test_a_window_crosses_from_rounds_into_tiles_and_back_on_a_reused_slot(
        monkeypatch, form, biased):
    """Slot 2 of layer 1: one sequence's rounds leave their rows there; the
    next sequence takes the slot with a fresh round, goes on for two rounds,
    then a mixed step's tiles (two, the last partly filled) read what the
    rounds left and leave their last three rows, and three more rounds read
    those; every ``y`` of the second sequence is the plain causal
    convolution of its own tokens. A second live row on slot 1 runs beside
    the rounds; the tiles leave its slot as the rounds left it."""
    rng = np.random.default_rng(11)
    K, ch = 4, 640
    before, tokens = 5, 3 + C + 5 + 3
    old = rng.normal(size=(before, ch)).astype(np.float32)
    x = rng.normal(size=(tokens, ch)).astype(np.float32)
    other = rng.normal(size=(before + tokens, ch)).astype(np.float32)
    taps = rng.normal(size=(K, ch)).astype(np.float32)
    bias = rng.normal(size=(ch,)).astype(np.float32) if biased else None
    window = jnp.asarray(rng.normal(size=(2, 4, K - 1, ch)), jnp.float32)
    slots = jnp.asarray([2, 0, 1, 0], jnp.int32)
    got, beside = [], []

    def round_(window, mine, its, fresh):
        rows = jnp.asarray(np.stack([mine, mine, its, its]))
        y, window = conv_rows_as(
            form, monkeypatch, window, jnp.int32(1), slots, rows,
            jnp.asarray(taps), None if bias is None else jnp.asarray(bias),
            jnp.asarray([fresh, False, len(beside) == 0, True]))
        beside.append(np.asarray(y)[2])
        return np.asarray(y)[0], window

    for t in range(before):
        _, window = round_(window, old[t], other[t], t == 0)
    for t in range(3):
        y, window = round_(window, x[t], other[before + t], t == 0)
        got.append(y[None])
    kept = np.asarray(window[1, 1])
    n = C + 5
    counts = jnp.asarray([C, 5], jnp.int32)
    y, window = la.conv_tiles(
        window, jnp.int32(1), jnp.asarray([2, 2], jnp.int32), counts,
        tiled(x[3:3 + n], 2), jnp.asarray(taps),
        None if bias is None else jnp.asarray(bias),
        jnp.asarray([False, False]))
    got.append(np.asarray(y).reshape(2 * C, ch)[:n])
    np.testing.assert_array_equal(np.asarray(window[1, 1]), kept)
    np.testing.assert_array_equal(np.asarray(window[1, 2]), x[n:3 + n])
    for t in range(3 + n, tokens):
        y, window = round_(window, x[t], other[before + t - n], False)
        got.append(y[None])
    b = 0.0 if bias is None else bias
    np.testing.assert_allclose(
        np.concatenate(got), causal_conv(x.astype(np.float64), taps, b),
        rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        np.stack(beside), causal_conv(
            other[:before + 6].astype(np.float64), taps, b),
        rtol=1e-5, atol=1e-5)


# -- (c) lightning is the parent's ------------------------------------------

def parents_decode_rows(state, layer, slots, q, k, v, fresh):
    """``decode_rows``' XLA form as the parent commit (14a2a85) had it."""
    hi = jax.lax.Precision.HIGHEST
    rates = jnp.asarray(la.head_decay_rates(q.shape[1]))
    s = state[layer, slots]
    keep = jnp.where(fresh, 0.0, 1.0)[:, None] * jnp.exp(-rates)[None]
    s = s * keep[:, :, None, None] + (
        k.astype(jnp.float32)[..., :, None]
        * v.astype(jnp.float32)[..., None, :])
    o = jnp.einsum("rhk,rhkv->rhv", q.astype(jnp.float32), s, precision=hi)
    return o, state.at[layer, slots].set(s)


def parents_chunk_tiles(state, layer, slots, counts, q, k, v, fresh):
    """``chunk_tiles`` as the parent commit (14a2a85) had it."""
    hi = jax.lax.Precision.HIGHEST
    N, C, H, _ = q.shape
    rates = jnp.asarray(la.head_decay_rates(H))
    d_const, row_decay = (jnp.asarray(a) for a in la._tile_decays(H, C))
    idx = jnp.arange(C)

    def tile(state, args):
        slot, n, q, k, v, fresh = args
        q, k, v = (a.astype(jnp.float32) for a in (q, k, v))
        s0 = jnp.where(fresh, 0.0, 1.0) * state[layer, slot]
        a = jnp.einsum("ihk,jhk->hij", q, k) * d_const
        o = (jnp.einsum("hij,jhv->ihv", a, v)
             + jnp.einsum("ihk,hkv->ihv", q, s0, precision=hi)
             * row_decay.T[:, :, None])
        w = jnp.where(idx[None] < n, jnp.exp(
            -rates[:, None] * jnp.maximum(n - 1 - idx, 0)[None]), 0.0)
        s = (jnp.exp(-rates * n)[:, None, None] * s0
             + jnp.einsum("jhk,jhv->hkv", k * w.T[:, :, None], v,
                          precision=hi))
        return state.at[layer, slot].set(s), o

    state, o = jax.lax.scan(tile, state, (slots, counts, q, k, v, fresh))
    return o, state


def test_lightnings_xla_forms_are_the_parents_bit_for_bit():
    rng = np.random.default_rng(0)
    heads, d = 8, 32
    state = jnp.asarray(rng.normal(size=(2, 5, heads, d, d)), jnp.float32)
    q, k, v = (jnp.asarray(rng.normal(size=(6, heads, d)), jnp.float32)
               for _ in range(3))
    args = (state, jnp.int32(1), jnp.asarray([0, 3, 1, 0, 4, 2]), q, k, v,
            jnp.asarray([False, True, False, False, False, False]))
    for got, want in zip(la.decode_rows(*args), parents_decode_rows(*args)):
        np.testing.assert_array_equal(got, want)
    qt, kt, vt = (jnp.asarray(rng.normal(size=(3, C, heads, d)), jnp.float32)
                  for _ in range(3))
    args = (state, jnp.int32(1), jnp.asarray([2, 2, 0]),
            jnp.asarray([C, 9, 0]), qt, kt, vt,
            jnp.asarray([True, False, False]))
    for got, want in zip(la.chunk_tiles(*args), parents_chunk_tiles(*args)):
        np.testing.assert_array_equal(got, want)


#: the lightning kernel's traced body at (48 rows, 32 heads of 128, bfloat16)
#: and (8 rows, 16 heads of 32, float32), every equation of it, as the parent
#: commit (14a2a85) traced it: the generalised kernel without the operand is
#: that kernel. Re-pin only when a PR changes the lightning walk on purpose
#: (compute it on the parent's tree too)
LIGHTNING_BODY_SHA = ["3162963b0755241a", "e9d70e34b76cc529"]


def test_lightnings_kernel_body_is_the_parents():
    sds = jax.ShapeDtypeStruct

    def eqns(jaxpr):
        for eqn in jaxpr.eqns:
            yield eqn
            for value in eqn.params.values():
                for sub in value if isinstance(value, (list, tuple)) \
                        else [value]:
                    sub = getattr(sub, "jaxpr", sub)
                    if hasattr(sub, "eqns"):
                        yield from eqns(sub)

    def body(call):
        return hashlib.sha256(
            str(call.params["jaxpr"]).encode()).hexdigest()[:16]

    digests = []
    for rows, heads, d, dtype in ((48, 32, 128, jnp.bfloat16),
                                  (8, 16, 32, jnp.float32)):
        act = sds((rows, heads, d), dtype)
        traced = jax.make_jaxpr(lambda *a: la.linear_decode(*a))(
            sds((6, rows + 1, heads, d, d), jnp.float32), sds((), jnp.int32),
            sds((rows,), jnp.int32), act, act, act, sds((rows,), jnp.bool_))
        [call] = [e for e in eqns(traced.jaxpr)
                  if e.primitive.name == "pallas_call"]
        digests.append(body(call))
    assert digests == LIGHTNING_BODY_SHA, digests
    # and the operand's kernel is another body of the same walk
    grouped = jax.make_jaxpr(lambda *a: la.linear_decode(
        *a[:-1], log_decay=a[-1]))(
        sds((2, 9, 32, 256, 128), jnp.float32), sds((), jnp.int32),
        sds((8,), jnp.int32), sds((8, 2, 256), jnp.bfloat16),
        sds((8, 2, 256), jnp.bfloat16), sds((8, 32, 128), jnp.float32),
        sds((8,), jnp.bool_), sds((8, 32), jnp.float32))
    [call] = [e for e in eqns(grouped.jaxpr)
              if e.primitive.name == "pallas_call"]
    assert body(call) not in digests
    assert len(call.invars) == 8        # the decay's block behind v


def test_the_kernel_walks_a_wide_key_axis_in_pieces(monkeypatch):
    """The cell's widths (keys of 256 beside values of 128, sixteen heads a
    group, bfloat16 keys and queries): the state block's key axis goes in two
    pieces of 128 rows, folded before the one reduction; against the XLA
    form."""
    rng = np.random.default_rng(3)
    R, heads, groups, dk, dv = 4, 16, 1, 256, 128
    q, k = (jnp.asarray(rng.normal(size=(R, groups, dk)), jnp.bfloat16)
            for _ in range(2))
    v = jnp.asarray(rng.normal(size=(R, heads, dv)), jnp.float32)
    ld = jnp.asarray(-rng.uniform(0.01, 1.0, size=(R, heads)), jnp.float32)
    state = jnp.asarray(rng.normal(size=(2, 4, heads, dk, dv)), jnp.float32)
    args = (state, jnp.int32(1), jnp.asarray([0, 3, 1, 0]), q, k, v,
            jnp.asarray([False, False, True, False]), ld)

    def decode(*a):
        return la.decode_rows(*a[:-1], log_decay=a[-1], scope="ssm_scan")

    want_o, want_s = jax.jit(decode)(*args)
    monkeypatch.setenv("DSTPU_FORCE_PAGED_KERNEL", "1")
    o, new = jax.jit(lambda *a: decode(*a))(*args)
    live = [1, 2]
    np.testing.assert_allclose(np.asarray(o)[live], np.asarray(want_o)[live],
                               rtol=1e-5, atol=1e-4)
    np.testing.assert_array_equal(np.asarray(o)[[0, 3]], 0.0)
    np.testing.assert_allclose(new[1, [1, 3]], want_s[1, [1, 3]], rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_array_equal(new[1, [0, 2]], state[1, [0, 2]])
    np.testing.assert_array_equal(new[0], state[0])
