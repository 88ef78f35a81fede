"""The one general traffic generator: a mix is a data file of parameters.

Every seed gets the same multiset of lengths and of arrival gaps (evenly
spaced quantiles of the stated distributions) in another order, so the work of
a run does not depend on the seed; the token ids do. Where how many requests
are alive together decides what a run reads (a median gap follows the rows of
its rounds), a mix says ``"cycle"``: the order is the mix's own, one round of
requests that fills the window (``cycle``), so every seed's window also holds
the same overlaps, and the seed is left the token ids and the weights.
"""

import math

import numpy as np


def _quantiles(dist, n):
    """n evenly spaced quantiles of a length distribution, as whole numbers."""
    u = (np.arange(n) + 0.5) / n
    lo, hi = dist["lo"], dist["hi"]
    if dist["dist"] == "loguniform":
        x = np.exp(math.log(lo) + u * (math.log(hi) - math.log(lo)))
    elif dist["dist"] == "uniform":
        x = lo + u * (hi - lo)
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    return np.clip(np.rint(x), lo, hi).astype(int)


def requests(mix, seed, n, vocab, max_total):
    """n requests: dicts of ``prompt`` (token ids) and ``out_len``."""
    rng = np.random.default_rng(seed)
    plen = rng.permutation(_quantiles(mix["prompt_len"], n))
    olen = rng.permutation(_quantiles(mix["output_len"], n))
    out = []
    for p, o in zip(plen, olen):
        o = int(min(o, max_total - p))
        out.append({"prompt": rng.integers(0, vocab, int(p)).tolist(),
                    "out_len": o})
    return out


def _gaps(rate_rps, n, horizon_s):
    """The ``n`` gaps of a Poisson process at ``rate_rps`` that fill
    ``horizon_s``: the exponential distribution's evenly spaced quantiles."""
    u = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-u) / rate_rps
    return gaps * (horizon_s / gaps.sum())  # the last request lands on the end


def poisson_dues(rate_rps, seed, horizon_s):
    """``round(rate * horizon)`` due times in [0, horizon) of a Poisson process
    at ``rate_rps``: the exponential distribution's evenly spaced quantiles as
    gaps, permuted by ``seed`` (an int or a list of ints)."""
    n = int(round(rate_rps * horizon_s))
    if n == 0:
        return np.zeros(0)
    gaps = np.random.default_rng(seed).permutation(_gaps(rate_rps, n, horizon_s))
    return np.cumsum(gaps) - gaps[0] / 2    # inside [0, horizon) by half a gap


def cycle(mix, rate_rps, seed, ramp_s, window_s, vocab, max_total):
    """The open loop's requests where the mix says ``"cycle": {"order": k}``:
    [(due, prompt, out_len)] over the ramp and the window, oldest first.

    One round of ``round(rate * window)`` requests fills the window: the gaps
    and the lengths are the quantiles ``poisson_dues`` and ``requests`` take,
    in the order that ``k``, and not the seed, permutes them into; request
    ``i`` of the round follows request ``i - 1`` by its own gap, the first
    half its gap after the window opens. The ramp is the round run backwards
    from there, the last request before the first, as far as the ramp's
    seconds reach: what a server that had always been serving this round
    would hold when the window opens. So every seed sends the same lengths at
    the same times, and which requests meet is the mix's and not the seed's;
    the seed draws the token ids (and the weights)."""
    n = int(round(rate_rps * window_s))
    order = np.random.default_rng([int(mix["cycle"]["order"]), 6])
    gaps = order.permutation(_gaps(rate_rps, n, window_s))
    plen = order.permutation(_quantiles(mix["prompt_len"], n))
    olen = order.permutation(_quantiles(mix["output_len"], n))
    dues = ramp_s + np.cumsum(gaps) - gaps[0] / 2
    steps = list(zip(range(n), dues))
    due, i = dues[0] - gaps[0], -1
    while due >= 0.0:
        steps.insert(0, (i % n, due))
        due, i = due - gaps[i % n], i - 1
    rng = np.random.default_rng(seed)
    out = []
    for i, due in steps:
        p = int(plen[i])
        out.append((float(due), rng.integers(0, vocab, p).tolist(),
                    int(min(olen[i], max_total - p))))
    return out
