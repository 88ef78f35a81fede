"""The one general traffic generator: a mix is a data file of parameters.

Every seed gets the same multiset of lengths and of arrival gaps (evenly
spaced quantiles of the stated distributions) in another order, so the work of
a run does not depend on the seed; the token ids do.
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


def poisson_dues(rate_rps, seed, horizon_s):
    """``round(rate * horizon)`` due times in [0, horizon) of a Poisson process
    at ``rate_rps``: the exponential distribution's evenly spaced quantiles as
    gaps, permuted by ``seed`` (an int or a list of ints)."""
    n = int(round(rate_rps * horizon_s))
    if n == 0:
        return np.zeros(0)
    u = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-u) / rate_rps
    gaps *= horizon_s / gaps.sum()          # the last request lands on the end
    gaps = np.random.default_rng(seed).permutation(gaps)
    return np.cumsum(gaps) - gaps[0] / 2    # inside [0, horizon) by half a gap
