"""The one traffic generator. A traffic mix is a data file under
``bench/traffic/`` (its parameters); this module turns it and a seed into
requests.

Every seed gets the same work in another order: the lengths are the
quantiles of their clipped lognormal at stratified points, paired prompt
with output by a fixed shuffle that no seed changes, and the gaps between
arrivals the quantiles of the exponential at the mix's rate, scaled so
that exactly ``round(rate * seconds)`` requests fall due in the window. The
seed orders the requests and, on its own, the gaps, and draws the token
ids. So runs with different seeds differ in order, and so in queueing, but
not in the amount, the sizes or the pairing of the work.
"""
from __future__ import annotations

import dataclasses
import math
from typing import List

import numpy as np


def _ndtri(p: np.ndarray) -> np.ndarray:
    """Standard normal quantile (Acklam's rational approximation, relative
    error under 1.2e-9), so that the generator needs numpy alone."""
    a = [-3.969683028665376e+01, 2.209460984245205e+02,
         -2.759285104469687e+02, 1.383577518672690e+02,
         -3.066479806614716e+01, 2.506628277459239e+00]
    b = [-5.447609879822406e+01, 1.615858368580409e+02,
         -1.556989798598866e+02, 6.680131188771972e+01,
         -1.328068155288572e+01]
    c = [-7.784894002430293e-03, -3.223964580411365e-01,
         -2.400758277161838e+00, -2.549732539343734e+00,
         4.374664141464968e+00, 2.938163982698783e+00]
    d = [7.784695709041462e-03, 3.224671290700398e-01,
         2.445134137142996e+00, 3.754408661907416e+00]
    p = np.asarray(p, np.float64)
    out = np.empty_like(p)
    lo, hi = p < 0.02425, p > 1 - 0.02425
    mid = ~(lo | hi)
    q = np.sqrt(-2 * np.log(p[lo]))
    out[lo] = ((((((c[0] * q + c[1]) * q + c[2]) * q + c[3]) * q + c[4]) * q
                + c[5]) / ((((d[0] * q + d[1]) * q + d[2]) * q + d[3]) * q
                           + 1))
    q = p[mid] - 0.5
    r = q * q
    out[mid] = ((((((a[0] * r + a[1]) * r + a[2]) * r + a[3]) * r + a[4]) * r
                 + a[5]) * q / (((((b[0] * r + b[1]) * r + b[2]) * r + b[3])
                                 * r + b[4]) * r + 1))
    q = np.sqrt(-2 * np.log(1 - p[hi]))
    out[hi] = -((((((c[0] * q + c[1]) * q + c[2]) * q + c[3]) * q + c[4]) * q
                 + c[5]) / ((((d[0] * q + d[1]) * q + d[2]) * q + d[3]) * q
                            + 1))
    return out


def strata(n: int) -> np.ndarray:
    """n stratified probabilities (i + 1/2) / n."""
    return (np.arange(n) + 0.5) / n


def lognormal_lengths(spec: dict, p: np.ndarray) -> np.ndarray:
    """Integer lengths at probabilities ``p`` of a lognormal with the
    given ``median`` and ``sigma``, clipped to [``min``, ``max``]."""
    x = spec["median"] * np.exp(spec["sigma"] * _ndtri(p))
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def _pairing(n: int, which: int = 0) -> np.ndarray:
    """A fixed shuffle of n, the same for every seed: it pairs one sorted
    list of lengths with another."""
    return np.random.default_rng([n, which]).permutation(n)


@dataclasses.dataclass
class Request:
    rid: int
    due_s: float          # seconds after the window opens (< 0: pre-roll)
    prompt: np.ndarray    # int32 token ids
    max_new: int


def open_loop(mix: dict, seed: int, seconds: float, vocab: int,
              max_len: int) -> List[Request]:
    """The requests due in a window of ``seconds``, in order of due time,
    from an open-loop Poisson mix."""
    rng = np.random.default_rng(seed)
    n = max(1, int(round(mix["rate_per_s"] * seconds)))
    gaps = -np.log1p(-strata(n))                 # exponential quantiles
    gaps = rng.permutation(gaps) * (seconds / gaps.sum())
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    prompts = lognormal_lengths(mix["prompt"], strata(n))
    outputs = lognormal_lengths(mix["output"], strata(n))[_pairing(n)]
    order = rng.permutation(n)
    prompts, outputs = prompts[order], outputs[order]
    out = []
    for i in range(n):
        p = int(min(prompts[i], max_len - 1))
        m = int(min(outputs[i], max_len - p))
        out.append(Request(i, float(due[i]),
                           rng.integers(0, vocab, p, dtype=np.int32), m))
    return out


def preroll(mix: dict, seed: int, count: int, vocab: int,
            max_len: int) -> List[Request]:
    """``count`` requests that stand for the slots' occupants at steady
    state, all due before the window. In steady state a slot holds a
    request picked in proportion to its output length X, part way
    through it: its context is its prompt plus the A = X - R tokens it has
    made, and R tokens remain. Each is drawn so, at stratified points,
    and served as a prompt of P + A tokens with R new tokens."""
    if count <= 0:
        return []
    rng = np.random.default_rng([seed, 1])
    grid = lognormal_lengths(mix["output"], strata(4096)).astype(np.float64)
    cdf = np.cumsum(grid) / grid.sum()           # length-biased X
    xs = grid[np.searchsorted(cdf, strata(count))]
    frac = strata(count)[_pairing(count, 1)]
    rem = np.maximum(1, np.rint(frac * xs)).astype(np.int64)
    made = xs.astype(np.int64) - rem
    prompts = lognormal_lengths(mix["prompt"], strata(count))[
        _pairing(count, 2)]
    order = rng.permutation(count)
    rem, made, prompts = rem[order], made[order], prompts[order]
    out = []
    for i in range(count):
        r = int(min(rem[i], max_len - 1))
        p = int(min(prompts[i] + made[i], max_len - r))
        out.append(Request(-1 - i, -math.inf,
                           rng.integers(0, vocab, p, dtype=np.int32), r))
    return out
