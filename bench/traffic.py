"""The one traffic generator: reads a mix from ``bench/traffic/<name>.json``.

An open loop: requests are due at fixed times whether or not earlier ones
have finished, at the mix's ``rate_per_s``. Every seed gets the same
sizes: the inter-arrival gaps, prompt lengths and output lengths are
fixed multisets (evenly spaced quantiles of their distributions, so the
same for every seed at a given window length) that the seed only puts in
another order; the seed also draws the prompt tokens. The order is
stratified: each run of about ``engine.max_batch`` consecutive requests
holds one value from each band of the distribution, so an overloaded
window, which serves only the first requests, serves nearly the same
sizes; which of them are in flight at the close still follows the order.
Gaps follow a gamma distribution with the mix's coefficient of variation
(1 is Poisson); lengths follow clipped lognormals given by median and
sigma.

Mix keys: ``source`` (where the shape comes from), ``rate_per_s``,
``arrival_cv``, ``prompt`` and ``output`` (each ``median``, ``sigma``,
``min``, ``max``), ``engine`` (``max_batch``, ``max_len`` and, where the
pool is sized apart from them, ``num_blocks``) and ``trace_window``
(``start_s``, ``seconds``: the part of the window a ``--trace 1`` run
records).
"""
from __future__ import annotations

import dataclasses
import json
import math
from pathlib import Path
from statistics import NormalDist

import numpy as np

TRAFFIC_DIR = Path(__file__).resolve().parent / "traffic"


def load(name: str) -> dict:
    mix = json.loads((TRAFFIC_DIR / f"{name}.json").read_text())
    mix["name"] = name
    return mix


@dataclasses.dataclass
class Arrival:
    due_s: float          # seconds after the window opens
    prompt: np.ndarray    # (S,) int32
    max_new_tokens: int


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def _lognormal_lengths(dist: dict, n: int) -> np.ndarray:
    z = np.array([NormalDist().inv_cdf(u) for u in _quantiles(n)])
    x = dist["median"] * np.exp(dist["sigma"] * z)
    return np.clip(np.round(x), dist["min"], dist["max"]).astype(int)


def _gamma_gaps(rate: float, cv: float, n: int) -> np.ndarray:
    """Evenly spaced quantiles of a gamma with mean 1/rate and the given
    coefficient of variation, by inverse-CDF bisection."""
    shape = 1.0 / (cv * cv)
    scale = 1.0 / (rate * shape)

    def cdf(x):
        return _reg_lower_gamma(shape, x / scale)

    out = []
    for u in _quantiles(n):
        lo, hi = 0.0, scale * (shape + 40.0 * math.sqrt(shape) + 40.0)
        for _ in range(80):
            mid = 0.5 * (lo + hi)
            lo, hi = (mid, hi) if cdf(mid) < u else (lo, mid)
        out.append(0.5 * (lo + hi))
    return np.asarray(out)


def _reg_lower_gamma(a: float, x: float) -> float:
    """Regularized lower incomplete gamma P(a, x) (series / continued
    fraction, as in Numerical Recipes)."""
    if x <= 0:
        return 0.0
    lg = math.lgamma(a)
    if x < a + 1:
        term = total = 1.0 / a
        ap = a
        for _ in range(500):
            ap += 1
            term *= x / ap
            total += term
            if abs(term) < abs(total) * 1e-15:
                break
        return total * math.exp(-x + a * math.log(x) - lg)
    b, c, d = x + 1 - a, 1e300, 1.0 / (x + 1 - a)
    h = d
    for i in range(1, 500):
        an = -i * (i - a)
        b += 2
        d = an * d + b
        d = 1e-300 if abs(d) < 1e-300 else d
        c = b + an / c
        c = 1e-300 if abs(c) < 1e-300 else c
        d = 1.0 / d
        h *= d * c
        if abs(d * c - 1) < 1e-15:
            break
    return 1.0 - math.exp(-x + a * math.log(x) - lg) * h


def n_arrivals(mix: dict, seconds: float) -> int:
    return max(1, math.ceil(mix["rate_per_s"] * seconds))


def lengths(mix: dict, seconds: float) -> tuple[np.ndarray, np.ndarray]:
    """The fixed multisets of prompt and output lengths (seed-free)."""
    n = n_arrivals(mix, seconds)
    return (_lognormal_lengths(mix["prompt"], n),
            _lognormal_lengths(mix["output"], n))


def _stratified(rng: np.random.Generator, values: np.ndarray,
                block: int) -> np.ndarray:
    """``values`` in an order drawn from ``rng`` in which every run of
    ``block`` consecutive entries takes one value from each of ``block``
    bands of the sorted values."""
    v = np.sort(values)
    n_blocks = -(-len(v) // block)
    blocks = [rng.permutation(v[j::n_blocks]) for j in range(n_blocks)]
    return np.concatenate([blocks[j] for j in rng.permutation(n_blocks)])


def schedule(mix: dict, seed: int, seconds: float,
             vocab: int) -> list[Arrival]:
    """Every request the window will send, in due order."""
    n = n_arrivals(mix, seconds)
    rng = np.random.default_rng(int(seed))
    block = mix["engine"]["max_batch"]
    gaps = _stratified(rng, _gamma_gaps(mix["rate_per_s"],
                                        mix["arrival_cv"], n), block)
    prompts, outputs = lengths(mix, seconds)
    prompts = _stratified(rng, prompts, block)
    outputs = _stratified(rng, outputs, block)
    due = np.cumsum(gaps) - gaps[0]
    return [Arrival(float(t), rng.integers(0, vocab, p).astype(np.int32),
                    int(o)) for t, p, o in zip(due, prompts, outputs)]

