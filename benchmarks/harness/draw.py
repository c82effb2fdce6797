"""Seeded draws shared by the generator kinds.

Every seed gets the same set of sizes and gaps, in another order: a draw of
``n`` values takes the distribution's quantiles at (i + 0.5) / stratum and
permutes them inside each block of ``stratum`` values.  So two seeds offer
the same work, and differ in how it falls together.
"""

from __future__ import annotations

import math
from statistics import NormalDist
from typing import Callable, Optional

import numpy as np

_NORMAL = NormalDist()


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """An independent stream of ``seed`` (any non-negative whole number)."""
    return np.random.default_rng([int(seed), int(stream)])


def stratified(n: int, rng: np.random.Generator,
               ppf: Callable[[float], float],
               stratum: Optional[int] = None) -> np.ndarray:
    stratum = n if not stratum else min(stratum, n)
    quantiles = np.array([ppf((i + 0.5) / stratum) for i in range(stratum)])
    blocks = [rng.permutation(quantiles) for _ in range(math.ceil(n / stratum))]
    return np.concatenate(blocks)[:n]


def lognormal_int(n: int, rng: np.random.Generator, dist: dict,
                  stratum: Optional[int] = None) -> np.ndarray:
    """``dist`` = {"median", "sigma", "min", "max"}: whole numbers, clipped."""
    mu, sigma = math.log(dist["median"]), dist["sigma"]
    values = stratified(
        n, rng, lambda q: math.exp(mu + sigma * _NORMAL.inv_cdf(q)), stratum)
    return np.clip(np.rint(values), dist["min"], dist["max"]).astype(np.int64)


def exponential_gaps(n: int, rng: np.random.Generator, rate: float,
                     stratum: Optional[int] = None) -> np.ndarray:
    """Gaps of a Poisson process of ``rate`` per second."""
    return stratified(n, rng, lambda q: -math.log1p(-q) / rate, stratum)


def token_ids(lengths: np.ndarray, rng: np.random.Generator,
              vocab: int) -> list[list[int]]:
    """Unshared prompts: uniform ids in [0, vocab)."""
    flat = rng.integers(0, vocab, size=int(lengths.sum()), dtype=np.int64)
    cuts = np.cumsum(lengths)[:-1]
    return [part.tolist() for part in np.split(flat, cuts)]
