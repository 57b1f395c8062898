"""Traffic generators, one module per kind, found by the ``generator`` name
in a traffic file.  Each draws a fixed multiset of sizes and gaps from the
traffic's parameters (quantiles of the stated distributions) and lets
``--seed`` choose only their order and the token ids, so every seed offers
the same work."""
from __future__ import annotations

from statistics import NormalDist

import numpy as np


def quantiles(dist: dict, n: int) -> np.ndarray:
    """``n`` sizes at the mid-quantiles of ``dist``, clipped and rounded.

    ``{"dist": "lognormal", "median", "sigma", "min", "max"}`` or
    ``{"dist": "uniform", "min", "max"}``."""
    q = (np.arange(n) + 0.5) / n
    if dist["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(x) for x in q])
        x = dist["median"] * np.exp(dist["sigma"] * z)
    elif dist["dist"] == "uniform":
        x = dist["min"] + q * (dist["max"] - dist["min"])
    else:
        raise ValueError(f"unknown distribution {dist['dist']!r}")
    return np.clip(np.round(x), dist["min"], dist["max"]).astype(np.int64)


def rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([seed, stream])


def tokens(seed: int, i: int, n: int, vocab: int) -> list:
    """Uniform token ids of request ``i``, from the seed."""
    return rng(seed, 1_000_000 + i).integers(1, vocab, n).tolist()


def exp_gaps(rate: float, n: int) -> np.ndarray:
    q = (np.arange(n) + 0.5) / n
    return -np.log1p(-q) / rate


def mean(dist: dict) -> float:
    return float(quantiles(dist, 4096).mean())

