"""Lengths, gaps and tokens from a seed: host arithmetic, no JAX.

Every seed gets the SAME multiset of sizes and gaps in another order: a
distribution is cut into ``n`` equal-probability strata (its quantiles at
(i + 0.5) / n), that fixed cycle is repeated, and the seed only permutes each
repetition. Two seeds then differ in order and in token values, not in the
work they carry.
"""

from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np


def cycle(spec: dict, n: int) -> list:
    """``n`` stratified draws of a distribution given as a dict:
    ``{"dist": "lognormal", "median": m, "sigma": s, "min": a, "max": b}``,
    ``{"dist": "uniform", "min": a, "max": b}``,
    ``{"dist": "exponential", "mean": m}`` or ``{"dist": "fixed", "value": v}``.
    Lengths (``"int": true``, the default for all but exponential) are
    rounded and clipped."""
    us = [(i + 0.5) / n for i in range(n)]
    kind = spec["dist"]
    if kind == "lognormal":
        nd = NormalDist()
        xs = [spec["median"] * math.exp(spec["sigma"] * nd.inv_cdf(u))
              for u in us]
    elif kind == "uniform":
        xs = [spec["min"] + (spec["max"] - spec["min"]) * u for u in us]
    elif kind == "exponential":
        xs = [-spec["mean"] * math.log(1.0 - u) for u in us]
    elif kind == "fixed":
        xs = [spec["value"]] * n
    else:
        raise ValueError(f"unknown distribution {kind!r}")
    if "min" in spec:
        xs = [max(spec["min"], x) for x in xs]
    if "max" in spec:
        xs = [min(spec["max"], x) for x in xs]
    if spec.get("int", kind != "exponential"):
        xs = [int(round(x)) for x in xs]
    return xs


def rng(seed: int, *stream: int) -> np.random.Generator:
    """Any whole number is a seed (the driver's pass 2**31)."""
    return np.random.default_rng([int(seed), *[int(s) for s in stream]])


def permuted(values: list, seed: int, stream: int, epoch: int) -> list:
    """One repetition of a cycle, in this seed's order."""
    order = rng(seed, stream, epoch).permutation(len(values))
    return [values[i] for i in order]


def tokens(seed: int, stream: int, index: int, n: int, vocab: int) -> list:
    return rng(seed, stream, index).integers(0, vocab, n).tolist()
