"""Length and gap distributions of the traffic mixes, as quantile grids.

A mix names a distribution; a run needs ``n`` values of it. Drawing
them at random would give every seed another amount of work, and the
spread between seeds would hide what a change does. So the values are
the distribution's quantiles at (i + 0.5) / n, the same multiset for
every seed, and the seed only orders them.
"""
import math
from statistics import NormalDist

import numpy as np


def quantiles(spec, n):
    """``n`` values of the distribution ``spec`` (a dict from a mix
    file), ascending. Integer distributions are rounded and clipped."""
    u = (np.arange(n) + 0.5) / n
    kind = spec["dist"]
    if kind == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(p)) for p in u])
        v = spec["median"] * np.exp(spec["sigma"] * z)
    elif kind == "uniform":
        v = spec["min"] + (spec["max"] - spec["min"]) * u
    elif kind == "exponential":
        v = -np.log1p(-u) * spec["mean"]
    elif kind == "constant":
        v = np.full(n, spec["value"], float)
    else:
        raise ValueError(f"unknown distribution {kind!r}")
    if "min" in spec or "max" in spec:
        v = np.clip(v, spec.get("min", -math.inf), spec.get("max", math.inf))
    if spec.get("integer", kind != "exponential"):
        v = np.rint(v).astype(np.int64)
    return v


def shuffled(values, rng):
    values = np.array(values)
    rng.shuffle(values)
    return values
