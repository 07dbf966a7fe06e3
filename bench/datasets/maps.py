"""Maps-shaped keys: longitudes of map points, in degrees.

The shape of ``repro.core.datasets.maps_like`` (the paper's Maps data,
arXiv:1801.10207 Sec. 7): 72% uniform on [-180, 180] and 28% in 40 city
clusters (centres uniform on [-170, 170], Dirichlet weights, sd 0.8 degrees).
Drawn in bulk from that mixture's density on 2^20 bins (0.00034 degrees
each, against a cluster sd of 0.8), uniform within a bin.
"""
from __future__ import annotations

import math

import numpy as np

from bench.keygen import from_density

N_BINS = 1 << 20
CITIES = 40
UNIFORM_SHARE = 0.72
CITY_SD = 0.8


def _normal_cdf(x: np.ndarray) -> np.ndarray:
    erf = np.frompyfunc(math.erf, 1, 1)
    return 0.5 * (1.0 + erf(x / math.sqrt(2.0)).astype(np.float64))


def generate(n_keys: int, seed: int) -> np.ndarray:
    """Sorted f64 longitudes in [-180, 180]."""
    rng = np.random.default_rng(seed)
    centres = rng.uniform(-170.0, 170.0, size=CITIES)
    weights = rng.dirichlet(np.ones(CITIES))
    edges = np.linspace(-180.0, 180.0, N_BINS + 1)
    mass = np.full(N_BINS, UNIFORM_SHARE / N_BINS)
    for c, w in zip(centres, weights):
        # a cluster's mass lies within 8 sd of its centre
        lo, hi = np.searchsorted(edges, [c - 8 * CITY_SD, c + 8 * CITY_SD])
        cdf = _normal_cdf((edges[lo:hi + 1] - c) / CITY_SD)
        mass[lo:hi] += (1.0 - UNIFORM_SHARE) * w * np.diff(cdf)
    return from_density(n_keys, rng, edges, mass)
