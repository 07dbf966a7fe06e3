"""Weblogs-shaped keys: web request timestamps, in seconds from the log's
start.

The shape of ``repro.core.datasets.weblogs_like`` (the paper's Weblogs data,
arXiv:1801.10207 Sec. 7): Poisson arrivals whose rate multiplies a diurnal
peak at 15:00, a weekday / weekend step and a school-year season.  Drawn in
bulk: the rate on one-minute bins, then sorted draws from that density
(uniform within a minute), which is an inhomogeneous Poisson process
conditioned on its count -- no thinning loop.  A server log stamps a
request to the second, so ``resolution_s=1`` floors each draw to its second.
"""
from __future__ import annotations

import numpy as np

from bench.keygen import from_density

DAY = 86400.0
BIN_S = 60.0


def rate(t: np.ndarray) -> np.ndarray:
    hour = (t % DAY) / 3600.0
    dow = (t // DAY) % 7
    doy = (t / DAY) % 365.0
    diurnal = 0.25 + np.exp(-0.5 * ((hour - 15.0) / 4.0) ** 2)
    weekly = np.where(dow < 5, 1.0, 0.45)
    season = 0.5 + 0.5 * (np.cos(2 * np.pi * (doy - 45) / 365.0) ** 2)
    return 0.02 + diurnal * weekly * season


def generate(n_keys: int, seed: int, span_s: float = 365 * DAY,
             resolution_s: float = 0.0) -> np.ndarray:
    """Sorted f64 timestamps in [0, span_s), floored to multiples of
    ``resolution_s`` when it is positive."""
    rng = np.random.default_rng(seed)
    edges = np.append(np.arange(0.0, span_s, BIN_S), span_s)
    t = from_density(n_keys, rng, edges, rate(edges[:-1] + BIN_S / 2))
    if resolution_s > 0:
        np.floor(t / resolution_s, out=t)
        t *= resolution_s
    return t
