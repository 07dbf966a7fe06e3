"""YCSB workload D's "latest" keys: zipfian over recency, the newest key
(the largest, for time-ordered keys) the most popular (YCSB
``SkewedLatestGenerator``).  Params: ``theta``."""
from __future__ import annotations

import numpy as np

from bench.zipfian import Zipfian


class Latest:
    def __init__(self, n_keys: int, theta: float):
        self.n = int(n_keys)
        self.zipf = Zipfian(n_keys, theta)

    def draw(self, rng: np.random.Generator, size: int) -> np.ndarray:
        """Indices into the sorted key column."""
        return self.n - 1 - self.zipf.ranks(rng, size)


def make(n_keys: int, params: dict) -> Latest:
    return Latest(n_keys, params["theta"])
