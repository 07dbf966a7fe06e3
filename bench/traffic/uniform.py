"""Keys drawn uniformly over the key column (the paper's lookup
experiment).  No params."""
from __future__ import annotations

import numpy as np


class Uniform:
    def __init__(self, n_keys: int):
        self.n = int(n_keys)

    def draw(self, rng: np.random.Generator, size: int) -> np.ndarray:
        """Indices into the sorted key column."""
        return rng.integers(0, self.n, size)


def make(n_keys: int, params: dict) -> Uniform:
    return Uniform(n_keys)
