"""YCSB's zipfian generator (Gray et al., SIGMOD 1994; YCSB
``ZipfianGenerator``), vectorised: ranks 0..items-1, rank 0 the most
popular, P(rank r) proportional to 1 / (r + 1)^theta."""
from __future__ import annotations

import numpy as np

EXACT_TERMS = 1 << 20


def zeta(items: int, theta: float) -> float:
    """sum_{i=1}^{items} i^-theta: the first 2^20 terms summed, the rest by
    Euler-Maclaurin (its error is below 1e-12 past 2^20 terms)."""
    m = min(items, EXACT_TERMS)
    head = float(np.sum(np.arange(1, m + 1, dtype=np.float64) ** -theta))
    if items == m:
        return head
    a, b, s = float(m), float(items), theta
    return (head + (b ** (1 - s) - a ** (1 - s)) / (1 - s)
            + (b ** -s - a ** -s) / 2
            - s * (b ** (-s - 1) - a ** (-s - 1)) / 12)


class Zipfian:
    def __init__(self, items: int, theta: float, zetan: float | None = None):
        self.items, self.theta = int(items), float(theta)
        self.zetan = zeta(items, theta) if zetan is None else float(zetan)
        zeta2 = zeta(2, theta)
        self.alpha = 1.0 / (1.0 - theta)
        self.eta = ((1 - (2.0 / items) ** (1 - theta))
                    / (1 - zeta2 / self.zetan))

    def ranks(self, rng: np.random.Generator, size: int) -> np.ndarray:
        u = rng.random(size)
        uz = u * self.zetan
        r = (self.items * (self.eta * u - self.eta + 1) ** self.alpha)
        r = np.minimum(r.astype(np.int64), self.items - 1)
        r = np.where(uz < 1.0 + 0.5 ** self.theta, 1, r)
        return np.where(uz < 1.0, 0, r)

    def mass(self, lo: int, hi: int) -> float:
        """P(lo <= rank < hi) under the exact law."""
        head = zeta(hi, self.theta) - (zeta(lo, self.theta) if lo else 0.0)
        return head / self.zetan
