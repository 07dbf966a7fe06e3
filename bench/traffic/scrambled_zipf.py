"""YCSB workload C's scrambled zipfian keys (YCSB
``ScrambledZipfianGenerator``): zipfian ranks over 10^10 items (YCSB's fixed
``ITEM_COUNT`` and its zeta), hashed with 64-bit FNV-1a and taken modulo the
key count, so the hot keys are scattered over the key column.  Params:
``theta``, ``item_count``, ``zetan``."""
from __future__ import annotations

import numpy as np

from bench.zipfian import Zipfian

FNV_OFFSET_BASIS_64 = np.uint64(0xCBF29CE484222325)
FNV_PRIME_64 = np.uint64(1099511628211)


def fnvhash64(values: np.ndarray) -> np.ndarray:
    """YCSB ``Utils.fnvhash64``: FNV-1a over the 8 little-endian octets,
    then ``Math.abs`` of the signed result."""
    v = values.astype(np.uint64)
    h = np.full(v.shape, FNV_OFFSET_BASIS_64, np.uint64)
    for _ in range(8):
        h ^= v & np.uint64(0xFF)
        h *= FNV_PRIME_64
        v >>= np.uint64(8)
    return np.abs(h.view(np.int64))


class ScrambledZipf:
    def __init__(self, n_keys: int, theta: float, item_count: int,
                 zetan: float):
        self.n = int(n_keys)
        self.zipf = Zipfian(item_count, theta, zetan)

    def draw(self, rng: np.random.Generator, size: int) -> np.ndarray:
        """Indices into the sorted key column."""
        return fnvhash64(self.zipf.ranks(rng, size)) % self.n


def make(n_keys: int, params: dict) -> ScrambledZipf:
    return ScrambledZipf(n_keys, params["theta"], params["item_count"],
                         params["zetan"])
