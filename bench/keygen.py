"""Sorted keys from a density, in bulk: the dataset generators' one helper."""
from __future__ import annotations

import numpy as np


def sorted_uniforms(n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` sorted uniforms on [0, 1), drawn as normalised partial sums of
    ``n + 1`` exponentials (the order statistics of ``n`` uniforms), so no
    sort is needed."""
    gaps = rng.standard_exponential(n + 1)
    np.cumsum(gaps, out=gaps)
    out = gaps[:n]
    out /= gaps[n]
    return out


def from_density(n: int, rng: np.random.Generator, edges: np.ndarray,
                 mass: np.ndarray) -> np.ndarray:
    """``n`` sorted draws from the piecewise-constant density that puts
    ``mass[i]`` on ``[edges[i], edges[i + 1])``: inverse CDF of sorted
    uniforms, uniform within each bin."""
    cdf = np.concatenate([[0.0], np.cumsum(mass, dtype=np.float64)])
    cdf /= cdf[-1]
    u = sorted_uniforms(n, rng)
    return np.interp(u, cdf, edges)


KEY_DTYPES = ("float64", "float32")     # the key types open_pipeline takes


def to_key_column(keys: np.ndarray, dtype: str) -> np.ndarray:
    """Round to the configuration's key type and return the values as f64,
    the type ``open_pipeline`` takes (rounding keeps the order)."""
    if dtype not in KEY_DTYPES:
        raise ValueError(f"key_dtype {dtype!r} is not one of {KEY_DTYPES}")
    return keys.astype(np.dtype(dtype)).astype(np.float64, copy=False)
