"""jit'd wrappers around the Pallas kernels (thin compatibility layer).

``fitting_lookup``: XLA prelude (router + interpolation + bucketing) ->
Pallas compare-reduce kernel -> each query's answer read at its bucket slot
+ bisect fallback for bucket overflow.  The orchestration now lives once in
``repro.index.engine`` (``pallas_lookup`` / the ``pallas`` backend of
``make_engine``); this module keeps the historical entry points.
Equivalent to ``ref.lookup_ref`` on every input (tests sweep
shapes/dtypes/errors); the kernel path answers all queries whenever each key
block starts at most QCAP windows (overflow is per-block, flagged, and rare
for non-adversarial batches).
"""
from __future__ import annotations

import functools

import jax

from repro.index.engine import (DeviceIndex, LookupPlan, make_plan, pad_keys,
                                pallas_lookup)

__all__ = ["LookupPlan", "make_plan", "pad_keys", "fitting_lookup",
           "make_lookup_fn"]


def make_lookup_fn(idx: DeviceIndex, *, qcap: int = 256,
                   fallback: bool = True):
    """jit-compiled lookup over a fixed index: the index arrays are passed
    as arguments (``error`` is static), not baked in as constants."""
    arrays, error = tuple(idx)[:5], int(idx.error)
    fn = jax.jit(lambda arrays, q: fitting_lookup(
        DeviceIndex(*arrays, error), q, qcap=qcap, fallback=fallback))
    return functools.partial(fn, arrays)


def fitting_lookup(idx: DeviceIndex, queries: jax.Array, *, qcap: int = 256,
                   fallback: bool = True) -> jax.Array:
    """Batched point lookup via the Pallas kernel.  Returns ranks (-1 absent).

    ``idx.error`` must be a Python int (it sizes the kernel window), so jit
    this via ``make_lookup_fn`` rather than passing idx as one traced
    argument."""
    return pallas_lookup(idx, queries, qcap=qcap, fallback=fallback)
