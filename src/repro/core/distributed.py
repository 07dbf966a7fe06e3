"""Range-partitioned FITing-Tree across a device mesh: compatibility wrapper.

The canonical implementation now lives in ``repro.index.device``: the
``shard_map`` collective kernels exist once
(``sharded_lookup_allgather`` / ``sharded_lookup_a2a``, plus the two-sided
``sharded_search_*`` rank primitives they derive from), and the *served*
plane -- delta epoch publish, the versioned ``DeviceShardSet`` manifest,
a2a overflow resolution, telemetry -- is ``DeviceShardedService``.  This
module keeps the seed-era public surface (``ShardedIndex``,
``build_sharded_index``, ``lookup_allgather``, ``lookup_a2a``) as thin
wrappers over those kernels, the same treatment as ``core/jax_index.py``.

Semantics are unchanged for the seed layout (equal-count shards, unique
keys): global rank of each query, -1 if absent.  ``lookup_a2a`` still
returns the legacy ``(ranks, ok)`` pair where ``ok=False`` marks queries
dropped by bucket overflow under skew -- callers re-ask via
``lookup_allgather``, or use ``DeviceShardedService``, which performs that
follow-up pass itself.  The psum-based kernels are additionally exact when
duplicate runs straddle shard cuts (the old ownership-mask implementation
was not).  Tests run under
XLA_FLAGS=--xla_force_host_platform_device_count=8 in a subprocess.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.index.device import sharded_lookup_a2a, sharded_lookup_allgather
from repro.index.sharded import pack_shard_tables
from repro.index.table import build_shard_tables


class ShardedIndex(NamedTuple):
    seg_start: jax.Array   # (D, S_max) f32, padded with +inf
    slope: jax.Array       # (D, S_max) f32
    base: jax.Array        # (D, S_max) i32
    seg_end: jax.Array     # (D, S_max) i32
    keys: jax.Array        # (D, M) f32 -- equal-count shards
    boundaries: jax.Array  # (D,) f32 replicated router: first key per shard
    error: int


def build_sharded_index(keys: np.ndarray, error: int, n_shards: int,
                        mesh: Mesh | None = None, axis: str = "data") -> ShardedIndex:
    keys = np.asarray(keys, np.float64)
    n = keys.shape[0]
    m = n // n_shards
    # equal shards; tail handled by caller.  One canonical SegmentTable per
    # shard (local ranks) -- the same construction every other layer uses --
    # padded into the rectangular device layout by the shared bridge.
    tables = build_shard_tables(keys, error, n_shards)
    shards = keys[: m * n_shards].reshape(n_shards, m)
    packed = pack_shard_tables(tables)

    arrays = dict(
        seg_start=jnp.asarray(packed.seg_start, jnp.float32),
        slope=jnp.asarray(packed.slope, jnp.float32),
        base=jnp.asarray(packed.base, jnp.int32),
        seg_end=jnp.asarray(packed.seg_end, jnp.int32),
        keys=jnp.asarray(shards, jnp.float32),
        boundaries=jnp.asarray(packed.boundaries, jnp.float32),
    )
    if mesh is not None:
        shard = NamedSharding(mesh, P(axis, None))
        repl = NamedSharding(mesh, P())
        arrays = {k: jax.device_put(v, repl if k == "boundaries" else shard)
                  for k, v in arrays.items()}
    return ShardedIndex(error=int(error), **arrays)


def _seed_layout(si: ShardedIndex, d: int):
    """The seed layout's implied row metadata: equal-count shards (every row
    fully live) and the prefix offsets ``arange(d) * m``."""
    m = si.keys.shape[1]
    n_local = jnp.full((d,), m, jnp.int32)
    offsets = jnp.arange(d, dtype=jnp.int32) * m
    return n_local, offsets


def lookup_allgather(si: ShardedIndex, queries: jax.Array, mesh: Mesh,
                     axis: str = "data") -> jax.Array:
    """Every shard answers the full query set; one psum combines the answers.

    Deprecated entry point: delegates to
    :func:`repro.index.device.sharded_lookup_allgather` (use
    ``DeviceShardedService`` for the served plane)."""
    n_local, _ = _seed_layout(si, mesh.shape[axis])
    with jax.set_mesh(mesh):    # eager shard_map over an Explicit-axis mesh
        return sharded_lookup_allgather(
            si.seg_start, si.slope, si.base, si.seg_end, si.keys, n_local,
            queries, mesh=mesh, axis=axis, error=si.error)


def lookup_a2a(si: ShardedIndex, queries: jax.Array, mesh: Mesh,
               axis: str = "data", slack: float = 2.0
               ) -> tuple[jax.Array, jax.Array]:
    """Bucketed all_to_all exchange; returns the legacy ``(ranks, ok)`` pair.

    Deprecated entry point: delegates to
    :func:`repro.index.device.sharded_lookup_a2a`.  ``ok=False`` marks
    queries dropped by bucket overflow under skew beyond ``slack`` -- the
    caller may re-ask those via :func:`lookup_allgather`;
    ``DeviceShardedService`` performs that follow-up pass itself, so the
    mask never reaches *its* callers."""
    n_local, offsets = _seed_layout(si, mesh.shape[axis])
    with jax.set_mesh(mesh):    # eager shard_map over an Explicit-axis mesh
        return sharded_lookup_a2a(
            si.seg_start, si.slope, si.base, si.seg_end, si.keys, n_local,
            offsets, si.boundaries, queries, mesh=mesh, axis=axis,
            error=si.error, slack=slack)
