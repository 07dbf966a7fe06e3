"""Canonical index core: one segment table, one router, one engine per backend.

The front door is declarative (SLO-driven, see ``fit.py``): write a
``FitSpec`` -- a latency budget, a storage budget, or an expert-pinned
error, plus workload hints -- and ``open_index(keys, spec)`` resolves it
through the Sec. 6 cost model into a ready-to-serve ``IndexService`` or
``ShardedIndexService``; ``plan(keys, spec)`` exposes the intermediate
``IndexPlan`` (with an ``explain()`` audit trail) for review first.

Module map (see ROADMAP.md):
  table.py    -- immutable ``SegmentTable`` + ``route_keys`` (THE router) +
                 the shard partition (``shard_boundaries``/``shard_partition``);
                 numpy-only, shared by every layer
  query.py    -- the typed query plane: ``PointResult``/``RangeResult`` and
                 the ``QueryVerbs`` mixin deriving point / range / count /
                 predecessor / successor from the one ``search`` primitive
  keycode.py  -- the order-preserving 64-bit key code (two int32 words) and
                 the key compares of the device tiers; numpy-only
  engine.py   -- ``LookupEngine`` registry: numpy / xla-window / xla-bisect /
                 pallas bounded-window search (point lookups *and* the
                 two-sided ``search`` rank primitive), ``DeviceIndex`` device
                 form (float32 keys, or 64-bit codes where float32 cannot
                 hold them), and ``DispatchEngine`` (batch-size-aware tier
                 routing with cost-model-derived default thresholds)
  snapshot.py -- epoch publishing: Alg. 4 inserts -> ``publish()`` ->
                 ``ServingHandle`` atomic swap into serving
  sharded.py  -- ``ShardedIndexService``: N key-partitioned writers with
                 per-shard epoch streams; ``pack_shard_tables`` device bridge
  lsm.py      -- ``LsmIndexService``: the tiered write plane (bounded
                 ``Memtable`` -> immutable learned runs -> background
                 ``Compactor``), one atomic versioned ``LevelSet`` manifest,
                 and the multi-level leftmost-rank fan-in for every verb
  device.py   -- ``DeviceShardedService``: the device-sharded serving plane
                 (replicated boundary router, ``shard_map`` collective
                 search under allgather / bucketed all_to_all exchange, and
                 delta epoch publish re-shipping only dirty shards' rows
                 via the versioned ``DeviceShardSet`` manifest)
  fit.py      -- ``FitSpec`` -> ``plan()`` -> ``IndexPlan`` -> ``open_index``:
                 the Sec. 6 cost model resolving SLOs into every knob above
  pipeline.py -- ``AsyncIndexService``/``open_pipeline``: the coalescing
                 async front door (concurrent callers fuse into one
                 fast-tier batch) + the background publish/rebalance cadence
  telemetry.py - ``Monitor`` (lock-free named-channel recorder, in-memory /
                 JSONL backends), the typed ``MetricsSnapshot`` tree
                 (``ServiceMetrics``), and ``Replanner`` -- the measure ->
                 re-fit -> re-plan feedback loop hot-swapping plans live

``table`` and ``query`` are imported eagerly (pure numpy); the
engine/snapshot/sharded/fit names are resolved lazily (PEP 562) so host-only
code -- including the tree's ``from repro.index.table import ...`` -- never
pulls in jax.
"""
from .query import PointResult, QueryVerbs, RangeResult
from .table import (SegmentTable, build_shard_tables, numpy_lookup,
                    numpy_search, route_keys, shard_boundaries,
                    shard_cut_indices, shard_partition)

_ENGINE_NAMES = {
    "DeviceIndex", "DispatchEngine", "LookupEngine", "LookupPlan",
    "available_backends", "device_index", "make_engine", "make_plan",
    "pad_keys", "pallas_lookup", "pallas_search", "predict_positions",
    "register_backend", "snap_leftmost", "snap_side", "xla_lookup",
    "xla_search",
}
_SNAPSHOT_NAMES = {"ServingHandle", "Snapshot", "SnapshotPublisher"}
_SHARDED_NAMES = {"PackedShardTables", "ShardSet", "ShardStats",
                  "ShardedIndexService", "pack_shard_tables"}
_FIT_NAMES = {"FitSpec", "IndexPlan", "InfeasibleSpecError", "PlanCandidate",
              "open_index", "plan"}
_LSM_NAMES = {"Compactor", "LevelSet", "LsmIndexService", "MemView",
              "Memtable", "MemtableFullError", "Run"}
_DEVICE_NAMES = {"DeviceShardSet", "DeviceShardedService",
                 "sharded_lookup_a2a", "sharded_lookup_allgather",
                 "sharded_search_a2a", "sharded_search_allgather"}
_PIPELINE_NAMES = {"AsyncIndexService", "PipelineClosed",
                   "PipelineOverloaded", "open_pipeline"}
_TELEMETRY_NAMES = {"DeviceMetrics", "JSONLBackend", "LsmMetrics",
                    "MemoryBackend", "MetricsSnapshot", "Monitor",
                    "PipelineMetrics", "Replanner", "ServiceMetrics",
                    "ShardMetrics", "TierMetrics", "tier_metrics"}

__all__ = [
    "PointResult", "QueryVerbs", "RangeResult", "SegmentTable",
    "build_shard_tables", "numpy_lookup", "numpy_search", "route_keys",
    "shard_boundaries", "shard_cut_indices", "shard_partition",
    *sorted(_ENGINE_NAMES), *sorted(_SNAPSHOT_NAMES), *sorted(_SHARDED_NAMES),
    *sorted(_FIT_NAMES), *sorted(_LSM_NAMES), *sorted(_DEVICE_NAMES),
    *sorted(_PIPELINE_NAMES), *sorted(_TELEMETRY_NAMES),
]


def __getattr__(name):
    if name in _ENGINE_NAMES:
        from . import engine
        return getattr(engine, name)
    if name in _SNAPSHOT_NAMES:
        from . import snapshot
        return getattr(snapshot, name)
    if name in _SHARDED_NAMES:
        from . import sharded
        return getattr(sharded, name)
    if name in _FIT_NAMES:
        from . import fit
        return getattr(fit, name)
    if name in _LSM_NAMES:
        from . import lsm
        return getattr(lsm, name)
    if name in _DEVICE_NAMES:
        from . import device
        return getattr(device, name)
    if name in _PIPELINE_NAMES:
        from . import pipeline
        return getattr(pipeline, name)
    if name in _TELEMETRY_NAMES:
        from . import telemetry
        return getattr(telemetry, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
