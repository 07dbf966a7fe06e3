"""Machine-readable serving-stack contracts (the *declarations* the tools read).

This module is the single source of truth shared by the static checker
(``repro.analysis.invariants``) and the runtime sanitizer
(``repro.analysis.sanitizer``).  It is deliberately dependency-free (pure
stdlib, no numpy/jax) so host-only modules can import ``hot_path`` without
pulling anything heavy, and so ``python -m repro.analysis`` runs on a bare
interpreter.

Contracts declared here:

* ``FROZEN_CLASSES``      -- value types that are immutable after construction
                             (RI001: no attribute writes outside builders).
* ``FROZEN_SETATTR_ALLOW``-- the builder allowlist: (module suffix, function)
                             pairs that may use ``object.__setattr__`` on a
                             frozen instance (caches filled exactly once).
* ``PINNED_FIELDS`` / ``PINNED_SUFFIXES`` -- swap-on-publish handle fields
                             that read paths must dereference at most once per
                             method (RI002: pin a local, then use the local).
* ``FROZEN_ARRAY_FIELDS`` -- array attributes published inside snapshots /
                             tables; no in-place numpy mutation (RI003).
* ``HOST_ONLY_MODULES`` / ``ACCEL_IMPORT_ROOTS`` -- modules that must stay
                             importable without jax, and the import roots that
                             would (transitively) pull jax in (RI004).
* ``HOT_PATH_FORBIDDEN_CALLS`` -- call roots banned under ``@hot_path``
                             (RI005, alongside any lock acquisition).
* ``DEPRECATED_CALLS``    -- legacy dict-shaped stats surfaces kept only for
                             external callers (RI006: internal code uses the
                             typed ``metrics()`` tree).
* ``LOCK_ORDER``          -- the global partial order (outermost first) every
                             ``threading`` lock in the serving stack must be
                             acquired in (RI007 statically, the sanitizer's
                             watchdog at runtime).
"""
from __future__ import annotations

from typing import Callable, TypeVar

F = TypeVar("F", bound=Callable)


def hot_path(fn: F) -> F:
    """Mark ``fn`` as a serving hot path: no lock acquisition, no logging,
    no heap-allocating diagnostics (RI005).  Runtime no-op; the static
    checker keys off the decorator name and the sanitizer off the attribute."""
    fn.__hot_path__ = True
    return fn


# --------------------------------------------------------------------- RI001
# Value types whose instances are immutable once constructed.  Everything a
# reader thread can reach through a published snapshot must be in this set.
FROZEN_CLASSES = frozenset({
    "SegmentTable", "Snapshot", "ShardSet", "IndexPlan", "PlanCandidate",
    "PackedShardTables", "PointResult", "RangeResult", "ShardStats",
    "Segments",
    # typed metrics tree (read-only views handed to callers)
    "TierMetrics", "ShardMetrics", "PipelineMetrics", "ServiceMetrics",
    "MetricsSnapshot", "LsmMetrics",
    # LSM tiered write plane: the atomic level manifest and its parts
    "LevelSet", "Run", "MemView",
    # device serving plane: the device-resident manifest + its metrics node
    "DeviceShardSet", "DeviceMetrics",
})

# Builder allowlist: (module path suffix, qualified function name) pairs that
# may call ``object.__setattr__`` on a frozen instance *outside* the class's
# own ``__init__``/``__post_init__`` (self-construction is always allowed).
# Keep this list short and each entry a write-once cache.
FROZEN_SETATTR_ALLOW = frozenset({
    # one-shot device-form cache hung off the (host) SegmentTable
    ("repro/index/engine.py", "device_index"),
})

# --------------------------------------------------------------------- RI002
# Swap-on-publish handle fields: read paths must bind the current value to a
# local exactly once ("pin"), then work off the local, or two reads may span
# a concurrent publish and observe a torn pair of versions.
PINNED_FIELDS = frozenset({"_shard_set", "_state", "_level_set",
                           "_device_set"})
PINNED_SUFFIXES = ("_handle", "_snapshot")

# --------------------------------------------------------------------- RI003
# Array attributes reachable from a published Snapshot / SegmentTable /
# ShardSet; in-place numpy mutation through any of these is a data race.
FROZEN_ARRAY_FIELDS = frozenset({
    "keys", "start_key", "slope", "base", "seg_end", "payload", "boundaries",
    "count", "tombstones", "shadow_keys", "shadow_cum", "offsets",
})
# ndarray methods that mutate in place.
INPLACE_NDARRAY_METHODS = frozenset({
    "fill", "sort", "partition", "put", "resize", "setfield", "itemset",
    "byteswap",
})

# --------------------------------------------------------------------- RI004
# Modules that the host-only tree path imports; they must never import jax
# (directly or through a jax-at-module-scope repro module) at module scope.
HOST_ONLY_MODULES = (
    "repro/index/table.py",
    "repro/index/query.py",
    "repro/index/telemetry.py",
    "repro/core/tree.py",
    "repro/core/segmentation.py",
    "repro/core/cost_model.py",
)
# Import roots that pull jax in at module scope (transitively included).
ACCEL_IMPORT_ROOTS = (
    "jax", "jaxlib",
    "repro.kernels", "repro.models",
    "repro.index.engine", "repro.index.snapshot", "repro.index.sharded",
    "repro.index.pipeline", "repro.index.fit", "repro.index.lsm",
    "repro.index.device",
    "repro.core.jax_index", "repro.core.distributed",
)

# --------------------------------------------------------------------- RI005
# Call roots banned inside ``@hot_path`` functions (heap-allocating logging /
# diagnostics); lock acquisition is banned structurally, not by name.
HOT_PATH_FORBIDDEN_CALLS = frozenset({
    "print", "open", "logging", "warnings", "traceback",
})

# --------------------------------------------------------------------- RI006
# Deprecated dict-shaped surfaces; internal code must use ``metrics()``.
DEPRECATED_CALLS = frozenset({"stats", "service_stats", "pipeline_stats"})

# --------------------------------------------------------------------- RI007
# The global lock order, outermost first.  A thread holding lock i may only
# acquire locks j > i.  Names are ``ClassName.attr`` (matching both the
# static graph keys and the names passed to ``sanitizer.make_lock``).
LOCK_ORDER = (
    "Compactor._lock",                   # one merge in flight (outermost:
                                         # the merge section swaps manifests
                                         # via the LSM write lock)
    "DeviceShardedService._write_lock",  # device publish wraps host publish
    "ShardedIndexService._write_lock",   # writer serialisation
    "LsmIndexService._write_lock",       # LSM writer / manifest swap
    "AsyncIndexService._lock",           # pipeline queue state
    "Memtable._lock",                    # memtable mutate / view build
    "ServingHandle._lock",               # per-shard install swap
    "DispatchEngine._lock",              # lazy tier-engine build
    "DeviceShardedService._fn_lock",     # lazy collective-kernel build
    "Monitor._make_lock",                # channel-ring creation
    "JSONLBackend._io_lock",             # telemetry sink flush
    "DeviceShardedService._counts_lock",  # device verb counters
    "ShardedIndexService._counts_lock",  # verb counters
    "LsmIndexService._counts_lock",      # LSM verb counters (innermost)
)

LOCK_RANK = {name: i for i, name in enumerate(LOCK_ORDER)}
