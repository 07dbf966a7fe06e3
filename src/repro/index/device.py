"""Device-sharded serving plane: replicated router, ``shard_map`` fan-out,
delta epoch publish (ROADMAP open item 1).

The paper's recursive structure -- a tiny top-level router over per-partition
linear segments -- maps directly onto a device mesh: the shard-boundary
router is *replicated* (every device holds the (D,) cut column), each
device owns one shard's packed segment table and sorted key column, and the
two-sided bounded-window ``search`` primitive runs under ``shard_map`` with
one of two exchange strategies:

* ``"allgather"`` -- every device gathers the full query batch, answers it
  against its local shard, and a ``psum`` of the per-shard insertion ranks
  yields the exact global rank: over contiguous sorted shard runs,
  ``searchsorted(all_keys, q) == sum_d searchsorted(shard_d, q)``.  No
  ownership masks, duplicate-safe by construction, two collectives total.
* ``"a2a"`` -- queries are bucketed to their *owning* shard by the
  replicated router (duplicate-safe serving cuts guarantee
  owner-local rank + prefix offset == global rank), exchanged with
  ``all_to_all`` under a slack-capacity factor, answered locally, and
  exchanged back.  Bucket overflow beyond slack is **resolved inside the
  service** by a follow-up allgather pass over just the overflowed queries
  -- the dropped-query mask never leaks to callers.

``DeviceShardedService`` wraps the existing ``ShardedIndexService`` write
path (insert routing, Alg. 4 buffers, per-shard epoch publish, rebalance)
and installs snapshots onto devices as an immutable versioned
:class:`DeviceShardSet` -- the same single-reference-swap / pinned-reader
discipline as ``ShardSet`` and the LSM ``LevelSet``.  Publishes are **delta
uploads**: the manifest keeps per-shard epoch fingerprints, and a publish
that dirtied one shard re-transfers only that shard's padded table row via
``jax.device_put`` on the owning device; the clean D-1 rows' device buffers
are *reused* (same buffer identity) through
``jax.make_array_from_single_device_arrays``.  Rows are padded to capacity
(``s_cap``/``m_cap``, headroom over the current maxima) so steady-state
publishes stay delta-eligible and shape-stable (no jit retrace); cap
overflow or a boundary change (rebalance / structural replan) falls back to
a full re-pack with fresh headroom.

All five query verbs stay bit-identical to the numpy oracle when keys and
queries are all float32 (e.g. integers < 2^24).  The rows hold float32
keys, so a publish of a column that needs 64 bits raises ``ValueError``
(``_pack_row``) instead of answering wrongly; the single-chip engines in
``repro.index.engine`` serve such columns in their wide form.

Runs on CPU via ``XLA_FLAGS=--xla_force_host_platform_device_count=8``
(see ``tests/_device_check.py``); the collectives are the same on real
accelerator meshes.
"""
from __future__ import annotations

import dataclasses
import time
import warnings
from functools import partial
from typing import TYPE_CHECKING, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.analysis import sanitizer
from repro.core.cost_model import choose_exchange
from repro.index.table import route_keys

from . import keycode
from .engine import DeviceIndex, xla_search
from .query import PointResult, RangeResult, check_range, check_side
from .sharded import ShardedIndexService
from .snapshot import Snapshot
from .telemetry import (CH_DEVICE_COLLECTIVE, CH_DEVICE_OVERFLOW,
                        CH_DEVICE_PUBLISH, XCHG_A2A, XCHG_ALLGATHER,
                        DeviceMetrics, Monitor)

if TYPE_CHECKING:   # runtime import is lazy (fit builds services via plans)
    from .fit import IndexPlan

_EXCHANGES = ("allgather", "a2a", "auto")


# --------------------------------------------------------- shard_map kernels
def sharded_search_allgather(seg_start, slope, base, seg_end, keys, n_local,
                             queries, *, mesh: Mesh, axis: str = "data",
                             error: int, side: str = "left"):
    """Global insertion ranks by psum of per-shard local ranks.

    Each device all-gathers the query batch, runs the bounded-window
    ``xla_search`` against its (+inf padded) local shard, and a ``psum``
    sums the local ranks: shard runs are contiguous in key order, so the
    sum *is* the global ``searchsorted`` rank -- duplicate runs straddling
    a shard cut included (a sum needs no ownership decision).  Padded +inf
    keys are never counted for finite queries, so capacity padding is
    invisible to the answer."""
    @partial(jax.shard_map, mesh=mesh,
             in_specs=(P(axis, None), P(axis, None), P(axis, None),
                       P(axis, None), P(axis, None), P(axis), P(axis)),
             out_specs=P(axis))
    def impl(seg_start, slope, base, seg_end, keys, n_loc, q_local):
        me = jax.lax.axis_index(axis)
        q_all = jax.lax.all_gather(q_local, axis, tiled=True)     # (Q_total,)
        idx = DeviceIndex(seg_start[0], slope[0], base[0], seg_end[0],
                          keys[0], error)
        r = xla_search(idx, q_all, side, "bisect").astype(jnp.int32)
        r = jnp.where(n_loc[0] > 0, r, 0)       # empty-shard row: all padding
        total = jax.lax.psum(r, axis)
        q_per = q_local.shape[0]
        return jax.lax.dynamic_slice_in_dim(total, me * q_per, q_per)

    return impl(seg_start, slope, base, seg_end, keys, n_local, queries)


def sharded_search_a2a(seg_start, slope, base, seg_end, keys, n_local,
                       offsets, boundaries, queries, *, mesh: Mesh,
                       axis: str = "data", error: int, side: str = "left",
                       slack: float = 2.0):
    """Owner-bucketed ``all_to_all`` insertion-rank search.

    Each device routes its local queries through the replicated boundary
    router, slots them into D buckets of capacity ``ceil(Q/D^2 * slack)``
    (+inf sentinel padding), exchanges buckets, answers the queries it owns
    (local rank + its replicated prefix ``offsets`` entry == global rank,
    because serving cuts are duplicate-safe: no equal-key run straddles a
    shard), and reverses the exchange.  Returns ``(ranks, ok)`` where
    ``ok=False`` marks queries dropped by bucket overflow under skew --
    ``DeviceShardedService`` resolves those with a follow-up allgather pass
    so callers never see the mask."""
    d = mesh.shape[axis]
    q_per = queries.shape[0] // d
    cap = max(1, int(np.ceil(q_per / d * slack)))

    @partial(jax.shard_map, mesh=mesh,
             in_specs=(P(axis, None), P(axis, None), P(axis, None),
                       P(axis, None), P(axis, None), P(axis), P(), P(),
                       P(axis)),
             out_specs=(P(axis), P(axis)))
    def impl(seg_start, slope, base, seg_end, keys, n_loc, offs, bounds,
             q_local):
        me = jax.lax.axis_index(axis)
        idx = DeviceIndex(seg_start[0], slope[0], base[0], seg_end[0],
                          keys[0], error)
        owner = jnp.clip(jnp.searchsorted(bounds, q_local, side="right") - 1,
                         0, d - 1)
        # slot each query into its owner bucket via one stable sort
        order = jnp.argsort(owner, stable=True)
        sorted_owner = owner[order]
        rank_in_bkt = jnp.arange(q_local.shape[0]) - jnp.searchsorted(
            sorted_owner, sorted_owner, side="left")
        ok_sorted = rank_in_bkt < cap
        buckets = jnp.full((d, cap), jnp.inf, q_local.dtype)
        src_pos = jnp.full((d, cap), -1, jnp.int32)
        slot = jnp.clip(rank_in_bkt, 0, cap - 1)
        buckets = buckets.at[sorted_owner, slot].set(
            jnp.where(ok_sorted, q_local[order], jnp.inf))
        src_pos = src_pos.at[sorted_owner, slot].set(
            jnp.where(ok_sorted, order.astype(jnp.int32), -1))
        # exchange: after a2a, row j of `incoming` is what device j sent me
        incoming = jax.lax.all_to_all(buckets, axis, split_axis=0,
                                      concat_axis=0, tiled=True)
        flat = incoming.reshape(-1)
        r = xla_search(idx, flat, side, "bisect").astype(jnp.int32)
        r = jnp.where(n_loc[0] > 0, r, 0) + offs[me]
        back = jax.lax.all_to_all(r.reshape(d, cap), axis, split_axis=0,
                                  concat_axis=0, tiled=True).reshape(d, cap)
        # scatter answers back to original slots; sentinel slots carry
        # src_pos=-1 and contribute a harmless 0 to the max (ranks are >= 0)
        flat_src = src_pos.reshape(-1)
        good = flat_src >= 0
        result = jnp.zeros(q_local.shape, jnp.int32).at[
            jnp.clip(flat_src, 0, None)].max(
            jnp.where(good, back.reshape(-1), 0))
        okq = jnp.zeros(q_local.shape, bool).at[
            jnp.clip(flat_src, 0, None)].max(good)
        return result, okq

    return impl(seg_start, slope, base, seg_end, keys, n_local, offsets,
                boundaries, queries)


def sharded_lookup_allgather(seg_start, slope, base, seg_end, keys, n_local,
                             queries, *, mesh: Mesh, axis: str = "data",
                             error: int):
    """Point semantics over the allgather search kernel: leftmost rank where
    the key is present (``right > left``), -1 where absent.  Two collective
    rounds; the back-compat target for ``repro.core.distributed``."""
    args = (seg_start, slope, base, seg_end, keys, n_local, queries)
    kw = dict(mesh=mesh, axis=axis, error=error)
    left = sharded_search_allgather(*args, side="left", **kw)
    right = sharded_search_allgather(*args, side="right", **kw)
    return jnp.where(right > left, left, -1)


def sharded_lookup_a2a(seg_start, slope, base, seg_end, keys, n_local,
                       offsets, boundaries, queries, *, mesh: Mesh,
                       axis: str = "data", error: int, slack: float = 2.0):
    """Point semantics over the a2a search kernel; returns ``(ranks, ok)``
    with ``ok=False`` marking bucket-overflow drops (the legacy
    ``lookup_a2a`` contract -- the service path resolves the mask itself)."""
    args = (seg_start, slope, base, seg_end, keys, n_local, offsets,
            boundaries, queries)
    kw = dict(mesh=mesh, axis=axis, error=error, slack=slack)
    left, ok_l = sharded_search_a2a(*args, side="left", **kw)
    right, ok_r = sharded_search_a2a(*args, side="right", **kw)
    return jnp.where(right > left, left, -1), ok_l & ok_r


# ------------------------------------------------------------- the manifest
@dataclasses.dataclass(frozen=True)
class DeviceShardSet:
    """One immutable, versioned device-resident serving view.

    Published with a single reference assignment
    (``service._device_set = DeviceShardSet(...)``) and pinned once per
    verb, exactly the ``ShardSet`` discipline: a reader resolves routing,
    device arrays, rank offsets and host-side materialization against this
    one object, so a concurrent (delta) publish can never tear a batch.

    ``snapshots`` pins the host epoch each device row was packed from --
    the per-shard dirtiness fingerprint for delta publish (a host publish
    always installs a *new* ``Snapshot`` object) and the materialization
    source for ``range``.  ``s_cap``/``m_cap`` are the padded row
    capacities; rows are re-shipped in place while the new tables fit, so
    array shapes (and jit caches) are stable across delta publishes."""
    version: int
    host_version: int                   # ShardSet.version this was built from
    error: int
    n_keys: int                         # total keys served
    n_segments: int                     # total segments across shards
    s_cap: int                          # padded segment columns per row
    m_cap: int                          # padded key columns per row
    boundaries: np.ndarray              # (D,) f64 router cuts (host copy)
    offsets: np.ndarray                 # (D,) i64 global-rank prefix offsets
    snapshots: tuple[Snapshot, ...]     # pinned host snapshots, one per shard
    epochs: tuple[int, ...]             # per-shard epoch fingerprints
    d_seg_start: jax.Array              # (D, s_cap) f32 sharded, +inf padded
    d_slope: jax.Array                  # (D, s_cap) f32 sharded
    d_base: jax.Array                   # (D, s_cap) i32 sharded
    d_seg_end: jax.Array                # (D, s_cap) i32 sharded
    d_keys: jax.Array                   # (D, m_cap) f32 sharded, +inf padded
    d_n_local: jax.Array                # (D,) i32 sharded: live keys per row
    d_offsets: jax.Array                # (D,) i32 replicated prefix offsets
    d_boundaries: jax.Array             # (D,) f32 replicated router

    def __post_init__(self):
        # published = immutable: freeze the host-side columns a pinned
        # reader routes/lifts with (the device arrays are immutable already)
        object.__setattr__(self, "boundaries",
                           sanitizer.published_array(self.boundaries))
        object.__setattr__(self, "offsets",
                           sanitizer.published_array(self.offsets))

    @property
    def n_devices(self) -> int:
        return len(self.snapshots)

    def row_bytes(self) -> int:
        """Device-resident bytes of ONE shard row (sharded arrays only)."""
        return int(4 * self.s_cap * 4 + self.m_cap * 4 + 4)

    def replicated_bytes(self) -> int:
        """Bytes of the replicated router + offsets on ONE device."""
        return int(self.n_devices * (4 + 4))


def _pack_row(table, s_cap: int, m_cap: int):
    """One shard's padded device row: +inf start-key / key padding, 0 slope,
    n_keys base/seg_end (an empty trailing window) -- the
    ``pack_shard_tables`` scheme widened to capacity, in device dtypes.
    Refuses a key column that float32 cannot hold."""
    if not keycode.f32_exact(table.keys):
        raise ValueError(
            "DeviceShardSet rows hold 32-bit (float32) keys, and this key "
            "column needs 64 bits: some keys do not survive float64 -> "
            "float32 -> float64.  Serve it on one chip (open_index without "
            "device_count), whose engines compare 64-bit keys.")
    s, n = table.n_segments, table.n_keys
    seg_start = np.full(s_cap, np.inf, np.float32)
    slope = np.zeros(s_cap, np.float32)
    base = np.full(s_cap, n, np.int32)
    seg_end = np.full(s_cap, n, np.int32)
    seg_start[:s] = table.start_key
    slope[:s] = table.slope
    base[:s] = table.base
    seg_end[:s] = table.seg_end
    keys = np.full(m_cap, np.inf, np.float32)
    keys[:n] = table.keys
    return seg_start, slope, base, seg_end, keys, n


# ------------------------------------------------------------- the service
class DeviceShardedService:
    """``ShardedIndexService`` write path, device-resident read path.

    Construction partitions the keys into ``device_count`` contiguous
    shards (one host ``ShardedIndexService`` with the same cuts owns the
    writers/publishers) and uploads the packed layout onto a 1-D device
    mesh.  From then on:

        svc = DeviceShardedService(keys, error=64, device_count=8,
                                   buffer_size=16)
        svc.insert(k)        # routed + buffered on the host writer (Alg. 4)
        svc.publish()        # host epoch cut, then a DELTA upload: only
                             # dirty shards' rows are re-shipped on device
        svc.search(q)        # shard_map collective search, global ranks
        svc.lookup(q)        # and the full typed verb surface

    ``exchange`` picks the collective strategy: ``"allgather"`` (robust,
    per-device work is the whole batch), ``"a2a"`` (owner-routed,
    per-device work shrinks with D; slack overflow resolved internally via
    a follow-up allgather pass), or ``"auto"`` (per-batch cost-model
    crossover, :func:`repro.core.cost_model.choose_exchange`).

    Requires ``jax.device_count() >= device_count`` (CI forces 8 host
    devices via XLA_FLAGS) and at least ``device_count`` distinct keys.
    """

    def __init__(self, keys: np.ndarray, error: int | None = None, *,
                 plan: "IndexPlan | None" = None,
                 device_count: int | None = None,
                 buffer_size: int | None = None,
                 publish_every: int | None = None,
                 exchange: str | None = None,
                 payload: np.ndarray | None = None,
                 mesh: Mesh | None = None, axis: str = "data",
                 slack: float = 2.0, headroom: float = 0.5,
                 skew_threshold: float = 2.0, pending_weight: float = 1.0,
                 mode: str = "paper", assume_sorted: bool = False,
                 monitor: Monitor | None = None):
        from .fit import IndexPlan

        raw = {"error": error, "device_count": device_count,
               "buffer_size": buffer_size, "publish_every": publish_every,
               "exchange": exchange}
        if plan is None:
            if error is None:
                raise TypeError("pass error=... (expert knobs) or plan=... "
                                "(an IndexPlan from repro.index.fit)")
            d = int(device_count) if device_count is not None \
                else jax.device_count()
            plan = dataclasses.replace(
                IndexPlan.from_knobs(
                    error=error, n_shards=d,
                    buffer_size=0 if buffer_size is None else buffer_size,
                    backend="device", publish_every=publish_every),
                device_count=d,
                exchange="allgather" if exchange is None else exchange)
        else:
            clashing = sorted(k for k, v in raw.items() if v is not None)
            if clashing:
                raise TypeError("pass either the raw knobs or plan=, not "
                                f"both -- the plan already fixes "
                                f"{', '.join(clashing)}")
        if plan.backend != "device":
            raise ValueError(f"DeviceShardedService needs backend='device', "
                             f"plan has {plan.backend!r}")
        d = int(plan.device_count or plan.n_shards)
        if len(jax.devices()) < d:
            raise ValueError(f"device_count={d} exceeds the {len(jax.devices())} "
                             "available devices (CPU runs force more via "
                             "XLA_FLAGS=--xla_force_host_platform_device_"
                             f"count={d})")
        if plan.exchange is not None and plan.exchange not in _EXCHANGES:
            raise ValueError(f"exchange must be one of {_EXCHANGES}, got "
                             f"{plan.exchange!r}")
        self.plan = plan
        self.exchange = plan.exchange or "allgather"
        self.publish_every = plan.publish_every
        self.monitor = monitor
        self.slack = float(slack)
        self.headroom = float(headroom)
        self._axis = axis
        self._mesh = mesh if mesh is not None else Mesh(
            np.asarray(jax.devices()[:d]), (axis,))
        self._devices = list(np.asarray(self._mesh.devices).ravel())
        self._shard_spec = NamedSharding(self._mesh, P(axis, None))
        self._row_spec = NamedSharding(self._mesh, P(axis))
        self._repl_spec = NamedSharding(self._mesh, P())

        # the host write plane: same cuts, same writers, numpy verbs kept as
        # the bit-identity oracle.  Plain dataclasses.replace (not
        # plan.replace) so the host plan keeps the device plan's revision;
        # the device service runs the publish cadence itself.
        host_plan = dataclasses.replace(plan, backend="numpy", n_shards=d,
                                        publish_every=None, device_count=None,
                                        exchange=None)
        self._host = ShardedIndexService(
            keys, plan=host_plan, payload=payload, mode=mode,
            skew_threshold=skew_threshold, pending_weight=pending_weight,
            assume_sorted=assume_sorted, monitor=monitor)

        # ranks *before* the host service's write lock: device mutators wrap
        # the host ones (publish -> host.publish under both locks)
        self._write_lock = sanitizer.make_rlock(
            "DeviceShardedService._write_lock")
        self._fn_lock = sanitizer.make_lock("DeviceShardedService._fn_lock")
        self._counts_lock = sanitizer.make_lock(
            "DeviceShardedService._counts_lock")
        self._fns: dict = {}
        self._query_counts = {"points": 0, "ranges": 0, "counts": 0,
                              "predecessors": 0, "successors": 0,
                              "searches": 0}
        self._publishes = 0
        self._delta_publishes = 0
        self._full_publishes = 0
        self._bytes_uploaded = 0
        self._bytes_full_equivalent = 0
        self._xchg_counts = {"allgather": 0, "a2a": 0}
        self._overflow_queries = 0
        self._collective_wall_ns = 0.0
        ds0 = self._full_set(version=1)
        self._device_set = ds0
        self._account_publish(ds0, self._full_bytes(ds0), full=True,
                              dirty=d, wall_ns=0)

    @classmethod
    def from_plan(cls, keys: np.ndarray, plan: "IndexPlan", *,
                  payload: np.ndarray | None = None,
                  **service_kwargs) -> "DeviceShardedService":
        """Build from a resolved ``IndexPlan`` (the ``fit.open_index`` path
        for ``backend='device'``)."""
        return cls(keys, plan=plan, payload=payload, **service_kwargs)

    # ------------------------------------------------------------------ shape
    @property
    def host(self) -> ShardedIndexService:
        """The wrapped host write plane (writers, publishers, rebalancer)."""
        return self._host

    @property
    def n_devices(self) -> int:
        return len(self._devices)

    @property
    def n_shards(self) -> int:
        return self._host.n_shards

    @property
    def device_set(self) -> DeviceShardSet:
        """The current immutable device manifest (pin it for consistency)."""
        return self._device_set

    @property
    def boundaries(self) -> np.ndarray:
        return self._host.boundaries

    @property
    def pending_inserts(self) -> int:
        return self._host.pending_inserts

    def shard_of(self, key: float) -> int:
        return self._host.shard_of(key)

    def epochs(self) -> list[int]:
        return self._host.epochs()

    def imbalance(self) -> float:
        return self._host.imbalance()

    def needs_rebalance(self) -> bool:
        return self._host.needs_rebalance()

    def _pin_device_set(self) -> DeviceShardSet:
        """THE read-path pin: one reference read of the live device manifest
        per verb (RI002); the pinned version is reported to the sanitizer's
        PinTracker, which asserts no verb mixes two manifests end-to-end."""
        ds = self._device_set
        sanitizer.observe_pin(ds.version)
        return ds

    def _count(self, shape: str, n: int) -> None:
        with self._counts_lock:
            self._query_counts[shape] += n

    # ------------------------------------------------------------ build/upload
    def _caps_for(self, snaps: Sequence[Snapshot]) -> tuple[int, int]:
        """Padded row capacities with headroom over the current maxima, so
        steady-state inserts re-publish into the same shapes (delta-eligible,
        no retrace); the +8/+64 floors keep tiny shards delta-able too."""
        s_max = max(s.table.n_segments for s in snaps)
        m_max = max(s.n_keys for s in snaps)
        s_cap = int(np.ceil(max(s_max, 1) * (1.0 + self.headroom))) + 8
        m_cap = int(np.ceil(max(m_max, 1) * (1.0 + self.headroom))) + 64
        return s_cap, m_cap

    def _manifest_arrays(self, snaps, host_version: int, version: int,
                         s_cap: int, m_cap: int, device_arrays
                         ) -> DeviceShardSet:
        boundaries = np.asarray(self._host.boundaries, np.float64)
        sizes = np.asarray([s.n_keys for s in snaps], np.int64)
        offsets = np.concatenate([[0], np.cumsum(sizes)[:-1]]).astype(np.int64)
        return DeviceShardSet(
            version=version, host_version=host_version,
            error=int(self._host.error), n_keys=int(sizes.sum()),
            n_segments=int(sum(s.table.n_segments for s in snaps)),
            s_cap=s_cap, m_cap=m_cap, boundaries=boundaries, offsets=offsets,
            snapshots=tuple(snaps),
            epochs=tuple(s.epoch for s in snaps),
            d_offsets=jax.device_put(offsets.astype(np.int32),
                                     self._repl_spec),
            d_boundaries=jax.device_put(boundaries.astype(np.float32),
                                        self._repl_spec),
            **device_arrays)

    def _full_set(self, version: int) -> DeviceShardSet:
        """Pack every shard's snapshot and upload the whole layout (build,
        rebalance, structural replan, or capacity overflow)."""
        host_ss = self._host.shard_set
        snaps = [h.current() for h in host_ss.handles]
        s_cap, m_cap = self._caps_for(snaps)
        rows = [_pack_row(s.table, s_cap, m_cap) for s in snaps]
        stacked = [np.stack([r[i] for r in rows]) for i in range(5)]
        n_local = np.asarray([r[5] for r in rows], np.int32)
        seg_start, slope, base, seg_end, keys = [
            jax.device_put(a, self._shard_spec) for a in stacked]
        return self._manifest_arrays(
            snaps, host_ss.version, version, s_cap, m_cap,
            dict(d_seg_start=seg_start, d_slope=slope, d_base=base,
                 d_seg_end=seg_end, d_keys=keys,
                 d_n_local=jax.device_put(n_local, self._row_spec)))

    def _swap_rows(self, old: jax.Array, dirty_rows: dict[int, np.ndarray]
                   ) -> jax.Array:
        """Rebuild a sharded array reusing the clean rows' existing device
        buffers and ``device_put``-ing only the dirty rows onto their owning
        devices -- the delta-upload primitive.  Buffer identity of clean
        rows is preserved (asserted in tests via unsafe_buffer_pointer)."""
        bufs: dict[int, jax.Array] = {}
        for s in old.addressable_shards:
            bufs[int(s.index[0].start or 0)] = s.data
        for r, row in dirty_rows.items():
            bufs[r] = jax.device_put(row[None, ...] if row.ndim else
                                     np.asarray([row]), self._devices[r])
        arrays = [bufs[r] for r in range(len(self._devices))]
        return jax.make_array_from_single_device_arrays(
            old.shape, old.sharding, arrays)

    def _delta_set(self, cur: DeviceShardSet, snaps: list[Snapshot],
                   dirty: list[int]) -> DeviceShardSet:
        """Delta upload: re-pack ONLY the dirty shards' rows into the current
        capacities and swap them in; clean rows keep their device buffers."""
        rows = {d: _pack_row(snaps[d].table, cur.s_cap, cur.m_cap)
                for d in dirty}
        names = ("d_seg_start", "d_slope", "d_base", "d_seg_end", "d_keys")
        device_arrays = {
            name: self._swap_rows(getattr(cur, name),
                                  {d: r[i] for d, r in rows.items()})
            for i, name in enumerate(names)}
        device_arrays["d_n_local"] = self._swap_rows(
            cur.d_n_local, {d: np.int32(r[5]) for d, r in rows.items()})
        return self._manifest_arrays(snaps, cur.host_version,
                                     cur.version + 1, cur.s_cap, cur.m_cap,
                                     device_arrays)

    def _full_bytes(self, ds: DeviceShardSet) -> int:
        return ds.row_bytes() * ds.n_devices + \
            ds.replicated_bytes() * ds.n_devices

    def _account_publish(self, ds: DeviceShardSet, up_bytes: int, *,
                         full: bool, dirty: int, wall_ns: int) -> None:
        self._publishes += 1
        if full:
            self._full_publishes += 1
        else:
            self._delta_publishes += 1
        self._bytes_uploaded += up_bytes
        self._bytes_full_equivalent += self._full_bytes(ds)
        if self.monitor is not None:
            self.monitor.record(CH_DEVICE_PUBLISH, dirty, up_bytes, wall_ns,
                                1 if full else 0)

    def _sync_locked(self) -> None:
        """Reconcile the device manifest with the host serving state: delta
        upload when only snapshots moved and the new tables fit the current
        capacities; full re-pack on a boundary change (rebalance/replan),
        shard-count change, or capacity overflow.  Ends in the single
        reference assignment that publishes the new manifest."""
        t0 = time.perf_counter_ns()
        cur = self._device_set
        host_ss = self._host.shard_set
        snaps = [h.current() for h in host_ss.handles]
        structural = (host_ss.version != cur.host_version
                      or len(snaps) != len(cur.snapshots)
                      or max(s.table.n_segments for s in snaps) > cur.s_cap
                      or max(s.n_keys for s in snaps) > cur.m_cap)
        if structural:
            new = self._full_set(cur.version + 1)
            self._device_set = new
            self._account_publish(new, self._full_bytes(new), full=True,
                                  dirty=len(snaps),
                                  wall_ns=time.perf_counter_ns() - t0)
            return
        dirty = [d for d in range(len(snaps))
                 if snaps[d] is not cur.snapshots[d]]
        if not dirty:
            return
        new = self._delta_set(cur, snaps, dirty)
        # dirty rows' bytes + the re-shipped replicated offsets/router
        up = new.row_bytes() * len(dirty) + \
            new.replicated_bytes() * new.n_devices
        self._device_set = new
        self._account_publish(new, up, full=False, dirty=len(dirty),
                              wall_ns=time.perf_counter_ns() - t0)

    # ------------------------------------------------------------- write path
    def insert(self, key: float, value=None) -> None:
        """Buffer an insert in the owning shard's host writer (Alg. 4);
        invisible on device until that shard publishes."""
        with self._write_lock:
            self._host.insert(key, value)
            if self.publish_every is not None and \
                    self._host.pending_inserts >= self.publish_every:
                self.publish()

    def publish(self, shards: Sequence[int] | None = None,
                force: bool = False) -> dict[int, Snapshot]:
        """Cut new host epochs on dirty shards, then delta-upload exactly
        those shards' device rows.  Clean shards keep their epoch *and*
        their device buffers.  Returns the newly installed snapshots."""
        with self._write_lock:
            published = self._host.publish(shards, force=force)
            self._sync_locked()
            return published

    def rebalance(self, force: bool = False) -> dict | None:
        """Recut boundaries on the host plane (migrating key runs between
        writers), then re-upload the full device layout -- a boundary change
        invalidates every row's routing, so there is no delta to take."""
        with self._write_lock:
            info = self._host.rebalance(force)
            if info is not None:
                self._sync_locked()
            return info

    def apply_plan(self, new_plan: "IndexPlan", *,
                   reshard: bool = False) -> "IndexPlan":
        """Hot-swap the served configuration (the ``Replanner`` path).  The
        shard count is pinned to the device count (``reshard`` only
        re-segments; it never changes D -- a mesh is not resizable at
        runtime), exchange/device hints carry over unless the new plan sets
        its own, and the device layout is fully re-uploaded."""
        with self._write_lock:
            host_plan = dataclasses.replace(
                new_plan, backend="numpy", n_shards=self.n_devices,
                publish_every=None, device_count=None, exchange=None)
            applied = self._host.apply_plan(host_plan, reshard=False)
            self.plan = dataclasses.replace(
                new_plan, backend="device", n_shards=applied.n_shards,
                device_count=self.n_devices,
                exchange=new_plan.exchange or self.exchange)
            self.exchange = self.plan.exchange
            self.publish_every = (self.plan.publish_every
                                  if self.plan.buffer_size > 0 else None)
            self._sync_locked()
            return self.plan

    # -------------------------------------------------------------- read path
    def _kernel(self, kind: str, side: str, error: int):
        """The jitted collective for (strategy, side, error), cached under
        ``_fn_lock``.  Device arrays enter as *arguments* (not closures), so
        a delta publish swaps buffers without retracing; a capacity change
        retraces naturally through the new shapes."""
        key = (kind, side, error)
        with self._fn_lock:
            fn = self._fns.get(key)
            if fn is None:
                mesh, axis, slack = self._mesh, self._axis, self.slack
                if kind == "ag":
                    def fn(seg_start, slope, base, seg_end, keys, n_local, q):
                        return sharded_search_allgather(
                            seg_start, slope, base, seg_end, keys, n_local,
                            q, mesh=mesh, axis=axis, error=error, side=side)
                else:
                    def fn(seg_start, slope, base, seg_end, keys, n_local,
                           offsets, boundaries, q):
                        return sharded_search_a2a(
                            seg_start, slope, base, seg_end, keys, n_local,
                            offsets, boundaries, q, mesh=mesh, axis=axis,
                            error=error, side=side, slack=slack)
                fn = jax.jit(fn)
                self._fns[key] = fn
        return fn

    def _pad(self, flat: np.ndarray) -> np.ndarray:
        """Pad to a device-divisible batch with a finite filler (padding
        lanes compute real-but-discarded ranks; +inf would be routed to the
        last shard, which is also fine -- finite keeps the a2a buckets
        honest about real skew only)."""
        d = self.n_devices
        q_per = max(1, -(-flat.size // d))
        if flat.size == q_per * d:
            return flat
        out = np.zeros(q_per * d, np.float32)
        out[:flat.size] = flat
        return out

    def _search_set(self, ds: DeviceShardSet, queries,
                    side: str) -> np.ndarray:
        """Global insertion ranks against a pinned manifest.  The exchange
        strategy is the service's (or the per-batch cost-model choice under
        ``"auto"``); a2a bucket overflow is resolved here with a follow-up
        allgather pass over just the overflowed queries."""
        q = np.asarray(queries, np.float64)
        flat = q.astype(np.float32).ravel()
        if flat.size == 0:
            return np.empty(q.shape, np.int64)
        strategy = self.exchange
        if strategy == "auto":
            strategy = choose_exchange(flat.size, ds.n_devices, ds.error,
                                       ds.n_segments)
        if ds.n_devices == 1:
            strategy = "allgather"
        t0 = time.perf_counter_ns()
        shard_args = (ds.d_seg_start, ds.d_slope, ds.d_base, ds.d_seg_end,
                      ds.d_keys, ds.d_n_local)
        if strategy == "a2a":
            ranks_d, ok_d = self._kernel("a2a", side, ds.error)(
                *shard_args, ds.d_offsets, ds.d_boundaries, self._pad(flat))
            ranks = np.asarray(ranks_d, np.int64)[:flat.size]
            miss = ~np.asarray(ok_d)[:flat.size]
            n_miss = int(miss.sum())
            if n_miss:
                # the follow-up pass the a2a contract promises: overflowed
                # queries re-ask via allgather, which cannot drop anything
                sub = self._kernel("ag", side, ds.error)(
                    *shard_args, self._pad(flat[miss]))
                ranks[miss] = np.asarray(sub, np.int64)[:n_miss]
                with self._counts_lock:
                    self._overflow_queries += n_miss
                if self.monitor is not None:
                    self.monitor.record(CH_DEVICE_OVERFLOW, n_miss)
        else:
            ranks = np.asarray(self._kernel("ag", side, ds.error)(
                *shard_args, self._pad(flat)), np.int64)[:flat.size]
        wall = time.perf_counter_ns() - t0
        with self._counts_lock:
            self._xchg_counts[strategy] += 1
            self._collective_wall_ns += wall
        if self.monitor is not None:
            self.monitor.record(
                CH_DEVICE_COLLECTIVE,
                XCHG_A2A if strategy == "a2a" else XCHG_ALLGATHER,
                flat.size, wall)
        return ranks.reshape(q.shape)

    def search(self, queries, side: str = "left") -> np.ndarray:
        """Global ``searchsorted(all_keys, queries, side)`` insertion ranks
        (f32 key compares) via one collective round on the device mesh."""
        check_side(side)
        self._count("searches", int(np.size(queries)))
        with sanitizer.pin_scope("device.search"):
            return self._search_set(self._pin_device_set(), queries, side)

    def lookup(self, queries) -> np.ndarray:
        """Global rank of each query, -1 if absent (found == some key equals
        the query in f32, i.e. right rank > left rank)."""
        self._count("points", int(np.size(queries)))
        with sanitizer.pin_scope("device.lookup"):
            ds = self._pin_device_set()
            left = self._search_set(ds, queries, "left")
            right = self._search_set(ds, queries, "right")
            return np.where(right > left, left, -1)

    def point(self, queries) -> PointResult:
        """Typed membership: global leftmost rank + found flag per query."""
        self._count("points", int(np.size(queries)))
        with sanitizer.pin_scope("device.point"):
            ds = self._pin_device_set()
            left = self._search_set(ds, queries, "left")
            right = self._search_set(ds, queries, "right")
            found = right > left
            return PointResult(rank=np.where(found, left, -1), found=found)

    def count(self, lo, hi) -> np.ndarray:
        """Keys in the inclusive ``[lo, hi]`` ranges (vectorized), both
        bounds resolved against one pinned manifest."""
        with sanitizer.pin_scope("device.count"):
            ds = self._pin_device_set()
            lo = np.asarray(lo, np.float64)
            hi = np.asarray(hi, np.float64)
            counts = np.maximum(self._search_set(ds, hi, "right")
                                - self._search_set(ds, lo, "left"), 0)
            self._count("counts", int(counts.size))
            return counts.astype(np.int64)

    def predecessor(self, queries) -> PointResult:
        """Global rank of the largest key <= each query (rightmost)."""
        self._count("predecessors", int(np.size(queries)))
        with sanitizer.pin_scope("device.predecessor"):
            ds = self._pin_device_set()
            rank = self._search_set(ds, queries, "right") - 1
            found = rank >= 0
            return PointResult(rank=np.where(found, rank, -1), found=found)

    def successor(self, queries) -> PointResult:
        """Global rank of the smallest key >= each query (leftmost)."""
        self._count("successors", int(np.size(queries)))
        with sanitizer.pin_scope("device.successor"):
            ds = self._pin_device_set()
            rank = self._search_set(ds, queries, "left")
            found = rank < ds.n_keys
            return PointResult(rank=np.where(found, rank, -1), found=found)

    def range(self, lo, hi, *, materialize: bool = True) -> RangeResult:
        """Inclusive ``[lo, hi]`` scan: the rank span comes from the device
        collectives, the materialized keys/payloads from the SAME pinned
        manifest's host snapshots -- one epoch combination end to end."""
        lo, hi = check_range(lo, hi)
        with sanitizer.pin_scope("device.range"):
            ds = self._pin_device_set()
            self._count("ranges", 1)
            lo_rank = int(self._search_set(ds, np.asarray([lo]), "left")[0])
            hi_rank = max(int(self._search_set(ds, np.asarray([hi]),
                                               "right")[0]), lo_rank)
            keys = payload = None
            if materialize:
                d0 = int(route_keys(ds.boundaries, np.float64(lo)))
                d1 = int(route_keys(ds.boundaries, np.float64(hi)))
                k_parts, p_parts = [], []
                for d in range(d0, d1 + 1):
                    snap = ds.snapshots[d]
                    off = int(ds.offsets[d])
                    a = max(lo_rank - off, 0) if d == d0 else 0
                    b = (min(hi_rank - off, snap.n_keys) if d == d1
                         else snap.n_keys)
                    if b <= a:
                        continue
                    k_parts.append(snap.table.keys[a:b])
                    if snap.payload is not None:
                        p_parts.append(snap.payload[a:b])
                keys = (np.concatenate(k_parts) if k_parts
                        else np.empty(0, np.float64))
                if self._host.has_payload:
                    payload = (np.concatenate(p_parts) if p_parts
                               else np.empty(0))
            return RangeResult(lo=lo, hi=hi, lo_rank=lo_rank,
                               hi_rank=hi_rank, keys=keys, payload=payload)

    def prewarm(self, batch_sizes: Sequence[int] | None = None) -> None:
        """Compile the collective kernels for both sides (and both
        strategies when the service may use a2a) at the given batch shapes
        before serving traffic."""
        for n in (batch_sizes or (self.n_devices,)):
            probe = np.zeros(int(n), np.float64)
            self.search(probe, side="left")
            self.search(probe, side="right")

    # ------------------------------------------------------------ observability
    def metrics(self):
        """The typed snapshot: the host plane's tree (shards, rebalances,
        imbalance) re-rooted at ``service="device"`` with this service's
        query counters and the :class:`DeviceMetrics` node -- manifest
        shape, per-device resident bytes, the delta-upload fraction, and
        the exchange-strategy counters."""
        base = self._host.metrics()
        ds = self._device_set
        with self._counts_lock:
            counts = dict(self._query_counts)
            xchg = dict(self._xchg_counts)
            overflow = self._overflow_queries
            wall = self._collective_wall_ns
        dm = DeviceMetrics(
            device_set_version=ds.version, n_devices=ds.n_devices,
            exchange=self.exchange, s_cap=ds.s_cap, m_cap=ds.m_cap,
            per_device_bytes=tuple(ds.row_bytes() + ds.replicated_bytes()
                                   for _ in range(ds.n_devices)),
            replicated_bytes=ds.replicated_bytes(),
            publishes=self._publishes,
            delta_publishes=self._delta_publishes,
            full_publishes=self._full_publishes,
            bytes_uploaded=self._bytes_uploaded,
            bytes_full_equivalent=self._bytes_full_equivalent,
            delta_fraction=(self._bytes_uploaded
                            / self._bytes_full_equivalent
                            if self._bytes_full_equivalent else 1.0),
            allgather_calls=xchg["allgather"], a2a_calls=xchg["a2a"],
            a2a_overflow_queries=overflow, collective_wall_ns=wall)
        return dataclasses.replace(base, service="device",
                                   plan_revision=self.plan.revision,
                                   query_counts=counts, device=dm)

    def stats(self) -> list:
        """Deprecated: use :meth:`metrics`\\ ``().shards``."""
        warnings.warn("DeviceShardedService.stats() is deprecated; use "
                      "metrics().shards", DeprecationWarning, stacklevel=2)
        return list(self.metrics().shards)
