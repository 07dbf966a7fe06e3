"""Serving the paper's index through the unified core (repro.index).

The SLO-driven path (Sec. 6 -- the paper's actual user contract) is three
lines; no error / shard count / threshold picked by hand:

    spec = FitSpec(latency_budget_ns=500.0)     # or storage_budget_bytes=...
    svc = open_index(keys, spec)                # cost model resolves the rest
    svc.insert(k); svc.publish(); svc.lookup(q)

``plan(keys, spec).explain()`` shows the predicted latency/size of every
candidate error before anything is built.

The async front door (``repro.index.pipeline``) wraps any service in a
coalescing queue: concurrent callers' tiny probes fuse into one fast-tier
batch (threshold-or-deadline flush, knobs resolved by the plan, engines
prewarmed so the first flush skips the compile spike), and a background
cadence thread publishes buffered inserts / runs auto-rebalance off the
request path:

    pipe = AsyncIndexService(svc)       # or open_pipeline(keys, spec)
    pipe.lookup(q)                      # sync facade over lookup_async(q)
    pipe.close()                        # drains in-flight futures

The typed query plane (``repro.index.query``) answers more than point
membership -- the clustered layout gives predecessor search, and therefore
range scans, for free:

    svc.point(qs)            # typed membership: leftmost rank + found flag
    svc.range(lo, hi)        # inclusive [lo, hi]: global rank span +
                             #   materialized keys (and payloads)
    svc.count(los, his)      # span sizes only, nothing materialized
    svc.predecessor(qs)      # rank of the largest key <= q (rightmost)
    svc.successor(qs)        # rank of the smallest key >= q (leftmost)

All five verbs derive from one per-backend ``search(queries, side)``
primitive, so every backend (and the sharded service, which stitches spans
across shards) returns identical answers.  A scan-heavy workload tells the
SLO path so: ``FitSpec(latency_budget_ns=..., range_fraction=0.3,
range_scan_rows=512)`` folds the range-scan cost term (fixed predecessor
cost + per-row scan marginal) into every candidate's predicted latency and
the dispatch-tier crossings.

An ingest-heavy workload declares itself (``FitSpec(...,
write_heavy=True, insert_rate=...)``) and ``open_index`` builds the LSM
write plane instead (``repro.index.lsm``): writes land in a bounded sorted
memtable, spill into immutable learned runs, and a background compactor
merges + re-fits off the serving path -- reads fan in across all levels by
leftmost-rank merge, so every verb keeps its exact searchsorted semantics
(duplicates, deletes via tombstones, newest-level-wins upserts) while the
service absorbs insert floods the single Alg. 4 buffer cannot:

    svc = open_index(keys, FitSpec(error=64, write_heavy=True,
                                   insert_rate=100_000))
    svc.insert_many(batch)   # vectorized; spills are automatic
    svc.delete(k); svc.upsert(k, v)
    svc.metrics().lsm        # levels, runs, spills, read amplification

The telemetry plane (``repro.index.telemetry``) closes the Sec. 6 loop:
attach a ``Monitor`` (``open_index(keys, spec, monitor=Monitor())``) and the
dispatch tiers record measured (batch, wall_ns) samples on lock-free rings;
``svc.metrics()`` returns the typed ``MetricsSnapshot`` tree (JSON
round-trip), and a ``Replanner`` re-fits the tier cost curves from the
measurements, re-plans against the served keys, and hot-swaps thresholds /
shard count / pipeline knobs when the predicted win clears its hysteresis
bar -- inside an ``AsyncIndexService`` this runs on the maintenance cadence
thread (``open_pipeline(keys, spec, replan_interval_s=5.0)``).

Everything below the SLO demo is the expert raw-knob path:

  * one `SegmentTable`, every engine backend (numpy / xla-window / xla-bisect
    / pallas / dispatch) checked against the oracle and timed;
  * the epoch write path: buffered inserts -> publish() -> atomic snapshot
    swap, after which every backend serves the new keys;
  * the sharded service: N key-partitioned writers with per-shard epoch
    streams -- insert into some shards, publish, and watch only the dirty
    shards' epochs advance while the rest keep serving their old snapshot;
  * optionally the device-sharded serving plane (``repro.index.device``;
    run under 8 fake devices to see the collectives):

  XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
      PYTHONPATH=src python examples/serve_index.py --distributed

    ``FitSpec(..., device_count=D)`` plans ``backend="device"`` and
    ``open_index`` builds a ``DeviceShardedService``: one shard per
    device, replicated boundary router, the two-sided ``search`` run
    under ``shard_map`` (the plan's cost model picks allgather for small
    batches, bucketed all_to_all past the modeled crossover --
    ``explain()`` shows the choice), and publish delta-uploads only the
    dirty shards' device rows (clean rows keep their buffers).  The
    seed-era ``core/distributed.py`` entry points are thin wrappers over
    the same kernels.

Shard-partitioning knobs (`ShardedIndexService`):
  * ``n_shards`` (CLI ``--shards``) -- equal-count contiguous key ranges; the
    replicated boundary router (first key per shard) is the paper's structure
    recursed once.  More shards = smaller per-shard tables and finer publish
    granularity, at the cost of more snapshots to manage.
  * ``buffer_size`` -- per-segment Alg. 4 insert buffer inside each shard's
    writer; the user-visible error bound still holds (err_seg = error -
    buffer_size).
  * ``publish_every`` -- auto-publish cadence: after this many buffered
    inserts (service-wide) the dirty shards republish.  ``publish()`` is
    always safe to call unconditionally: clean shards are skipped, and a
    fully clean service is a no-op.

Rebalancing knobs (shard boundaries are NOT frozen at construction):
  * ``skew_threshold`` (CLI ``--skew-threshold``) -- max/mean keys-per-shard
    ratio above which ``rebalance()`` recuts the boundaries (duplicate-safe:
    cuts snap to unique-key run starts) and migrates key runs between the
    shard writers; 1.0 is perfectly even, 2.0 the default trigger.
  * ``pending_weight`` -- how strongly unpublished per-shard inserts count
    toward the skew metric (pressure forecast for write-hot shards).
  * ``auto_rebalance`` -- run the skew check after every ``publish()``; the
    recut swaps boundaries + serving handles atomically as one versioned
    ``ShardSet``, so concurrent lookups never mix old routing with new
    offsets.  ``service_stats()`` exposes the version + rebalance counters.

The concurrency contracts behind all of this (immutable published
snapshots, read-once pinning of the ``ShardSet``, one global lock order)
are written down in ``docs/INVARIANTS.md`` and mechanically enforced:
``python -m repro.analysis src/ --strict`` checks the source statically,
and running any of this with ``REPRO_SANITIZE=1`` turns on the runtime
sanitizer (frozen served arrays, pin tracking, lock-order watchdog).

Backend-dispatch knobs (``backend="dispatch"``, see
``repro.index.engine.DispatchEngine``):
  * ``small_max`` -- batches up to this size stay on the host (``numpy``):
    no device round trip for tiny point probes.
  * ``large_min`` -- batches at least this size take the Pallas plan/
    bucketing kernel (``pallas``); in between, the XLA bisect path wins.
  * both default to the cost-model crossings for the table's error and
    segment count (``repro.core.cost_model.dispatch_thresholds``); a plan
    pins them explicitly, and hand-set values override everything.
  * per-tier engines are overridable (``small=``/``medium=``/``large=``) and
    receive ``engine_opts[backend]`` kwargs, e.g. the Pallas bucket capacity.
"""
import argparse
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.index import SegmentTable, available_backends, make_engine, plan
from repro.kernels.ref import lookup_ref
from repro.serve import (AsyncIndexService, FitSpec, IndexService, Monitor,
                         Replanner, ServiceMetrics, ShardedIndexService,
                         open_index)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=200_000)
    ap.add_argument("--queries", type=int, default=4096)
    ap.add_argument("--error", type=int, default=64)
    ap.add_argument("--latency-ns", type=float, default=600.0,
                    help="lookup SLO for the FitSpec demo")
    ap.add_argument("--inserts", type=int, default=2000)
    ap.add_argument("--shards", type=int, default=4)
    ap.add_argument("--skew-threshold", type=float, default=1.5)
    ap.add_argument("--distributed", action="store_true")
    args = ap.parse_args()
    enable_compile_cache()

    rng = np.random.default_rng(0)
    keys = np.sort(rng.choice(2 ** 23, size=args.n, replace=False)).astype(
        np.float64)

    # --- the SLO-driven path: declare the budget, let Sec. 6 pick the knobs
    spec = FitSpec(latency_budget_ns=args.latency_ns)
    resolved = plan(keys, spec)          # review it, then build from it
    print(resolved.explain())
    svc = open_index(keys, resolved)
    probe = float(keys[0]) - 1.0
    svc.insert(probe)
    svc.publish()
    assert svc.lookup(np.array([probe]))[0] == 0
    print(f"  open_index: {type(svc).__name__} serving error="
          f"{svc.plan.error} (no knob hand-picked); insert -> publish -> "
          f"lookup OK\n")

    # --- the async front door: coalescing + the background publish cadence
    # 8 concurrent callers of tiny probes fuse into threshold/deadline
    # flushes (knobs from svc.plan); a daemon thread publishes buffered
    # inserts off the request path -- nobody calls publish() below.
    with AsyncIndexService(svc, publish_interval_s=0.2) as pipe:
        mismatches = []

        def caller(seed):
            r = np.random.default_rng(seed)
            for _ in range(32):
                qs = keys[r.integers(0, args.n, int(r.integers(1, 5)))]
                if not np.array_equal(pipe.lookup(qs, timeout=30.0),
                                      svc.lookup(qs)):
                    mismatches.append(seed)

        callers = [threading.Thread(target=caller, args=(t,))
                   for t in range(8)]
        for t in callers:
            t.start()
        for t in callers:
            t.join()
        assert not mismatches, "coalesced answers diverged from the oracle"
        cadence_key = float(keys[-1]) + 3.0
        svc.insert(cadence_key)
        deadline = time.perf_counter() + 10.0
        # wait for the publish *counter*, not just snapshot visibility --
        # the snapshot installs mid-publish, before the stats update lands
        st = pipe.metrics().pipeline
        while st.publishes < 1 and time.perf_counter() < deadline:
            time.sleep(0.05)
            st = pipe.metrics().pipeline
        assert st.publishes >= 1, "cadence thread never published"
        assert pipe.lookup(np.array([cadence_key]), 30.0)[0] != -1
    print(f"  async front door: 8 callers x 32 batches -> {st.flushes} "
          f"fused flushes ({st.threshold_flushes} threshold / "
          f"{st.deadline_flushes} deadline, max fused batch "
          f"{st.max_fused_batch}); background cadence made the insert "
          f"visible with no caller publish()\n")

    # --- the typed query plane: point vs range vs count -------------------
    # a scan-heavy SLO folds the range-scan cost term into the plan
    scan_spec = FitSpec(latency_budget_ns=max(args.latency_ns, 800.0),
                        range_fraction=0.3, range_scan_rows=512)
    scan_svc = open_index(keys, scan_spec)
    lo, hi = float(keys[len(keys) // 4]), float(keys[len(keys) // 2])
    res = scan_svc.range(lo, hi)            # inclusive [lo, hi], materialized
    n_only = scan_svc.count([lo], [hi])[0]  # same span, nothing materialized
    pt = scan_svc.point(keys[:4])
    pred = scan_svc.predecessor(np.asarray([hi + 0.5]))
    assert res.count == n_only == res.keys.shape[0]
    assert pt.found.all() and pred.found[0]
    print(f"  query plane: range [{lo:.0f}, {hi:.0f}] -> "
          f"[{res.lo_rank}, {res.hi_rank}) = {res.count} keys "
          f"(count-only agrees: {n_only}); point found {pt.n_found}/4; "
          f"predecessor({hi:.0f}+0.5) = rank {pred.rank[0]}")
    shapes = scan_svc.metrics().query_counts
    print(f"  query counters: {shapes}\n")

    # --- telemetry + online re-planning: measure -> re-fit -> hot-swap ----
    # a Monitor records per-tier (batch, wall_ns) samples on the dispatch
    # hot path (lock-free ring writes, ~0.5us); metrics() returns the typed
    # snapshot tree; a Replanner re-fits the tier cost curves from the
    # measurements and hot-swaps the plan when the predicted win is real.
    mon = Monitor()
    live = open_index(keys, FitSpec(error=args.error,
                                    batch_sizes=(1, 256, 1024)),
                      monitor=mon)
    for size in (1, 8, 32, 256, 1024):      # traffic across the tiers
        for _ in range(10):
            live.lookup(keys[rng.integers(0, args.n, size)])
    m = live.metrics()
    assert ServiceMetrics.from_json(m.to_json()) == m  # dashboard-ready
    print(f"  telemetry: plan rev {m.plan_revision}, "
          f"{sum(t.calls for t in m.tiers)} dispatched calls")
    for t in m.tiers:
        fit = (f"measured curve {t.fixed_ns:.0f} + {t.per_query_ns:.1f}*b ns"
               if t.per_query_ns is not None else "too few samples to fit")
        print(f"    tier {t.tier:6s}: {t.calls} calls, "
              f"mean batch {t.mean_batch:.0f}; {fit}")
    old_sm, old_lg = live.plan.small_max, live.plan.large_min
    rp = Replanner(live, interval_s=0.01, hysteresis=0.05,
                   min_tier_samples=8)
    served = rp.replan()                    # the maintenance cadence calls
    if served is not None:                  # rp.step() for you in a pipeline
        print(f"  replanner: measured curves beat the model by "
              f"{rp.last_win:.0%} on the observed mix -> hot-swapped "
              f"thresholds ({old_sm}, {old_lg}) -> ({served.small_max}, "
              f"{served.large_min}), plan rev {served.revision} "
              f"(readers never torn)\n")
    else:
        print(f"  replanner: predicted win {rp.last_win} below the "
              f"hysteresis bar -> plan kept (no flapping)\n")

    # --- the LSM write plane: declared ingest-heavy, built tiered ---------
    lsm = open_index(keys, FitSpec(error=args.error, write_heavy=True,
                                   insert_rate=50_000))
    flood = rng.uniform(float(keys[0]), float(keys[-1]),
                        size=4 * lsm.memtable_capacity)
    lsm.insert_many(flood)                   # spills cut runs automatically
    victim = float(keys[args.n // 2])
    lsm.delete(victim)                       # tombstone shadows every level
    assert not lsm.point(victim).found
    q16 = np.sort(flood[:16])
    assert np.all(lsm.lookup(q16) >= 0)      # spilled keys stay visible
    lsm.publish()                            # maintenance tick: spill+compact
    ml = lsm.metrics().lsm
    print(f"  lsm write plane: {type(lsm).__name__}, memtable "
          f"{ml.memtable_keys}/{ml.memtable_capacity}, {ml.n_runs} runs "
          f"over {ml.n_levels} levels ({ml.spills} spills, "
          f"{ml.compactions} compactions); delete + {flood.size} inserts "
          f"served exactly, read amp {ml.read_amplification:.1f}\n")

    # --- expert raw-knob path from here down
    q = jnp.asarray(keys[rng.integers(0, args.n, args.queries)], jnp.float32)
    table = SegmentTable.from_keys(keys, args.error, assume_sorted=True)

    want = np.asarray(lookup_ref(jnp.asarray(keys, jnp.float32), q[:256]))
    for backend in available_backends():
        eng = make_engine(table, backend)
        got = np.asarray(eng.lookup(q[:256]))
        assert np.array_equal(got, want), backend
        eng.lookup(q)                       # warm the compile cache
        t0 = time.perf_counter()
        for _ in range(5):
            np.asarray(eng.lookup(q))
        dt = (time.perf_counter() - t0) / 5
        print(f"  {backend:11s}: {dt/args.queries*1e9:8.0f} ns/query "
              f"({args.queries} queries/batch, == oracle)")

    # --- write path: insert -> publish -> every backend serves the new epoch
    svc = IndexService(keys, error=args.error, buffer_size=args.error // 2,
                       backend="xla-bisect")
    fresh = np.setdiff1d(
        rng.choice(2 ** 23, size=2 * args.inserts, replace=False).astype(
            np.float64), keys)[: args.inserts]
    for k in fresh:
        svc.insert(float(k))
    assert np.all(svc.lookup(fresh[:64]) == -1), "unpublished inserts invisible"
    t0 = time.perf_counter()
    snap = svc.publish()
    dt = time.perf_counter() - t0
    assert np.all(svc.lookup(fresh[:64]) >= 0)
    print(f"  publish: epoch {snap.epoch}, {args.inserts} inserts, "
          f"{snap.n_refit} segments re-fit, {dt*1e3:.1f} ms; "
          f"serving swapped atomically")

    # --- sharded serving: per-shard epoch streams, batch-size dispatch
    sharded = ShardedIndexService(keys, args.error, n_shards=args.shards,
                                  buffer_size=args.error // 2,
                                  backend="dispatch")
    fresh2 = np.setdiff1d(
        rng.choice(2 ** 23, size=4 * args.inserts, replace=False).astype(
            np.float64), np.concatenate([keys, fresh]))
    # write only into the first and last shard (half the inserts each)
    if args.shards > 1:
        half = max(1, args.inserts // 2)
        lo_hi = np.concatenate([
            fresh2[fresh2 < sharded.boundaries[1]][:half],
            fresh2[fresh2 >= sharded.boundaries[-1]][:half]])
    else:
        lo_hi = fresh2[: args.inserts]
    for k in lo_hi:
        sharded.insert(float(k))
    t0 = time.perf_counter()
    published = sharded.publish()
    dt = time.perf_counter() - t0
    epochs = sharded.epochs()
    assert np.all(sharded.lookup(lo_hi) >= 0)
    print(f"  sharded: {args.shards} shards, {lo_hi.size} inserts into "
          f"shards {sorted(published)}; publish {dt*1e3:.1f} ms touched "
          f"only those (epochs now {epochs})")
    for s in sharded.metrics().shards:
        print(f"    shard {s.shard}: epoch {s.epoch}, {s.n_segments} segs, "
              f"{s.n_keys} keys, {s.pending_inserts} pending")

    # --- adaptive rebalancing: a write-hot range skews one shard; recut
    if args.shards > 1:
        reb = ShardedIndexService(keys, args.error, n_shards=args.shards,
                                  buffer_size=args.error // 2,
                                  skew_threshold=args.skew_threshold)
        hot_n = max(args.inserts, args.n // args.shards)  # ~2x one shard
        hot = np.setdiff1d(
            rng.uniform(reb.boundaries[0], reb.boundaries[1],
                        size=3 * hot_n).astype(np.float64), keys)[:hot_n]
        for k in hot:
            reb.insert(float(k))
        reb.publish()
        before = reb.imbalance()
        tripped = reb.needs_rebalance()  # or auto_rebalance=True at build
        t0 = time.perf_counter()
        info = reb.rebalance(force=not tripped)   # demo always recuts
        dt = time.perf_counter() - t0
        assert np.all(reb.lookup(hot[: 256]) >= 0)
        why = "threshold tripped" if tripped else "forced for the demo"
        print(f"  rebalance ({why}): imbalance {before:.2f} -> "
              f"{info['imbalance_after']:.2f}, moved {info['moved_keys']} "
              f"keys in {dt*1e3:.1f} ms; ShardSet v{reb.shard_set.version} "
              f"swapped atomically (lookups still oracle-exact)")
        for s in reb.metrics().shards:
            print(f"    shard {s.shard}: cut {s.boundary:.0f} (routes), "
                  f"snapshot starts {s.snapshot_first_key:.0f}, "
                  f"{s.n_keys} keys, epoch {s.epoch}")

    # --- the device-sharded serving plane: shard_map fan-out + delta publish
    if args.distributed:
        n_dev = len(jax.devices())
        dev_plan = plan(keys, FitSpec(error=args.error, device_count=n_dev,
                                      batch_sizes=(args.queries,),
                                      insert_rate=1000.0))
        # the exchange strategy is a cost-model choice, audited by explain()
        print("  " + next(line.strip() for line in
                          dev_plan.explain().splitlines()
                          if "device plane" in line))
        dsvc = open_index(keys, dev_plan)
        qd = np.asarray(q[: n_dev * 32], np.float64)
        got = dsvc.lookup(qd)
        want = np.searchsorted(keys.astype(np.float32), qd.astype(np.float32))
        assert np.array_equal(got, want)
        dsvc.insert(float(keys[0]) + 0.5)        # dirties exactly one shard
        dsvc.publish()
        dm = dsvc.metrics().device
        print(f"  device plane: {type(dsvc).__name__} over {dm.n_devices} "
              f"devices, exchange={dm.exchange}; lookups == oracle; "
              f"uploaded {dm.bytes_uploaded} B vs "
              f"{dm.bytes_full_equivalent} B full-equivalent "
              f"({dm.delta_publishes} delta / {dm.full_publishes} full)")
        # the seed-era kernels remain as thin wrappers over the same plane
        from repro.core.distributed import build_sharded_index, lookup_allgather
        mesh = jax.make_mesh((n_dev,), ("data",))
        si = build_sharded_index(keys, args.error, n_dev, mesh, "data")
        legacy = np.asarray(lookup_allgather(si, q[: n_dev * 32], mesh,
                                             "data"))
        print(f"  legacy distributed wrapper over {n_dev} devices OK "
              f"({np.mean(legacy == want)*100:.0f}% exact)")


if __name__ == "__main__":
    main()
