"""Async coalescing front door (``repro.index.pipeline``): concurrent
callers get bit-identical answers to the single-thread oracle, flushes fire
on threshold *and* deadline, a full queue backpressures, the maintenance
cadence publishes off the request path, shutdown drains in-flight futures,
and a maintenance crash is surfaced -- plus the satellite fixes: the locked
query counters under hammer and ``DispatchEngine.prewarm``.

Timing-sensitive assertions use generous margins (seconds, not the
microsecond knobs under test) so CI runners never flake on scheduling jitter.
"""
import threading
import time

import numpy as np
import pytest

import repro.index as ri
from repro.index.engine import _bucket_size
from repro.serve import (AsyncIndexService, FitSpec, IndexService,
                         PipelineClosed, PipelineOverloaded,
                         ShardedIndexService, open_pipeline)


def _keys(n=512, seed=0):
    rng = np.random.default_rng(seed)
    return np.sort(rng.choice(n * 8, size=n, replace=False)).astype(np.float64)


# ------------------------------------------------- concurrency == the oracle
@pytest.mark.parametrize("backend", ri.available_backends())
def test_concurrent_callers_match_single_thread_oracle(backend):
    """N threads of mixed lookup/search traffic through the coalescing queue
    == the same calls made single-threaded on the bare service, bit for bit,
    on every backend."""
    keys = _keys()
    svc = IndexService(keys, error=16, backend=backend, assume_sorted=True)
    n_threads, per_thread = 6, 12
    barrier = threading.Barrier(n_threads)
    failures: list = []

    # small queue_depth bounds the padded bucket set (pallas compiles a
    # kernel per shape, and interpret mode on CPU is slow per compile)
    with AsyncIndexService(svc, flush_threshold=16, max_wait_us=2_000.0,
                           queue_depth=32, prewarm=False) as pipe:
        def caller(tid):
            rng = np.random.default_rng(100 + tid)
            try:
                barrier.wait(30)
                for _ in range(per_thread):
                    size = int(rng.integers(1, 6))
                    hits = keys[rng.integers(0, keys.size, size)]
                    misses = rng.uniform(keys[0], keys[-1], size)
                    q = np.where(rng.random(size) < 0.7, hits, misses)
                    verb = rng.integers(0, 3)
                    if verb == 0:
                        got, want = pipe.lookup(q, 60.0), svc.lookup(q)
                    else:
                        side = "left" if verb == 1 else "right"
                        got = pipe.search(q, side, 60.0)
                        want = svc.search(q, side)
                    if not np.array_equal(got, want):
                        failures.append((tid, q, got, want))
            except BaseException as exc:  # pragma: no cover - surfaced below
                failures.append((tid, exc))

        threads = [threading.Thread(target=caller, args=(t,))
                   for t in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
        stats = pipe.pipeline_stats()
    assert not failures, failures[:3]
    assert stats["coalesced_queries"] > 0          # traffic actually coalesced
    assert stats["flushes"] >= 1


# -------------------------------------------------------------- flush paths
def test_deadline_flush_fires_with_partial_batch():
    svc = IndexService(_keys(), error=16, assume_sorted=True)
    with AsyncIndexService(svc, flush_threshold=10_000,
                           max_wait_us=50_000.0, prewarm=False) as pipe:
        q = _keys()[:3]
        t0 = time.perf_counter()
        got = pipe.lookup(q, timeout=30.0)          # can never hit threshold
        elapsed = time.perf_counter() - t0
        stats = pipe.pipeline_stats()
    np.testing.assert_array_equal(got, svc.lookup(q))
    assert stats["deadline_flushes"] >= 1
    assert stats["threshold_flushes"] == 0
    assert elapsed < 20.0                           # generous CI margin


def test_threshold_flush_and_inline_bypass():
    keys = _keys()
    svc = IndexService(keys, error=16, assume_sorted=True)
    with AsyncIndexService(svc, flush_threshold=8, max_wait_us=1e6,
                           prewarm=False) as pipe:
        # an over-threshold submission runs fused inline (already fast-tier)
        fut = pipe.lookup_async(keys[:32])
        assert fut.done()
        np.testing.assert_array_equal(fut.result(), svc.lookup(keys[:32]))
        # eight 1-query submissions trip the threshold without any deadline
        futs = [pipe.lookup_async(keys[i:i + 1]) for i in range(8)]
        for i, f in enumerate(futs):
            np.testing.assert_array_equal(f.result(30.0),
                                          svc.lookup(keys[i:i + 1]))
        stats = pipe.pipeline_stats()
    assert stats["inline_batches"] == 1
    assert stats["threshold_flushes"] >= 1


def test_shapes_and_empty_batches_preserved():
    keys = _keys()
    svc = IndexService(keys, error=16, assume_sorted=True)
    with AsyncIndexService(svc, flush_threshold=64, max_wait_us=500.0,
                           prewarm=False) as pipe:
        q2d = keys[:6].reshape(2, 3)
        got = pipe.lookup(q2d, timeout=30.0)
        assert got.shape == (2, 3)
        np.testing.assert_array_equal(got.ravel(), svc.lookup(keys[:6]))
        empty = pipe.lookup(np.empty(0), timeout=30.0)
        assert empty.shape == (0,) and empty.dtype == np.int64
        scalar = pipe.lookup(float(keys[5]), timeout=30.0)
        assert scalar.shape == () and scalar == 5


# ------------------------------------------------------------- backpressure
def test_full_queue_backpressures_then_drains_on_close():
    keys = _keys()
    svc = IndexService(keys, error=16, assume_sorted=True)
    # threshold never reached, deadline far away: the queue can only fill
    pipe = AsyncIndexService(svc, flush_threshold=128, queue_depth=128,
                             max_wait_us=10_000_000.0, prewarm=False)
    try:
        futs = [pipe.lookup_async(keys[4 * i:4 * i + 4]) for i in range(25)]
        with pytest.raises(PipelineOverloaded):
            pipe.lookup_async(keys[:32], timeout=0.2)   # 100 + 32 > 128
    finally:
        pipe.close()
    # close() drained the parked requests instead of abandoning them
    for i, f in enumerate(futs):
        np.testing.assert_array_equal(f.result(0),
                                      svc.lookup(keys[4 * i:4 * i + 4]))
    assert pipe.pipeline_stats()["drain_flushes"] >= 1


def test_close_drains_and_rejects_new_work():
    keys = _keys()
    svc = IndexService(keys, error=16, assume_sorted=True)
    pipe = AsyncIndexService(svc, flush_threshold=10_000,
                             max_wait_us=5_000_000.0, prewarm=False)
    futs = [pipe.lookup_async(keys[i:i + 2]) for i in range(6)]
    pipe.close()
    for i, f in enumerate(futs):
        np.testing.assert_array_equal(f.result(0), svc.lookup(keys[i:i + 2]))
    assert pipe.closed
    with pytest.raises(PipelineClosed):
        pipe.lookup_async(keys[:1])
    pipe.close()                                    # idempotent


def test_knob_validation():
    svc = IndexService(_keys(), error=16, assume_sorted=True)
    with pytest.raises(ValueError):
        AsyncIndexService(svc, flush_threshold=0, prewarm=False)
    with pytest.raises(ValueError):
        AsyncIndexService(svc, max_wait_us=0.0, prewarm=False)
    with pytest.raises(ValueError):
        AsyncIndexService(svc, flush_threshold=64, queue_depth=32,
                          prewarm=False)


# -------------------------------------------------------- maintenance cadence
@pytest.mark.slow
def test_cadence_publishes_dirty_shards_without_blocking_readers():
    keys = _keys(1024)
    svc = ShardedIndexService(keys, error=64, n_shards=2, buffer_size=16,
                              assume_sorted=True)
    new_key = float(keys[0]) + 0.5                  # lands in shard 0
    stop = threading.Event()
    reader_errors: list = []

    with AsyncIndexService(svc, flush_threshold=64, max_wait_us=500.0,
                           publish_interval_s=0.05, prewarm=False) as pipe:
        def reader():
            while not stop.is_set():
                if pipe.lookup(keys[:4], timeout=30.0)[0] != 0:
                    reader_errors.append("wrong rank")

        t = threading.Thread(target=reader)
        t.start()
        try:
            svc.insert(new_key)                     # dirty, not yet visible
            deadline = time.monotonic() + 20.0      # cadence is 0.05s
            # wait on the publish *counter*: the snapshot installs mid-
            # publish, before the maintenance thread's stats update lands
            stats = pipe.pipeline_stats()
            while time.monotonic() < deadline and stats["publishes"] < 1:
                time.sleep(0.01)
                stats = pipe.pipeline_stats()
            visible = pipe.lookup(np.array([new_key]), 30.0)[0] != -1
        finally:
            stop.set()
            t.join(30)
    assert visible, "maintenance cadence never published the dirty shard"
    assert not reader_errors
    assert stats["publishes"] >= 1
    assert stats["maintenance_ticks"] >= 1
    assert svc.pending_inserts == 0


def test_maintenance_crash_is_surfaced_to_callers(monkeypatch):
    svc = IndexService(_keys(), error=16, assume_sorted=True)

    def boom():
        raise RuntimeError("publish exploded")

    monkeypatch.setattr(svc, "publish", boom)
    pipe = AsyncIndexService(svc, publish_interval_s=0.02, prewarm=False)
    deadline = time.monotonic() + 20.0
    while time.monotonic() < deadline and not pipe.closed:
        time.sleep(0.01)
    assert pipe.closed
    with pytest.raises(PipelineClosed) as exc:
        pipe.lookup_async(np.array([1.0]))
    assert isinstance(exc.value.__cause__, RuntimeError)
    with pytest.raises(PipelineClosed):
        pipe.close()


# --------------------------------------------------------------- satellites
def test_query_counters_exact_under_thread_hammer():
    """The unlocked ``_query_counts`` increments lost updates under the async
    front door; the locked ``_count`` path must be exact."""
    keys = _keys(1024)
    svc = ShardedIndexService(keys, error=16, n_shards=2, assume_sorted=True)
    base = svc.service_stats()["query_counts"]
    n_threads, iters = 8, 100
    barrier = threading.Barrier(n_threads)

    def hammer():
        barrier.wait(30)
        for _ in range(iters):
            svc.lookup(keys[:3])
            svc.search(keys[:2])

    threads = [threading.Thread(target=hammer) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(120)
    counts = svc.service_stats()["query_counts"]
    assert counts["points"] - base["points"] == n_threads * iters * 3
    assert counts["searches"] - base["searches"] == n_threads * iters * 2


def test_dispatch_prewarm_builds_every_tier():
    keys = _keys()
    table = ri.SegmentTable.from_keys(keys, 16, assume_sorted=True)
    eng = ri.make_engine(table, "dispatch")
    assert not eng._engines                         # lazy until prewarmed
    eng.prewarm()
    built = set(eng._engines)
    assert len(built) >= 2                          # small + large at least
    # the warmed instances are the very ones dispatch routes to afterwards
    for size in (1, 10_000):
        assert eng.engine_for(size) in eng._engines.values()
    q = keys[:8]
    np.testing.assert_array_equal(eng.lookup(q),
                                  np.searchsorted(keys, q, side="left"))


def test_open_pipeline_takes_knobs_from_the_plan():
    keys = _keys(2048)
    spec = FitSpec(error=32)
    plan = ri.plan(keys, spec)
    assert plan.flush_threshold is not None and plan.max_wait_us is not None
    with open_pipeline(keys, spec, prewarm=False) as pipe:
        assert pipe.flush_threshold == plan.flush_threshold
        assert pipe.max_wait_us == plan.max_wait_us
        assert pipe.queue_depth == plan.queue_depth
        got = pipe.lookup(keys[:5], timeout=30.0)
        np.testing.assert_array_equal(got, np.arange(5))
        # explain() audits the pipeline knobs alongside the index knobs
        assert "async pipeline" in plan.explain()


def test_bucket_padding_is_pow2_and_bounded():
    assert _bucket_size(1) == 16
    assert _bucket_size(16) == 16
    assert _bucket_size(17) == 32
    assert _bucket_size(1000) == 1024


def test_service_stats_carries_pipeline_section():
    svc = IndexService(_keys(), error=16, assume_sorted=True)
    with AsyncIndexService(svc, flush_threshold=8, max_wait_us=500.0,
                           prewarm=False) as pipe:
        pipe.lookup(_keys()[:2], timeout=30.0)
        stats = pipe.service_stats()
    assert "pipeline" in stats and stats["pipeline"]["flushes"] >= 1
    assert stats["query_counts"]["points"] >= 2
