#!/usr/bin/env python3
"""Bring-up smoke test: the FITing-Tree serving path, compiled, on a TPU.

    python3 chip_smoke.py              # one chip: served path + device plane
    python3 chip_smoke.py --chips 4    # four chips: the device plane only,
                                       # under both collective exchanges

Phases, each timed, with the number of executables JAX compiled in it:

* ``data``: 2^26 keys of the paper's Weblogs shape (fixed seed), rounded to
  f32 -- the device backends' key contract -- and sorted; duplicates are
  allowed.  The oracle is ``np.searchsorted`` on that f32 column.
* ``served``: ``open_pipeline(keys, FitSpec(error=64, hardware="tpu",
  batch_sizes=...))`` -- the async front door over the planned (sharded)
  service, whose ``DispatchEngine`` routes each batch to the numpy,
  xla-bisect or pallas tier by size.  Requests of one key, of a medium
  batch from one shard's key range, and of a large batch spread over every
  shard; ``lookup`` and ``search`` on both sides through the pipeline,
  ``count`` / ``range`` through the service under it, then insert ->
  publish -> read back.  Each request reports the tiers that served it
  (the ``tier.*`` telemetry channels) and must reach its own tier.
* ``device``: ``open_index(keys, FitSpec(..., device_count=D))`` -- the
  ``DeviceShardedService`` over D chips; the same verbs and the same
  oracle, under ``allgather`` and (with D > 1) ``a2a``.

Every answer must equal the oracle.  The last line of standard output is
``{"ok": true, "device": {...}}`` only when JAX runs on a TPU and every
phase passed; otherwise the script exits non-zero without it.
"""
from __future__ import annotations

import argparse
import functools
import json
import pathlib
import sys
import threading
import time
import traceback

import numpy as np

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

from repro.compile_cache import enable_compile_cache  # noqa: E402

N_KEYS = 1 << 26
ERROR = 64
SEED = 0
MEDIUM = 1024          # a medium-tier request (between the tier crossings)
DEVICE_BATCH = 4096    # device-plane batch
TIERS = ("small", "medium", "large")
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


# ------------------------------------------------------------------ counters
class CompileCounter:
    """Executables JAX compiled (or loaded from the persistent cache), with
    their seconds, from JAX's own compile events."""

    def __init__(self):
        import jax
        self.count = 0
        self.seconds = 0.0
        self._lock = threading.Lock()   # shards compile on several threads
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event == COMPILE_EVENT:
            with self._lock:
                self.count += 1
                self.seconds += duration


@functools.lru_cache(maxsize=1)
def compile_counter() -> CompileCounter:
    return CompileCounter()


def run_phase(name: str, fn, *args, **kwargs) -> bool:
    """Run one phase; print its wall and compile time.  False on failure."""
    counter = compile_counter()
    c0, s0 = counter.count, counter.seconds
    t0 = time.perf_counter()
    try:
        fn(*args, **kwargs)
        ok = True
    except Exception:
        traceback.print_exc()
        ok = False
    print(f"[{name}] {'passed' if ok else 'FAILED'}: "
          f"wall_s={time.perf_counter() - t0:.3f} "
          f"compiles={counter.count - c0} "
          f"compile_s={counter.seconds - s0:.3f}", flush=True)
    return ok


# ---------------------------------------------------------------------- data
@functools.lru_cache(maxsize=1)
def dataset(n_keys: int, seed: int = SEED) -> np.ndarray:
    """Sorted f32-rounded Weblogs-shaped keys (as f64 values)."""
    from repro.core.datasets import weblogs_like
    keys = weblogs_like(n_keys, seed=seed).astype(np.float32)
    keys.sort()
    return keys.astype(np.float64)


class Oracle:
    """``np.searchsorted`` over the f32 key column: the answer every verb
    must reproduce."""

    def __init__(self, keys: np.ndarray):
        self.k32 = np.sort(np.asarray(keys, np.float32))

    def search(self, q, side: str) -> np.ndarray:
        return np.searchsorted(self.k32, np.asarray(q, np.float32), side)

    def lookup(self, q) -> np.ndarray:
        left, right = self.search(q, "left"), self.search(q, "right")
        return np.where(right > left, left, -1)

    def count(self, lo, hi) -> np.ndarray:
        return np.maximum(self.search(hi, "right") - self.search(lo, "left"),
                          0)


def check(what: str, got, want) -> None:
    got, want = np.asarray(got), np.asarray(want)
    if got.shape != want.shape or not np.array_equal(got, want):
        bad = (int(np.sum(got != want)) if got.shape == want.shape
               else "shape")
        raise AssertionError(f"{what}: {bad} answers differ from the oracle")


def probes(keys: np.ndarray, idx: np.ndarray, rng) -> np.ndarray:
    """Queries around ``keys[idx]``: the keys themselves and their f32
    neighbours above (mostly absent), shuffled; every value is f32-exact."""
    k32 = keys[idx].astype(np.float32)
    above = np.nextafter(k32, np.float32(np.inf))
    q = np.where(rng.random(idx.size) < 0.5, k32, above).astype(np.float64)
    rng.shuffle(q)
    return q


def fresh_keys(keys: np.ndarray, idx: np.ndarray, count: int = 8
               ) -> np.ndarray:
    """Up to ``count`` f32-exact values not in ``keys``, each just above one
    of ``keys[idx]`` (dense columns leave few such gaps: pass many idx)."""
    k32 = np.asarray(keys, np.float32)
    cand = np.unique(np.nextafter(k32[idx], np.float32(np.inf)))
    new = cand[~np.isin(cand, k32)]
    return new[np.linspace(0, new.size - 1, min(count, new.size)).astype(
        int)].astype(np.float64) if new.size else new.astype(np.float64)


def data_phase(n_keys: int, seed: int = SEED) -> None:
    keys = dataset(n_keys, seed)
    k32 = keys.astype(np.float32)
    print(f"  data: {keys.size} Weblogs-shaped keys (seed {seed}), "
          f"{np.unique(k32).size} distinct in f32; f32 key column "
          f"{k32.nbytes} bytes", flush=True)


# -------------------------------------------------------------------- served
def _tier_counts(monitor) -> dict[str, int]:
    return {t: monitor.count("tier." + t) for t in TIERS}


def served_phase(n_keys: int, seed: int = SEED, *,
                 n_keys_hint: int | None = None) -> None:
    """The front door: pipeline -> planned service -> DispatchEngine tiers.
    ``n_keys_hint`` lets a small rehearsal plan the shard count of a larger
    deployment."""
    from repro.index import FitSpec, Monitor, open_pipeline
    from repro.index.engine import _bucket_size

    keys = dataset(n_keys, seed)
    oracle = Oracle(keys)
    rng = np.random.default_rng(seed + 1)
    monitor = Monitor()
    spec = FitSpec(error=ERROR, hardware="tpu",
                   batch_sizes=(1, MEDIUM, 1 << 17), n_keys_hint=n_keys_hint)
    with open_pipeline(keys, spec, monitor=monitor,
                       assume_sorted=True) as pipe:
        svc = pipe.service
        plan = svc.plan
        handles = getattr(svc, "handles", None) or (svc.handle,)
        engine = handles[0].engine(plan.backend)
        backends = engine.tiers
        bounds = np.asarray(getattr(svc, "boundaries", keys[:1]), np.float64)
        cuts = np.searchsorted(keys, bounds, "left")
        cuts = np.append(cuts, keys.size)
        table_bytes = sum(h.current().n_keys * 4
                          + h.current().table.n_segments * 16
                          for h in handles)
        print(f"  served: {type(svc).__name__}, {len(handles)} shard(s), "
              f"error={plan.error}, tiers: {backends['small']} <= "
              f"{plan.small_max} < {backends['medium']} < {plan.large_min} "
              f"<= {backends['large']}; device tables {table_bytes} bytes",
              flush=True)

        # one key; a medium batch from the middle shard's key range; a large
        # batch spread so every shard's share reaches the large tier
        mid = len(handles) // 2
        lo, hi = int(cuts[mid]), int(cuts[mid + 1])
        start = lo + max(0, (hi - lo - 2 * MEDIUM) // 2)
        medium = probes(keys, np.arange(start, min(start + MEDIUM, hi)), rng)
        per_shard = _bucket_size(len(handles) * plan.large_min) // len(handles)
        large = np.concatenate([
            probes(keys, rng.integers(cuts[d], cuts[d + 1], per_shard), rng)
            for d in range(len(handles))])
        requests = (("one key", keys[[keys.size // 3]], "small"),
                    ("medium", medium, "medium"),
                    ("large", large, "large"))
        for name, q, tier in requests:
            before = _tier_counts(monitor)
            check(f"{name} lookup", pipe.lookup(q), oracle.lookup(q))
            for side in ("left", "right"):
                check(f"{name} search {side}", pipe.search(q, side),
                      oracle.search(q, side))
            after = _tier_counts(monitor)
            served = {t: after[t] - before[t] for t in TIERS
                      if after[t] > before[t]}
            print(f"  request {name!r}: {q.size} queries served by "
                  + ", ".join(f"{t}={backends[t]} x{n}"
                              for t, n in served.items())
                  + "; lookup, search left/right == oracle", flush=True)
            if tier not in served:
                raise AssertionError(f"request {name!r} never reached the "
                                     f"{tier} tier ({backends[tier]})")

        # count / range through the service under the front door
        i = np.sort(rng.integers(0, keys.size, (2, MEDIUM)), axis=0)
        lo_q, hi_q = keys[i[0]], keys[i[1]]
        check("count", svc.count(lo_q, hi_q), oracle.count(lo_q, hi_q))
        a = int(cuts[mid]) + 10
        span = svc.range(keys[a], keys[a + 500])
        want_lo = int(oracle.search([keys[a]], "left")[0])
        want_hi = int(oracle.search([keys[a + 500]], "right")[0])
        check("range ranks", (span.lo_rank, span.hi_rank), (want_lo, want_hi))
        check("range keys", span.keys, oracle.k32[want_lo:want_hi])
        print(f"  count x{MEDIUM} spans and range over {want_hi - want_lo} "
              f"keys == oracle", flush=True)

        # insert -> publish -> read back (the new epoch serves every tier)
        new = fresh_keys(keys, np.arange(start, min(start + 4 * MEDIUM, hi)))
        for k in new:
            svc.insert(float(k))
        pipe.publish()
        oracle = Oracle(np.concatenate([keys, new]))
        got = pipe.lookup(new)
        check("inserted keys", got, oracle.lookup(new))
        if np.any(got < 0):
            raise AssertionError("an inserted key is missing after publish")
        check("medium after publish", pipe.lookup(medium),
              oracle.lookup(medium))
        print(f"  inserted {new.size} keys, published, read back == oracle",
              flush=True)


# -------------------------------------------------------------- device plane
def device_plane_phase(n_keys: int, seed: int = SEED, *,
                       device_count: int = 1,
                       exchanges: tuple[str, ...] = ("allgather",)) -> None:
    """``open_index`` with ``device_count``: the DeviceShardedService."""
    from repro.index import FitSpec, open_index

    keys = dataset(n_keys, seed)
    oracle = Oracle(keys)
    rng = np.random.default_rng(seed + 2)
    spec = FitSpec(error=ERROR, hardware="tpu", device_count=device_count,
                   batch_sizes=(1, DEVICE_BATCH))
    svc = open_index(keys, spec, assume_sorted=True)
    ds = svc.device_set
    print(f"  device plane: {svc.n_devices} device(s), "
          f"{ds.row_bytes()} bytes per device row "
          f"({ds.n_keys} keys, {ds.n_segments} segments)", flush=True)
    q = probes(keys, rng.integers(0, keys.size, DEVICE_BATCH), rng)
    i = np.sort(rng.integers(0, keys.size, (2, 256)), axis=0)
    lo_q, hi_q = keys[i[0]], keys[i[1]]
    for exchange in exchanges:
        if svc.exchange != exchange:
            svc.apply_plan(svc.plan.replace(exchange=exchange))
        for side in ("left", "right"):
            check(f"{exchange} search {side}", svc.search(q, side),
                  oracle.search(q, side))
        check(f"{exchange} lookup", svc.lookup(q), oracle.lookup(q))
        check(f"{exchange} one-key lookup", svc.lookup(q[:1]),
              oracle.lookup(q[:1]))
        check(f"{exchange} count", svc.count(lo_q, hi_q),
              oracle.count(lo_q, hi_q))
        a = keys.size // 2
        span = svc.range(keys[a], keys[a + 500])
        want = (int(oracle.search([keys[a]], "left")[0]),
                int(oracle.search([keys[a + 500]], "right")[0]))
        check(f"{exchange} range", (span.lo_rank, span.hi_rank), want)
        check(f"{exchange} range keys", span.keys,
              oracle.k32[want[0]:want[1]])
        print(f"  exchange={exchange}: search left/right, lookup, count, "
              f"range == oracle", flush=True)
    new = fresh_keys(keys, rng.integers(0, keys.size, 4 * MEDIUM))
    for k in new:
        svc.insert(float(k))
    svc.publish()
    oracle = Oracle(np.concatenate([keys, new]))
    got = svc.lookup(new)
    check("inserted keys", got, oracle.lookup(new))
    if np.any(got < 0):
        raise AssertionError("an inserted key is missing after publish")
    check("search after publish", svc.search(q, "left"),
          oracle.search(q, "left"))
    m = svc.metrics().device
    print(f"  inserted {new.size} keys, published ({m.delta_publishes} delta,"
          f" {m.full_publishes} full uploads), read back == oracle",
          flush=True)


# ---------------------------------------------------------------------- main
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the device plane across four chips")
    ap.add_argument("--keys", type=int, default=N_KEYS)
    ap.add_argument("--seed", type=int, default=SEED)
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    dev = devices[0]
    print(f"platform={dev.platform} device_kind={dev.device_kind} "
          f"device_count={len(devices)}", flush=True)
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU ({dev.platform!r} backend); this smoke "
              "test runs only on the chip", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devices)} "
              "device(s)", file=sys.stderr)
        return 2
    print(f"compile cache: {enable_compile_cache()}", flush=True)

    ok = run_phase("data", data_phase, args.keys, args.seed)
    if ok and args.chips == 1:
        ok = run_phase("served", served_phase, args.keys, args.seed) & \
            run_phase("device", device_plane_phase, args.keys, args.seed)
    elif ok:
        ok = run_phase("device", device_plane_phase, args.keys, args.seed,
                       device_count=args.chips,
                       exchanges=("allgather", "a2a"))
    for d in devices[:args.chips]:
        stats = d.memory_stats() or {}
        print(f"  {d}: peak_bytes_in_use={stats.get('peak_bytes_in_use')}")
    if not ok:
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
