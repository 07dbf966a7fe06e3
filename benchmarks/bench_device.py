"""Device plane: lookup qps vs device count, delta vs full republish.

Measures the two claims ``repro.index.device`` makes, on CPU with forced
host devices (``XLA_FLAGS=--xla_force_host_platform_device_count=D``, the
same simulation the tests use):

(a) **collective search scales with the mesh.**  The bucketed all_to_all
    exchange gives each device ~``slack * Q / D`` queries of local work, so
    the per-device critical path -- the wall clock of a real D-device mesh
    -- shrinks as devices are added.  CI hosts are time-sliced (the forced
    host devices of one CPU run sequentially), so the measured host wall
    clock is the *sum* of per-device work; ``mesh_qps = Q * D / host_wall``
    recovers the per-device critical path a concurrent mesh would run.
    Both numbers are reported; the monotonicity assert is on ``mesh_qps``
    at a fixed large batch, same kernel at every D (D=1 pays the same
    bucketing machinery, so the curve isolates the fan-out, not the
    presence of collectives).

(b) **delta publish beats full republish on a single-dirty-shard stream.**
    An insert stream routed to ONE shard publishes by re-shipping one
    padded row; the bench asserts the uploaded bytes are < 1/4 of the
    full-republish equivalent (D=8 ships 1 row instead of 8) and compares
    wall latency against a full re-pack-and-upload of the same manifest.

Every device-plane verb is also asserted bit-identical to the numpy
``searchsorted`` oracle (f32 key contract) under BOTH exchange strategies
before any number is reported.

The measurement runs in a subprocess (``run()`` re-invokes this module with
the forced-device-count XLA flag), so importing jax in the parent process
never pins the device topology for other benches.
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import subprocess
import sys
import tempfile
import time

from .common import emit, write_json

N = 500_000
NQ = 131_072
ERROR = 256
DEVICE_COUNTS = (1, 2, 4, 8)
SLACK = 1.5
INSERTS = 64


def _inner(n: int, n_queries: int, error: int,
           device_counts: tuple[int, ...], slack: float,
           inserts: int) -> dict:
    """Runs under the forced-device-count XLA flag (see ``run``)."""
    import jax
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.index.device import DeviceShardedService, sharded_search_a2a

    d_max = max(device_counts)
    assert jax.device_count() >= d_max, (jax.device_count(), d_max)
    assert n_queries % d_max == 0, "batch must tile the largest mesh"
    rng = np.random.default_rng(11)
    keys = np.sort(rng.integers(0, 1 << 23, n).astype(np.float64))
    k32 = keys.astype(np.float32)
    q = keys[rng.integers(0, n, n_queries)]
    q32 = q.astype(np.float32)

    def timeit(fn, *args, repeats=5, warmup=2):
        for _ in range(warmup):
            jax.block_until_ready(fn(*args))
        ts = []
        for _ in range(repeats):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*args))
            ts.append(time.perf_counter() - t0)
        ts.sort()
        return ts[len(ts) // 2]

    # --- verb bit-identity vs the searchsorted oracle, both strategies ----
    left = np.searchsorted(k32, q32, "left")
    right = np.searchsorted(k32, q32, "right")
    for xchg in ("allgather", "a2a"):
        svc = DeviceShardedService(keys, error=error, device_count=d_max,
                                   exchange=xchg, assume_sorted=True)
        np.testing.assert_array_equal(svc.search(q, "left"), left, err_msg=xchg)
        np.testing.assert_array_equal(svc.search(q, "right"), right,
                                      err_msg=xchg)
        np.testing.assert_array_equal(svc.lookup(q),
                                      np.where(right > left, left, -1))
        pt = svc.point(q)
        np.testing.assert_array_equal(pt.found, right > left)
        np.testing.assert_array_equal(
            svc.predecessor(q).rank, np.where(right >= 1, right - 1, -1))
        np.testing.assert_array_equal(
            svc.successor(q).rank, np.where(left < n, left, -1))
        np.testing.assert_array_equal(
            svc.count(q - 2.0, q + 2.0),
            np.maximum(np.searchsorted(k32, (q + 2.0).astype(np.float32),
                                       "right")
                       - np.searchsorted(k32, (q - 2.0).astype(np.float32),
                                         "left"), 0))

    # --- (a) qps vs device count: same a2a kernel at every D --------------
    curve = []
    for d in device_counts:
        svc = DeviceShardedService(keys, error=error, device_count=d,
                                   exchange="a2a", slack=slack,
                                   assume_sorted=True)
        ds = svc.device_set
        mesh = Mesh(np.asarray(jax.devices()[:d]), ("data",))
        q_dev = jax.device_put(q32, NamedSharding(mesh, P("data")))

        def fn(ss, sl, ba, se, ke, nl, of, bo, qq, mesh=mesh):
            return sharded_search_a2a(ss, sl, ba, se, ke, nl, of, bo, qq,
                                      mesh=mesh, axis="data", error=error,
                                      side="left", slack=slack)[0]

        jfn = jax.jit(fn)
        wall = timeit(jfn, ds.d_seg_start, ds.d_slope, ds.d_base,
                      ds.d_seg_end, ds.d_keys, ds.d_n_local, ds.d_offsets,
                      ds.d_boundaries, q_dev)
        # sanity: the timed kernel answers exactly like the oracle
        got = np.asarray(jfn(ds.d_seg_start, ds.d_slope, ds.d_base,
                             ds.d_seg_end, ds.d_keys, ds.d_n_local,
                             ds.d_offsets, ds.d_boundaries, q_dev))
        np.testing.assert_array_equal(got, left)
        curve.append({"n_devices": d, "host_wall_ms": wall * 1e3,
                      "mesh_qps": n_queries * d / wall})
    for a, b in zip(curve, curve[1:]):
        assert b["mesh_qps"] > a["mesh_qps"], \
            (f"mesh qps must increase with device count: "
             f"{a['n_devices']}dev {a['mesh_qps']:.0f} -> "
             f"{b['n_devices']}dev {b['mesh_qps']:.0f}")

    # --- (b) delta vs full republish on a single-dirty-shard stream -------
    svc = DeviceShardedService(keys, error=error, device_count=d_max,
                               buffer_size=max(2, error // 4),
                               assume_sorted=True)
    lo = float(svc.boundaries[0])
    for i in range(inserts):            # every insert routes to shard 0
        svc.insert(lo + 0.25 + i * 1e-6)
    before = svc.metrics().device
    t0 = time.perf_counter()
    svc.publish()
    delta_ms = (time.perf_counter() - t0) * 1e3
    after = svc.metrics().device
    assert after.delta_publishes == before.delta_publishes + 1
    delta_bytes = after.bytes_uploaded - before.bytes_uploaded
    full_bytes = after.bytes_full_equivalent - before.bytes_full_equivalent
    assert delta_bytes * 4 < full_bytes, (delta_bytes, full_bytes)
    # full-republish latency: re-pack + upload the whole manifest (the
    # transfer the delta path avoids; private by design -- the service
    # never takes this path for a clean-boundary publish)
    t0 = time.perf_counter()
    jax.block_until_ready(svc._full_set(svc.device_set.version).d_keys)
    full_ms = (time.perf_counter() - t0) * 1e3

    return {
        "config": {"n": n, "n_queries": n_queries, "error": error,
                   "device_counts": list(device_counts), "slack": slack,
                   "inserts": inserts},
        "verbs_bit_identical": True,
        "qps_curve": curve,
        "publish": {"delta_bytes": delta_bytes, "full_bytes": full_bytes,
                    "bytes_ratio": delta_bytes / full_bytes,
                    "delta_ms": delta_ms, "full_ms": full_ms},
    }


def run(n: int = N, n_queries: int = NQ, error: int = ERROR,
        device_counts: tuple[int, ...] = DEVICE_COUNTS,
        slack: float = SLACK, inserts: int = INSERTS):
    """Spawn the measurement under the forced-device-count XLA flag and
    collect/emit its results (the smoke-wired entry point)."""
    params = dict(n=n, n_queries=n_queries, error=error,
                  device_counts=tuple(device_counts), slack=slack,
                  inserts=inserts)
    env = dict(os.environ)
    # a forced-host-device simulation by design: the child never reaches for
    # an accelerator (the parent may already hold it -- one process per chip)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count="
                        f"{max(device_counts)}")
    env["REPRO_SANITIZE"] = "0"          # measuring, not debugging
    root = pathlib.Path(__file__).parents[1]
    env["PYTHONPATH"] = os.pathsep.join(
        [str(root / "src")] + env.get("PYTHONPATH", "").split(os.pathsep))
    with tempfile.TemporaryDirectory() as tmp:
        out = pathlib.Path(tmp) / "device.json"
        res = subprocess.run(
            [sys.executable, "-m", "benchmarks.bench_device", "--inner",
             "--params", json.dumps(params), "--out", str(out)],
            cwd=root, env=env, capture_output=True, text=True, timeout=1800)
        assert res.returncode == 0, res.stdout + "\n" + res.stderr
        results = json.loads(out.read_text())

    for row in results["qps_curve"]:
        emit("device", f"mesh_qps_{row['n_devices']}dev", row["mesh_qps"],
             f"host_wall_ms={row['host_wall_ms']:.1f}")
    pub = results["publish"]
    emit("device", "delta_vs_full_bytes_ratio", pub["bytes_ratio"],
         f"{pub['delta_bytes']}B_vs_{pub['full_bytes']}B")
    emit("device", "delta_publish_ms", pub["delta_ms"],
         f"full_republish_ms={pub['full_ms']:.1f}")
    write_json("bench_device", results)
    return results


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--inner", action="store_true")
    ap.add_argument("--params", default="{}")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    if args.inner:
        params = json.loads(args.params)
        params["device_counts"] = tuple(params["device_counts"])
        results = _inner(**params)
        pathlib.Path(args.out).write_text(json.dumps(results))
    else:
        run()


if __name__ == "__main__":
    main()
