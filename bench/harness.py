"""One run of one cell: keys -> service -> warm-up -> window -> check.

``run_cell`` is the whole run as a function, so the tests can drive it on
the CPU at a small size (``require_tpu=False``); ``bench/run.py`` is the
command.  Progress goes to standard output as ``[bench] ...`` lines; the
last line of standard output is the JSON result, and the numbers compared
with the reference are the last lines of standard error.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import os
import shutil
import sys
import tempfile
import time
from concurrent.futures import ThreadPoolExecutor
from types import SimpleNamespace

import numpy as np

from bench import driver, keygen, manifest, oracle, xplane
from bench.compiles import CompileCounter

WARM_WORKERS = 4          # shards compiling side by side in the warm-up
SHARE_DRAWS = 1 << 20     # draws that estimate each shard's share of keys
SHARE_SD = 5.0            # warm per-shard sizes within 5 sd of their mean
QUIET_S = 2.0             # traffic warm-up ends after this long with no build
MAX_TRAFFIC_WARM_S = 120.0
DRAIN_S = 60.0            # how long open requests may take past the close
TRACE_S = 3.0             # traced stretch at the start of a --trace 1 window
MONITOR_ROWS = 1 << 20    # telemetry ring rows per channel (traced runs)
CACHE_DIR = manifest.ROOT / ".jax_cache"
VERBS = ("lookup",)        # the traffic verbs the harness drives


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def log(msg: str) -> None:
    print(f"[bench] {msg}", flush=True)


def enable_compile_cache() -> str:
    """JAX's persistent cache at a fixed place in the checkout (or where
    ``JAX_COMPILATION_CACHE_DIR`` says), keeping every executable."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def check_device(chips: int, require_tpu: bool):
    import jax

    devices = jax.devices()
    dev = devices[0]
    log(f"platform={dev.platform} device_kind={dev.device_kind} "
        f"device_count={len(devices)}")
    if require_tpu and dev.platform != "tpu":
        raise NoChip(f"no TPU: JAX runs on {dev.platform!r}")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chip(s), JAX has "
                     f"{len(devices)}")
    return dev, devices


def make_keys(cfg: dict, n_keys: int) -> np.ndarray:
    """The configuration's sorted keys, as f64 values of its key type."""
    gen = manifest.dataset(cfg["generator"])
    raw = gen.generate(n_keys, cfg["data_seed"],
                       **cfg.get("generator_params", {}))
    return keygen.to_key_column(raw, cfg["key_dtype"])


CONTROL_LADDER = ("float64", "float32", "bfloat16")


def narrower_dtype(key_dtype: str, keys: np.ndarray) -> str:
    """The control's key type: the first type below ``key_dtype`` on
    ``CONTROL_LADDER`` that rounds some key (a type that holds every key
    exactly would be the reference again, not a control)."""
    import ml_dtypes

    if key_dtype not in CONTROL_LADDER[:-1]:
        raise ValueError(f"no control type below key_dtype {key_dtype!r}")
    for name in CONTROL_LADDER[CONTROL_LADDER.index(key_dtype) + 1:]:
        dt = np.dtype(getattr(ml_dtypes, name, name))
        if np.any(keys.astype(dt).astype(np.float64) != keys):
            return name
    raise ValueError("every narrower type holds these keys exactly")


# ------------------------------------------------------------------ warm-up
def shard_cuts(service, keys: np.ndarray) -> np.ndarray:
    """Index of each shard's first key (one shard: ``[0]``)."""
    bounds = getattr(service, "boundaries", None)
    if bounds is None:
        return np.zeros(1, np.int64)
    return np.searchsorted(keys, np.asarray(bounds, np.float64), "left")


def flush_sizes(pipe, clients: int, request_keys: int) -> list[int]:
    """Fused batch sizes the pipeline can form from whole requests: one
    request at a time where a request alone reaches the flush threshold
    (it then runs inline), else 1 .. (what the queue holds) requests."""
    if request_keys >= pipe.flush_threshold:
        return [request_keys]
    most = max(1, min(clients, pipe.queue_depth // request_keys))
    return [k * request_keys for k in range(1, most + 1)]


def warm_sizes(share: float, fused: list[int], crossings: list[int]
               ) -> list[int]:
    """Per-shard batch sizes to compile: the ends of the range a shard's
    count takes over the fused sizes (within ``SHARE_SD`` sd), every power
    of two and one past it inside it, and the tier crossings inside it."""
    if share <= 0:
        return []
    lo = min(f * share - SHARE_SD * math.sqrt(f * share * (1 - share))
             for f in fused)
    hi = max(f * share + SHARE_SD * math.sqrt(f * share * (1 - share))
             for f in fused)
    lo, hi = max(1, math.floor(lo)), max(1, math.ceil(hi))
    sizes = {lo, hi}
    for k in range(hi.bit_length() + 1):
        sizes |= {s for s in (1 << k, (1 << k) + 1) if lo <= s <= hi}
    sizes |= {c for c in crossings if lo <= c <= hi}
    return sorted(sizes)


def warm_up(pipe, keys: np.ndarray, sampler, seed: int, mix: dict) -> int:
    """Build every shard's engines and compile each batch shape the cell's
    traffic can reach, on a few threads.  Returns the calls made."""
    svc = pipe.service
    cuts = shard_cuts(svc, keys)
    ends = np.append(cuts[1:], keys.size)
    idx = sampler.draw(np.random.default_rng([seed, SHARE_DRAWS]),
                       SHARE_DRAWS)
    shard = np.searchsorted(cuts, idx, "right") - 1
    shares = np.bincount(shard, minlength=cuts.size) / idx.size
    plan = svc.plan
    crossings = [plan.small_max, plan.small_max + 1, plan.large_min - 1,
                 plan.large_min]
    fused = flush_sizes(pipe, mix["clients"], mix["request_keys"])
    calls = [(keys[np.resize(np.arange(cuts[d], ends[d]), s)])
             for d, p in enumerate(shares)
             for s in warm_sizes(float(p), fused, crossings)]
    calls.sort(key=len, reverse=True)      # the big compiles first
    with ThreadPoolExecutor(WARM_WORKERS, "bench-warm") as pool:
        for done in [pool.submit(svc.lookup, q) for q in calls]:
            done.result()
    return len(calls)


def device_bytes(dev) -> int:
    """Bytes of every live JAX array on ``dev``."""
    import jax

    return sum(a.nbytes for a in jax.live_arrays() if dev in a.devices())


# ------------------------------------------------------------------ targets
class Program:
    """The system under test: ``open_pipeline`` over the keys."""

    def __init__(self, keys: np.ndarray, cfg: dict, monitor,
                 n_keys_hint: int | None):
        from repro.index import FitSpec, open_pipeline

        spec = FitSpec(error=cfg["error"], hardware="tpu",
                       n_keys_hint=n_keys_hint)
        kwargs = {} if monitor is None else {"monitor": monitor}
        self.pipe = open_pipeline(keys, spec, assume_sorted=True,
                                  prewarm=False, **kwargs)
        p = self.pipe.service.plan
        log(f"service: {type(self.pipe.service).__name__}, "
            f"{p.n_shards} shard(s), error={p.error} "
            f"(buffer {p.buffer_size}), backend={p.backend}, tiers: "
            f"<= {p.small_max} < {p.large_min} <=; flush threshold "
            f"{self.pipe.flush_threshold}, queue depth "
            f"{self.pipe.queue_depth}, max wait {self.pipe.max_wait_us} us")
        self.call = self.pipe.lookup

    def warm(self, keys, sampler, seed, mix) -> int:
        return warm_up(self.pipe, keys, sampler, seed, mix)

    def close(self) -> None:
        self.pipe.close()
        self.pipe = self.call = None


class LowPrecisionReference:
    """The control: the reference in the program's place, on the device,
    over the key column rounded to a narrower type (``narrower_dtype``):
    ``searchsorted`` left, -1 where the key is absent."""

    def __init__(self, keys: np.ndarray, dtype: str):
        import jax
        import jax.numpy as jnp

        if dtype not in CONTROL_LADDER[1:]:
            raise ValueError(f"the control runs in one of "
                             f"{CONTROL_LADDER[1:]}, not {dtype!r}")
        self.dtype = jnp.dtype(dtype)
        self.keys = jax.device_put(np.asarray(keys, np.float32)
                                   ).astype(self.dtype)

        @jax.jit
        def lookup(k, q):
            q = q.astype(k.dtype)
            r = jnp.searchsorted(k, q, side="left")
            hit = k[jnp.minimum(r, k.shape[0] - 1)] == q
            return jnp.where(hit, r, -1)

        self._lookup = lookup

    def call(self, q: np.ndarray) -> np.ndarray:
        return np.asarray(self._lookup(self.keys, np.asarray(q, np.float32)))

    def warm(self, keys, sampler, seed, mix) -> int:
        self.call(keys[:mix["request_keys"]])
        return 1

    def close(self) -> None:
        self.keys = self._lookup = None


# ------------------------------------------------------------------ the run
def run_cell(workload: str | dict, seed: int, seconds: float, trace: bool,
             *, require_tpu: bool = True, t_start_ns: int | None = None,
             control: str | None = None, witness: bool = False,
             overrides: dict | None = None) -> dict:
    """One run of one cell: a name in ``BENCHMARK.json``, or a cell's
    entry (``name``, ``config``, ``traffic``, ``chips``) for a
    configuration the manifest leaves out.  ``control`` puts the
    reference in a narrower key type in the program's place (a type name,
    or ``"narrower"`` for ``narrower_dtype``); ``witness`` also answers
    the window's queries with the program's float64 numpy tier and counts
    where it differs from the reference; ``overrides`` shrink or grow the
    configuration and the mix: ``n_keys``, ``n_keys_hint``, ``clients``,
    ``request_keys``."""
    t0 = time.perf_counter_ns() if t_start_ns is None else t_start_ns
    over = dict(overrides or {})
    cell = (manifest.workload(workload) if isinstance(workload, str)
            else workload)
    workload = cell["name"]
    cfg = manifest.config_file(cell["config"])
    mix = manifest.traffic(cell["traffic"])
    if mix["verb"] not in VERBS:
        raise ValueError(f"traffic verb {mix['verb']!r}: the harness "
                         f"drives only {VERBS}")
    for k in ("clients", "request_keys"):
        mix[k] = over.get(k, mix[k])
    n_keys = over.get("n_keys", cfg["n_keys"])

    dev, devices = check_device(cell["chips"], require_tpu)
    peaks = manifest.peaks(dev.device_kind) if require_tpu else None
    if require_tpu:
        log(f"compile cache: {enable_compile_cache()}")
    counter = CompileCounter()

    t = time.perf_counter()
    keys = make_keys(cfg, n_keys)
    column = keys.astype(cfg["key_dtype"], copy=False)  # the reference's
    sampler = manifest.distribution(mix["distribution"]).make(
        keys.size, mix["params"])
    log(f"data: {keys.size} {cfg['generator']} keys (data seed "
        f"{cfg['data_seed']}), {time.perf_counter() - t:.3f} s")

    monitor = None
    if trace and control is None:
        from repro.index import Monitor
        monitor = Monitor(capacity=MONITOR_ROWS)
    t = time.perf_counter()
    if control is None:
        target = Program(keys, cfg, monitor,
                         over.get("n_keys_hint", cfg["n_keys"]))
    else:
        if control == "narrower":
            control = narrower_dtype(cfg["key_dtype"], keys)
        target = LowPrecisionReference(keys, control)
        log(f"control: the reference over {control} keys")
    log(f"build: {time.perf_counter() - t:.3f} s")

    t = time.perf_counter()
    c0 = counter.snapshot()
    calls = target.warm(keys, sampler, seed, mix)
    c1 = counter.snapshot()
    log(f"warm-up: {calls} calls, {c1[0] - c0[0]} executables "
        f"({c1[2] - c0[2]} from the cache), {c1[1] - c0[1]:.3f} s of "
        f"compile, {time.perf_counter() - t:.3f} s")
    dev_bytes = device_bytes(dev)

    gc_pauses = driver.GcPauses()
    loop = driver.ClosedLoop(target.call, keys, sampler, seed,
                             clients=mix["clients"],
                             request_keys=mix["request_keys"],
                             annotate=trace)
    t = time.perf_counter()
    started = time.perf_counter_ns()
    loop.start()
    while True:        # traffic until QUIET_S passes with no build
        time.sleep(0.1)
        now = time.perf_counter_ns()
        quiet = (now - max(counter.last_ns, started)) * 1e-9 >= QUIET_S
        if quiet or (now - started) * 1e-9 >= MAX_TRAFFIC_WARM_S:
            break
    t_open = time.perf_counter_ns()
    builds_open = counter.snapshot()
    log(f"traffic warm-up: {time.perf_counter() - t:.3f} s, "
        f"{len(loop.log)} requests; {builds_open[0]} executables in all")
    if monitor is not None:
        monitor.clear()

    # ------------------------------------------------------------ the window
    t_close = t_open + int(seconds * 1e9)
    watch = driver.StallWatch(loop)
    watch.start()
    reduction = traced = trace_dir = None
    if trace:
        trace_dir, traced = _traced_stretch(min(TRACE_S, seconds), t_close)
    time.sleep(max(0.0, (t_close - time.perf_counter_ns()) * 1e-9))
    builds_close = counter.snapshot()
    watch.stop()
    quiet = driver.quiet_stretches(loop.log, t_open, t_close)
    log(f"window: {builds_close[0] - builds_open[0]} executables built; "
        f"longest stretch with no answer {quiet['longest_ms']:.3f} ms at "
        f"+{quiet['at_s']:.3f} s; slowest request {quiet['slowest_ms']:.3f}"
        f" ms; ops in each second {quiet['ops_each_s']}")
    channels = {}
    if monitor is not None:
        channels = {name: monitor.channel(name)
                    for name in monitor.channels()}
    log(f"stall watch: {len(watch.snapshots)} stall(s) of "
        f"{watch.after_s} s or more; the watch ran up to "
        f"{watch.late_ms:.3f} ms late")
    for at, silent, stacks in watch.snapshots:
        log(f"stall: no answer for {silent:.3f} s at "
            f"+{(at - t_open) * 1e-9:.3f} s; threads: "
            + " | ".join(f"{n}x {where}" for where, n in stacks.items()))
    ended = loop.stop(DRAIN_S)
    gcs = gc_pauses.summary(t_open, t_close)
    gc_pauses.close()
    log(f"gc: {sum(gcs['per_gen'])} collections in the window (by "
        f"generation {gcs['per_gen']}), {gcs['total_ms']:.3f} ms in all; "
        f"longest {gcs['longest_ms']:.3f} ms (generation "
        f"{gcs['longest_gen']}) at +{gcs['longest_at_s']:.3f} s")
    win = driver.window_numbers(loop.log, t_open, t_close)
    if win["latencies_ms"].size:
        log(f"latency: p50 {np.percentile(win['latencies_ms'], 50):.3f} ms,"
            f" p99 {np.percentile(win['latencies_ms'], 99):.3f} ms over "
            f"{win['latencies_ms'].size} requests")
    stats = dev.memory_stats() or {}
    peak = int(stats.get("peak_bytes_in_use", 0))
    due = [r for r in loop.log if r.t_done is not None and r.error is None
           and (t_open <= r.t_issue < t_close
                or t_open <= r.t_done < t_close)]
    q = keys[np.concatenate([r.idx for r in due])] if due else keys[:0]
    seen = None
    if witness and control is None:
        t = time.perf_counter()
        seen = target.pipe.service.lookup(q, "numpy")
        log(f"witness: the program's numpy tier answered {q.size} queries,"
            f" {time.perf_counter() - t:.3f} s")
    target.close()
    del target
    gc.collect()
    if trace_dir is not None:
        reduction = _reduce_trace(trace_dir)

    # -------------------------------------------------------- the reference
    t = time.perf_counter()
    wrong = 0
    if due:
        got = np.concatenate([r.answer.ravel() for r in due])
        wrong = oracle.compare(column, q, got)
    checked = q.size
    log(f"reference: {checked} answers of {len(due)} requests compared, "
        f"{time.perf_counter() - t:.3f} s")
    # every number compared must stay at or under its limit
    checks = {"wrong_answers": [wrong, 0],
              "unanswered_requests": [win["failed"] + (0 if ended else 1), 0],
              "window_without_answers": [int(checked == 0), 0]}
    if seen is not None:
        checks["witness_wrong_answers"] = [
            oracle.compare(column, q, seen), 0]
    correct = all(v <= lim for v, lim in checks.values())

    ctx = SimpleNamespace(
        window=win, channels=channels, trace=reduction,
        traced_ops_window=traced,
        ops=win["ops"], config=cfg, mix=mix, peaks=peaks,
        setup_compile_s=builds_open[1],
        window_compiles=builds_close[0] - builds_open[0],
        device_bytes=dev_bytes, n_keys=keys.size,
        setup_s=(t_open - t0) * 1e-9, log=loop.log, t_open=t_open)
    kind = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in manifest.metrics_of(workload, kind):
        value = manifest.metric_reader(m["name"]).read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    result = {"correct": bool(correct), "attempted": win["attempted"],
              "failed": win["failed"], "metrics": metrics, "device": device}
    if reduction is not None:
        device["busy_s"] = reduction.busy_s
        device["window_s"] = reduction.window_s
        result["breakdown"] = {"device_ops": reduction.top_ops(),
                               "idle_gaps": reduction.idle_by_label(),
                               "device_scopes": reduction.top_scopes()}
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}
    return result


def _traced_stretch(trace_s: float, t_close: int):
    """Profile ``trace_s`` seconds of the window (host tracer only).
    Returns the trace's directory and the host-clock stretch; the trace is
    read after the window, so its parsing does not load the window."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    log_dir = tempfile.mkdtemp(prefix="bench-trace-")
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    with jax.profiler.TraceAnnotation(xplane.WINDOW_EVENT):
        a = time.perf_counter_ns()
        time.sleep(max(0.0, min(trace_s, (t_close - a) * 1e-9)))
        b = time.perf_counter_ns()
    jax.profiler.stop_trace()
    return log_dir, (a, b)


def _reduce_trace(log_dir: str):
    """Read and reduce the trace in ``log_dir``, then delete it."""
    try:
        path = xplane.find_trace(log_dir)
        size = os.path.getsize(path)
        t = time.perf_counter()
        reduction = xplane.reduce(xplane.planes(path))
    finally:
        shutil.rmtree(log_dir, ignore_errors=True)
    log(f"trace: {size} bytes, reduced in {time.perf_counter() - t:.3f} s;"
        f" busy {reduction.busy_s:.6f} s of {reduction.window_s:.6f} s on "
        f"{reduction.devices} device(s); device s by scope: "
        + ", ".join(f"{n} {s:.6f}" for n, s in reduction.top_scopes()))
    return reduction


def print_result(result: dict) -> None:
    for name, c in result["checks"].items():
        print(f"check {name}={c['value']} limit={c['limit']}",
              file=sys.stderr)
    print(json.dumps(result), flush=True)


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None, t_start_ns: int | None = None) -> int:
    args = parse(argv)
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace), t_start_ns=t_start_ns)
    except NoChip as exc:
        print(f"bench: {exc}; this benchmark runs only on the chip",
              file=sys.stderr)
        return 2
    print_result(result)
    return 0
