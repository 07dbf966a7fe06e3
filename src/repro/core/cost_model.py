"""Sec. 6 cost model: pick the error threshold from a latency SLA or space budget.

Implements the paper's two models verbatim plus a TPU-roofline variant
(DESIGN.md Sec. 2): on TPU the router lives in VMEM (free of HBM traffic) and a
lookup pays one HBM->VMEM DMA of the +-error window, so the latency model is a
bandwidth term instead of a cache-miss count.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Callable, Sequence

import numpy as np

from .segmentation import shrinking_cone


@dataclasses.dataclass(frozen=True)
class CostParams:
    c_ns: float = 50.0        # random-access / cache-miss penalty (paper Sec. 7.4: 50ns)
    fanout: int = 16          # b, router fanout
    fill: float = 0.5         # f, tree fill ratio (Sec. 6.2)
    buffer_size: int = 16     # buff
    scan_ns_per_row: float = 0.5  # sequential page-scan marginal (range queries)


@dataclasses.dataclass(frozen=True)
class TPUCostParams:
    """TPU v5e's figures (Google Cloud, "TPU v5e": 819 GB/s HBM); planning
    for another TPU with these defaults is refused (``fit.plan``)."""
    hbm_gbps: float = 819.0   # v5e HBM bandwidth
    dma_setup_ns: float = 600.0   # fixed DMA issue latency
    vmem_step_ns: float = 3.0     # per router level probe in VMEM
    bytes_per_key: int = 8
    launch_ns: float = 25_000.0   # host->device dispatch of one jitted call
    plan_ns: float = 75_000.0     # Pallas prelude: bucketing argsort + scatter


# ``device_kind`` strings, as JAX reports them, of the chip whose figures the
# TPUCostParams defaults hold
TPU_COST_PARAMS_KINDS = ("TPU v5 lite", "TPU v5e")


def latency_ns(error: int, n_segments: int, p: CostParams) -> float:
    """Paper Eq. (1), Sec. 6.1: c * [log_b(S_e) + log2(e) + log2(buff)]."""
    tree = math.log(max(n_segments, 2), p.fanout)
    seg = math.log2(max(error, 2))
    buf = math.log2(max(p.buffer_size, 2))
    return p.c_ns * (tree + seg + buf)


def size_bytes(error: int, n_segments: int, p: CostParams) -> float:
    """Paper Eq. (1), Sec. 6.2: f*S_e*log_b(S_e)*16B + S_e*24B (pessimistic).

    The tree height term is clamped to >= 1 (a one-node tree still stores its
    S_e entries), keeping the bound pessimistic for tiny segment counts."""
    s = max(n_segments, 2)
    return p.fill * s * max(1.0, math.log(s, p.fanout)) * 16.0 + s * 24.0


# VMEM router fanout on device (v5e: one 16-wide vector compare per level);
# shared by latency_ns_tpu and tier_cost_curves so the planner's candidate
# scoring and its dispatch-threshold crossings use the same router model.
TPU_ROUTER_FANOUT = 16


def latency_ns_tpu(error: int, n_segments: int, p: TPUCostParams,
                   router_levels: int | None = None) -> float:
    """TPU adaptation: router probes in VMEM + one window DMA from HBM."""
    levels = router_levels or max(1, math.ceil(
        math.log(max(n_segments, 2), TPU_ROUTER_FANOUT)))
    window_bytes = (2 * error + 2) * p.bytes_per_key
    return p.dma_setup_ns + levels * p.vmem_step_ns + window_bytes / p.hbm_gbps


# ----------------------------------------------------------- range-scan model
def scan_ns_per_row_tpu(p: TPUCostParams) -> float:
    """Sequential scan marginal on TPU: rows stream at HBM bandwidth."""
    return p.bytes_per_key / p.hbm_gbps


def range_latency_ns(error: int, n_segments: int, p: CostParams,
                     scan_rows: float) -> float:
    """Range-scan latency: the clustered layout answers a range with one
    predecessor search (the paper's Eq. 1 point cost locates the scan start)
    plus a sequential page scan -- fixed predecessor cost + per-row scan
    marginal."""
    return latency_ns(error, n_segments, p) + scan_rows * p.scan_ns_per_row


def range_latency_ns_tpu(error: int, n_segments: int, p: TPUCostParams,
                         scan_rows: float) -> float:
    """TPU form of :func:`range_latency_ns`: predecessor DMA + streamed rows."""
    return (latency_ns_tpu(error, n_segments, p)
            + scan_rows * scan_ns_per_row_tpu(p))


def learn_segments_fn(keys: np.ndarray, errors: Sequence[int],
                      sample: int | None = 200_000) -> Callable[[int], int]:
    """Sec. 6: 'learned for a specific dataset' -- segment at each candidate error
    (optionally on a contiguous sample, scaled back up) and interpolate log-log."""
    keys = np.asarray(keys, np.float64)
    scale = 1.0
    if sample is not None and keys.shape[0] > sample:
        scale = keys.shape[0] / sample
        keys = keys[: sample]
    es, ss = [], []
    for e in sorted(set(int(e) for e in errors)):
        segs = shrinking_cone(keys, e)
        es.append(e)
        ss.append(max(1, segs.n_segments) * scale)
    log_e, log_s = np.log(np.array(es, float)), np.log(np.array(ss, float))

    def fn(error: int) -> int:
        le = math.log(max(1, error))
        return int(round(math.exp(np.interp(le, log_e, log_s))))

    return fn


def choose_error_for_latency(l_req_ns: float, segments_fn: Callable[[int], int],
                             candidates: Sequence[int], p: CostParams,
                             latency_fn: Callable[[int, int], float] | None = None
                             ) -> int | None:
    """Sec. 6.1 Eq. (2): smallest-size index meeting the latency requirement.

    ``latency_fn(error, n_segments)`` substitutes a different latency model
    (e.g. the TPU roofline :func:`latency_ns_tpu`) while the size side stays
    the paper's Eq. 1 metadata accounting; ``None`` means the paper model."""
    lat = latency_fn or (lambda e, s: latency_ns(e, s, p))
    best, best_size = None, float("inf")
    for e in candidates:
        s = segments_fn(e)
        if lat(e, s) <= l_req_ns:
            sz = size_bytes(e, s, p)
            if sz < best_size:
                best, best_size = e, sz
    return best


def choose_error_for_space(s_req_bytes: float, segments_fn: Callable[[int], int],
                           candidates: Sequence[int], p: CostParams,
                           latency_fn: Callable[[int, int], float] | None = None
                           ) -> int | None:
    """Sec. 6.2 Eq. (2): fastest index within the storage budget.

    ``latency_fn`` as in :func:`choose_error_for_latency`."""
    lat = latency_fn or (lambda e, s: latency_ns(e, s, p))
    best, best_lat = None, float("inf")
    for e in candidates:
        s = segments_fn(e)
        if size_bytes(e, s, p) <= s_req_bytes:
            l = lat(e, s)
            if l < best_lat:
                best, best_lat = e, l
    return best


# ------------------------------------------------------- dispatch tier curves
def tier_cost_curves(error: int, n_segments: int,
                     cpu: CostParams | None = None,
                     tpu: TPUCostParams | None = None,
                     range_fraction: float = 0.0,
                     scan_rows: float = 0.0
                     ) -> dict[str, tuple[float, float]]:
    """Modeled batched-lookup cost per dispatch tier: ``{tier: (fixed_ns,
    per_query_ns)}`` so a batch of ``n`` queries costs ``fixed + n * per``.

    The three tiers of ``repro.index.engine.DispatchEngine`` trade fixed cost
    against marginal cost, and both sides come from the Sec. 6 models:

    * ``small`` (host numpy): no dispatch cost; each query pays the paper's
      Eq. 1 host latency (:func:`latency_ns`) minus its buffer-scan term --
      the dispatch tiers serve a *published snapshot*, whose lookups never
      touch write-side insert buffers.
    * ``medium`` (xla-bisect): one device launch plus the DMA issue latency
      up front; each query then pays ``log2(2e+2)`` single-element probes at
      VMEM speed (the bisect touches one key per halving step).
    * ``large`` (pallas): the launch plus the plan/bucketing prelude up
      front; each query's +-error window is then streamed through the
      compare-reduce kernel at HBM bandwidth.

    ``range_fraction``/``scan_rows`` fold a scan-heavy workload into the
    marginal costs: that fraction of queries additionally scans ``scan_rows``
    rows, at the host's sequential-scan rate on the ``small`` tier and at HBM
    bandwidth on the device tiers -- scans amortize the device launch faster
    than point probes, so the crossings shift left as ``range_fraction``
    grows."""
    cpu = cpu or CostParams()
    tpu = tpu or TPUCostParams()
    steps = math.ceil(math.log2(2 * max(error, 1) + 2))
    window_bytes = (2 * error + 2) * tpu.bytes_per_key
    levels = max(1, math.ceil(
        math.log(max(n_segments, 2), TPU_ROUTER_FANOUT)))
    host_ns = (latency_ns(error, n_segments, cpu)
               - cpu.c_ns * math.log2(max(cpu.buffer_size, 2)))
    host_scan = range_fraction * scan_rows * cpu.scan_ns_per_row
    dev_scan = range_fraction * scan_rows * scan_ns_per_row_tpu(tpu)
    return {
        "small": (0.0, host_ns + host_scan),
        "medium": (tpu.launch_ns + tpu.dma_setup_ns,
                   steps * tpu.vmem_step_ns + levels * tpu.vmem_step_ns
                   + dev_scan),
        "large": (tpu.launch_ns + tpu.dma_setup_ns + tpu.plan_ns,
                  window_bytes / tpu.hbm_gbps + tpu.vmem_step_ns + dev_scan),
    }


def curve_crossings(curves: dict[str, tuple[float, float]]) -> tuple[int, int]:
    """``(small_max, large_min)`` where the per-tier affine cost curves cross.

    ``curves`` maps the three ``DispatchEngine`` tiers to ``(fixed_ns,
    per_query_ns)`` pairs -- modeled (:func:`tier_cost_curves`), measured
    (:func:`fit_tier_curves`), or a mixture.  ``small_max`` is the largest
    batch the host tier still wins (the medium tier's fixed launch cost
    amortizes beyond it); ``large_min`` the smallest batch where the large
    tier's extra plan cost pays for its lower marginal cost.  Degenerate
    slopes (a tier whose marginal cost is not strictly better than its
    predecessor's) push the crossing to the extreme, so the invariant
    ``0 <= small_max < large_min`` always holds."""
    (f_s, p_s), (f_m, p_m), (f_l, p_l) = (
        curves["small"], curves["medium"], curves["large"])
    if p_s > p_m:
        small_max = max(1, int((f_m - f_s) / (p_s - p_m)))
    else:                  # host never loses per-query: keep batches on host
        small_max = 1 << 30
    if p_m > p_l:
        large_min = max(small_max + 1, int(math.ceil((f_l - f_m) / (p_m - p_l))))
    else:                  # pallas never wins per-query: effectively disabled
        large_min = max(small_max + 1, 1 << 31)
    return small_max, large_min


def dispatch_thresholds(error: int, n_segments: int,
                        cpu: CostParams | None = None,
                        tpu: TPUCostParams | None = None,
                        range_fraction: float = 0.0,
                        scan_rows: float = 0.0) -> tuple[int, int]:
    """Cost-model-calibrated ``(small_max, large_min)`` for ``DispatchEngine``:
    the batch sizes where the modeled per-tier latency curves cross (see
    :func:`curve_crossings`).  ``range_fraction``/``scan_rows`` make the
    crossings scan-aware (see :func:`tier_cost_curves`)."""
    return curve_crossings(tier_cost_curves(error, n_segments, cpu, tpu,
                                            range_fraction, scan_rows))


# ------------------------------------------- device-plane exchange strategies
def exchange_cost_ns(strategy: str, batch: int, n_devices: int, error: int,
                     n_segments: int, p: TPUCostParams | None = None,
                     *, slack: float = 2.0) -> float:
    """Modeled wall cost of one device-sharded ``search`` collective round.

    Two exchange strategies move a batch of queries across a ``D``-device
    mesh (``repro.index.device``):

    * ``"allgather"``: one gather of the full batch; every device then
      answers all ``batch`` queries against its local shard and a ``psum``
      combines the per-shard ranks.  Cheap to launch, but per-device work
      is the *whole* batch -- it never shrinks as devices are added.
    * ``"a2a"``: queries are bucketed to their owning shard (a host-style
      argsort prelude, ``plan_ns``), exchanged with ``all_to_all``,
      answered locally, and exchanged back -- three collective hops, but
      per-device work is only ``slack * batch / D`` queries.

    Per-query search work on a shard is the TPU roofline's window cost over
    the shard's (smaller) segment slice; the DMA-issue constant stays a
    fixed per-hop cost rather than a per-query one."""
    p = p or TPUCostParams()
    d = max(1, n_devices)
    s_local = max(1, math.ceil(max(1, n_segments) / d))
    per_q = latency_ns_tpu(error, s_local, p) - p.dma_setup_ns
    wire = p.bytes_per_key / p.hbm_gbps
    if strategy == "allgather":
        return (p.launch_ns + p.dma_setup_ns + batch * wire + batch * per_q)
    if strategy == "a2a":
        routed = slack * batch / d
        return (p.launch_ns + p.plan_ns
                + 2 * (p.dma_setup_ns + routed * wire) + routed * per_q)
    raise ValueError(f"unknown exchange strategy {strategy!r}")


def choose_exchange(batch: int, n_devices: int, error: int, n_segments: int,
                    p: TPUCostParams | None = None,
                    *, slack: float = 2.0) -> str:
    """Pick the cheaper exchange strategy for a representative batch size.

    Small batches amortize nothing: the a2a path's bucketing prelude and
    extra hops dominate, so ``allgather`` wins.  Past the crossover the
    ``slack/D < 1`` per-device work reduction pays for the hops and ``a2a``
    wins.  On a single device there is nothing to exchange -- allgather
    degenerates to a local search and always wins."""
    if n_devices <= 1:
        return "allgather"
    a = exchange_cost_ns("allgather", batch, n_devices, error, n_segments, p,
                         slack=slack)
    b = exchange_cost_ns("a2a", batch, n_devices, error, n_segments, p,
                         slack=slack)
    return "a2a" if b < a else "allgather"


def exchange_crossover_batch(n_devices: int, error: int, n_segments: int,
                             p: TPUCostParams | None = None,
                             *, slack: float = 2.0,
                             max_batch: int = 1 << 22) -> int | None:
    """Smallest power-of-two batch where ``a2a`` beats ``allgather`` (for
    ``plan().explain()`` audits), or ``None`` if it never does below
    ``max_batch``."""
    if n_devices <= 1:
        return None
    b = 1
    while b <= max_batch:
        if choose_exchange(b, n_devices, error, n_segments, p,
                           slack=slack) == "a2a":
            return b
        b *= 2
    return None


# ----------------------------------------------- measured-curve re-calibration
def fit_tier_curves(samples: dict[str, np.ndarray | Sequence],
                    min_samples: int = 8
                    ) -> dict[str, tuple[float, float]]:
    """Least-squares re-fit of the per-tier affine cost curves from measured
    ``(batch_size, wall_ns)`` samples (e.g. a telemetry ``Monitor``'s
    ``tier.*`` channels): ``{tier: (fixed_ns, per_query_ns)}``.

    To keep one-off spikes (first-call compiles, scheduler hiccups) from
    skewing the fixed/marginal split, the line is fit through the *median*
    latency per distinct batch size, weighted by how often that size was
    seen.  Tiers with fewer than ``min_samples`` rows or fewer than two
    distinct batch sizes are omitted -- callers fall back to the modeled
    curve (:func:`tier_cost_curves`) for those.  Coefficients are clamped
    non-negative (a latency curve cannot slope down)."""
    out: dict[str, tuple[float, float]] = {}
    for tier, rows in samples.items():
        a = np.asarray(rows, np.float64).reshape(-1, 2)
        if a.shape[0] < min_samples:
            continue
        sizes = np.unique(a[:, 0])
        if sizes.size < 2:
            continue
        med = np.array([np.median(a[a[:, 0] == s, 1]) for s in sizes])
        wts = np.array([float((a[:, 0] == s).sum()) for s in sizes])
        per, fixed = np.polyfit(sizes, med, 1, w=np.sqrt(wts))
        out[tier] = (max(float(fixed), 0.0), max(float(per), 0.0))
    return out


def refit_params(curves: dict[str, tuple[float, float]],
                 error: int, n_segments: int,
                 cpu: CostParams | None = None,
                 tpu: TPUCostParams | None = None
                 ) -> tuple[CostParams, TPUCostParams]:
    """Invert measured tier curves back into ``(CostParams, TPUCostParams)``.

    The inverse of :func:`tier_cost_curves` at the serving configuration
    ``(error, n_segments)``: each measured coefficient pins the model
    parameter that produces it, so re-running the Sec. 6 planner with the
    returned params reproduces the measured curves (modulo non-negativity
    clamps).  Tiers absent from ``curves`` leave their parameters at the
    prior's value; ``cpu``/``tpu`` default to the hand-tuned constants."""
    cpu = cpu or CostParams()
    tpu = tpu or TPUCostParams()
    steps = math.ceil(math.log2(2 * max(error, 1) + 2))
    window_bytes = (2 * error + 2) * tpu.bytes_per_key
    levels = max(1, math.ceil(
        math.log(max(n_segments, 2), TPU_ROUTER_FANOUT)))
    if "small" in curves:
        # host marginal = c_ns * (log_b(S_e) + log2(e)): snapshot lookups pay
        # no buffer-scan term (see tier_cost_curves)
        denom = (math.log(max(n_segments, 2), cpu.fanout)
                 + math.log2(max(error, 2)))
        cpu = dataclasses.replace(
            cpu, c_ns=max(curves["small"][1] / max(denom, 1e-9), 1e-3))
    if "medium" in curves:
        fixed, per = curves["medium"]
        tpu = dataclasses.replace(
            tpu,
            launch_ns=max(fixed - tpu.dma_setup_ns, 0.0),
            vmem_step_ns=max(per / (steps + levels), 1e-6))
    if "large" in curves:
        fixed, per = curves["large"]
        tpu = dataclasses.replace(
            tpu,
            plan_ns=max(fixed - tpu.launch_ns - tpu.dma_setup_ns, 0.0),
            hbm_gbps=window_bytes / max(per - tpu.vmem_step_ns, 1e-6))
    return cpu, tpu


def calibrate(keys: np.ndarray, engine=None, *,
              errors: Sequence[int] = (16, 256), batch: int = 1024,
              repeats: int = 3, safety: float = 1.3) -> CostParams:
    """One-shot micro-calibration of ``CostParams.c_ns`` against this host.

    Seeds the Sec. 6 latency model from a measurement instead of the paper's
    hand-tuned 50ns constant: builds a published-snapshot table at each
    anchor ``error``, times a ``batch``-sized host lookup (best of
    ``repeats``), and solves Eq. 1 for the ``c_ns`` that reproduces it --
    ``measured_per_query = c_ns * (log_b(S_e) + log2(e))`` (no buffer term:
    snapshots carry no insert buffer).  The worst anchor times ``safety``
    keeps the model an upper bound across the error sweep, which is what
    planner SLA admission (``choose_error_for_latency``) needs.

    ``engine`` substitutes a lookup callable ``engine(queries)`` timed in
    place of the host ``numpy_lookup``; by default the host tier is measured,
    matching the paper's cache-miss model."""
    from repro.index.table import SegmentTable, numpy_lookup  # lazy: no cycle
    import time
    keys = np.asarray(keys, np.float64)
    if not np.all(np.diff(keys) >= 0):
        keys = np.sort(keys, kind="stable")
    q = np.resize(keys, max(int(batch), 1))
    worst = 0.0
    for e in sorted(set(int(e) for e in errors)):
        table = SegmentTable.from_keys(keys, e, assume_sorted=True)
        fn = engine if engine is not None else (
            lambda qq, t=table: numpy_lookup(t, qq))
        fn(q)  # warm caches / compiles before timing
        best = float("inf")
        for _ in range(max(int(repeats), 1)):
            t0 = time.perf_counter_ns()
            fn(q)
            best = min(best, time.perf_counter_ns() - t0)
        per_query = best / q.size
        denom = (math.log(max(table.n_segments, 2), CostParams.fanout)
                 + math.log2(max(e, 2)))
        worst = max(worst, per_query / max(denom, 1e-9))
    return dataclasses.replace(CostParams(), c_ns=max(worst * safety, 1e-3))
