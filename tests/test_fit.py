"""SLO-driven construction: FitSpec validation, plan() vs a brute-force
cost-model oracle, JSON round trips, open_index routing, and planned-dispatch
lookups agreeing with the numpy oracle at every tier boundary."""
import dataclasses

import numpy as np
import pytest

from repro.core import TPUCostParams, latency_ns, size_bytes
from repro.core.datasets import lognormal_keys, uniform_keys
from repro.index import (FitSpec, IndexPlan, InfeasibleSpecError, numpy_lookup,
                         open_index, plan)
from repro.index.fit import brute_force_choice, planned_buffer
from repro.serve import IndexService, ShardedIndexService

CANDS = (8, 32, 128, 512, 2048)


def _duplicate_heavy(n=20_000, seed=5):
    rng = np.random.default_rng(seed)
    return np.sort(rng.choice(np.arange(n // 8, dtype=np.float64), size=n))


DATASETS = {
    "uniform": lambda: uniform_keys(20_000, seed=3),
    "lognormal": lambda: lognormal_keys(20_000, seed=4),
    "duplicate_heavy": _duplicate_heavy,
}


# ------------------------------------------------------------ spec validation
def test_spec_requires_exactly_one_objective():
    with pytest.raises(ValueError, match="exactly one objective"):
        FitSpec()
    with pytest.raises(ValueError, match="exactly one objective"):
        FitSpec(latency_budget_ns=500.0, error=64)
    with pytest.raises(ValueError, match="exactly one objective"):
        FitSpec(latency_budget_ns=500.0, storage_budget_bytes=1e6, error=64)


def test_spec_rejects_nonpositive_budgets_and_bad_hints():
    with pytest.raises(ValueError, match="latency_budget_ns must be > 0"):
        FitSpec(latency_budget_ns=0.0)
    with pytest.raises(ValueError, match="storage_budget_bytes must be > 0"):
        FitSpec(storage_budget_bytes=-5.0)
    with pytest.raises(ValueError, match="error must be >= 1"):
        FitSpec(error=0)
    with pytest.raises(ValueError, match="key_sample must be non-empty"):
        FitSpec(error=64, key_sample=())
    with pytest.raises(ValueError, match="insert_rate must be >= 0"):
        FitSpec(error=64, insert_rate=-1.0)
    with pytest.raises(ValueError, match="duplicate_density"):
        FitSpec(error=64, duplicate_density=1.0)
    with pytest.raises(ValueError, match="batch_sizes"):
        FitSpec(error=64, batch_sizes=(16, 0))
    with pytest.raises(ValueError, match="hardware"):
        FitSpec(error=64, hardware="gpu")
    with pytest.raises(ValueError, match="candidate_errors"):
        FitSpec(error=64, candidate_errors=())
    with pytest.raises(ValueError, match="segment_sample"):
        FitSpec(error=64, segment_sample=0)
    with pytest.raises(ValueError, match="segment_sample"):
        FitSpec(error=64, segment_sample=-5)


def test_spec_json_round_trip_equality():
    spec = FitSpec(latency_budget_ns=500.0, batch_sizes=[4, 2048],
                   insert_rate=1_000.0, duplicate_density=0.25,
                   key_sample=[1.0, 2.0, 5.5], n_keys_hint=10_000_000,
                   hardware="tpu",
                   tpu_params=TPUCostParams(hbm_gbps=1600.0),
                   candidate_errors=[16, 64, 256])
    again = FitSpec.from_json(spec.to_json())
    assert again == spec
    # list inputs normalize to tuples, so equality is structural
    assert isinstance(again.batch_sizes, tuple)
    with pytest.raises(ValueError, match="unknown FitSpec fields"):
        FitSpec.from_json('{"error": 64, "not_a_knob": 1}')
    with pytest.raises(ValueError, match="unknown FitSpec fields.*cpu_params"):
        FitSpec.from_json(
            '{"error": 64, "cpu_params": {"c_ns": 50.0, "bogus": 1}}')
    # numpy arrays are natural inputs for the workload hints; they must
    # normalize to JSON-serializable Python scalars
    np_spec = FitSpec(error=64, batch_sizes=np.array([1, 8, 64]),
                      key_sample=np.array([1.5, 2.5]),
                      candidate_errors=np.array([16, 64]))
    assert FitSpec.from_json(np_spec.to_json()) == np_spec


# ------------------------------------------------------- planner vs the oracle
@pytest.mark.parametrize("name", sorted(DATASETS))
@pytest.mark.parametrize("objective", ["latency", "space"])
def test_plan_matches_brute_force_oracle(name, objective):
    """The chooser-driven planner picks exactly the error an exhaustive
    sweep of the same cost model picks, on every dataset shape."""
    keys = DATASETS[name]()
    probe = plan(keys, FitSpec(error=64, candidate_errors=CANDS))
    lats = [c.latency_ns for c in probe.candidates]
    sizes = [c.size_bytes for c in probe.candidates]
    if objective == "latency":
        budgets = [(min(lats) + max(lats)) / 2, max(lats)]
        specs = [FitSpec(latency_budget_ns=b, candidate_errors=CANDS)
                 for b in budgets]
    else:
        budgets = [(min(sizes) + max(sizes)) / 2, max(sizes)]
        specs = [FitSpec(storage_budget_bytes=b, candidate_errors=CANDS)
                 for b in budgets]
    for spec in specs:
        got = plan(keys, spec)
        assert got.error == brute_force_choice(keys, spec)
        chosen = [c for c in got.candidates if c.chosen]
        assert len(chosen) == 1 and chosen[0].error == got.error
        assert chosen[0].feasible


def test_plan_candidates_audit_the_model():
    """Every candidate row reproduces the Sec. 6 formulas for the
    configuration the planner would *build*: segmentation and windows at
    err_seg = error - planned_buffer(error), buffer-scan term at the
    planned buffer."""
    keys = uniform_keys(20_000, seed=7)
    spec = FitSpec(latency_budget_ns=900.0, candidate_errors=CANDS)
    p = plan(keys, spec)
    for c in p.candidates:
        buf = planned_buffer(c.error)
        eff = dataclasses.replace(spec.cpu_params, buffer_size=buf)
        assert c.latency_ns == pytest.approx(
            latency_ns(c.error - buf, c.n_segments, eff))
        assert c.size_bytes == pytest.approx(
            size_bytes(c.error, c.n_segments, spec.cpu_params))
        assert c.feasible == (c.latency_ns <= 900.0)
    report = p.explain()
    assert "chosen" in report and f"error={p.error}" in report
    assert str(p.small_max) in report and str(p.large_min) in report


def test_built_service_satisfies_the_budget_under_its_own_model():
    """Regression: the plan is scored on the effective (err_seg, buffer)
    configuration, so the *actually built* snapshot -- which serves at
    err_seg with the planned buffer -- still fits the budget when the same
    Sec. 6 model is evaluated on its real segment count."""
    keys = uniform_keys(20_000, seed=18)
    budget = 700.0
    spec = FitSpec(latency_budget_ns=budget)
    p = plan(keys, spec)
    svc = open_index(keys, p)
    table = svc.handle.current().table
    assert table.error == p.error - p.buffer_size      # served at err_seg
    eff = dataclasses.replace(spec.cpu_params, buffer_size=p.buffer_size)
    modeled = latency_ns(table.error, table.n_segments, eff)
    # 5% headroom for the segments-curve interpolation between candidates
    assert modeled <= budget * 1.05


def test_infeasible_budgets_raise_with_tightest_achievable():
    keys = uniform_keys(20_000, seed=8)
    with pytest.raises(InfeasibleSpecError, match="tightest achievable") \
            as exc:
        plan(keys, FitSpec(latency_budget_ns=1e-3, candidate_errors=CANDS))
    assert exc.value.objective == "latency"
    assert exc.value.tightest > exc.value.budget
    with pytest.raises(InfeasibleSpecError, match="tightest achievable") \
            as exc:
        plan(keys, FitSpec(storage_budget_bytes=1.0, candidate_errors=CANDS))
    assert exc.value.objective == "space"
    assert exc.value.tightest > 1.0


def test_plan_from_key_sample_without_keys():
    keys = uniform_keys(20_000, seed=9)
    spec = FitSpec(latency_budget_ns=800.0,
                   key_sample=tuple(keys[::20]), n_keys_hint=keys.shape[0],
                   candidate_errors=CANDS)
    p = plan(None, spec)
    assert p.error in CANDS
    assert p.n_keys == keys[::20].shape[0]
    with pytest.raises(ValueError, match="needs keys"):
        plan(None, FitSpec(error=64))


def test_tpu_hardware_profile_uses_roofline_latency():
    keys = uniform_keys(20_000, seed=10)
    cpu_p = plan(keys, FitSpec(error=64, candidate_errors=CANDS))
    tpu_p = plan(keys, FitSpec(error=64, candidate_errors=CANDS,
                               hardware="tpu"))
    cpu_lat = {c.error: c.latency_ns for c in cpu_p.candidates}
    tpu_lat = {c.error: c.latency_ns for c in tpu_p.candidates}
    assert all(tpu_lat[e] != cpu_lat[e] for e in CANDS)
    # the DMA setup floor dominates small errors on TPU
    assert tpu_lat[8] > TPUCostParams().dma_setup_ns


@pytest.mark.parametrize("kind,params,refused", [
    ("TPU v5 lite", TPUCostParams(), False),
    ("TPU v4", TPUCostParams(), True),
    ("TPU v4", TPUCostParams(hbm_gbps=1200.0), False),
])
def test_tpu_plan_refuses_another_chip_with_v5e_figures(monkeypatch, kind,
                                                        params, refused):
    import repro.index.fit as fit
    keys = uniform_keys(5_000, seed=10)
    spec = FitSpec(error=64, candidate_errors=CANDS, hardware="tpu",
                   tpu_params=params)
    monkeypatch.setattr(fit, "_attached_tpu_kind", lambda: kind)
    if refused:
        with pytest.raises(ValueError, match="TPU v5e figures"):
            plan(keys, spec)
    else:
        assert plan(keys, spec).hardware == "tpu"
    # the CPU profile never asks which chip is attached
    assert plan(keys, FitSpec(error=64, candidate_errors=CANDS)).error == 64


# ------------------------------------------------------------------ open_index
def test_open_index_sharded_iff_plan_says_so():
    keys = uniform_keys(20_000, seed=11)
    single = plan(keys, FitSpec(error=64, candidate_errors=CANDS))
    assert single.n_shards == 1
    svc = open_index(keys, single)
    assert isinstance(svc, IndexService)

    write_hot = plan(keys, FitSpec(error=64, candidate_errors=CANDS,
                                   insert_rate=200_000.0))
    assert write_hot.n_shards > 1
    svc = open_index(keys, write_hot)
    assert isinstance(svc, ShardedIndexService)
    assert svc.n_shards == write_hot.n_shards
    with pytest.raises(TypeError, match="FitSpec or IndexPlan"):
        open_index(keys, {"error": 64})


def test_open_index_end_to_end_latency_and_space():
    """Acceptance: both SLO forms work insert -> publish -> lookup with no
    raw knob supplied by the caller."""
    rng = np.random.default_rng(12)
    keys = np.sort(rng.choice(2 ** 22, size=20_000,
                              replace=False)).astype(np.float64)
    fresh = np.setdiff1d(
        rng.choice(2 ** 22, size=256, replace=False).astype(np.float64),
        keys)[:64]
    for spec in (FitSpec(latency_budget_ns=700.0),
                 FitSpec(storage_budget_bytes=1e6),
                 FitSpec(latency_budget_ns=700.0, insert_rate=150_000.0)):
        svc = open_index(keys, spec)
        assert np.array_equal(svc.lookup(keys[::97]),
                              np.searchsorted(keys, keys[::97]))
        for k in fresh:
            svc.insert(float(k))
        svc.publish()
        union = np.sort(np.concatenate([keys, fresh]))
        got = svc.lookup(fresh)
        assert np.array_equal(got, np.searchsorted(union, fresh))


def test_open_index_sorts_unsorted_keys_and_payload_once():
    """open_index accepts unsorted keys (sorting exactly once, payload
    permuted alongside) and the built service serves correct ranks/values."""
    rng = np.random.default_rng(19)
    keys = rng.permutation(uniform_keys(5_000, seed=19))
    payload = keys * 2.0
    svc = open_index(keys, FitSpec(error=64, candidate_errors=CANDS),
                     payload=payload)
    srt = np.sort(keys)
    probe = srt[::173]
    ranks = svc.lookup(probe)
    assert np.array_equal(ranks, np.searchsorted(srt, probe))
    snap = svc.handle.current()
    assert np.array_equal(snap.table.keys, srt)


def test_raw_knob_constructors_carry_a_trivial_plan():
    keys = uniform_keys(5_000, seed=13)
    svc = IndexService(keys, error=64, buffer_size=8)
    assert svc.plan.objective == "raw" and svc.plan.error == 64
    sharded = ShardedIndexService(keys, 32, n_shards=3, buffer_size=4,
                                  backend="dispatch")
    assert sharded.plan.n_shards == 3 and sharded.plan.backend == "dispatch"
    with pytest.raises(TypeError, match="error=.*or plan="):
        ShardedIndexService(keys)


def test_raw_knobs_alongside_a_plan_are_rejected_loudly():
    """A plan fixes error/n_shards/buffer/backend/cadence; passing any of
    them beside plan= must fail, not be silently overwritten."""
    keys = uniform_keys(5_000, seed=13)
    p = IndexPlan.from_knobs(16, n_shards=2, buffer_size=4)
    with pytest.raises(TypeError, match="not both.*error"):
        ShardedIndexService(keys, 32, plan=p)
    with pytest.raises(TypeError, match="not both.*buffer_size, n_shards"):
        ShardedIndexService(keys, plan=p, n_shards=7, buffer_size=999)
    with pytest.raises(TypeError, match="not both.*backend"):
        IndexService(keys, plan=p, backend="numpy")


def test_open_index_policy_kwargs_reach_both_service_shapes():
    """The documented pass-through kwargs must work whether the planner
    resolves to one shard (IndexService) or many (sharded)."""
    keys = uniform_keys(5_000, seed=17)
    one = open_index(keys, FitSpec(error=64, candidate_errors=CANDS),
                     skew_threshold=3.0, auto_rebalance=True,
                     assume_sorted=True)
    assert isinstance(one, IndexService)
    many = open_index(keys, FitSpec(error=64, candidate_errors=CANDS,
                                    insert_rate=200_000.0),
                      skew_threshold=3.0, auto_rebalance=True,
                      assume_sorted=True)
    assert isinstance(many, ShardedIndexService)
    assert many.skew_threshold == 3.0 and many.auto_rebalance
    for svc in (one, many):
        assert np.array_equal(svc.lookup(keys[:16]), np.arange(16))


def test_index_service_forces_plan_to_one_shard():
    keys = uniform_keys(5_000, seed=14)
    multi = dataclasses.replace(plan(keys, FitSpec(error=64)), n_shards=4)
    svc = IndexService.from_plan(keys, multi)
    assert svc.plan.n_shards == 1
    assert np.array_equal(svc.lookup(keys[:32]), np.arange(32))


# ------------------------------------------- planned dispatch at the breakpoints
def test_planned_dispatch_matches_oracle_at_tier_boundaries():
    """Acceptance: with cost-model-planned thresholds, lookups agree with the
    numpy oracle at every tier boundary +-1, and every registered backend
    serves the same ranks through the planned service."""
    rng = np.random.default_rng(15)
    keys = np.sort(rng.choice(2 ** 22, size=3_000,
                              replace=False)).astype(np.float64)
    # a hardware profile with small launch/plan overheads keeps the planned
    # crossings tiny, so the pallas tier is exercised cheaply in interpret mode
    spec = FitSpec(error=16, candidate_errors=CANDS,
                   tpu_params=TPUCostParams(launch_ns=1200.0, plan_ns=300.0))
    p = plan(keys, spec)
    assert p.backend == "dispatch"
    assert 0 < p.small_max < p.large_min < 256
    svc = open_index(keys, p)
    eng = svc.handle.engine("dispatch")
    assert (eng.small_max, eng.large_min) == (p.small_max, p.large_min)

    table = svc.handle.current().table
    # absent probes at half-integers: exactly representable in f32, so the
    # f64 host tier and the f32 device tiers agree on membership
    absent = np.floor(rng.uniform(0, 2 ** 22, size=128)) + 0.5
    pool = np.concatenate([keys[rng.integers(0, keys.shape[0], 128)], absent])
    for size in sorted({1, p.small_max - 1, p.small_max, p.small_max + 1,
                        p.large_min - 1, p.large_min, p.large_min + 1}):
        if size < 1:
            continue
        q = pool[rng.integers(0, pool.shape[0], size)]
        want = numpy_lookup(table, q)
        assert eng.engine_for(size).backend == eng.backend_for(size)
        np.testing.assert_array_equal(
            svc.lookup(q), want,
            err_msg=f"batch {size} -> {eng.backend_for(size)}")
    q = pool[rng.integers(0, pool.shape[0], 64)]
    want = numpy_lookup(table, q)
    for backend in ("numpy", "xla-window", "xla-bisect", "pallas",
                    "dispatch"):
        np.testing.assert_array_equal(svc.lookup(q, backend), want,
                                      err_msg=backend)


def test_batch_size_hints_pick_the_tier_backend():
    keys = uniform_keys(20_000, seed=16)
    base = dict(latency_budget_ns=900.0, candidate_errors=CANDS)
    p = plan(keys, FitSpec(**base))
    assert p.backend == "dispatch"          # no hint -> mixed-size router
    tiny = plan(keys, FitSpec(**base, batch_sizes=(1, 2, 4)))
    assert tiny.backend == "numpy"
    huge = plan(keys, FitSpec(**base,
                              batch_sizes=(p.large_min, 4 * p.large_min)))
    assert huge.backend == "pallas"
    mid = plan(keys, FitSpec(**base, batch_sizes=(p.small_max + 1,
                                                  p.large_min - 1)))
    assert mid.backend == "xla-bisect"
