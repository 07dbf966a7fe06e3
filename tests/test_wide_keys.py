"""Exact answers on float64 key columns that float32 cannot hold.

Every device tier, the dispatch engine and the served pipeline must give
``np.searchsorted``'s answers on the float64 column (-1 where a lookup's key
is absent), with no tolerance: on the paper's Maps longitudes, on keys
planted to collide in float32 (runs of up to 40 keys, and one of 10^5), on
absent queries float32-equal to present keys, on duplicate runs across
segment starts, on signed keys with +-0.0 / +-inf / NaN queries, and with
the window's slack planted at 0 so that the whole-column fallback
(``fit.widen``) must answer.  A column of
float32 keys keeps the float32 form and its device bytes.
"""
import jax
import numpy as np
import pytest

from repro.core.datasets import maps_like, weblogs_like
from repro.index import (FitSpec, Monitor, SegmentTable, ShardedIndexService,
                         make_engine, open_pipeline)
from repro.index.device import DeviceShardedService
from repro.index import engine as engine_module
from repro.index.engine import device_index, key_slack, make_plan, slack_cap
from repro.index.keycode import Wide, encode, f32_exact
from repro.index.telemetry import CH_WIDEN

BACKENDS = ("xla-window", "xla-bisect", "pallas", "dispatch")
SIDES = ("left", "right")
F32_MAX = float(np.finfo(np.float32).max)


def _lookup_want(keys, q):
    rank = np.searchsorted(keys, q, "left")
    hit = rank < keys.size
    hit[hit] = keys[rank[hit]] == q[hit]
    return np.where(hit, rank, -1)


def _check(answer, keys, q):
    """``answer(verb, side, q)`` equals the float64 reference exactly."""
    np.testing.assert_array_equal(answer("lookup", None, q),
                                  _lookup_want(keys, q))
    for side in SIDES:
        np.testing.assert_array_equal(answer("search", side, q),
                                      np.searchsorted(keys, q, side))


def _engine_answer(engine):
    def answer(verb, side, q):
        return engine.lookup(q) if verb == "lookup" else engine.search(q, side)
    return answer


# --------------------------------------------------------------- key sets
def _maps(n):
    def make(rng):
        keys = np.sort(maps_like(n, seed=2))
        q = np.concatenate([keys[rng.integers(0, n, 1500)],
                            rng.uniform(-181.0, 181.0, 500),
                            np.nextafter(keys[rng.integers(0, n, 300)],
                                         np.inf)])
        return keys, 256, q
    return make


def _f32_collisions(rng):
    """Runs of 1-40 distinct float64 keys on one float32 value each, at an
    error below the runs, so the float32 predictor misses ranks."""
    base = np.unique(rng.uniform(1e6, 2e6, 600).astype(np.float32))
    runs = rng.integers(1, 41, base.size)
    keys = np.concatenate([
        b + np.arange(r) * np.spacing(b) for b, r in
        zip(base.astype(np.float64), runs)])
    assert np.array_equal(keys.astype(np.float32), np.repeat(base, runs))
    q = np.concatenate([keys[rng.integers(0, keys.size, 1500)],
                        base.astype(np.float64),
                        rng.uniform(0.9e6, 2.1e6, 300)])
    return keys, 16, q


def _f32_collision_run(rng):
    """10^5 distinct float64 keys on one float32 value amid plain float64
    keys: the float32 predictor misses by far more than any window."""
    b = np.float64(np.float32(1.5e6))
    run = b + np.arange(100_000) * np.spacing(b)
    assert np.all(run.astype(np.float32) == np.float32(b))
    keys = np.sort(np.concatenate([run, rng.uniform(1e6, 2e6, 20_000)]))
    q = np.concatenate([run[rng.integers(0, run.size, 1500)],
                        [b, b - np.spacing(b), run[-1] + np.spacing(b)],
                        keys[rng.integers(0, keys.size, 500)],
                        rng.uniform(0.9e6, 2.1e6, 300)])
    return keys, 64, q


def _absent_f32_equal(rng):
    """Queries float32-equal to a present key but absent from the column."""
    keys = np.sort(rng.uniform(-1e4, 1e4, 30_000))
    near = keys[rng.integers(0, keys.size, 1200)]
    q = np.concatenate([near + np.spacing(near), near - np.spacing(near),
                        keys[rng.integers(0, keys.size, 600)]])
    return keys, 64, q


def _duplicate_runs(rng):
    """Duplicate runs longer than the window, so they cross segment
    starts, of keys float32 cannot hold."""
    values = np.sort(rng.uniform(0.0, 1e5, 300)) + 0.1
    keys = np.repeat(values, rng.integers(1, 200, values.size))
    q = np.concatenate([values, keys[rng.integers(0, keys.size, 1000)],
                        values + np.spacing(values)])
    return keys, 16, q


def _signs_and_specials(rng):
    """Keys of both signs and both zeros; queries +-0.0, +-inf, NaN."""
    keys = np.sort(np.concatenate([rng.uniform(-1e3, 1e3, 20_000),
                                   [0.0, -0.0, 0.0]]))
    specials = np.array([0.0, -0.0, np.inf, -np.inf, np.nan, 1e300, -1e300,
                         keys[0], keys[-1]])
    q = np.concatenate([np.repeat(specials, 40),
                        keys[rng.integers(0, keys.size, 1000)],
                        -keys[rng.integers(0, keys.size, 500)]])
    return keys, 64, q


CASES = {"maps-2^16": _maps(1 << 16), "maps-2^18": _maps(1 << 18),
         "f32-collisions": _f32_collisions,
         "f32-collision-run": _f32_collision_run,
         "absent-f32-equal": _absent_f32_equal,
         "duplicate-runs": _duplicate_runs,
         "signs-and-specials": _signs_and_specials}


@pytest.fixture(scope="module", params=sorted(CASES))
def case(request):
    keys, error, q = CASES[request.param](np.random.default_rng(
        [18, sorted(CASES).index(request.param)]))
    assert not f32_exact(keys)
    table = SegmentTable.from_keys(keys, error, assume_sorted=True)
    return request.param, keys, table, q


# -------------------------------------------------------------- the tests
def test_codes_order_like_np_sort():
    rng = np.random.default_rng(18)
    x = np.concatenate([rng.standard_normal(4000)
                        * 10.0 ** rng.integers(-300, 300, 4000),
                        [0.0, -0.0, np.inf, -np.inf, np.nan, 5e-324, -5e-324,
                         np.finfo(np.float64).max]])
    code = encode(np.sort(x))
    joined = code.hi.astype(np.int64) * 2 ** 32 + code.lo.astype(np.int64)
    assert np.all(np.diff(joined) >= 0)
    # -0.0 and +0.0 share a code, as they share a searchsorted rank
    z = encode(np.array([0.0, -0.0]))
    assert z.hi[0] == z.hi[1] and z.lo[0] == z.lo[1]


@pytest.mark.parametrize("backend", BACKENDS)
def test_device_tiers_agree_with_searchsorted(case, backend):
    _, keys, table, q = case
    engine = make_engine(table, backend)
    if backend != "dispatch":
        assert isinstance(engine.index.keys, Wide)
        _check(_engine_answer(engine), keys, q)
        return
    # every tier of the dispatch: numpy, xla-bisect, pallas
    for size in (engine.small_max, engine.large_min - 1, engine.large_min):
        part = np.resize(q, size)
        _check(_engine_answer(engine), keys, part)


def test_served_pipeline_agrees_with_searchsorted(case):
    _, keys, table, q = case
    pipe = open_pipeline(keys, FitSpec(error=table.error, hardware="tpu"),
                         assume_sorted=True, prewarm=False)
    try:
        def answer(verb, side, q):
            if verb == "lookup":
                return pipe.lookup(q)
            return pipe.search(q, side)
        _check(answer, keys, q)
    finally:
        pipe.close()


@pytest.mark.parametrize("backend", BACKENDS)
def test_slack_zero_widens_and_stays_exact(backend, monkeypatch):
    """With no slack, float32 collisions push answers past the window: the
    whole-column fallback must answer them, and ``engine.widen`` count
    them."""
    keys, error, q = _f32_collisions(np.random.default_rng(180))
    table = SegmentTable.from_keys(keys, error, assume_sorted=True)
    assert key_slack(table) > 1
    monkeypatch.setattr(engine_module, "key_slack", lambda table: 0)
    mon = Monitor()
    engine = make_engine(table, backend, monitor=mon)
    if backend == "dispatch":
        q = np.resize(q, engine.large_min)      # the pallas tier
    else:
        assert engine.index.slack == 0
    _check(_engine_answer(engine), keys, q)
    widened = mon.channel(CH_WIDEN)
    assert widened.shape[0] == 3 and widened[:, 0].sum() > 0


def test_long_collision_run_caps_the_slack():
    """A run of 10^5 keys on one float32 value asks for a slack of ~10^5:
    it is capped where the window still fits the float32 form's kernel
    block, and the queries past it are settled by ``fit.widen``."""
    keys, error, q = _f32_collision_run(np.random.default_rng(183))
    table = SegmentTable.from_keys(keys, error, assume_sorted=True)
    cap = slack_cap(error)
    assert key_slack(table) == cap
    n = table.n_keys
    assert make_plan(n, error + cap).kb == make_plan(n, error).kb
    mon = Monitor()
    engine = make_engine(table, "pallas", monitor=mon)
    assert engine.index.slack == cap
    _check(_engine_answer(engine), keys, q)
    assert mon.channel(CH_WIDEN)[:, 0].sum() > 0


def test_shards_keep_their_own_slack():
    """Each shard's device engines search with its own table's slack: a
    shard of float32 collisions reaches further than one of plain float64
    keys, and the service stays exact across both."""
    rng = np.random.default_rng(182)
    keys, error, q = _f32_collisions(rng)
    plain = np.sort(rng.uniform(3e6, 4e6, keys.size))
    keys = np.concatenate([keys, plain])
    q = np.concatenate([q, plain[rng.integers(0, plain.size, 500)]])
    svc = ShardedIndexService(keys, error=error, n_shards=4,
                              backend="dispatch", assume_sorted=True)
    tables = [h.current().table for h in svc.shard_set.handles]
    slacks = [key_slack(t) for t in tables]
    assert min(slacks) == 1 < max(slacks)
    for backend in ("xla-bisect", "pallas"):
        assert [make_engine(t, backend).index.slack
                for t in tables] == slacks

    def answer(verb, side, q):
        return svc.lookup(q) if verb == "lookup" else svc.search(q, side)
    _check(answer, keys, q)


def test_float32_columns_keep_the_float32_form():
    """weblogs-194d's keys: whole seconds below 2^24, all float32."""
    keys = np.sort(np.floor(weblogs_like(1 << 16, days=194.0, seed=1)))
    assert f32_exact(keys)
    table = SegmentTable.from_keys(keys, 64, assume_sorted=True)
    idx = device_index(table)
    assert key_slack(table) is None and idx.slack == 0
    assert idx.keys.dtype == np.float32
    nbytes = sum(a.nbytes for a in jax.tree.leaves(tuple(idx)[:5]))
    assert nbytes == 4 * table.n_keys + 16 * table.n_segments


@pytest.mark.parametrize("backend", BACKENDS)
def test_float32_form_answers_any_float64_query(backend):
    """On float32 keys a query float32 cannot hold is rounded toward the
    searched side, misses as a lookup, and NaN sorts after every key."""
    rng = np.random.default_rng(181)
    keys = np.sort(np.floor(rng.uniform(0, 2 ** 24, 20_000)))
    keys = np.concatenate([keys, np.repeat(keys[5000], 300)])
    keys.sort()
    table = SegmentTable.from_keys(keys, 32, assume_sorted=True)
    pick = keys[rng.integers(0, keys.size, 800)]
    q = np.concatenate([pick, pick + 0.5, pick - 1e-3, pick + 1e-9,
                        [np.nan, np.inf, -np.inf, 1e300, -1e300, 0.0,
                         2.0 ** 24 + 1, keys[5000] + 0.25,
                         F32_MAX * (1 + 2 ** -30), -F32_MAX * (1 + 2 ** -30)]])
    engine = make_engine(table, backend)
    if backend == "dispatch":
        q = np.resize(q, engine.large_min)
    else:
        assert engine.index.keys.dtype == np.float32
    _check(_engine_answer(engine), keys, q)


@pytest.mark.parametrize("backend", BACKENDS)
def test_float32_keys_past_the_predictor_take_the_wide_form(backend):
    """float32 keys with a gap worth more than 2^30 ranks at its segment's
    slope: the float32 predictor would leave int32 inside the gap, so the
    table takes the wide form, whose search checks its window's edges."""
    keys = np.concatenate([np.arange(10_000.0) * 100,
                           2.0 ** 22 + np.arange(20_000.0),
                           [2.0 ** 40, 2.0 ** 40 + 2 ** 20]])
    assert f32_exact(keys)
    table = SegmentTable.from_keys(keys, 32, assume_sorted=True)
    assert key_slack(table) is not None
    q = np.concatenate([np.repeat([2.0 ** 39, 2.0 ** 36, 1e12, F32_MAX, 0.5,
                                   2.0 ** 40 + 1, -1.0], 30),
                        keys[np.random.default_rng(184).integers(
                            0, keys.size, 800)]])
    engine = make_engine(table, backend)
    if backend == "dispatch":
        q = np.resize(q, engine.large_min)
    else:
        assert isinstance(engine.index.keys, Wide)
    _check(_engine_answer(engine), keys, q)


def test_device_plane_refuses_64bit_keys():
    keys = np.sort(maps_like(1 << 12, seed=2))
    with pytest.raises(ValueError, match="64 bits"):
        DeviceShardedService(keys, error=64, device_count=1,
                             assume_sorted=True)
