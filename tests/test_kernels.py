"""Pallas fitting_lookup kernel vs the pure-jnp oracle (interpreted on CPU).

Sweeps shapes / errors / distributions / duplicates / overflow, per the brief.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st
from jax.extend.core import ClosedJaxpr, Jaxpr

from repro.core import build_device_index
from repro.index.engine import (DeviceIndex, _pallas_bucketize, _run_on_index,
                                pallas_lookup, pallas_search)
from repro.kernels.ops import fitting_lookup, make_plan
from repro.kernels.ref import lookup_ref


def _keys(n, seed=0, dist="uniform"):
    rng = np.random.default_rng(seed)
    if dist == "uniform":
        ks = np.sort(rng.choice(2 ** 23, size=n, replace=False))
    elif dist == "clustered":
        centers = rng.choice(2 ** 22, size=max(4, n // 200), replace=False)
        ks = np.sort((centers[rng.integers(0, len(centers), n)]
                      + rng.integers(0, 2 ** 10, n)))
    elif dist == "dups":
        ks = np.sort(rng.choice(2 ** 12, size=n, replace=True))
    return ks.astype(np.float64)


def _check(keys, error, queries, qcap=256):
    idx = build_device_index(keys, error)
    q = jnp.asarray(queries, jnp.float32)
    got = np.asarray(fitting_lookup(idx, q, qcap=qcap))
    want = np.asarray(lookup_ref(idx.keys, q))
    found = want >= 0
    # ranks of found queries must locate an equal key (with duplicates any
    # occurrence is a correct answer; lookup_ref returns the leftmost)
    ks32 = keys.astype(np.float32)
    assert np.array_equal(got >= 0, found), "presence mismatch"
    if found.any():
        np.testing.assert_array_equal(ks32[got[found]], np.asarray(q)[found])


@pytest.mark.parametrize("n", [100, 1000, 20_000])
@pytest.mark.parametrize("error", [4, 16, 64, 250])
def test_sweep_sizes_errors(n, error):
    keys = _keys(n, seed=n + error)
    rng = np.random.default_rng(1)
    q = np.concatenate([keys[rng.integers(0, n, size=128)],
                        keys[rng.integers(0, n, size=64)] + 0.5])
    _check(keys, error, q)


@pytest.mark.parametrize("dist", ["uniform", "clustered", "dups"])
def test_sweep_distributions(dist):
    keys = _keys(5000, seed=7, dist=dist)
    rng = np.random.default_rng(2)
    q = np.concatenate([keys[rng.integers(0, keys.shape[0], size=200)],
                        rng.uniform(0, 2 ** 23, size=100)])
    _check(keys, 32, q)


def test_bucket_overflow_fallback():
    """All queries in one block at qcap=128 -> overflow path must still answer."""
    keys = _keys(10_000, seed=3)
    q = np.repeat(keys[500], 300)  # 300 identical queries, one block
    _check(keys, 16, q, qcap=128)


def test_query_batch_edge_sizes():
    keys = _keys(2000, seed=4)
    for nq in (1, 2, 127, 128, 129):
        q = keys[np.arange(nq) % keys.shape[0]]
        _check(keys, 8, q)


def test_plan_geometry():
    p = make_plan(n_keys=1000, error=4)
    assert p.kb == 128 and p.window == 10 and p.n_pad % p.kb == 0
    p = make_plan(n_keys=10 ** 6, error=250)
    assert p.kb == 512 and p.kb >= p.window


def test_matches_ref_exactly_on_ranks_without_dups():
    keys = _keys(8000, seed=5)
    idx = build_device_index(keys, 64)
    rng = np.random.default_rng(6)
    q = jnp.asarray(keys[rng.integers(0, 8000, 400)], jnp.float32)
    got = np.asarray(fitting_lookup(idx, q))
    want = np.asarray(lookup_ref(idx.keys, q))
    np.testing.assert_array_equal(got, want)


@given(seed=st.integers(0, 25), error=st.sampled_from([4, 30, 120]),
       n=st.sampled_from([64, 500, 3000]))
@settings(max_examples=15, deadline=None)
def test_property_kernel_equals_oracle(seed, error, n):
    keys = _keys(n, seed=seed)
    rng = np.random.default_rng(seed + 1)
    q = np.concatenate([keys[rng.integers(0, n, size=64)],
                        rng.uniform(0, 2 ** 23, size=32)])
    idx = build_device_index(keys, error)
    got = np.asarray(fitting_lookup(idx, jnp.asarray(q, jnp.float32)))
    want = np.asarray(lookup_ref(idx.keys, jnp.asarray(q, jnp.float32)))
    np.testing.assert_array_equal(got, want)


# --- read-back at each query's bucket slot (engine.pallas_lookup / _search)
def _read_back_case(case):
    """(keys, error, qcap, queries) with duplicate runs and absent keys.
    ``crowded``: ~400 queries start their windows in one key block, past
    ``qcap``.  ``sparse``: n_blocks x qcap is over 100x the batch."""
    rng = np.random.default_rng(11)
    if case == "crowded":
        n, error, qcap, nq = 20_000, 16, 128, 777
    else:
        n, error, qcap, nq = 1 << 16, 64, 256, 333
    keys = np.sort(rng.choice(2 ** 22, size=n, replace=False) * 2)
    keys[n // 3:n // 3 + 600] = keys[n // 3]        # a run past any window
    keys[n // 2:n // 2 + 40] = keys[n // 2]
    keys = np.sort(keys).astype(np.float64)
    parts = [keys[[0, n // 3, n // 3 + 599, n // 2 + 39, n - 1]],
             [-5.0, 2.0 ** 24],                      # below and above all
             keys[rng.integers(0, n, 60)] + 1]       # odd: absent
    if case == "crowded":
        parts.append(keys[5000 + rng.integers(0, 100, 400)])
    parts.append(keys[rng.integers(0, n, nq - sum(map(len, parts)))])
    return keys, error, qcap, rng.permutation(np.concatenate(parts))


@pytest.mark.parametrize("case", ["crowded", "sparse"])
@pytest.mark.parametrize("op", ["lookup", "left", "right"])
def test_pallas_read_back_matches_searchsorted(case, op):
    keys, error, qcap, q = _read_back_case(case)
    idx = build_device_index(keys, error)
    q = jnp.asarray(q, jnp.float32)
    plan = make_plan(keys.shape[0], error)
    *_, slots = _pallas_bucketize(idx, q, plan, qcap)
    if case == "crowded":
        assert not bool(slots.ok.all())             # the fallback runs
    else:
        assert plan.n_blocks * qcap > 100 * q.shape[0]
    if op == "lookup":
        impl, opts = pallas_lookup, (("fallback", True), ("qcap", qcap))
        want = np.asarray(lookup_ref(idx.keys, q))
    else:
        impl, opts = pallas_search, (("qcap", qcap), ("side", op))
        want = np.searchsorted(np.asarray(idx.keys), np.asarray(q), op)
    got = np.asarray(_run_on_index(tuple(idx)[:5], q, impl=impl,
                                   error=error, opts=opts))
    assert got.shape == q.shape
    np.testing.assert_array_equal(got, want)


def _array_eqns(jaxpr):
    """Every equation of ``jaxpr`` and its sub-jaxprs (cond branches, loop
    bodies), except inside a Pallas kernel body."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "pallas_call":
            continue
        for p in eqn.params.values():
            for sub in p if isinstance(p, (tuple, list)) else (p,):
                if isinstance(sub, ClosedJaxpr):
                    sub = sub.jaxpr
                if isinstance(sub, Jaxpr):
                    yield from _array_eqns(sub)


@pytest.mark.parametrize("op", ["lookup", "left", "right"])
def test_pallas_read_back_touches_only_the_batch(op):
    """No scatter writes, no sort orders, and no gather reads more than nq
    elements, however many bucket slots the plan has."""
    error, nq = 64, 512
    keys = np.arange(1 << 16, dtype=np.float64) * 3
    idx = build_device_index(keys, error)
    plan = make_plan(keys.shape[0], error)
    assert plan.n_blocks * 256 >= 100 * nq

    def fn(arrays, q):
        if op == "lookup":
            return pallas_lookup(DeviceIndex(*arrays, error), q)
        return pallas_search(DeviceIndex(*arrays, error), q, op)

    jaxpr = jax.make_jaxpr(fn)(tuple(idx)[:5],
                               jax.ShapeDtypeStruct((nq,), jnp.float32))
    seen = set()
    for eqn in _array_eqns(jaxpr.jaxpr):
        name = eqn.primitive.name
        if name.startswith("scatter"):
            size = eqn.invars[2].aval.size              # its updates
        elif name == "sort":
            size = eqn.invars[0].aval.size
        elif name == "gather":
            size = eqn.outvars[0].aval.size             # the elements read
        else:
            continue
        seen.add(name)
        assert size <= nq, (name, [v.aval for v in eqn.invars])
    assert {"scatter", "sort", "gather"} <= seen
