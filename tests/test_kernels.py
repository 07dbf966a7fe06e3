"""Pallas fitting_lookup kernel vs the pure-jnp oracle (interpreted on CPU).

Sweeps shapes / errors / distributions / duplicates / overflow, per the brief.
"""
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import build_device_index
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
