"""flash_attention + rglru_scan Pallas kernels vs pure-jnp oracles
(interpreted on the CPU backend), sweeping shapes/masks/dtypes per the brief."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import flash_attention
from repro.kernels.ref import attention_ref
from repro.kernels.rglru_scan import rglru_scan_pallas
from repro.models.blocks import _linear_scan_impl


def _qkv(b, h, hkv, tq, s, hd, seed=0, dtype=jnp.float32):
    ks = jax.random.split(jax.random.key(seed), 3)
    q = jax.random.normal(ks[0], (b, h, tq, hd), dtype)
    k = jax.random.normal(ks[1], (b, hkv, s, hd), dtype)
    v = jax.random.normal(ks[2], (b, hkv, s, hd), dtype)
    return q, k, v


def _ref(q, k, v, **kw):
    g = q.shape[1] // k.shape[1]
    kr = jnp.repeat(k, g, axis=1)
    vr = jnp.repeat(v, g, axis=1)
    return attention_ref(q, kr, vr, **kw)


@pytest.mark.parametrize("tq,s", [(128, 128), (256, 384), (100, 200)])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_causal_shapes(tq, s, causal):
    q, k, v = _qkv(2, 4, 2, tq, s, 64)
    got = flash_attention(q, k, v, causal=causal)
    want = _ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_flash_window_and_softcap():
    q, k, v = _qkv(1, 4, 4, 256, 256, 32, seed=3)
    got = flash_attention(q, k, v, causal=True, window=64, softcap=50.0)
    want = _ref(q, k, v, causal=True, window=64, softcap=50.0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_flash_decode_one_query():
    """Tq=1 against a long KV (the decode shape): end-aligned positions."""
    q, k, v = _qkv(2, 8, 2, 1, 512, 64, seed=5)
    got = flash_attention(q, k, v, causal=True)
    want = _ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_flash_bf16():
    q, k, v = _qkv(1, 2, 2, 128, 128, 64, seed=7, dtype=jnp.bfloat16)
    got = flash_attention(q, k, v, causal=True)
    want = _ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("b,t,w", [(2, 16, 128), (1, 100, 256), (3, 7, 384)])
def test_rglru_kernel_matches_scan(b, t, w):
    rng = np.random.default_rng(b + t)
    u = jnp.asarray(rng.normal(size=(b, t, w)), jnp.float32)
    a = jnp.asarray(rng.uniform(0.3, 0.99, size=(b, t, w)), jnp.float32)
    got, h_last = rglru_scan_pallas(u, a)
    want = _linear_scan_impl(u, a)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(h_last), np.asarray(want[:, -1]),
                               rtol=1e-5, atol=1e-6)


def test_rglru_kernel_initial_state():
    rng = np.random.default_rng(0)
    u = jnp.asarray(rng.normal(size=(2, 8, 128)), jnp.float32)
    a = jnp.asarray(rng.uniform(0.5, 0.9, size=(2, 8, 128)), jnp.float32)
    h0 = jnp.asarray(rng.normal(size=(2, 128)), jnp.float32)
    got, _ = rglru_scan_pallas(u, a, h0)
    # sequential reference with initial state
    h = np.asarray(h0)
    outs = []
    for ti in range(8):
        h = np.asarray(a[:, ti]) * h + np.asarray(u[:, ti])
        outs.append(h.copy())
    np.testing.assert_allclose(np.asarray(got),
                               np.stack(outs, axis=1), rtol=1e-5, atol=1e-6)
