"""Pallas TPU kernel: blocked linear recurrence for RG-LRU (RecurrentGemma).

h_t = a_t * h_{t-1} + u_t over time, independently per (batch, channel).
The channel dim is tiled into 128-lane blocks (grid = (B, W/bw)); each grid
step keeps its (T, bw) tile of a and u resident in VMEM and walks time with a
fori_loop carrying the (1, bw) state in registers/VMEM -- the memory-bound
roofline is one read of a,u + one write of h (3 * T * W * 4 B), with zero
HBM round-trips for the carried state (vs. 2x for a lax.scan whose carry
spills per step).

The associative-scan form (models/blocks._rglru_scan) remains the training
path (parallel depth log T); this kernel is the serving/long-context form
(sequential time, O(1) state) and the oracle for both is kernels/ref.rglru
/ _linear_scan_impl.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .platform import pallas_call


def _rglru_kernel(a_ref, u_ref, h0_ref, out_ref, hT_ref, *, t: int):
    h = h0_ref[0, :]                             # (bw,)

    def body(i, h):
        h = a_ref[0, i, :] * h + u_ref[0, i, :]
        out_ref[0, i, :] = h
        return h

    h = jax.lax.fori_loop(0, t, body, h)
    hT_ref[0, :] = h


def rglru_scan_pallas(u: jax.Array, a: jax.Array, h0: jax.Array | None = None,
                      *, bw: int = 128):
    """u, a: (B, T, W) f32; h0: (B, W) initial state.  Returns (h, h_last)."""
    b, t, w = u.shape
    assert w % bw == 0, (w, bw)
    if h0 is None:
        h0 = jnp.zeros((b, w), jnp.float32)
    grid = (b, w // bw)
    in_specs = [
        pl.BlockSpec((1, t, bw), lambda i, j: (i, 0, j)),
        pl.BlockSpec((1, t, bw), lambda i, j: (i, 0, j)),
        pl.BlockSpec((1, bw), lambda i, j: (i, j)),
    ]
    out_specs = [
        pl.BlockSpec((1, t, bw), lambda i, j: (i, 0, j)),
        pl.BlockSpec((1, bw), lambda i, j: (i, j)),
    ]

    h, h_last = pallas_call(
        functools.partial(_rglru_kernel, t=t),
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=[jax.ShapeDtypeStruct((b, t, w), jnp.float32),
                   jax.ShapeDtypeStruct((b, w), jnp.float32)],
    )(a.astype(jnp.float32), u.astype(jnp.float32), h0.astype(jnp.float32))
    return h, h_last
