"""Pallas fitting_lookup kernel: correctness vs oracle + device-path timing
(XLA window/bisect strategies; interpret-mode kernel checked for equality,
its wall-clock is not meaningful on CPU)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import build_device_index, lookup
from repro.kernels.ops import fitting_lookup, make_plan
from repro.kernels.ref import lookup_ref

from .common import emit, timeit, write_csv

N = 100_000
NQ = 4096


def run():
    rng = np.random.default_rng(6)
    keys = np.sort(rng.choice(2 ** 23, size=N, replace=False)).astype(np.float64)
    q = jnp.asarray(keys[rng.integers(0, N, size=NQ)], jnp.float32)
    rows = []
    for e in (16, 64, 256):
        idx = build_device_index(keys, e)
        got = np.asarray(fitting_lookup(idx, q[:512]))
        want = np.asarray(lookup_ref(idx.keys, q[:512]))
        assert np.array_equal(got, want), "kernel != oracle"
        f_win = jax.jit(lambda qq, i=idx: lookup(i, qq, "window"))
        f_bis = jax.jit(lambda qq, i=idx: lookup(i, qq, "bisect"))
        f_ref = jax.jit(lambda qq, i=idx: lookup_ref(i.keys, qq))
        t_win = timeit(lambda: f_win(q).block_until_ready()) / NQ * 1e9
        t_bis = timeit(lambda: f_bis(q).block_until_ready()) / NQ * 1e9
        t_ref = timeit(lambda: f_ref(q).block_until_ready()) / NQ * 1e9
        plan = make_plan(N, e)
        hbm_bytes = plan.window * 4  # per query window DMA on TPU
        rows.append((e, t_win, t_bis, t_ref, plan.kb, hbm_bytes))
        emit("kernel", f"window_ns_e{e}", t_win,
             f"bisect={t_bis:.0f}ns;full_searchsorted={t_ref:.0f}ns")
    write_csv("kernel_lookup", ["error", "window_ns", "bisect_ns",
                                "searchsorted_ns", "kb", "hbm_bytes_per_q"],
              rows)
    return rows


if __name__ == "__main__":
    run()
