"""Pallas TPU kernel for batched FITing-Tree lookups (the paper's hot path).

TPU-native formulation (DESIGN.md Sec. 2): after the (cheap, XLA-side) router
pass predicts each query's position, every query owns a +-error *window* of the
sorted key column.  Queries are bucketed by the key block their window starts
in; the kernel answers each block's bucket with a **gather-free masked
compare-reduce**:

    rank(q)  = window_start + #{ j in window : keys[j] < q }
    found(q) = any( j in window : keys[j] == q )

Because a window (2e+2 keys, e = error) never spans more than two consecutive
key blocks when KB >= 2e+2, bucket b only reads key blocks b and b+1.

Layout (every block is (8, 128)-tiled, as Mosaic requires): the key column
is viewed as a (n_blocks, KB) matrix, one key block per row, and the buckets
as (n_blocks, QCAP) matrices.  A grid step takes 8 consecutive rows of each
(one sublane tile) plus the next 8 key rows, for the b+1 neighbour of its
last bucket.  Per bucket, 128-key chunks of the window are transposed into a
column and compared against the bucket's query row, so the compare tile is
(128, QCAP) and the count reduces over sublanes straight into the output
row.  All shapes are static; there is no gather and no revisit.

Memory per grid step (VMEM): 2 x 8 x KB x 4 B of keys + 8 x QCAP x 16 B of
queries, starts and outputs, plus (128, QCAP) compare tiles -- well under the
scoped VMEM budget for every error.

Bucket overflow (more than QCAP windows starting in one block) is detected in
the wrapper and those queries fall back to the XLA bisect path (engine.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .platform import pallas_call

ROWS = 8       # buckets per grid step: one f32 sublane tile
LANES = 128    # keys per compare chunk: one lane tile


def _lookup_kernel(keys_a_ref, keys_b_ref, q_ref, qlo_ref,
                   rank_ref, found_ref, *, kb: int, window: int,
                   side: str = "left"):
    g = pl.program_id(0)
    chunks = kb // LANES
    for r in range(ROWS):
        q = q_ref[pl.ds(r, 1), :]                                     # (1, QCAP)
        qlo = qlo_ref[pl.ds(r, 1), :]                                 # (1, QCAP)
        base = (g * ROWS + r) * kb               # global index of key block r
        # the window spans this bucket's key row and the next one; the next
        # row of the last bucket in the step is row 0 of the following step
        halves = ((keys_a_ref, r, 0),
                  (keys_a_ref, r + 1, kb) if r + 1 < ROWS else
                  (keys_b_ref, 0, kb))

        def count(c, acc, ref, row, off):
            cnt, eq = acc
            start = pl.multiple_of(c * LANES, LANES)
            col = ref[pl.ds(row, 1), pl.ds(start, LANES)].T           # (128, 1)
            j = base + off + start + jax.lax.broadcasted_iota(
                jnp.int32, (LANES, 1), 0)
            in_win = (j >= qlo) & (j < qlo + window)                  # (128, QCAP)
            # side is static: "left" counts keys < q (rank of the first key
            # >= q), "right" counts keys <= q (one past the last key <= q) --
            # the same masked compare-reduce serves point lookups and both
            # search sides
            below = (col < q) if side == "left" else (col <= q)
            cnt = cnt + jnp.sum((in_win & below).astype(jnp.int32), axis=0,
                                keepdims=True)
            eq = jnp.maximum(eq, jnp.max((in_win & (col == q)).astype(
                jnp.int32), axis=0, keepdims=True))
            return cnt, eq

        acc = (jnp.zeros_like(qlo), jnp.zeros_like(qlo))
        for ref, row, off in halves:
            acc = jax.lax.fori_loop(
                0, chunks, functools.partial(count, ref=ref, row=row, off=off),
                acc)
        rank_ref[pl.ds(r, 1), :] = qlo + acc[0]
        found_ref[pl.ds(r, 1), :] = acc[1]


def fitting_lookup_pallas(keys_padded: jax.Array, q_bucketed: jax.Array,
                          qlo_bucketed: jax.Array, *, kb: int, window: int,
                          side: str = "left") -> tuple[jax.Array, jax.Array]:
    """Run the kernel over all key blocks.

    Args:
      keys_padded:  (n_blocks*KB,) f32, padded with +inf.
      q_bucketed:   (n_blocks, QCAP) f32 queries (+inf padding).
      qlo_bucketed: (n_blocks, QCAP) i32 global window starts
                    (must satisfy qlo // KB == block row).
      kb:           key block size (multiple of 128, >= window).
      window:       2*error + 2.
      side:         "left" counts keys < q (point lookups and left search),
                    "right" counts keys <= q (right search); static.
    ``n_blocks`` must be a multiple of 8 and ``QCAP`` of 128
    (:func:`repro.index.engine.make_plan` pads to both).
    Returns:
      rank:  (n_blocks, QCAP) i32 -- global rank of each bucketed query
             (the searchsorted insertion rank when the true rank is in the
             window; the wrapper's snap repairs straddling duplicate runs).
      found: (n_blocks, QCAP) bool.
    """
    n_blocks, qcap = q_bucketed.shape
    assert keys_padded.shape[0] == n_blocks * kb
    assert window <= kb and kb % LANES == 0, (window, kb)
    assert n_blocks % ROWS == 0 and qcap % LANES == 0, (n_blocks, qcap)
    last = n_blocks // ROWS - 1

    grid_spec = pl.GridSpec(
        grid=(n_blocks // ROWS,),
        in_specs=[
            pl.BlockSpec((ROWS, kb), lambda g: (g, 0)),              # key rows
            pl.BlockSpec((ROWS, kb),                                 # next rows
                         lambda g: (jnp.minimum(g + 1, last), 0)),
            pl.BlockSpec((ROWS, qcap), lambda g: (g, 0)),            # queries
            pl.BlockSpec((ROWS, qcap), lambda g: (g, 0)),            # starts
        ],
        out_specs=[
            pl.BlockSpec((ROWS, qcap), lambda g: (g, 0)),
            pl.BlockSpec((ROWS, qcap), lambda g: (g, 0)),
        ],
    )
    kernel = functools.partial(_lookup_kernel, kb=kb, window=window, side=side)
    keys2d = keys_padded.reshape(n_blocks, kb)
    rank, found = pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((n_blocks, qcap), jnp.int32),
            jax.ShapeDtypeStruct((n_blocks, qcap), jnp.int32),
        ],
    )(keys2d, keys2d, q_bucketed, qlo_bucketed)
    return rank, found.astype(jnp.bool_)
