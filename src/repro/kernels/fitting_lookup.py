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

from repro.index import keycode
from repro.index.keycode import Wide

from .platform import pallas_call

ROWS = 8       # buckets per grid step: one f32 sublane tile
LANES = 128    # keys per compare chunk: one lane tile


def _word(planes):
    """One key (or query) value from its planes: the float32 value, or the
    two words of a 64-bit code (``repro.index.keycode.Wide``)."""
    planes = tuple(planes)
    return Wide(*planes) if len(planes) == 2 else planes[0]


def _lookup_kernel(*refs, kb: int, window: int, side: str = "left",
                   planes: int = 1):
    # refs: the key rows (one ref per plane), the next key rows, the
    # queries (one per plane), the window starts, then the two outputs
    keys_a = refs[:planes]
    keys_b = refs[planes:2 * planes]
    q_refs = refs[2 * planes:3 * planes]
    qlo_ref, rank_ref, found_ref = refs[3 * planes:]
    # side is static: "left" counts keys < q (rank of the first key >= q),
    # "right" counts keys <= q (one past the last key <= q) -- the same
    # masked compare-reduce serves point lookups and both search sides
    below = keycode.below(side)
    g = pl.program_id(0)
    chunks = kb // LANES
    for r in range(ROWS):
        q = _word(ref[pl.ds(r, 1), :] for ref in q_refs)              # (1, QCAP)
        qlo = qlo_ref[pl.ds(r, 1), :]                                 # (1, QCAP)
        base = (g * ROWS + r) * kb               # global index of key block r
        # the window spans this bucket's key row and the next one; the next
        # row of the last bucket in the step is row 0 of the following step
        halves = ((keys_a, r, 0),
                  (keys_a, r + 1, kb) if r + 1 < ROWS else
                  (keys_b, 0, kb))

        def count(c, acc, key_refs, row, off):
            cnt, eq = acc
            start = pl.multiple_of(c * LANES, LANES)
            col = _word(ref[pl.ds(row, 1), pl.ds(start, LANES)].T     # (128, 1)
                        for ref in key_refs)
            j = base + off + start + jax.lax.broadcasted_iota(
                jnp.int32, (LANES, 1), 0)
            in_win = (j >= qlo) & (j < qlo + window)                  # (128, QCAP)
            cnt = cnt + jnp.sum((in_win & below(col, q)).astype(jnp.int32),
                                axis=0, keepdims=True)
            eq = jnp.maximum(eq, jnp.max((in_win & keycode.eq(col, q)).astype(
                jnp.int32), axis=0, keepdims=True))
            return cnt, eq

        acc = (jnp.zeros_like(qlo), jnp.zeros_like(qlo))
        for key_refs, row, off in halves:
            acc = jax.lax.fori_loop(
                0, chunks,
                functools.partial(count, key_refs=key_refs, row=row, off=off),
                acc)
        rank_ref[pl.ds(r, 1), :] = qlo + acc[0]
        found_ref[pl.ds(r, 1), :] = acc[1]


def fitting_lookup_pallas(keys_padded, q_bucketed, qlo_bucketed: jax.Array,
                          *, kb: int, window: int, side: str = "left"
                          ) -> tuple[jax.Array, jax.Array]:
    """Run the kernel over all key blocks.

    Args:
      keys_padded:  (n_blocks*KB,) f32, padded with +inf; or a ``Wide`` pair
                    of (n_blocks*KB,) i32 code words, padded with
                    ``keycode.TOP`` (the kernel then compares the codes
                    lexicographically, high word first).
      q_bucketed:   (n_blocks, QCAP) queries of the keys' form (filler
                    above every key).
      qlo_bucketed: (n_blocks, QCAP) i32 global window starts
                    (must satisfy qlo // KB == block row).
      kb:           key block size (multiple of 128, >= window).
      window:       the window's length: 2*error + 2.
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
    keys = tuple(keys_padded) if isinstance(keys_padded, Wide) \
        else (keys_padded,)
    queries = tuple(q_bucketed) if isinstance(q_bucketed, Wide) \
        else (q_bucketed,)
    planes = len(keys)
    n_blocks, qcap = queries[0].shape
    assert keys[0].shape[0] == n_blocks * kb
    assert window <= kb and kb % LANES == 0, (window, kb)
    assert n_blocks % ROWS == 0 and qcap % LANES == 0, (n_blocks, qcap)
    last = n_blocks // ROWS - 1

    grid_spec = pl.GridSpec(
        grid=(n_blocks // ROWS,),
        in_specs=(
            [pl.BlockSpec((ROWS, kb), lambda g: (g, 0))] * planes    # key rows
            + [pl.BlockSpec((ROWS, kb),                              # next rows
                            lambda g: (jnp.minimum(g + 1, last), 0))] * planes
            + [pl.BlockSpec((ROWS, qcap), lambda g: (g, 0))] * planes  # queries
            + [pl.BlockSpec((ROWS, qcap), lambda g: (g, 0))]           # starts
        ),
        out_specs=[
            pl.BlockSpec((ROWS, qcap), lambda g: (g, 0)),
            pl.BlockSpec((ROWS, qcap), lambda g: (g, 0)),
        ],
    )
    kernel = functools.partial(_lookup_kernel, kb=kb, window=window,
                               side=side, planes=planes)
    keys2d = [k.reshape(n_blocks, kb) for k in keys]
    rank, found = pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((n_blocks, qcap), jnp.int32),
            jax.ShapeDtypeStruct((n_blocks, qcap), jnp.int32),
        ],
    )(*keys2d, *keys2d, *queries, qlo_bucketed)
    return rank, found.astype(jnp.bool_)
