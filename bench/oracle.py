"""The plain reference: ``np.searchsorted`` on the key column, in the
configuration's key type.

It imports nothing of the program and takes nothing the program made: the
key column is the benchmark's own, from the configuration's generator.  A
lookup's answer is the leftmost rank of the key, or -1 when it is absent."""
from __future__ import annotations

import numpy as np


def lookup(column: np.ndarray, queries: np.ndarray) -> np.ndarray:
    """Leftmost rank of each query in the sorted ``column``, -1 if absent,
    compared in the column's own type.  Queries are searched once each, in
    sorted order (fast and exact)."""
    q = np.asarray(queries, column.dtype)
    uq, inv = np.unique(q, return_inverse=True)
    rank = np.searchsorted(column, uq, side="left")
    hit = rank < column.size
    hit[hit] = column[rank[hit]] == uq[hit]
    return np.where(hit, rank, -1)[inv.ravel()]


def compare(column: np.ndarray, queries: np.ndarray,
            answers: np.ndarray) -> int:
    """How many answers differ from the reference."""
    want = lookup(column, queries)
    return int(np.count_nonzero(np.asarray(answers, np.int64) != want))
