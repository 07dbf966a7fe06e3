"""Least work of a bounded-window index probe, for ``search_roofline``.

For one probe the least the chip must move is the query in (one key), the
answer out (a 4 B rank) and the paper's bounded search window of
2 * error + 2 keys (FITing-Tree Sec. 6 cost model), keys at the width the
configuration states: the segment lookup is left out, so this is a floor.
The search does no arithmetic worth a compute bound, so HBM bandwidth
bounds it.  The count does not depend on which tier or kernel serves the
probe."""
from __future__ import annotations

import numpy as np

ANSWER_BYTES = 4


def key_bytes(key_dtype: str) -> int:
    """Bytes of one key of the configuration's ``key_dtype``."""
    return np.dtype(key_dtype).itemsize


def probe_bytes(error: int, key_bytes: int) -> int:
    """Least HBM bytes of one probe at the configuration's error."""
    return key_bytes + ANSWER_BYTES + (2 * error + 2) * key_bytes


def least_seconds(ops: int, error: int, key_bytes: int,
                  hbm_bytes_per_s: float) -> float:
    """Least time for ``ops`` probes at the chip's HBM peak."""
    return ops * probe_bytes(error, key_bytes) / hbm_bytes_per_s
