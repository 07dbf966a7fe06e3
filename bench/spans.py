"""The program's span rows, as the per-layer readers take them.

A span row (``repro.index.telemetry.Monitor.span``) is the channel's
attribute columns followed by ``start_ns``, ``dur_ns``, ``span_id`` and
``parent_id``, on the ``time.perf_counter_ns`` clock the window is timed on.
A reader counts a span when it starts inside the window.  A channel whose
rows are not that wide (a program that records no spans there) reads as
absent, so its readers return nothing.
"""
from __future__ import annotations

import numpy as np

START, DUR, ID, PARENT = -4, -3, -2, -1


def all_rows(ctx, channel: str, attrs: int = 0) -> np.ndarray | None:
    """Every span row of ``channel`` (``attrs`` attribute columns first),
    or None when the channel holds no span rows."""
    got = ctx.channels.get(channel)
    if got is None or got.ndim != 2 or got.shape[0] == 0 \
            or got.shape[1] != attrs + 4:
        return None
    return got


def rows(ctx, channel: str, attrs: int = 0) -> np.ndarray | None:
    """``channel``'s span rows that start in ``[t_open, t_open + window
    seconds)``, or None when the channel holds no span rows."""
    got = all_rows(ctx, channel, attrs)
    if got is None:
        return None
    lo = ctx.t_open
    hi = lo + ctx.window["seconds"] * 1e9
    start = got[:, START]
    return got[(start >= lo) & (start < hi)]


def mean_us(ctx, channel: str, attrs: int = 0) -> float | None:
    """Mean duration, in microseconds, of ``channel``'s spans in the
    window."""
    got = rows(ctx, channel, attrs)
    if got is None or got.shape[0] == 0:
        return None
    return float(got[:, DUR].mean()) / 1000.0


def self_ns(parents: np.ndarray, children: np.ndarray) -> np.ndarray:
    """Each parent span's duration minus the part of its interval that its
    children (rows whose ``parent_id`` is its ``span_id``) cover; children
    that overlap one another count once."""
    out = parents[:, DUR].copy()
    by_parent: dict[float, list[tuple[float, float]]] = {}
    for c in children:
        by_parent.setdefault(c[PARENT], []).append(
            (c[START], c[START] + c[DUR]))
    for i, p in enumerate(parents):
        lo, hi = p[START], p[START] + p[DUR]
        covered, end = 0.0, lo
        for s, e in sorted(by_parent.get(p[ID], ())):
            s, e = max(s, end), min(e, hi)
            if e > s:
                covered += e - s
                end = e
        out[i] -= covered
    return out
