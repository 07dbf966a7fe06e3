"""Mean host time of one fused flush outside the engine calls, in
microseconds: each ``pipeline.flush`` span's duration minus what its
``tier.*`` child spans cover.  What is left is fusing the requests,
routing them to shards, lifting the local ranks and answering the futures
(``index/pipeline.py``, ``index/sharded.py``).  Flushes count when they
start in the window, each with all of its calls."""

import numpy as np

from bench import spans

TIERS = ("tier.small", "tier.medium", "tier.large")


def read(ctx):
    flushes = spans.rows(ctx, "pipeline.flush", attrs=2)
    if flushes is None or flushes.shape[0] == 0:
        return None
    calls = [spans.all_rows(ctx, name, attrs=2) for name in TIERS]
    calls = [c for c in calls if c is not None]
    children = np.concatenate(calls) if calls else flushes[:0]
    return float(spans.self_ns(flushes, children).mean()) / 1000.0
