"""99th percentile, in ms, over every request issued in the window, from
issue to answer (host clock); a request still open at the close counts
with its age then."""

import numpy as np


def read(ctx):
    lat = ctx.window["latencies_ms"]
    if lat.size == 0:
        return None
    return float(np.percentile(lat, 99))
