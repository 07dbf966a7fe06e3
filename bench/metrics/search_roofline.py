"""Share of the search's roofline: the least time the chip needs for the
probes answered in the traced stretch (``bench.roofline``: query, answer
and the bounded window, keys at the configuration's width, at HBM
peak) over the device's busy time there."""

from bench import roofline


def read(ctx):
    if ctx.trace is None or ctx.trace.busy_s <= 0 or ctx.peaks is None:
        return None
    a, b = ctx.traced_ops_window
    ops = sum(r.idx.size for r in ctx.log if r.t_done is not None
              and r.error is None and a <= r.t_done < b)
    if ops == 0:
        return None
    least = roofline.least_seconds(
        ops, ctx.config["error"], roofline.key_bytes(ctx.config["key_dtype"]),
        ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / ctx.trace.busy_s
