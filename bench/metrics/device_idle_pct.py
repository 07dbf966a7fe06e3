"""Device idle share of the traced stretch: 1 - (union of device-op
intervals / stretch), from the profiler trace (``bench.xplane``)."""


def read(ctx):
    if ctx.trace is None or ctx.trace.devices == 0:
        return None
    return 100.0 * ctx.trace.idle_share
