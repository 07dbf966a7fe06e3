"""Mean host wall time of one call to a device tier (``tier.medium`` and
``tier.large``, the xla-bisect and pallas tiers), in microseconds: the
``tier.*`` rows' ``wall_ns``, taken by the engine on the host clock around a
call that ends in a blocking read of the answer."""

DEVICE_TIERS = ("tier.medium", "tier.large")


def read(ctx):
    walls = [ctx.channels[name][:, 1] for name in DEVICE_TIERS
             if name in ctx.channels and ctx.channels[name].size]
    if not walls:
        return None
    total = sum(float(w.sum()) for w in walls)
    calls = sum(w.size for w in walls)
    return total / calls / 1000.0
