"""Engine calls per 1000 ops: the shard fan-out and dispatch layer
(``index/sharded.py``, ``index/engine.py`` ``DispatchEngine``), from the
rows of the ``tier.*`` channels recorded in the window (one per call)."""


def read(ctx):
    calls = sum(rows.shape[0] for name, rows in ctx.channels.items()
                if name.startswith("tier.") and rows.size)
    if calls == 0 or ctx.ops == 0:
        return None
    return calls / (ctx.ops / 1000.0)
