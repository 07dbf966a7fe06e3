"""Queries that a device tier searched again over the whole key column
because their answer could lie outside the bounded window (the program's
``fit.widen`` step on 64-bit keys), per thousand queries answered in the
window: the ``engine.widen`` rows (one per engine call, recorded from the
window's opening until the clients stop) summed, over the window's ops.
Absent where the program records no such channel."""

CHANNEL = "engine.widen"


def read(ctx):
    rows = ctx.channels.get(CHANNEL)
    if rows is None or rows.ndim != 2 or rows.shape[1] != 1 \
            or ctx.ops == 0:
        return None
    return 1000.0 * float(rows[:, 0].sum()) / ctx.ops
