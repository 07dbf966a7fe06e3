"""Mean time, in microseconds, of one step of a device tier's call
(``index/engine.py`` ``_DeviceEngine._run``): the jitted call from its
start until it returns to the host (``engine.launch``)."""

from bench import spans


def read(ctx):
    return spans.mean_us(ctx, "engine.launch")
