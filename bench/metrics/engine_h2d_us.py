"""Mean time, in microseconds, of one step of a device tier's call
(``index/engine.py`` ``_DeviceEngine._run``): the bucket padding of the
queries and their copy to the device (``engine.h2d``)."""

from bench import spans


def read(ctx):
    return spans.mean_us(ctx, "engine.h2d")
