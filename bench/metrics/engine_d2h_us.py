"""Mean time, in microseconds, of one step of a device tier's call
(``index/engine.py`` ``_DeviceEngine._run``): the blocking read of the
answer back to the host (``engine.d2h``), which also waits for the device
to finish."""

from bench import spans


def read(ctx):
    return spans.mean_us(ctx, "engine.d2h")
