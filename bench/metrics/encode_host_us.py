"""Mean time, in microseconds, of the step of a device tier's call that
puts the queries in the key form (``index/engine.py`` ``_DeviceEngine._run``,
the ``engine.encode`` span inside ``engine.h2d``): each query rounded to
float32, or its float32 rounding and the two words of its 64-bit code, and
the bucket padding.  Absent where the program records no such span."""

from bench import spans


def read(ctx):
    return spans.mean_us(ctx, "engine.encode")
