"""Bytes of every live JAX array on the cell's device after the warm-up,
over the keys served: the index's HBM cost per key.  The benchmark holds no
device arrays of its own."""


def read(ctx):
    return ctx.device_bytes / ctx.n_keys
