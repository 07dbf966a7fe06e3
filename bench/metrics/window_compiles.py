"""Executables JAX built inside the measured window (JAX's
``backend_compile_duration`` events): 0 when the warm-up covered every
shape the traffic reaches."""


def read(ctx):
    return ctx.window_compiles
