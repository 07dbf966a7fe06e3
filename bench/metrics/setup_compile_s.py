"""Seconds JAX spent building executables during set-up (compiling, or
loading from the persistent cache), summed over threads, from the same
events as ``window_compiles``."""


def read(ctx):
    return ctx.setup_compile_s
