"""Seconds from process start to the window's opening: data, fit, compile
or cache load, warm-up."""


def read(ctx):
    return ctx.setup_s
