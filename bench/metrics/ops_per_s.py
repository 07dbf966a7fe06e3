"""Keys answered inside the window, over the window's seconds (host clock).
An op is one probe key."""


def read(ctx):
    return ctx.window["ops"] / ctx.window["seconds"]
