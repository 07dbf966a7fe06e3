"""Share, in percent, of the device's op time in the traced stretch that
the search's key compares take: the ``fit.bisect``, ``fit.kernel`` and
``fit.widen`` scopes (``bench/xplane.py`` ``Reduction.scopes``, each op
under its innermost scope) over the time of every scope, ``(no scope)``
included."""

SCOPES = ("fit.bisect", "fit.kernel", "fit.widen")


def read(ctx):
    if ctx.trace is None:
        return None
    scopes = ctx.trace.scopes
    total = sum(scopes.values())
    if total <= 0:
        return None
    return 100.0 * sum(scopes.get(s, 0.0) for s in SCOPES) / total
