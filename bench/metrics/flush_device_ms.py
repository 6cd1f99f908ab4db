"""Tier flush (``engine.flush`` and the step's ``lax.cond`` around it):
device time of the ``flush`` scope on the fullest chip in the window's
trace (``bench/scopes.py``), per flush in the window."""


def read(ctx):
    from bench import scopes
    n_flush = sum(1 for s in ctx.steps if s["flush"])
    run = scopes.of(ctx)
    if run is None or not n_flush:
        return None
    return 1e3 * run.red.scope_s(scopes.obs.FLUSH) / n_flush
