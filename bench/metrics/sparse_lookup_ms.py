"""Sparse lookup (``engine.forward``: unique, tier probe, partition,
Shuffle, gather, stitch, pool): device time of the ``sparse_lookup`` scope
on the fullest chip in the window's trace (``bench/scopes.py``), per window
step."""


def read(ctx):
    from bench import scopes
    run = scopes.of(ctx)
    if run is None:
        return None
    return 1e3 * run.red.scope_s(scopes.obs.SPARSE_LOOKUP) / ctx.n_steps
