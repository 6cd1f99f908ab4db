"""Sparse update (``engine.backward``: segment grad, routed gradient
Shuffle, row-wise Adagrad into the master, tier update, frequency counts):
device time of the ``sparse_update`` scope on the fullest chip in the
window's trace (``bench/scopes.py``), per window step."""


def read(ctx):
    from bench import scopes
    run = scopes.of(ctx)
    if run is None:
        return None
    return 1e3 * run.red.scope_s(scopes.obs.SPARSE_UPDATE) / ctx.n_steps
