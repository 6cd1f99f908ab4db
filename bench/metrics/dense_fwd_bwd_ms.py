"""Dense interaction and MLP (``train/train_step.py``: the dense forward
and backward with the FM kernels, the dense gradient psum and the Adam
update): device time of the ``dense`` scope on the fullest chip in the
window's trace (``bench/scopes.py``), per window step."""


def read(ctx):
    from bench import scopes
    run = scopes.of(ctx)
    if run is None:
        return None
    return 1e3 * run.red.scope_s(scopes.obs.DENSE) / ctx.n_steps
