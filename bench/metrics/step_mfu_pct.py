"""Whole train step (``train/train_step.py``; FLOPs in ``models/wdl.py`` and
``layers/``): the dense forward and backward FLOPs of a step
(``bench/flops.py``: interactions and MLP, no sparse operations, nothing
recomputed) times the window's steps per second, over the chips' bf16
peak."""


def read(ctx):
    per_step = ctx.flops.dense_step_flops(ctx.cfg, ctx.global_batch)
    rate = ctx.n_steps / ctx.window_s
    return 100.0 * per_step * rate / (ctx.chips * ctx.peak["bf16_flops_per_s"])
