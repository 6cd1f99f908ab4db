"""Batch pipeline (``repro/data/pipeline.py``): the host time a window step
waited in ``next()`` on the prefetcher, summed over the steps and divided by
their number. Host clock, from the harness's ``input_wait`` span."""


def read(ctx):
    return 1e3 * sum(s["input_wait_s"] for s in ctx.steps) / len(ctx.steps)
