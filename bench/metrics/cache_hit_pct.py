"""Hot tier (``engine/``, ``embedding/state.py``): share of the distinct ids
each chip looked up in the window that the tier served. The numerator is the
program's ``cache_hits`` counter (distinct ids found in the tier, summed over
chips); the denominator counts the distinct (field, id) pairs in each chip's
share of every window batch, every id of a bag, drawn again from the seed."""
import numpy as np


def read(ctx):
    from bench import generator
    hits = sum(s["cache_hits"] for s in ctx.steps)
    looked = 0
    per = ctx.global_batch // ctx.chips
    for k in range(ctx.n_steps):
        b = generator.batch_at(ctx.mix, ctx.fields, ctx.n_dense, ctx.global_batch,
                               ctx.seed, ctx.first_window_batch + k)
        for f in ctx.fields:
            ids = b["fields"][f.name]["ids"]
            for c in range(ctx.chips):
                looked += np.unique(ids[c * per:(c + 1) * per]).shape[0]
    return 100.0 * hits / looked
