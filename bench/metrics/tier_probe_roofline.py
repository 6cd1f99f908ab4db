"""Sparse lookup, the hot-tier probe (``tier_probe`` scope: the L1 and L2
searches of ``mp_lookup``, which say whether a tier holds each distinct id
and at which slot; the hit rows are fetched under ``stitch``): the least
time of the probe's work on the roofline over its device time on the
fullest chip. The work is what any correct search moves per distinct id a
chip looked up (``flops.tier_probe_least``), for the distinct ids of the
window's steps (``scopes.distinct_rows``: what the program's
``distinct_ids`` counter sums over the chips, so divided by them).

Where the ``tier_probe`` Pallas kernel runs (off on the TPU, see
``TPU_KERNELS``), it fetches the hit rows inside its search pass, and that
fetch counts under the probe too: a change that turns the kernel on has to
count the hit rows' bytes here as well."""


def read(ctx):
    from bench import scopes
    run = scopes.of(ctx)
    if run is None:
        return None
    spent = run.red.scope_s(scopes.obs.TIER_PROBE)
    if spent <= 0:
        return None
    distinct = scopes.distinct_rows(ctx, run.micro)
    least = ctx.flops.least_seconds(
        ctx.flops.tier_probe_least(distinct / ctx.chips), ctx.peak)
    return 100.0 * least / spent
