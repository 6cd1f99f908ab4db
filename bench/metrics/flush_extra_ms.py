"""Tier flush (``train/train_step.py`` ``do_flush``, ``engine.flush``): host
time of each flush step minus the median step without a flush, averaged over
the window's flushes. A step's host time runs from the read of the previous
step's counters to the read of its own."""
import statistics


def read(ctx):
    plain = [s["step_s"] for s in ctx.steps if not s["flush"]]
    flush = [s["step_s"] for s in ctx.steps if s["flush"]]
    if not flush or not plain:
        return None
    med = statistics.median(plain)
    return 1e3 * sum(f - med for f in flush) / len(flush)
