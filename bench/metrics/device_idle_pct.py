"""Device: 1 - busy/window on the fullest chip, from the profiler trace of
the window (busy is the union of its operations' intervals)."""


def read(ctx):
    if ctx.trace is None:
        return None
    return 100.0 * ctx.trace.idle_share()
