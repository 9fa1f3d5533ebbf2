"""Device: share of the traced window with no operation running."""


def read(ctx):
    if ctx.trace_window_s <= 0.0 or ctx.busy_s <= 0.0:
        return None
    return 100.0 * (1.0 - ctx.busy_s / ctx.trace_window_s)
