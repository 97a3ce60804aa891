"""Device: the share of the traced window in which no op ran, %."""


def read(ctx):
    if ctx.device is None or not ctx.window_s:
        return None
    return 100.0 * max(0.0, 1.0 - ctx.busy_s / ctx.window_s)
