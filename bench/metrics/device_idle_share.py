"""Share (%) of the traced window in which no operation ran on the chip."""


def read(ctx):
    if ctx.trace is None or 0 not in ctx.trace.devices:
        return None
    return 100.0 * (1.0 - ctx.trace.devices[0].busy_s / ctx.trace.window_s)
