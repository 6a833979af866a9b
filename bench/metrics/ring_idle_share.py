"""Idle share (%) of the least idle chip of the ring: the straggler sets
the ring's time, so its idle share is the one the ring cannot hide."""


def read(ctx):
    if ctx.trace is None:
        return None
    devs = [ctx.trace.devices[i] for i in sorted(ctx.trace.devices)[: ctx.chips]]
    if len(devs) < ctx.chips:
        return None
    return 100.0 * (1.0 - max(d.busy_s for d in devs) / ctx.trace.window_s)
