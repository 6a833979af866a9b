"""Seconds from process start to the window: imports, device start, data,
and the warm-up join with its compiles or compile-cache loads."""


def read(ctx):
    return ctx.setup_s
