"""Seconds per whole ring join: the window's length over the joins in it."""


def read(ctx):
    return ctx.window_s / len(ctx.joins) if ctx.joins else None
