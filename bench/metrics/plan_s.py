"""Host seconds per join in the program's ``engine.snapshot_build`` span:
REORDER, grid, tile plan and device placement."""


def read(ctx):
    if ctx.trace is None or not ctx.joins:
        return None
    s = ctx.trace.span_seconds("engine.snapshot_build")
    return s / len(ctx.joins) if s > 0 else None
