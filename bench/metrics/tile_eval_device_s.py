"""Device seconds per join in the tile-evaluation programs: the engine's
count and pairs chunk programs, found by name on the trace's program line."""

PROGRAMS = ("count_chunk_step", "pairs_chunk_step")


def read(ctx):
    if ctx.trace is None or not ctx.joins:
        return None
    dev = ctx.trace.devices.get(0)
    if dev is None:
        return None
    s = sum(v for k, v in dev.module_s.items() if any(p in k for p in PROGRAMS))
    return s / len(ctx.joins) if s > 0 else None
