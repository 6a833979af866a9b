"""Host seconds per join in the fused ring's ``ring.pack`` span: the
|p|^2 bipartite plans, padding, the capacity sample and device placement."""


def read(ctx):
    if ctx.trace is None or not ctx.joins:
        return None
    s = ctx.trace.span_seconds("ring.pack")
    return s / len(ctx.joins) if s > 0 else None
