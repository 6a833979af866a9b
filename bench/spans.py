"""Host seconds per join in one of the program's spans, as the per-layer
readers of the join's phases report them."""


def per_join(ctx, name: str):
    """Seconds per join spent in the host span ``name`` in the traced
    window; ``None`` where the trace holds no such span (a program that
    does not open it, or a run without a trace)."""
    if ctx.trace is None or not ctx.joins:
        return None
    s = ctx.trace.span_seconds(name)
    return s / len(ctx.joins) if s > 0 else None
