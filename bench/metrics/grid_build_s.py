"""Host seconds per join in the program's ``engine.plan.grid`` span:
the epsilon-grid over the indexed dimensions."""
from bench.spans import per_join


def read(ctx):
    return per_join(ctx, "engine.plan.grid")
