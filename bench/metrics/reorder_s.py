"""Host seconds per join in the program's ``engine.plan.reorder`` span:
REORDER, the variance permutation and the permuted copy of the points."""
from bench.spans import per_join


def read(ctx):
    return per_join(ctx, "engine.plan.reorder")
