"""Host seconds per join in the program's ``engine.plan.place`` span:
the index tables' uploads and the dispatch of the tiling program."""
from bench.spans import per_join


def read(ctx):
    return per_join(ctx, "engine.plan.place")
