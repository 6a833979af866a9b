"""Host seconds per join in the program's ``engine.plan.tiles`` span:
the tiles and the candidate tile-pair list."""
from bench.spans import per_join


def read(ctx):
    return per_join(ctx, "engine.plan.tiles")
