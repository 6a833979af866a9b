"""Host seconds per join in the program's ``engine.pairs.fetch`` span:
the pairs and their counts copied back to the host."""
from bench.spans import per_join


def read(ctx):
    return per_join(ctx, "engine.pairs.fetch")
