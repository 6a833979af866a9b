"""Host seconds per join in the program's ``engine.pairs.estimate`` span:
the result-size sample that sizes the pairs buffer."""
from bench.spans import per_join


def read(ctx):
    return per_join(ctx, "engine.pairs.estimate")
