"""Host seconds per join in the program's ``engine.pairs.remainder`` span:
the chunks that outgrew the first pass's rank window, run again for the
ranks they were missing."""
from bench.spans import per_join


def read(ctx):
    return per_join(ctx, "engine.pairs.remainder")
