"""Host seconds per join in the program's ``engine.chunk_tables`` span:
padding every chunk of the candidate list and uploading it to the device."""
from bench.spans import per_join


def read(ctx):
    return per_join(ctx, "engine.chunk_tables")
